"""The harness's comparison catches a broken timed path, and the control
(the reference in fp8) reads apart from sound runs: every cell's driver at
a tiny size on the CPU, the chip check skipped, each fault planted in the
program where the work is produced, judged by the cell's own limits."""
import os
import time

import jax
import jax.numpy as jnp
import pytest

import compare
import traffic
from common import bench_file, load_json
from harness import program_arch, run_cell
from weights import make_weights_fn

DATA = os.path.join(os.path.dirname(__file__), "data")
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 4294967311             # over 32 bits, as the benchmark's seeds are


# the SSM path has no cell in BENCHMARK.json yet (no chip readings); at
# this tiny size its sound runs read grad_gap 0.006-0.018, so it is judged
# here by limits of its own
TINY_SSM_LIMITS = {"grad_gap": 0.05, "change_gap": 0.15}


def limits(cell):
    if cell == "tiny-ssm":
        return TINY_SSM_LIMITS
    return load_json(bench_file("cells", cell + ".json"))["limits"]


def train_spec(config, cell, batch):
    return {"name": cell, "chips": 1, "config": load_json(os.path.join(DATA, config)),
            "traffic": dict(load_json(bench_file("traffic", "train_b2_s2048.json")),
                            seq_len=64, global_batch=batch),
            "cell": {"limits": limits(cell)},
            "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"}],
            "per_layer": []}


def serve_spec():
    return {"name": "qwen05b-serve-batch", "chips": 1,
            "config": load_json(os.path.join(DATA, "tiny_qwen.json")),
            "traffic": {"driver": "serve_batch", "batch": 4, "prompt_len": 24,
                        "max_len": 48, "output": {"median": 8, "sigma": 1.0,
                                                  "min": 2, "max": 24}},
            "cell": {"limits": limits("qwen05b-serve-batch")},
            "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s"}],
            "per_layer": []}


def run(spec, wrap=None):
    return run_cell(spec, SEED, 0.3, False, jax.devices()[:1], PEAK, time.time(),
                    wrap_driver=wrap)


TRAIN_CELLS = [("tiny_qwen.json", "qwen05b-train-1chip", 2),
               ("tiny_mamba2.json", "tiny-ssm", 4)]


def broken_step(monkeypatch, how):
    from repro.runtime import train_loop
    make = train_loop.make_train_step

    def patched(*a, **k):
        step = make(*a, **k)

        def bad(params, opt_state, ef, batch):
            if how == "unchanged":
                _, _, _, metrics = step(params, opt_state, ef, batch)
                return params, opt_state, ef, metrics
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, ef, half)
        return bad
    monkeypatch.setattr(train_loop, "make_train_step", patched)


@pytest.mark.parametrize("config,cell,batch", TRAIN_CELLS)
def test_sound_training_is_correct(config, cell, batch):
    assert run(train_spec(config, cell, batch))["correct"]


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
@pytest.mark.parametrize("config,cell,batch", TRAIN_CELLS)
def test_broken_training_step_is_caught(monkeypatch, config, cell, batch, how):
    broken_step(monkeypatch, how)
    out = run(train_spec(config, cell, batch))
    assert not out["correct"], out["compared"]


def test_missing_tp_exchange_is_caught(monkeypatch):
    """The output projection summed over half its contraction, as a
    tensor-parallel step without its all-reduce would leave it."""
    from repro.models import mamba

    dense = mamba.dense_

    def partial_sum(x, w):
        if w.shape[0] > w.shape[1]:                   # w_out [d_inner, d]
            h = w.shape[0] // 2
            return dense(x[..., :h], w[:h])
        return dense(x, w)
    monkeypatch.setattr(mamba, "dense_", partial_sum)
    out = run(train_spec("tiny_mamba2.json", "tiny-ssm", 4))
    assert not out["correct"], out["compared"]


def test_sound_serving_is_correct():
    assert run(serve_spec())["correct"]


def test_altered_token_is_caught(monkeypatch):
    """One request's token replaced, where it is sampled, by the least
    likely one."""
    from repro.runtime.serve_engine import ServeEngine

    sample = ServeEngine._sample

    def bad(self, logits):
        tok = sample(self, logits)
        return tok.at[0].set(jnp.argmin(logits[0]).astype(tok.dtype))
    monkeypatch.setattr(ServeEngine, "_sample", bad)
    out = run(serve_spec())
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("config,cell,batch,seq", [
    ("tiny_qwen.json", "qwen05b-train-1chip", 2, 256),     # 1.6x at 64 tokens
    ("tiny_mamba2.json", "tiny-ssm", 4, 64)])
def test_training_control_reads_apart(config, cell, batch, seq):
    """fp8 in the program's place reads at least three times what the
    program reads, on one of the numbers compared."""
    spec = train_spec(config, cell, batch)
    spec["traffic"]["seq_len"] = seq
    sound = run(spec)["compared"]
    arch = program_arch(spec["config"])
    from repro.models.model import build_model
    shapes = build_model(arch).init_shapes()
    make_plain = make_weights_fn(shapes, spec["config"]["init"])
    ref = compare.ref_module(spec["config"]["reference"])
    tr = spec["traffic"]
    corpus = traffic.UniformCorpus(arch.vocab_size, tr["seq_len"], batch, SEED)
    batches = [corpus.batch_at(k)["tokens"] for k in range(3)]
    go = lambda mode: compare.reference_training(
        ref, spec["config"], make_plain, SEED, shapes, batches, tr["optimizer"],
        jax.devices()[:1], mode=mode)
    ctrl = compare.training_numbers(go("fp8"), go("float32"))
    assert any(ctrl[k] >= 3 * sound[k]["value"] for k in sound), (ctrl, sound)


def test_serving_control_reads_apart():
    import control
    got = {r["variant"]: r["logit_gap"] for r in control.serve_readings(
        serve_spec(), [SEED], 0.3, jax.devices()[:1], PEAK)}
    assert got["control"] >= 3 * got["program"], got

"""``flops.py`` against the compiled program's own count, at the reduced
size, forward only, no rematerialisation, on the CPU.

The compiled count is of what XLA runs: it adds the elementwise work
(norms, softmax, SiLU, the SSD decay masks) and runs the attention and the
SSD's C·B product over the full square where the algorithm needs only the
causal half (and C·B once per group, not per head).  So the algorithmic
count lies below the compiled one, and within the share these add."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

import flops
from common import load_json
from harness import program_arch

DATA = os.path.join(os.path.dirname(__file__), "data")


def one_layer(cfg):
    """XLA's cost analysis visits a loop body once, so the layer scan is
    compared at one layer (and inner scans are unrolled)."""
    key = "num_hidden_layers" if "num_hidden_layers" in cfg else "n_layer"
    return dict(cfg, **{key: 1}, program=dict(cfg["program"], n_layers=1),
                arch_overrides={"n_layers": 1})


def compiled_forward_flops(cfg, batch, seq):
    from repro.models import costing_mode
    from repro.models.model import build_model
    arch = dataclasses.replace(program_arch(cfg), dtype="float32")
    model = build_model(arch)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    fn = jax.jit(lambda p, t: model.forward(p, t, remat="none")[0])
    with costing_mode.costing_unroll():
        c = fn.lower(model.init_shapes(), tokens).compile()
    cost = c.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


@pytest.mark.parametrize("name,seq,low,high", [
    ("tiny_qwen.json", 64, 0.85, 1.0),      # measured 0.903
    ("tiny_mamba2.json", 64, 0.75, 1.0),    # measured 0.830
])
def test_forward_flops_agree_with_compiled(name, seq, low, high):
    cfg = one_layer(load_json(os.path.join(DATA, name)))
    ours = flops.forward_flops(cfg, 2, seq)
    theirs = compiled_forward_flops(cfg, 2, seq)
    assert low <= ours / theirs <= high, (ours, theirs, ours / theirs)


def test_train_is_three_forwards():
    cfg = load_json(os.path.join(DATA, "tiny_qwen.json"))
    assert flops.train_flops_per_token(cfg, 64) * 64 * 2 == pytest.approx(
        3 * flops.forward_flops(cfg, 2, 64))


def test_published_sizes():
    here = os.path.dirname(DATA)
    q = load_json(os.path.join(os.path.dirname(here), "configs", "qwen15_0p5b.json"))
    mp = flops.matmul_params(q)
    # 24 x (4 x 1024^2 + 3 x 1024 x 2816) + 1024 x 151936
    assert mp["layers"] == 24 * (4 * 1024 ** 2 + 3 * 1024 * 2816)
    assert mp["head"] == 1024 * 151936
    m = load_json(os.path.join(os.path.dirname(here), "configs", "mamba2_1p3b.json"))
    mp = flops.matmul_params(m)
    assert mp["layers"] == 48 * (2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048)
    # decode bytes: weights once plus 2 x 24 x 16 x 64 x 2 B per cached position
    base = flops.decode_step_bytes(q, [])
    assert flops.decode_step_bytes(q, [100, 50]) - base == 150 * 2 * 24 * 16 * 64 * 2

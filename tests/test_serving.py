"""Serving-path correctness: prefill+decode must match full forward
(ring caches, absorbed-MLA decode, SSD decode state), and the engine
must produce deterministic greedy completions."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.runtime.serve_engine import EngineConfig, Request, ServeEngine

RNG = jax.random.PRNGKey(0)


def _tiny(arch_id):
    return dataclasses.replace(get_config(arch_id).reduced(), dtype="float32")


slow = pytest.mark.slow      # heavy jit-compiles: slow tier only


@pytest.mark.parametrize("arch_id", [
    "qwen1.5-0.5b",                            # dense, full cache
    pytest.param("gemma3-12b", marks=slow),    # local/global cycle, ring caches
    "mamba2-1.3b",                             # ssm state decode
    pytest.param("zamba2-2.7b", marks=slow),   # hybrid: ssm + shared attn caches
    pytest.param("whisper-small", marks=slow),  # enc-dec: self + cross caches
    pytest.param("deepseek-v3-671b", marks=slow),  # MLA absorbed decode
])
def test_prefill_decode_matches_forward(arch_id):
    cfg = _tiny(arch_id)
    model = build_model(cfg)
    params = model.init(RNG)
    B, S, P = 2, 24, 16
    tokens = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    fe = None
    fs = model.frontend_shape(B)
    if fs is not None:
        fe = jax.random.normal(RNG, fs, jnp.float32)
    cf = float(cfg.moe.n_experts) if cfg.moe else None   # dropless

    from repro.models import transformer as T
    logits_full, _ = T.forward(cfg, params, tokens, fe, capacity_factor=cf)
    off = fs[1] if (fs is not None and cfg.enc_dec is None) else 0

    cache = model.init_cache(B, S + off)
    lg, cache = model.prefill(params, tokens[:, :P], cache, fe,
                              capacity_factor=cf)
    np.testing.assert_allclose(lg, logits_full[:, off + P - 1],
                               rtol=1e-4, atol=1e-4)
    for t in range(P, S):
        lg, cache = model.decode_step(params, tokens[:, t], cache,
                                      capacity_factor=cf)
        np.testing.assert_allclose(lg, logits_full[:, off + t],
                                   rtol=1e-4, atol=2e-4)


def test_engine_greedy_deterministic():
    cfg = _tiny("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(RNG)
    engine = ServeEngine(model, params, EngineConfig(max_len=64))
    reqs = [Request(prompt=[5, 6, 7, 8], max_new_tokens=8),
            Request(prompt=[9, 10, 11], max_new_tokens=8)]
    out1 = engine.generate(reqs)
    out2 = engine.generate(reqs)
    assert [c.tokens for c in out1] == [c.tokens for c in out2]
    assert all(len(c.tokens) == 8 for c in out1)


def test_engine_eos_stops_early():
    cfg = _tiny("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(RNG)
    engine = ServeEngine(model, params, EngineConfig(max_len=64))
    base = engine.generate([Request(prompt=[3, 4, 5], max_new_tokens=8)])[0]
    eos = base.tokens[2]
    out = engine.generate([Request(prompt=[3, 4, 5], max_new_tokens=8,
                                   eos_id=int(eos))])[0]
    assert out.tokens == base.tokens[:3]


def test_engine_config_and_continuous_batching():
    """The EngineConfig surface; static batching is the degenerate
    continuous schedule (enough slots + everything submitted upfront ==
    bit-identical outputs); a smaller pool refills via admission rounds
    and still completes every request deterministically."""
    with pytest.raises(ValueError):
        EngineConfig(batching="sometimes")
    with pytest.raises(ValueError):
        EngineConfig(slots=0)
    cfg = _tiny("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(RNG)
    reqs = [Request(prompt=[5, 6, 7, 8], max_new_tokens=4),
            Request(prompt=[9, 10, 11], max_new_tokens=4),
            Request(prompt=[3, 4, 5], max_new_tokens=4)]
    static = ServeEngine(model, params, EngineConfig(max_len=64)).generate(reqs)
    # degenerate continuous schedule: slots cover the batch
    wide = ServeEngine(model, params,
                       EngineConfig(max_len=64, batching="continuous",
                                    slots=3))
    assert [c.tokens for c in wide.generate(reqs)] == \
        [c.tokens for c in static]
    assert wide.stats["admission_rounds"] == 1
    # 2 slots over 3 requests: a refill round must happen, all complete
    narrow = ServeEngine(model, params,
                         EngineConfig(max_len=64, batching="continuous",
                                      slots=2))
    out1 = narrow.generate(reqs)
    assert all(len(c.tokens) == 4 for c in out1)
    assert narrow.stats["admission_rounds"] >= 2
    assert [c.tokens for c in narrow.generate(reqs)] == \
        [c.tokens for c in out1]          # deterministic across sessions
    # submit()/run() matches generate() and reports rids in order
    for r in reqs:
        narrow.submit(r)
    drained = narrow.run()
    assert [c.rid for c in drained] == sorted(c.rid for c in drained)


def test_engine_masks_finished_slots_and_reports_per_request_decode():
    """A slot that stops early is masked out of the token accounting
    (wasted_slot_steps counts its padding decodes) and its decode seconds
    stop accruing — the lockstep-waste fix."""
    cfg = _tiny("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(RNG)
    engine = ServeEngine(model, params, EngineConfig(max_len=64))
    base = engine.generate([Request(prompt=[5, 6, 7, 8], max_new_tokens=8),
                            Request(prompt=[9, 10, 11], max_new_tokens=8)])
    eos = base[0].tokens[1]
    engine2 = ServeEngine(model, params, EngineConfig(max_len=64))
    out = engine2.generate(
        [Request(prompt=[5, 6, 7, 8], max_new_tokens=8, eos_id=int(eos)),
         Request(prompt=[9, 10, 11], max_new_tokens=8)])
    assert out[0].tokens == base[0].tokens[:2]     # stopped at eos
    assert out[1].tokens == base[1].tokens         # unaffected neighbour
    assert engine2.stats["wasted_slot_steps"] > 0
    assert out[0].decode_time_s < out[1].decode_time_s


@pytest.mark.parametrize("arch_id", [
    "qwen1.5-0.5b",                      # dense: one stacked K/V cache
    "phi3.5-moe-42b-a6.6b",              # MoE stack: the "moe" K/V cache
    "mamba2-1.3b",                       # SSM state: no K/V cache to carry
])
def test_engine_decode_donates_and_writes_cache_in_place(arch_id):
    """Decode steps through the engine give forward's greedy tokens, and
    the cache handed to each decode is donated (its buffers are deleted)."""
    from repro.models import transformer as T

    cfg = _tiny(arch_id)
    model = build_model(cfg)
    params = model.init(RNG)
    cf = float(cfg.moe.n_experts) if cfg.moe else None     # dropless
    engine = ServeEngine(model, params,
                         EngineConfig(max_len=32, capacity_factor=cf))
    prompts = [[5, 6, 7, 8], [9, 10, 11, 12]]
    rids = [engine.submit(Request(prompt=p, max_new_tokens=6))
            for p in prompts]
    done, donated = {}, []
    while engine.pending_requests:
        before, steps = engine._cache, engine.stats["decode_steps"]
        done.update((c.rid, c) for c in engine.step())
        if before is not None and engine.stats["decode_steps"] > steps:
            donated.append(all(a.is_deleted() for a in jax.tree.leaves(before)))
    assert donated and all(donated)
    steps = engine.stats["decode_steps"]
    assert steps == 5
    for rid, prompt in zip(rids, prompts):
        seq = prompt + done[rid].tokens
        logits, _ = T.forward(cfg, params, jnp.asarray([seq]),
                              capacity_factor=cf)
        greedy = np.asarray(jnp.argmax(logits[0], axis=-1))
        assert done[rid].tokens == greedy[len(prompt) - 1:-1].tolist()


def _value_shapes(jaxpr):
    """Shapes of every value ``jaxpr`` and its sub-jaxprs compute or take
    in."""
    out = [tuple(v.aval.shape) for v in jaxpr.invars]
    for eqn in jaxpr.eqns:
        out += [tuple(v.aval.shape) for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _value_shapes(sub)
    return out


@pytest.mark.parametrize("arch_id", [
    "qwen1.5-0.5b", "phi3.5-moe-42b-a6.6b", "deepseek-v3-671b", "gemma3-12b",
    "mamba2-1.3b", "zamba2-2.7b", "whisper-small"])
def test_decode_writes_every_kv_stack_in_place(arch_id):
    """The traced decode never holds one layer's slice of a stacked
    ``"kv"`` cache: each layer reads and writes the whole stack in place."""
    model = build_model(_tiny(arch_id))
    cache = model.cache_shapes(2, 32)
    kv = [leaf.shape for path, leaf in
          jax.tree_util.tree_flatten_with_path(cache)[0]
          if getattr(path[-1], "key", None) == "kv"]
    jaxpr = jax.make_jaxpr(model.decode_step)(
        model.init_shapes(), jax.ShapeDtypeStruct((2,), jnp.int32), cache)
    slices = {sh[1:] for sh in kv} | {(1,) + sh[1:] for sh in kv}
    held = slices & set(_value_shapes(jaxpr.jaxpr))
    assert not held, held

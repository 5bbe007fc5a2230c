"""Compile-only checks for a described TPU v5e (no chip attached).

The TPU compiler is installed with jaxlib, so programs compile for a
v5e:2x2 topology that is described rather than present.  Nothing runs:
these tests catch what interpret mode cannot — block shapes the Mosaic
tiling refuses, and train steps that do not fit the chip's HBM.

The topology is described inside a module-scoped fixture (never at import
or collection time): only one process at a time may load the TPU library,
and pytest-xdist workers must all collect the same tests.
"""
import dataclasses
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import SHAPES, get_config
from repro.core.cluster import TPU_V5E, local_cluster_config
from repro.core.planner import choose_plan
from repro.kernels import flash_attention as fa
from repro.kernels import matmul_epilogue as mme
from repro.kernels import ssd_scan as ssd
from repro.kernels import tsmm

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache here, so keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    x = _sds((4, 16, 2048, 64), jnp.bfloat16, one_chip)
    fn = partial(fa.flash_attention, bq=512, bk=512, interpret=False)
    _assert_kernel(jax.jit(fn).lower(x, x, x).compile())


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    # mamba2-1.3b: 64 heads of 64, state 128, one group, chunk 256
    x = _sds((1, 64, 2048, 64), jnp.float32, one_chip)
    log_a = _sds((1, 64, 2048), jnp.float32, one_chip)
    bc = _sds((1, 1, 2048, 128), jnp.float32, one_chip)
    fn = partial(ssd.ssd_scan_kernel, chunk=256, interpret=False)
    _assert_kernel(jax.jit(fn).lower(x, log_a, bc, bc).compile())


def test_tsmm_compiles(one_chip):
    x = _sds((8192, 1024), jnp.float32, one_chip)
    fn = partial(tsmm.tsmm_upper, interpret=False)
    _assert_kernel(jax.jit(fn).lower(x).compile())


def test_matmul_epilogue_compiles(one_chip):
    x = _sds((4096, 1024), jnp.bfloat16, one_chip)
    w = _sds((1024, 2816), jnp.bfloat16, one_chip)
    fn = partial(mme.matmul_epilogue, epilogue="silu", interpret=False)
    _assert_kernel(jax.jit(fn).lower(x, w).compile())


def compile_train_step(trainer):
    """Compile a Trainer's jitted step from shapes alone (its mesh may hold
    described devices)."""
    from repro.optim import adamw, compress

    pshapes = trainer.model.init_shapes()
    oshapes = jax.eval_shape(partial(adamw.init, trainer.opt_cfg), pshapes)
    eshapes = compress.EFState(residual=jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((), jnp.float32), pshapes))

    def place(shapes, shardings):
        return jax.tree.map(lambda s, h: _sds(s.shape, s.dtype, h),
                            shapes, shardings)

    shape = trainer.shape
    batch = {"tokens": _sds((shape.global_batch, shape.seq_len), jnp.int32,
                            trainer.batch_sh["tokens"])}
    return trainer.train_step.lower(
        place(pshapes, trainer.param_sh), place(oshapes, trainer.opt_sh),
        place(eshapes, trainer.ef_sh), batch).compile()


@pytest.mark.parametrize("batch,remat", [(2, None), (8, "full")])
def test_one_chip_train_step_fits_and_estimate_bounds_it(topo, batch, remat):
    """qwen1.5-0.5b at full width on one v5e, with the planner's plan
    (remat=None) or that plan at another remat: the compiled step fits,
    and estimate_hbm bounds its memory from above by no more than 1.5x."""
    from repro.core.planner import estimate_hbm
    from repro.runtime.train_loop import Trainer, TrainerConfig

    arch = get_config("qwen1.5-0.5b")
    shape = dataclasses.replace(SHAPES["train_4k"], global_batch=batch,
                                seq_len=2048)
    cc = local_cluster_config("TPU v5 lite", (1,), ("data",))
    best = choose_plan(arch, shape, cc, top_k=1)[0]
    plan = best.plan
    if remat is None:
        assert best.feasible and cc.chip.name == "tpu_v5e"
    else:
        plan = dataclasses.replace(plan, remat=remat, microbatches=1)
    mesh = Mesh(topo.devices[:1], ("data",))
    trainer = Trainer(arch, shape, cc, mesh, plan=plan,
                      tcfg=TrainerConfig(steps=1))
    mem = compile_train_step(trainer).memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    est = estimate_hbm(arch, shape, plan, cc)
    assert used <= est <= 1.5 * used, (plan.describe(), used, est)


SERVE_BATCH, SERVE_MAX_LEN, SERVE_PROMPT = 32, 1280, 1024


@pytest.fixture(scope="module")
def serve_engine(one_chip):
    """ServeEngine for qwen1.5-0.5b at the serving cell's size (32 lanes,
    1280 cache slots), its weights given as shapes on one v5e."""
    from repro.models.model import build_model
    from repro.runtime.serve_engine import EngineConfig, ServeEngine

    model = build_model(get_config("qwen1.5-0.5b"))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                          model.init_shapes())
    return ServeEngine(model, params, EngineConfig(max_len=SERVE_MAX_LEN))


def _used_bytes(mem):
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def test_serve_decode_keeps_kv_cache_in_place(serve_engine, one_chip):
    """The decode writes each layer's new K/V row into the donated cache
    where it lies: no copy of a layer's or the stack's K/V cache, the
    cache aliased from input to output, and almost no temporaries."""
    engine = serve_engine
    assert engine.model.decode_carries_cache
    cache = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        engine.model.cache_shapes(SERVE_BATCH, SERVE_MAX_LEN))
    token = _sds((SERVE_BATCH,), jnp.int32, one_chip)
    compiled = engine._decode.lower(engine.params, token, cache).compile()
    # a layer's K/V [32,16,1280,64] in any axis order, or its slot-major
    # rows [1280,16,32,128], alone or stacked
    kv_dims = [sorted((SERVE_BATCH, 16, SERVE_MAX_LEN, 64)),
               sorted((SERVE_MAX_LEN, 16, SERVE_BATCH, 128))]
    copies = re.findall(r"= \w+\[([\d,]*)\]\{[^}]*\} copy\(",
                        compiled.as_text())
    assert copies, "no copy at all: the pattern no longer matches the text"
    cache_copies = [c for c in copies if c.count(",") >= 3
                    and sorted(map(int, c.split(",")[-4:])) in kv_dims]
    assert not cache_copies, cache_copies
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 64 << 20
    assert _used_bytes(mem) <= TPU_V5E.hbm_bytes


def test_serve_prefill_fits_one_chip(serve_engine, one_chip):
    """The prefill of 32 prompts of 1024 tokens, which builds the batch's
    cache, fits one v5e's HBM."""
    engine = serve_engine
    tokens = _sds((SERVE_BATCH, SERVE_PROMPT), jnp.int32, one_chip)
    compiled = engine._prefill.lower(engine.params, tokens).compile()
    assert _used_bytes(compiled.memory_analysis()) <= TPU_V5E.hbm_bytes


MAMBA2_SCOPES = ("in_proj", "conv", "scan", "gate_norm", "out_proj")


def _replica_groups(line):
    """The device groups of one collective in compiled HLO text (explicit,
    iota ``[n,k]<=[dims]T(perm)`` or permute pairs), as frozensets."""
    m = re.search(r"replica_groups=\{((?:\{[\d,]+\},?)+)\}", line)
    if m:
        return {frozenset(map(int, g.split(",")))
                for g in re.findall(r"\{([\d,]+)\}", m.group(1))}
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                  line)
    if m:
        ids = np.arange(int(m.group(1)) * int(m.group(2))).reshape(
            [int(d) for d in m.group(3).split(",")])
        if m.group(4):
            ids = ids.transpose([int(d) for d in m.group(4).split(",")])
        return {frozenset(r) for r in ids.reshape(int(m.group(1)), -1).tolist()}
    m = re.search(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}", line)
    if m:
        return {frozenset(map(int, p.split(",")))
                for p in re.findall(r"\{(\d+,\d+)\}", m.group(1))}
    return set()


def test_mamba2_tp_step_fits_a_2x2_and_names_its_parts(topo):
    """mamba2-1.3b as published (tied head over 50288 rows, float32
    residual), 8 x 2048 tokens, with the planner's pick for a v5e 2x2: the
    step fits each chip's HBM, every part of the SSD block names its device
    operations, and each microbatch stays data-parallel, so the forward
    collectives of the SSD block run within a tensor-parallel pair."""
    from repro.runtime.train_loop import Trainer, TrainerConfig

    arch = dataclasses.replace(get_config("mamba2-1.3b"), tie_embeddings=True,
                               vocab_size=50288, residual_in_fp32=True)
    shape = dataclasses.replace(SHAPES["train_4k"], global_batch=8, seq_len=2048)
    cc = local_cluster_config("TPU v5 lite", (2, 2), ("data", "model"))
    best = choose_plan(arch, shape, cc, top_k=1)[0]
    assert best.feasible and best.plan.tp_axes == ("model",)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    trainer = Trainer(arch, shape, cc, mesh, plan=best.plan,
                      tcfg=TrainerConfig(steps=1))
    compiled = compile_train_step(trainer)
    assert _used_bytes(compiled.memory_analysis()) <= TPU_V5E.hbm_bytes
    text = compiled.as_text()
    for scope in MAMBA2_SCOPES:
        assert f"/ssd/{scope}/" in text, scope
    ids = np.arange(4).reshape(2, 2)
    tp_pairs = {frozenset(r) for r in ids.tolist()}
    forward = []
    for line in text.splitlines():
        op = re.search(r'op_name="([^"]*)"', line)
        if (re.search(r" (all-reduce|all-gather|collective-permute|all-to-all|"
                      r"reduce-scatter)(-start)?\(", line)
                and op and "/ssd/" in op.group(1)
                and "transpose(" not in op.group(1)):
            forward.append(_replica_groups(line))
    assert forward and all(g == tp_pairs for g in forward), forward

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
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import SHAPES, get_config
from repro.core.cluster import local_cluster_config
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

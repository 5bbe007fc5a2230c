"""End-to-end training integration on CPU (tiny config):
loss decreases, checkpoint/resume is exact, elastic replan works."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.planner import ShardingPlan
from repro.launch.mesh import make_host_mesh
from repro.launch.train import mesh_cluster
from repro.optim import adamw
from repro.runtime.train_loop import Trainer, TrainerConfig

pytestmark = pytest.mark.slow   # jit-compiles the real train step on CPU

TINY_SHAPE = ShapeConfig("tiny", seq_len=32, global_batch=8, mode="train")


def _trainer(tmp_path=None, steps=12, **tkw):
    arch = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                               dtype="float32")
    mesh = make_host_mesh()
    cc = mesh_cluster(mesh)
    plan = ShardingPlan(batch_axes=("data",))
    tcfg = TrainerConfig(steps=steps, log_every=1,
                         checkpoint_every=5,
                         ckpt_dir=str(tmp_path) if tmp_path else None,
                         seed=0, **tkw)
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=20)
    return Trainer(arch, TINY_SHAPE, cc, mesh, plan=plan, opt_cfg=opt,
                   tcfg=tcfg)


def test_loss_decreases_over_training():
    t = _trainer(steps=15)
    result = t.run()
    hist = result["history"]
    first = np.mean([h["loss"] for h in hist[:3]])
    last = np.mean([h["loss"] for h in hist[-3:]])
    assert last < first - 0.1, f"{first} -> {last}"


def test_checkpoint_resume_exact(tmp_path):
    # run 1: 10 steps straight through
    t1 = _trainer(None, steps=10, donate=False)
    r1 = t1.run()
    # run 2: 6 steps (checkpoint lands at step 5), then resume to 10
    t2 = _trainer(tmp_path / "ck", steps=6, donate=False)
    r2a = t2.run()
    t3 = _trainer(tmp_path / "ck", steps=10, donate=False)
    r2b = t3.run()
    # same final loss trajectory tail (deterministic data by step index)
    tail1 = [h["loss"] for h in r1["history"] if h["step"] >= 6]
    tail2 = [h["loss"] for h in r2b["history"] if h["step"] >= 6]
    np.testing.assert_allclose(tail1, tail2, rtol=1e-4)


def test_grad_compression_schemes_still_learn():
    for scheme in ("bf16", "int8_ef"):
        t = _trainer(steps=12, compress_scheme=scheme)
        hist = t.run()["history"]
        assert hist[-1]["loss"] < hist[0]["loss"], scheme


def test_elastic_replan_changes_lr_scale():
    from repro.runtime.elastic import replan
    arch = get_config("qwen1.5-0.5b")
    from repro.configs import SHAPES
    from repro.core.cluster import single_pod_config
    old_cc = single_pod_config()
    ep = replan(arch, SHAPES["train_4k"], old_cc=old_cc,
                new_mesh_shape=(8, 16), new_mesh_axes=("data", "model"))
    assert ep.lr_scale == pytest.approx(0.5)
    assert ep.decision.plan is not None

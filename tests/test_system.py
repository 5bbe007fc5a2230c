"""End-to-end behaviour tests for the paper's system: the full pipeline
from script-level program -> generated plan -> cost -> decision, plus the
production stack wired together."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.core import (estimate, explain, multi_pod_config,
                        single_pod_config)
from repro.core.cluster import ClusterConfig, CPU_HOST
from repro.core.linreg import SCENARIOS, build_linreg_program
from repro.core.planner import build_step_program, choose_plan

pytestmark = pytest.mark.slow   # end-to-end: jit-compiles the full stack


def test_end_to_end_costing_pipeline():
    """Script -> runtime plan -> symbol-table costing -> EXPLAIN."""
    cc = ClusterConfig(chip=CPU_HOST, mesh_shape=(72,), mesh_axes=("data",),
                       dispatch_latency=20.0)
    prog, choice = build_linreg_program(SCENARIOS["XL1"], cc)
    costed = estimate(prog, cc)
    assert costed.total > 0
    text = explain(costed)
    # every instruction visible with a cost annotation
    assert text.count("# C=") > 10
    # plan reflects the paper's XL1 decisions
    assert choice.tsmm_op == "tsmm+ak+" and choice.mm_op == "mapmm"


def test_cost_model_drives_consistent_decisions_across_meshes():
    """R3: the same arch/shape gets re-planned per cluster, and the chosen
    plan's estimated time never improves when the cluster shrinks."""
    arch = get_config("qwen1.5-4b")
    shape = SHAPES["train_4k"]
    pod = choose_plan(arch, shape, single_pod_config(), top_k=1)[0]
    two = choose_plan(arch, shape, multi_pod_config(), top_k=1)[0]
    assert pod.feasible and two.feasible
    # two pods must not be slower than 4x one pod (sanity band)
    assert two.time < 4 * pod.time


def test_analytical_vs_generated_plan_agreement():
    """The analytical program's FLOP total must agree with 6*N*D within a
    factor band (remat/attention overheads make HLO higher, never 5x)."""
    arch = get_config("qwen1.5-0.5b")
    shape = SHAPES["train_4k"]
    cc = single_pod_config()
    d = choose_plan(arch, shape, cc, top_k=1)[0]
    prog = build_step_program(arch, shape, d.plan, cc)
    costed = estimate(prog, cc)
    model_flops = 6 * arch.n_params * shape.tokens
    ideal_s = model_flops / (cc.num_chips * cc.chip.peak("bfloat16")
                             * cc.matmul_util)
    assert ideal_s * 0.5 < costed.breakdown.compute < ideal_s * 6


def test_trainer_smoke():
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import mesh_cluster
    from repro.optim import adamw
    from repro.runtime.train_loop import Trainer, TrainerConfig

    arch = dataclasses.replace(get_config("mamba2-1.3b").reduced(),
                               dtype="float32")
    shape = ShapeConfig("t", seq_len=32, global_batch=4, mode="train")
    mesh = make_host_mesh()
    cc = mesh_cluster(mesh)
    tr = Trainer(arch, shape, cc, mesh,
                 tcfg=TrainerConfig(steps=3, log_every=1),
                 opt_cfg=adamw.AdamWConfig(total_steps=3))
    hist = tr.run()["history"]
    assert len(hist) == 3
    assert all(np.isfinite(h["loss"]) for h in hist)

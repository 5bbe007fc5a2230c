"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Cost-model-driven: the planner enumerates sharding plans for the mesh,
ranks them with C(P, cc), and the winner configures the jitted step — the
paper's optimizer in the driver's seat.

``--mesh host`` (the default) runs on the devices this process sees and
prices the plans for the chip JAX reports there (``device_kind``): a TPU
v5e host is costed as ``tpu_v5e``, the CPU backend as ``cpu_host``, and an
unknown chip is an error.  One device is a (1,) "data" mesh, four are a
2x2 ("data", "model") mesh.  A winner whose HBM estimate exceeds the
chip's budget is refused before anything compiles.  On the CPU use
``--reduced`` for a tiny same-family model.  ``--mesh single|multi`` are
the 256- and 512-chip pod meshes (see dryrun.py for compile-only runs).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import List, Optional, Tuple

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core import explain as explain_mod
from repro.core.cluster import (ClusterConfig, local_cluster_config,
                                multi_pod_config, single_pod_config)
from repro.core.costmodel import estimate
from repro.core.planner import PlanDecision, build_step_program, choose_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim import adamw
from repro.runtime.train_loop import Trainer, TrainerConfig


class InfeasiblePlan(RuntimeError):
    """The planner's best plan does not fit the chip's HBM budget."""


def mesh_cluster(mesh) -> ClusterConfig:
    """The :class:`ClusterConfig` of a local device mesh: its chip by
    ``device_kind`` and its shape and axis names."""
    kind = mesh.devices.flat[0].device_kind
    return local_cluster_config(kind, tuple(mesh.devices.shape),
                                tuple(mesh.axis_names))


def rank_plans(arch: ArchConfig, shape: ShapeConfig, cc: ClusterConfig,
               top_k: int = 3) -> List[PlanDecision]:
    """The planner's top plans; raises :class:`InfeasiblePlan` when even
    the best one does not fit ``cc``'s HBM budget."""
    decisions = choose_plan(arch, shape, cc, top_k=top_k)
    best = decisions[0]
    if not best.feasible:
        raise InfeasiblePlan(
            f"best plan {best.plan.describe()} needs estimate_hbm "
            f"{best.hbm_est / 1e9:.2f} GB per device, over the "
            f"{cc.hbm_budget / 1e9:.2f} GB budget of {cc.chip.name} "
            f"(mesh {cc.mesh_shape}); shrink --global-batch or --seq-len")
    return decisions


def make_trainer(arch: ArchConfig, shape: ShapeConfig, mesh, *,
                 steps: int, lr: float = 3e-4, ckpt_dir: Optional[str] = None,
                 compress: str = "none", log_every: Optional[int] = None,
                 cc: Optional[ClusterConfig] = None
                 ) -> Tuple[Trainer, List[PlanDecision]]:
    """Cost the plans for ``mesh`` (or ``cc``), refuse an infeasible winner,
    and build the :class:`Trainer` that runs it."""
    cc = cc if cc is not None else mesh_cluster(mesh)
    decisions = rank_plans(arch, shape, cc)
    tcfg = TrainerConfig(steps=steps, ckpt_dir=ckpt_dir,
                         compress_scheme=compress,
                         log_every=log_every or max(steps // 10, 1))
    opt = adamw.AdamWConfig(lr=lr, total_steps=steps)
    trainer = Trainer(arch, shape, cc, mesh, plan=decisions[0].plan,
                      opt_cfg=opt, tcfg=tcfg)
    return trainer, decisions


def print_ranking(decisions: List[PlanDecision], cc: ClusterConfig) -> None:
    print(f"== cost-based plan ranking ({cc.chip.name}, mesh "
          f"{cc.mesh_shape} {cc.mesh_axes}) ==")
    for d in decisions:
        print(f"  {d.plan.describe():60s} T={d.time*1e3:9.2f}ms "
              f"hbm={d.hbm_est/1e9:6.2f}GB feasible={d.feasible}")


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "int8_ef"])
    ap.add_argument("--explain", action="store_true",
                    help="print the costed analytical plan and exit")
    args = ap.parse_args(argv)

    arch = get_config(args.arch)
    if args.reduced:
        arch = dataclasses.replace(arch.reduced(), dtype="float32")
    shape = SHAPES[args.shape]
    if args.global_batch or args.seq_len:
        shape = dataclasses.replace(
            shape, global_batch=args.global_batch or shape.global_batch,
            seq_len=args.seq_len or shape.seq_len)

    if args.mesh == "host":
        mesh = make_host_mesh()
        cc = mesh_cluster(mesh)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        cc = multi_pod_config() if args.mesh == "multi" else single_pod_config()

    if args.explain:
        decisions = choose_plan(arch, shape, cc, top_k=3)
        print_ranking(decisions, cc)
        prog = build_step_program(arch, shape, decisions[0].plan, cc)
        print(explain_mod.explain(estimate(prog, cc), max_depth=3))
        return

    print(f"compile cache: {enable_compile_cache()}")
    try:
        trainer, decisions = make_trainer(
            arch, shape, mesh, steps=args.steps, lr=args.lr,
            ckpt_dir=args.ckpt_dir, compress=args.compress, cc=cc)
    except InfeasiblePlan as e:
        print(f"refusing to train: {e}", file=sys.stderr)
        raise SystemExit(2) from None
    print_ranking(decisions, cc)
    result = trainer.run(on_metrics=lambda m: print(json.dumps(m)))
    hist = result["history"]
    if hist:
        print(f"\nloss: {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f} "
              f"over {len(hist)} logged steps")


if __name__ == "__main__":
    main()

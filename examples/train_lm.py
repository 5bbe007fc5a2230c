"""End-to-end training driver (deliverable b): train a reduced-family LM
for a few hundred steps on CPU with the full production stack — cost-based
plan selection, sharded data pipeline, AdamW, async checkpointing, resume,
straggler monitor.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import json
import tempfile

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.train import mesh_cluster
from repro.optim import adamw
from repro.runtime.train_loop import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    arch = dataclasses.replace(get_config(args.arch).reduced(),
                               dtype="float32")
    shape = ShapeConfig("cpu_train", seq_len=64, global_batch=16,
                        mode="train")
    mesh = make_host_mesh()
    cc = mesh_cluster(mesh)
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    tcfg = TrainerConfig(steps=args.steps, log_every=20,
                         checkpoint_every=100, ckpt_dir=ckpt)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
    trainer = Trainer(arch, shape, cc, mesh, opt_cfg=opt, tcfg=tcfg)
    print(f"plan: {trainer.plan.describe()}  params="
          f"{arch.n_params/1e6:.1f}M  ckpt={ckpt}")
    result = trainer.run(on_metrics=lambda m: print(json.dumps(m)))
    hist = result["history"]
    print(f"\nloss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"({args.steps} steps); straggler verdict: "
          f"{trainer.monitor.detect().action}")


if __name__ == "__main__":
    main()

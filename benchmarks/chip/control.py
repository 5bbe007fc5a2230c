"""Readings of the control and of planted faults, at a cell's own size, on
the chip, for setting the limits of ``cells/<cell>.json``.  The benchmark's
own runs do not run this.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

Training cells: each seed's numbers (``compare.training_numbers``) of
* ``control``: the reference in fp8 (``reference.common.Matmul("fp8")``)
  put in the program's place;
* ``half_batch``: the reference with half of the batch left out, the mean
  taken over the rest;
all against the float32 reference.  A state left unchanged reads 1 by
``grad_gap`` and ``change_gap`` and needs no run.

Serving cells: one window of the program per seed (``--seconds``), then
``logit_gap`` of the program's served tokens and of the control's first
choices at the same positions, both judged by the float32 reference.
Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import SRC, bench_file, load_json, log, resolve_cell  # noqa: E402


def half_batch(ref):
    def loss(p, t, cfg, mm):
        return ref.loss(p, t[:t.shape[0] // 2], cfg, mm)
    return loss


def train_readings(spec, seeds, devices):
    import compare
    import traffic
    from harness import program_arch
    from repro.models.model import build_model
    from weights import make_weights_fn

    arch = program_arch(spec["config"])
    shapes = build_model(arch).init_shapes()
    make_plain = make_weights_fn(shapes, spec["config"]["init"])
    ref = compare.ref_module(spec["config"]["reference"])
    tr = spec["traffic"]
    variants = {"control": ("fp8", None), "half_batch": ("float32", half_batch(ref))}
    for seed in seeds:
        corpus = traffic.UniformCorpus(arch.vocab_size, tr["seq_len"],
                                       tr["global_batch"], seed)
        batches = [corpus.batch_at(k)["tokens"] for k in range(3)]
        run = lambda mode="float32", loss_fn=None: compare.reference_training(
            ref, spec["config"], make_plain, seed, shapes, batches, tr["optimizer"],
            devices, mode=mode, loss_fn=loss_fn)
        t = time.perf_counter()
        base = run()
        log(f"seed {seed}: reference losses {base['losses']} ({time.perf_counter() - t:.1f}s)")
        for name, (mode, loss_fn) in variants.items():
            nums = compare.training_numbers(run(mode, loss_fn), base)
            yield {"cell": spec["name"], "seed": seed, "variant": name, **nums}


def serve_readings(spec, seeds, seconds, devices, peak):
    import compare
    from harness import run_cell

    found = []

    def wrap(driver):
        class Both:
            setup, window = driver.setup, driver.window

            @staticmethod
            def check(ctx):
                out = driver.check(ctx)
                pick = ctx.state["sample"]
                params = compare.weights_f32(ctx.state["make_plain"], ctx.seed, None)
                ctrl = compare.serve_logit_gaps(
                    compare.ref_module(ctx.config["reference"]), ctx.config, params,
                    pick["seqs"], pick["reads"], mode="fp8")
                found.append({"cell": ctx.name, "seed": ctx.seed, "variant": "program",
                              **out})
                found.append({"cell": ctx.name, "seed": ctx.seed, "variant": "control",
                              "logit_gap": ctrl["logit_gap"]})
                return out
        return Both

    for seed in seeds:
        run_cell(spec, seed, seconds, False, devices, peak, time.time(), wrap_driver=wrap)
        yield from found
        found.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = resolve_cell(args.workload)
    sys.path.insert(1, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        log("control.py: needs the cell's TPU chips; nothing run")
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    devices = devices[:spec["chips"]]
    if spec["traffic"]["driver"] == "train":
        readings = train_readings(spec, seeds, devices)
    else:
        peak = load_json(bench_file("peaks.json"))[devices[0].device_kind]
        readings = serve_readings(spec, seeds, args.seconds, devices, peak)
    for r in readings:
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

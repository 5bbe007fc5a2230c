"""Readings for a tensor-parallel training cell's limits, on the chip, at
the cell's own size, in one process: sound runs of the program, the
program with its tp all-reduce left out, and ``control.py``'s readings
(the fp8 control and half a batch).  The benchmark's own runs do not run
this.

    python3 benchmarks/chip/control_tp.py --workload <cell> --seeds 1,2 \\
        [--sound-seeds 3,4] [--seconds 2]

``program``: for each of ``--sound-seeds``, one run of the cell
(``harness.run_cell``, a short window) as it is.  ``no_tp_all_reduce``:
for each of ``--seeds``, one run whose Mamba2 output projection sums over
the first half of its contraction only, what each of two tensor-parallel
shards holds before the all-reduce that the step would leave out (the
fault that ``tests/test_faults.py`` plants at a tiny size).  Their
numbers are those the harness compares.  Then ``control.py``'s
``train_readings`` for ``--seeds``.  Each reading is one JSON line on
standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import SRC, bench_file, load_json, log, resolve_cell  # noqa: E402
import control  # noqa: E402


def without_tp_all_reduce():
    """Put the faulty output projection in ``repro.models.mamba``; returns
    the function that puts the sound one back."""
    from repro.models import mamba

    dense = mamba.dense_

    def partial_sum(x, w):
        if w.shape[0] > w.shape[1]:                   # w_out [d_inner, d]
            h = w.shape[0] // 2
            return dense(x[..., :h], w[:h])
        return dense(x, w)
    mamba.dense_ = partial_sum

    def restore():
        mamba.dense_ = dense
    return restore


def program_readings(spec, seeds, seconds, devices, peak, variant):
    from harness import run_cell

    for seed in seeds:
        out = run_cell(spec, seed, seconds, False, devices, peak, time.time())
        yield {"cell": spec["name"], "seed": seed, "variant": variant,
               **{k: v["value"] for k, v in out["compared"].items()}}


def no_tp_readings(spec, seeds, seconds, devices, peak):
    restore = without_tp_all_reduce()
    try:
        yield from program_readings(spec, seeds, seconds, devices, peak,
                                    "no_tp_all_reduce")
    finally:
        restore()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sound-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    spec = resolve_cell(args.workload)
    if spec["traffic"]["driver"] != "train":
        log("control_tp.py: a training cell only; nothing run")
        return 2
    sys.path.insert(1, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        log("control_tp.py: needs the cell's TPU chips; nothing run")
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    devices = devices[:spec["chips"]]
    peak = load_json(bench_file("peaks.json"))[devices[0].device_kind]
    sound = [int(s) for s in args.sound_seeds.split(",") if s]
    for r in program_readings(spec, sound, args.seconds, devices, peak, "program"):
        print(json.dumps(r), flush=True)
    for r in no_tp_readings(spec, seeds, args.seconds, devices, peak):
        print(json.dumps(r), flush=True)
    for r in control.train_readings(spec, seeds, devices):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, compares what the window
produced with the plain reference, and prints one JSON object as the last
line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and ``compared`` (each number compared with
its limit).  It exits non-zero and prints no result without a TPU, with
fewer chips than the cell asks for, on a chip missing from ``peaks.json``,
or without the repository's ``src/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import SRC, bench_file, load_json, log, process_start_time, resolve_cell  # noqa: E402


def main(argv=None) -> int:
    t_start = process_start_time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"run.py: no program source at {SRC}; nothing run")
        return 2
    spec = resolve_cell(args.workload)
    sys.path.insert(1, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"run.py: no TPU (JAX found {dev.platform}); nothing run")
        return 2
    if len(devices) < spec["chips"]:
        log(f"run.py: {args.workload} needs {spec['chips']} chips, found "
            f"{len(devices)}; nothing run")
        return 2
    peaks = load_json(bench_file("peaks.json"))
    if dev.device_kind not in peaks:
        log(f"run.py: no peaks for device kind {dev.device_kind!r} in peaks.json")
        return 2

    from harness import run_cell
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices[:spec["chips"]], peaks[dev.device_kind], t_start)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

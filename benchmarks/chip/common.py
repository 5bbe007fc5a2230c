"""Shared pieces of the chip benchmark: paths, the files a cell is made of,
the compile meter, host spans and the process clock."""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))
SRC = os.path.join(CHECKOUT, "src")
SPAN_PREFIX = "bench."


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def bench_file(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


def resolve_cell(name: str, benchmark: Optional[Dict] = None) -> Dict[str, Any]:
    """Everything one cell is made of, found by name: its entry in
    ``BENCHMARK.json``, its configuration, its traffic mix and its own file
    (``cells/<name>.json``: the limits of its comparison)."""
    if benchmark is None:
        benchmark = load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    entry = next((w for w in benchmark["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in benchmark["configs"] if c["name"] == entry["config"])
    return {
        "name": name,
        "chips": entry["chips"],
        "config": load_json(os.path.join(CHECKOUT, conf["file"])),
        "traffic": load_json(bench_file("traffic", entry["traffic"] + ".json")),
        "cell": load_json(bench_file("cells", name + ".json")),
        "per_layer": [m for m in benchmark["per_layer"]
                      if name in m.get("workloads", [name])],
        "end_to_end": [m for m in benchmark["end_to_end"]
                       if name in m.get("workloads", [name])],
    }


def process_start_time() -> float:
    """Wall-clock second at which this process started (from /proc)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])            # field 22 of stat(5)
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileMeter:
    """Seconds JAX spends compiling (or loading from the persistent cache)
    and when, read from ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.events: List[Tuple[float, float]] = []    # (end, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds(self, t0: float = float("-inf"), t1: float = float("inf")) -> float:
        return sum(d for t, d in self.events if t0 <= t <= t1)

    def count(self, t0: float = float("-inf"), t1: float = float("inf")) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


def span(name: str):
    """A host span in the profiler's trace, named ``bench.<name>``."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

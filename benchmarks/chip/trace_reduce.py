"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* device busy: the union of the intervals in which an operation runs on a
  device (its ``XLA Ops`` line), inside the traced window, averaged over
  the devices;
* top operations: self time (an event's duration less that of the events
  nested in it on the same line) summed by name, averaged over devices;
* idle gaps: the time inside the window in which no operation runs on the
  first device, given to the innermost host span of the harness
  (``bench.*``) that covers the gap's middle, summed by span;
* exposed collectives: per device, the time in which a collective runs and
  no other operation does;
* programs: per ``XLA Modules`` name, how often it ran and its device time.

The window is the host span ``bench.window``, or the whole trace without it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from common import SPAN_PREFIX

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "allreduce",
                    "allgather", "reducescatter")

Interval = Tuple[float, float]


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(m in n for m in COLLECTIVE_MARKS)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out: List[Interval] = []
    b = union(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of self time by name for possibly nested events."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []          # [end, name, child_time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, child = stack.pop()
            out[nm] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([e, name, 0.0])
    while stack:
        end, nm, child = stack.pop()
        out[nm] -= child
    return out


def op_label(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...), kind=..`` -> ``fusion.12 (fusion)``:
    the instruction's name and its opcode, without shapes and operands."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = re.search(r"\s([a-z][\w\-.]*)\(", rest)
    return f"{head.lstrip('%')} ({m.group(1)})" if m else head.lstrip("%")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> Dict:
    """Planes of the trace as plain lists, in seconds on one clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, op_label(ev.name))
                               for ev in ln.events] for ln in plane.lines
                     if ln.name in ("XLA Ops", "XLA Modules")}
            if "XLA Ops" in lines:
                devices.append({"name": plane.name, "ops": lines["XLA Ops"],
                                "modules": lines.get("XLA Modules", [])})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                            for ev in ln.events
                            if ev.name.startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "spans": host}


def reduce(planes: Dict, window_span: str = SPAN_PREFIX + "window",
           top: int = 10) -> Optional[Dict]:
    """The numbers of a loaded trace; None when no device ran anything."""
    devices, spans = planes["devices"], planes["spans"]
    if not devices or not any(d["ops"] for d in devices):
        return None
    win = [(s, e) for s, e, n in spans if n == window_span]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for d in devices for s, _, _ in d["ops"])
        hi = max(e for d in devices for _, e, _ in d["ops"])
    window = hi - lo
    busy, exposed = [], []
    op_time: Dict[str, float] = defaultdict(float)
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    n = len(devices)
    for d in devices:
        ops = [(max(s, lo), min(e, hi), nm) for s, e, nm in d["ops"]
               if min(e, hi) > max(s, lo)]
        u = union([(s, e) for s, e, _ in ops])
        busy.append(total(u))
        coll = union([(s, e) for s, e, nm in ops if is_collective(nm)])
        comp = [(s, e) for s, e, nm in ops if not is_collective(nm)]
        exposed.append(total(subtract(coll, comp)))
        for nm, t in self_times(ops).items():
            op_time[nm] += t / n
        for s, e, nm in d["modules"]:
            if min(e, hi) > max(s, lo):
                modules[nm][0] += 1.0 / n
                modules[nm][1] += (min(e, hi) - max(s, lo)) / n
    gaps = subtract([(lo, hi)],
                    [(s, e) for s, e, _ in devices[0]["ops"]])
    by_span: Dict[str, float] = defaultdict(float)
    inner = [(s, e, nm) for s, e, nm in spans if nm != window_span]
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(ss, nm) for ss, ee, nm in inner if ss <= mid <= ee]
        label = max(cover)[1] if cover else window_span
        by_span[label] += e - s
    return {
        "window_s": window,
        "busy_s": sum(busy) / n,
        "devices": n,
        "collective_exposed_s": sum(exposed) / n,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(by_span.items(), key=lambda kv: -kv[1])[:top],
        "modules": {k: {"count": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
    }


def program_run_n_times(reduced: Dict, n: int) -> Tuple[float, float]:
    """(runs, device seconds) of the program that ran ``n`` times in the
    window, the longest such: how a step called once per known event is
    found when JAX gives it no name of its own (``jit__unknown``)."""
    hits = [v for v in reduced["modules"].values() if round(v["count"]) == n]
    if not hits:
        return 0.0, 0.0
    best = max(hits, key=lambda v: v["seconds"])
    return best["count"], best["seconds"]

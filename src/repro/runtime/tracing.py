"""Host spans and compile records of the program.

``span(name, **ids)`` marks one piece of host work.  It always enters a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so the span lands
in the profiler's host plane, on the clock of the device planes.  While a
profiler session collects, it also appends the span to a bounded record in
memory on ``time.perf_counter``, with the span that encloses it on the same
thread as its parent.  With no session a span costs the annotation and one
``is_enabled()`` check, and records nothing.  ``window`` reads the record
between two ``perf_counter`` readings, and ``self_times`` gives each span's
time less its children's.

Every backend compile (or load from the persistent compilation cache) is
recorded with the name of what was compiled and its seconds, and every
persistent-cache hit is counted, with or without a session: compiles are
rare, and this record says which function compiled again.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "repro."
MAX_SPANS = 1 << 16
MAX_COMPILES = 1 << 12
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    sid: int                  # unique in the process
    name: str                 # without PREFIX
    start: float              # time.perf_counter seconds
    end: float
    parent: Optional[int]     # sid of the enclosing span, if recorded
    ids: Dict


class Compile(NamedTuple):
    end: float                # time.perf_counter seconds
    fun_name: str
    seconds: float


_spans: "collections.deque[Span]" = collections.deque(maxlen=MAX_SPANS)
_compiles: "collections.deque[Compile]" = collections.deque(maxlen=MAX_COMPILES)
_cache_hits = 0
_sids = itertools.count()
_local = threading.local()    # .stack: sids of this thread's open spans


class span:
    """``with span("serve.step", step=k):`` one host span (see the module
    docstring)."""

    annotation = TraceAnnotation
    __slots__ = ("name", "ids", "_ann", "_rec")

    def __init__(self, name: str, **ids):
        self.name, self.ids = name, ids
        self._ann = self.annotation(PREFIX + name, **ids)
        self._rec = None

    def __enter__(self) -> "span":
        self._ann.__enter__()
        if TraceAnnotation.is_enabled():
            stack = getattr(_local, "stack", None)
            if stack is None:
                stack = _local.stack = []
            sid = next(_sids)
            self._rec = (sid, stack[-1] if stack else None, time.perf_counter())
            stack.append(sid)
        return self

    def __exit__(self, *exc) -> None:
        if self._rec is not None:
            end = time.perf_counter()
            sid, parent, start = self._rec
            _local.stack.pop()
            _spans.append(Span(sid, self.name, start, end, parent, self.ids))
            self._rec = None
        self._ann.__exit__(*exc)


class step_span(span):
    """A span that is also the profiler's step marker
    (``StepTraceAnnotation``): pass ``step_num``."""

    annotation = StepTraceAnnotation
    __slots__ = ()


def window(t0: float, t1: float) -> List[Span]:
    """The recorded spans that overlap ``[t0, t1)`` (``perf_counter``
    seconds), clipped to it, in order of start."""
    out = [s._replace(start=max(s.start, t0), end=min(s.end, t1))
           for s in list(_spans) if s.start < t1 and s.end > t0]
    return sorted(out, key=lambda s: (s.start, -s.end))


def self_times(spans: List[Span]) -> List[float]:
    """Each span's seconds less those of its children among ``spans``."""
    child: Dict[int, float] = collections.defaultdict(float)
    for s in spans:
        if s.parent is not None:
            child[s.parent] += s.end - s.start
    return [s.end - s.start - child[s.sid] for s in spans]


def compiles(t0: float = float("-inf"), t1: float = float("inf")) -> List[Compile]:
    """The backend compiles that ended in ``[t0, t1]`` (``perf_counter``)."""
    return [c for c in list(_compiles) if t0 <= c.end <= t1]


def cache_hits() -> int:
    """Persistent compilation-cache hits so far in this process."""
    return _cache_hits


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == COMPILE_EVENT:
        _compiles.append(Compile(time.perf_counter(),
                                 str(kw.get("fun_name", "")), duration))


def _on_event(event: str, **_) -> None:
    global _cache_hits
    if event == CACHE_HIT_EVENT:
        _cache_hits += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

"""serve_host_step_ms: the host's own time in one decode step of the
serving engine.  From the program's own span record
(``repro.runtime.tracing``, spans clipped to the window): the median, over
the window's ``serve.step`` spans that hold a ``decode_wait``, of the step
less its ``prefill_wait`` and ``decode_wait`` (the read-backs that wait
for the device), in ms.  Nothing where the program records no such span.
Moves ``serve_itl_p95_ms``."""
import statistics

WAITS = ("serve.prefill_wait", "serve.decode_wait")


def read(rec):
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    spans = tracing.window(rec["t_open"], rec["t_close"])
    parent = {s.sid: s.parent for s in spans}
    host = {s.sid: s.end - s.start for s in spans if s.name == "serve.step"}
    decoded = set()
    for s in spans:
        if s.name not in WAITS:
            continue
        step = s.parent
        while step is not None and step not in host:
            step = parent.get(step)
        if step is None:
            continue
        host[step] -= s.end - s.start
        if s.name == "serve.decode_wait":
            decoded.add(step)
    if not decoded:
        return None
    return 1e3 * statistics.median(host[k] for k in decoded)

"""train_host_pct: share of the window in which the training loop's host
work, not the device, sets the pace.  From the program's own span record
(``repro.runtime.tracing``, spans clipped to the window): the sum over
the window's ``train.step`` spans of the step less its ``device_wait``
(the metrics' read-back, which waits for the step to end), over the
window.  Nothing where the program records no such span.  Moves
``train_tokens_per_s``."""


def read(rec):
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    t0, t1 = rec["t_open"], rec["t_close"]
    spans = tracing.window(t0, t1)
    steps = {s.sid: s.end - s.start for s in spans if s.name == "train.step"}
    if not steps:
        return None
    waits = sum(s.end - s.start for s in spans
                if s.name == "train.device_wait" and s.parent in steps)
    return 100.0 * (sum(steps.values()) - waits) / (t1 - t0)

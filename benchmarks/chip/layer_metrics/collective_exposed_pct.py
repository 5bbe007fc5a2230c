"""collective_exposed_pct: share of the traced window in which a collective
runs on a device and no other operation does (``trace_reduce``'s
``collective_exposed_s``, the mean over the devices), in percent of the
window.  Nothing without a trace or outside a training cell.  Moves
``train_tokens_per_s``."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or "tokens" not in rec or "collective_exposed_s" not in tr:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]

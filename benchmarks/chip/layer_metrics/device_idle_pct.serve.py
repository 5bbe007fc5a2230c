"""device_idle_pct.serve: share of the traced window in which no operation
runs on the device (1 - busy union / window).  Moves ``serve_itl_p95_ms``."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or "generated_tokens" not in rec:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

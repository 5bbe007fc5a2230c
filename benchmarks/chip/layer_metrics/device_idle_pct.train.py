"""device_idle_pct.train: share of the traced window in which no operation
runs on a device (1 - busy union / window), averaged over the devices.
Moves ``train_tokens_per_s``."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or "tokens" not in rec:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

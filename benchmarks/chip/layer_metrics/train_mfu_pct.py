"""train_mfu_pct: model operations per trained token (``flops.py``: 6 x
matmul parameters plus the sequence mixer, no recomputation) times tokens
per second over the chips' bf16 peak.  Moves ``train_tokens_per_s``."""
import flops


def read(rec):
    if "tokens" not in rec:
        return None
    per_token = flops.train_flops_per_token(rec["config"], rec["traffic"]["seq_len"])
    rate = rec["tokens"] / rec["window_s"]
    return 100.0 * per_token * rate / (rec["chips"] * rec["peak"]["bf16_flops_per_s"])

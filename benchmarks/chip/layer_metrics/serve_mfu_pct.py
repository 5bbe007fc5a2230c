"""serve_mfu_pct: the window's model operations (``flops.py``: each
prompt's prefill and each generated token's decode at its own context, no
padding) over the window and the chip's bf16 peak.  Moves
``serve_tokens_per_s``."""


def read(rec):
    if "model_flops" not in rec:
        return None
    return 100.0 * rec["model_flops"] / rec["window_s"] / (
        rec["chips"] * rec["peak"]["bf16_flops_per_s"])

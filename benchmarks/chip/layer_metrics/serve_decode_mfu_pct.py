"""serve_decode_mfu_pct: the decode steps' model operations (``flops.py``)
over the decode program's device time in the trace (the program run
once per decode step) and the chip's bf16 peak: the whole decode step's share of the peak, beside its roofline.
Moves ``serve_itl_p95_ms``."""
import flops
import trace_reduce


def read(rec):
    tr = rec.get("trace")
    steps = rec.get("decode_contexts")
    if tr is None or not steps:
        return None
    runs, secs = trace_reduce.program_run_n_times(tr, rec["decode_steps"])
    if runs < 1 or secs <= 0:
        return None
    ops = sum(flops.decode_flops(rec["config"], c) for s in steps for c in s) / len(steps)
    return 100.0 * ops / (secs / runs) / rec["peak"]["bf16_flops_per_s"]

"""decode_step_roofline: the least time the chip could take for the
window's decode steps (the larger of their operations over peak FLOP/s and
their bytes over peak HBM bytes/s: every matmul weight once and each live
request's keys and values at its own length, ``flops.py``) over the device
time of the decode program in the trace (the program run once
per decode step).  Moves ``serve_itl_p95_ms``."""
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
    cfg, peak = rec["config"], rec["peak"]
    ops = sum(flops.decode_flops(cfg, c) for s in steps for c in s) / len(steps)
    nbytes = sum(flops.decode_step_bytes(cfg, s) for s in steps) / len(steps)
    least = max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (secs / runs)

"""plan_est_error_pct: how far the planner's step time for its pick
(``decisions[0].time``) lies from the measured step, in percent of the
measured step.  Moves ``train_tokens_per_s`` through the plan ranking."""


def read(rec):
    if "plan_est_step_s" not in rec:
        return None
    return 100.0 * abs(rec["plan_est_step_s"] / rec["step_s"] - 1.0)

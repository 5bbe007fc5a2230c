"""serve_wasted_slot_pct: the engine's padding decodes (``stats[
"wasted_slot_steps"]``) over all slot-steps (decode steps x batch) in the
window.  Moves ``serve_tokens_per_s``."""


def read(rec):
    if not rec.get("slot_steps"):
        return None
    return 100.0 * rec["wasted_slot_steps"] / rec["slot_steps"]

"""Golden regression: the sweep grid's winning plans and costs must match
the checked-in tests/golden/sweep_golden.json cell for cell.

Cost-model drift (op formulas, collective models, HBM accounting, plan
enumeration, search behavior) shows up here as a readable diff at review
time.  If the change is intentional, regenerate and commit:

  PYTHONPATH=src python tests/golden/regen_sweep_golden.py
"""
import importlib.util
import json
import math
import os

_GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "golden")

# Import the regen script itself, so the grid definition and the cell
# builder can never drift between the test and the regeneration path.
_spec = importlib.util.spec_from_file_location(
    "regen_sweep_golden", os.path.join(_GOLDEN_DIR, "regen_sweep_golden.py"))
_regen = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_regen)


# Frozen pre-torus (PR-3) step times of every 2D golden cell.  The 3D-torus
# topology work (per-axis link counts, depth-axis roles, 3D candidates) must
# be purely additive: a 2D mesh prices exactly as it did before the new
# axis existed.  Any drift here means the flat model was disturbed — which
# is a bug, not a regeneration event (the new-axis cells live in
# sweep_golden.json and MAY move with intentional cost-model changes; these
# may not).  The only sanctioned move is a re-anchoring against the
# compiler or the chip: the train cells here moved once, when the HBM
# pre-filter's terms were reconciled with the compiled v5e train step's
# memory analysis and some winners changed.
PRE_TORUS_2D_STEP_TIMES = {
    "gemma3-12b|decode_32k|2pod": 0.005640601213984056,
    "gemma3-12b|decode_32k|pod": 0.01040860402796811,
    "gemma3-12b|decode_32k|v5p-pod": 0.011174433533523029,
    "gemma3-12b|decode_32k|v6e-pod": 0.005640400373059142,
    "gemma3-12b|train_4k|2pod": 2.0131965372365612,
    "gemma3-12b|train_4k|pod": 5.312043982447061,
    "gemma3-12b|train_4k|v5p-pod": 5.649483478591187,
    "gemma3-12b|train_4k|v6e-pod": 1.3457369376433121,
    "mamba2-1.3b|decode_32k|2pod": 2.8364671636859875e-05,
    "mamba2-1.3b|decode_32k|pod": 5.487094327371975e-05,
    "mamba2-1.3b|decode_32k|v5p-pod": 6.465244810658441e-05,
    "mamba2-1.3b|decode_32k|v6e-pod": 2.833234691535151e-05,
    "mamba2-1.3b|train_4k|2pod": 0.2823090089153571,
    "mamba2-1.3b|train_4k|pod": 0.3716940020409412,
    "mamba2-1.3b|train_4k|v5p-pod": 0.4217763868538556,
    "mamba2-1.3b|train_4k|v6e-pod": 0.09377382110569207,
    "qwen1.5-0.5b|decode_32k|2pod": 0.0016120377649572653,
    "qwen1.5-0.5b|decode_32k|pod": 0.0027855075299145302,
    "qwen1.5-0.5b|decode_32k|v5p-pod": 0.002752198992027129,
    "qwen1.5-0.5b|decode_32k|v6e-pod": 0.0016120126856368562,
    "qwen1.5-0.5b|train_4k|2pod": 0.14174568218918163,
    "qwen1.5-0.5b|train_4k|pod": 0.1252018982518281,
    "qwen1.5-0.5b|train_4k|v5p-pod": 0.1652115560696153,
    "qwen1.5-0.5b|train_4k|v6e-pod": 0.039441677448381694,
}


# Frozen pre-pipeline (PR-4) step times of every golden cell that existed
# before pipeline parallelism became a costed construct — the 24 2D cells
# above plus the six v5p-3d cells.  Teaching the stack pipelined loops
# (pp roles, P2P pricing, the PipelinedLoopBlock schedule) must be purely
# additive on these: every pre-pipeline winner keeps its exact cost.  The
# new pipeline cells (arch qwen1.5-110b, cluster v5p-dcn) live only in
# sweep_golden.json and MAY move with intentional cost-model changes;
# these may not.
PRE_PIPELINE_STEP_TIMES = dict(PRE_TORUS_2D_STEP_TIMES)
PRE_PIPELINE_STEP_TIMES.update({
    "gemma3-12b|decode_32k|v5p-3d": 0.011174433533523029,
    "gemma3-12b|train_4k|v5p-3d": 4.616045256762777,
    "mamba2-1.3b|decode_32k|v5p-3d": 6.465244810658441e-05,
    "mamba2-1.3b|train_4k|v5p-3d": 0.4083825957427445,
    "qwen1.5-0.5b|decode_32k|v5p-3d": 0.002752198992027129,
    "qwen1.5-0.5b|train_4k|v5p-3d": 0.159472259773319,
})


def test_pre_pipeline_cells_unchanged_by_pipeline_parallelism():
    """The checked-in golden file's pre-pipeline cells must equal the
    frozen PR-4 baseline bit for bit — pipelined loops are additive — and
    the grid must actually contain a *winning* pipelined cell (the
    frontier-dense train cell that only fits with stages over DCN)."""
    with open(_regen.GOLDEN_PATH) as f:
        golden = json.load(f)
    drift = []
    for key, want in PRE_PIPELINE_STEP_TIMES.items():
        got = golden.get(key)
        if got is None:
            drift.append(f"{key}: cell missing from golden")
        elif got["step_time_s"] != want:
            drift.append(f"{key}: {want!r} -> {got['step_time_s']!r}")
    assert not drift, (
        "pre-pipeline golden cells moved — the pipeline-parallelism "
        "change leaked into existing plans:\n  " + "\n  ".join(drift))
    pipelined = [k for k, v in golden.items() if "pp=" in v["plan"]]
    assert pipelined, "golden grid has no pipelined winner"
    assert any(golden[k]["feasible"] and "dcn" in k for k in pipelined), \
        "no feasible pipelined winner on a DCN multi-slice cell"


def test_2d_cells_unchanged_by_torus_topology():
    """The checked-in golden file's 2D cells must equal the frozen
    pre-torus baseline bit for bit — the 3D axis is additive."""
    with open(_regen.GOLDEN_PATH) as f:
        golden = json.load(f)
    drift = []
    for key, want in PRE_TORUS_2D_STEP_TIMES.items():
        got = golden.get(key)
        if got is None:
            drift.append(f"{key}: cell missing from golden")
        elif got["step_time_s"] != want:
            drift.append(f"{key}: {want!r} -> {got['step_time_s']!r}")
    assert not drift, (
        "2D golden cells moved — the torus topology change leaked into "
        "the flat model:\n  " + "\n  ".join(drift))
    # and the golden grid actually gained the 3D family
    assert any(k.endswith("|v5p-3d") for k in golden), \
        "golden grid has no v5p-3d cells"


def test_serving_cells_present_and_additive():
    """The golden grid gained the PR-6 serving cells (workload chat_2k on
    every golden cluster) without moving a single train/decode cell: the
    pre-pipeline frozen baselines above still pin those, and this test
    pins the serving family's existence and shape."""
    with open(_regen.GOLDEN_PATH) as f:
        golden = json.load(f)
    serve_keys = [k for k in golden if "|chat_2k|" in k]
    want = {f"{a}|chat_2k|{c}" for a in _regen.GOLDEN_SERVE_ARCHS
            for c in _regen.GOLDEN_CLUSTERS}
    assert set(serve_keys) == want
    assert any(golden[k]["feasible"] for k in serve_keys)


def test_sweep_grid_matches_golden():
    with open(_regen.GOLDEN_PATH) as f:
        golden = json.load(f)
    got = _regen.compute_cells()
    assert len(golden) >= 60
    assert set(got) == set(golden), (
        "grid keys drifted — regenerate the golden file if intentional")
    drift = []
    for key, want in golden.items():
        cell = got[key]
        if cell["plan"] != want["plan"]:
            drift.append(f"{key}: plan {want['plan']} -> {cell['plan']}")
        if not math.isclose(cell["step_time_s"], want["step_time_s"],
                            rel_tol=1e-9):
            drift.append(f"{key}: step {want['step_time_s']:.6g}s -> "
                         f"{cell['step_time_s']:.6g}s")
        if not math.isclose(cell["hbm_est_bytes"], want["hbm_est_bytes"],
                            rel_tol=1e-9):
            drift.append(f"{key}: hbm {want['hbm_est_bytes']:.6g} -> "
                         f"{cell['hbm_est_bytes']:.6g}")
        if cell["feasible"] != want["feasible"]:
            drift.append(f"{key}: feasible {want['feasible']} -> "
                         f"{cell['feasible']}")
    assert not drift, (
        "cost-model drift vs tests/golden/sweep_golden.json "
        "(PYTHONPATH=src python tests/golden/regen_sweep_golden.py "
        "if intentional):\n  " + "\n  ".join(drift))

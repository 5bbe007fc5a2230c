"""The estimate↔reality loop (repro.core.calibration + the online
recalibrator):

* the least-squares fitter recovers known synthetic factors, clamps
  super-peak coefficients, and rejects polluted samples;
* ``calibration=None`` (and the empty profile) change nothing — golden
  cells stay byte-identical;
* ``PlanCostCache`` keeps calibrated and uncalibrated costs apart
  (cluster-fingerprint separation);
* the drift band triggers a refit when the EWMA leaves it, and the
  drift-triggered ``elastic.replan`` fires exactly when the re-costed
  plan ranking flips — not merely when the ratio moves;
* profile (de)serialization round-trips (hypothesis).
"""
import json
import math

import pytest

from repro.configs import SHAPES, get_config
from repro.core.calibration import (SHAPE_CLASSES, CalibrationProfile,
                                    CalibrationSample, fit_profile,
                                    shape_class)
from repro.core.cluster import single_pod_config
from repro.core.costmodel import PlanCostCache, estimate
from repro.core.planner import (OVERLAP_FRACTION, build_step_program,
                                choose_plan, enumerate_plans)
from repro.core.sweep import SweepEngine
from repro.runtime.train_loop import OnlineRecalibrator


# ---------------------------------------------------------------------------
# The fitter
# ---------------------------------------------------------------------------

def test_fitter_recovers_synthetic_factors():
    """Generated (features, measured) pairs with known achieved fractions:
    the fit must invert them near-exactly (the system is well-posed)."""
    true = {"mxu:bfloat16:large": 0.55, "hbm": 0.80, "ici": 0.40}
    mixes = [
        {"mxu:bfloat16:large": 1.0, "hbm": 0.2, "ici": 0.05},
        {"mxu:bfloat16:large": 0.1, "hbm": 1.5, "ici": 0.30},
        {"mxu:bfloat16:large": 0.5, "hbm": 0.1, "ici": 1.20},
        {"mxu:bfloat16:large": 2.0, "hbm": 0.6, "ici": 0.70},
    ]
    samples = [
        CalibrationSample(
            features=m,
            measured_seconds=0.01 + sum(x / true[k] for k, x in m.items()),
            fixed_seconds=0.01, label=f"synth{i}")
        for i, m in enumerate(mixes)
    ]
    fit = fit_profile(samples, chip_name="synth")
    assert fit.n_samples == 4 and fit.n_rejected == 0
    for k, f in true.items():
        assert math.isclose(fit.factors[k], f, rel_tol=1e-6), k
    assert math.isclose(fit.profile.mxu["bfloat16"]["large"], 0.55,
                        rel_tol=1e-6)
    assert math.isclose(fit.profile.hbm_fraction, 0.80, rel_tol=1e-6)
    assert math.isclose(fit.profile.ici_fraction, 0.40, rel_tol=1e-6)
    assert fit.profile.dcn_fraction is None     # no feature mass -> absent
    assert fit.residual < 1e-6


def test_fitter_clamps_factors_into_bounds():
    # measured faster than ideal-at-peak: clamp to max_factor (a profile
    # must never promise super-peak rates — floor soundness)
    fast = [CalibrationSample(features={"hbm": 1.0}, measured_seconds=0.5)]
    assert fit_profile(fast).factors["hbm"] == 1.0
    # absurdly slow: clamp to min_factor
    slow = [CalibrationSample(features={"hbm": 1.0}, measured_seconds=1000.0)]
    assert fit_profile(slow).factors["hbm"] == pytest.approx(0.02)


def test_fitter_rejects_polluted_and_degenerate_samples():
    clean = CalibrationSample(features={"hbm": 1.0}, measured_seconds=2.0)
    polluted = CalibrationSample(features={"hbm": 1.0}, measured_seconds=9.0,
                                 polluted=True)
    negative = CalibrationSample(features={"hbm": 1.0}, measured_seconds=0.1,
                                 fixed_seconds=0.2)   # y <= 0
    empty = CalibrationSample(features={}, measured_seconds=1.0)
    fit = fit_profile([clean, polluted, negative, empty])
    assert fit.n_samples == 1 and fit.n_rejected == 3
    assert fit.factors["hbm"] == pytest.approx(0.5)
    # nothing usable at all -> identity profile, not a crash
    empty_fit = fit_profile([polluted, empty])
    assert empty_fit.profile.is_empty() and empty_fit.n_samples == 0


def test_shape_class_breakpoints_match_util_ramp():
    assert shape_class(1e7) == "small"
    assert shape_class(1e8) == "small"
    assert shape_class(1e9) == "medium"
    assert shape_class(1e10) == "large"
    assert shape_class(1e12) == "large"


# ---------------------------------------------------------------------------
# Bit-identity of the uncalibrated path
# ---------------------------------------------------------------------------

# Frozen step times from tests/test_golden_sweep.py's pre-pipeline
# baseline (which moves only when re-anchored against the compiler or the
# chip): cells built with the default ``calibration=None`` must
# reproduce them to the last bit — the calibration threading may not
# perturb the uncalibrated walk in any way.
FROZEN_CELLS = {
    "mamba2-1.3b|train_4k|pod": 0.3716940020409412,
    "qwen1.5-0.5b|decode_32k|v6e-pod": 0.0016120126856368562,
}


def test_calibration_none_keeps_golden_cells_byte_identical():
    engine = SweepEngine(search="beam")
    cells = engine.sweep(("mamba2-1.3b", "qwen1.5-0.5b"),
                         ("train_4k", "decode_32k"), ("pod", "v6e-pod"))
    got = {c.key: c.decision.time for c in cells}
    for key, frozen in FROZEN_CELLS.items():
        assert got[key] == frozen, key     # exact, not approx


def test_empty_profile_is_exact_identity():
    """An all-``None`` profile attached to the config changes nothing:
    every consultation falls back to the hand-set constants."""
    arch, shape = get_config("qwen1.5-0.5b"), SHAPES["train_4k"]
    cc = single_pod_config()
    cc_id = cc.with_calibration(CalibrationProfile(chip_name="x"))
    plan = enumerate_plans(arch, shape, cc)[0]
    for occ, occ_id in ((cc, cc_id),
                        (cc.with_overlap(OVERLAP_FRACTION),
                         cc_id.with_overlap(OVERLAP_FRACTION))):
        a = estimate(build_step_program(arch, shape, plan, occ), occ)
        b = estimate(build_step_program(arch, shape, plan, occ_id), occ_id)
        assert a.total == b.total          # bit-identical
        assert a.breakdown.collective == b.breakdown.collective


def test_calibrated_factors_slow_the_estimate():
    """Factors strictly below the hand-set constants can only add time."""
    arch, shape = get_config("qwen1.5-0.5b"), SHAPES["train_4k"]
    cc = single_pod_config()
    slow = CalibrationProfile(
        chip_name=cc.chip.name,
        mxu={dt: {c: 0.25 for c in SHAPE_CLASSES}
             for dt in ("bfloat16", "float32")},
        hbm_fraction=0.4, ici_fraction=0.3, dcn_fraction=0.3)
    plan = enumerate_plans(arch, shape, cc)[0]
    base = estimate(build_step_program(arch, shape, plan, cc), cc)
    cal = estimate(build_step_program(arch, shape, plan, cc),
                   cc.with_calibration(slow))
    assert cal.total > base.total


# ---------------------------------------------------------------------------
# Cache separation
# ---------------------------------------------------------------------------

def test_plan_cost_cache_never_mixes_calibrated_and_uncalibrated():
    arch, shape = get_config("qwen1.5-0.5b"), SHAPES["train_4k"]
    cc = single_pod_config()
    profile = CalibrationProfile(chip_name=cc.chip.name, hbm_fraction=0.3,
                                 mxu={"bfloat16": {"large": 0.3}})
    cc_cal = cc.with_calibration(profile)
    assert cc.fingerprint() != cc_cal.fingerprint()
    assert cc.fingerprint()[-1] is None
    assert cc_cal.fingerprint()[-1] == profile.fingerprint()

    cache = PlanCostCache()
    plan = enumerate_plans(arch, shape, cc)[0]
    prog = build_step_program(arch, shape, plan, cc)
    first = estimate(prog, cc, cache=cache).total
    calibrated = estimate(prog, cc_cal, cache=cache).total
    again = estimate(prog, cc, cache=cache).total
    assert calibrated > first              # the slow profile took effect
    assert again == first                  # cache did not cross-serve


# ---------------------------------------------------------------------------
# The online loop: drift band -> refit -> replan iff the ranking flips
# ---------------------------------------------------------------------------

def _flip_candidates(arch, shape, cc):
    """The verified swapped pair on mamba2-1.3b x train_4k x single pod:
    under a profile fitted from plan a's drifted (x8) step times, a's
    re-costed time overtakes b's, flipping the ranking."""
    plans = {p.describe(): p for p in enumerate_plans(arch, shape, cc)}
    a = plans["dp+tp[batch=data,tp=model,remat=full,ubatch=2]"]
    b = plans["dp+tp[batch=data,tp=model,remat=full,ubatch=4,"
              "gdtype=bfloat16]"]
    return a, b


def test_in_band_measurements_never_trigger():
    arch, shape = get_config("mamba2-1.3b"), SHAPES["train_4k"]
    cc = single_pod_config()
    rec = OnlineRecalibrator(arch, shape, cc)
    for step in range(20):
        assert rec.observe(rec.estimated * 1.05, step=step) is None
    assert rec.events == [] and rec.cc.calibration is None


def test_uniform_drift_refits_without_replan():
    """A single-candidate family can never flip: drift must refit the
    profile (the ratio left the band) but NOT fire elastic.replan."""
    arch, shape = get_config("mamba2-1.3b"), SHAPES["train_4k"]
    cc = single_pod_config()
    a, _ = _flip_candidates(arch, shape, cc)
    rec = OnlineRecalibrator(arch, shape, cc, candidates=[a])
    est0 = rec.estimated
    measured = est0 * 3.0                  # the drifted reality, fixed
    events = []
    for step in range(200):
        e = rec.observe(measured, step=step)
        if e is not None:
            events.append(e)
    assert events                           # drift tripped the band
    for e in events:
        assert not e.replanned and e.elastic is None
        assert not e.profile.is_empty()
    assert rec.plan == a
    assert rec.cc.calibration is not None   # estimates now calibrated
    # each refit pulls the calibrated estimate toward the measurement
    # (the linearized fit can't exactly match the max-roofline walk, so
    # convergence may take more than one refit — but it must improve)
    assert abs(rec.estimated - measured) < abs(est0 - measured)


def test_drift_triggers_replan_exactly_when_ranking_flips():
    """End-to-end: perturb measured step times until the re-costed plan
    ranking flips — the event must carry the elastic.replan decision that
    switches the job onto the new winner, priced under the fitted
    profile."""
    arch, shape = get_config("mamba2-1.3b"), SHAPES["train_4k"]
    cc = single_pod_config()
    a, b = _flip_candidates(arch, shape, cc)
    cache = PlanCostCache()
    rec = OnlineRecalibrator(arch, shape, cc, candidates=[a, b], cache=cache)
    assert rec.plan == a                   # a wins uncalibrated

    # a dozen in-band steps: nothing happens
    for step in range(12):
        assert rec.observe(rec.estimated, step=step) is None

    # drift: measured step times settle at 8x the estimate.  The min-norm
    # fit loads the drift onto a's term mix, which penalizes a (fewer,
    # larger microbatches and a float32 gradient reduce) harder than b —
    # the ranking flips.
    event = None
    for step in range(12, 64):
        event = rec.observe(rec.estimated * 8.0, step=step)
        if event is not None:
            break
    assert event is not None and event.replanned
    assert event.ratio > 1.18              # the band's upper edge
    assert event.old_plan == a.describe()
    assert event.new_plan == b.describe()
    assert event.elastic is not None
    assert event.elastic.decision.plan == b
    assert event.elastic.cc.calibration is not None
    # the recalibrator adopted the new winner and its calibrated estimate
    assert rec.plan == b
    assert rec.cc.calibration is not None
    assert rec.estimated == pytest.approx(
        choose_plan(arch, shape,
                    rec.cc, top_k=1, candidates=[a, b])[0].time)


# ---------------------------------------------------------------------------
# (De)serialization round-trip
# ---------------------------------------------------------------------------

def _roundtrip(p: CalibrationProfile) -> None:
    assert CalibrationProfile.loads(p.dumps()) == p
    wire = json.loads(json.dumps(p.to_json()))      # a real wire trip
    assert CalibrationProfile.from_json(wire) == p
    assert CalibrationProfile.loads(p.dumps()).fingerprint() == p.fingerprint()


def test_profile_serialization_roundtrip_fixed_cases():
    _roundtrip(CalibrationProfile())
    _roundtrip(CalibrationProfile(chip_name="tpu_v5e",
                                  mxu={"bfloat16": {"large": 0.61}}))
    _roundtrip(CalibrationProfile(
        chip_name="cpu_host",
        mxu={"bfloat16": {"small": 0.21, "medium": 0.5, "large": 0.68},
             "float64": {"large": 1.0}},
        hbm_fraction=1 / 3, ici_fraction=0.55, dcn_fraction=0.625,
        overlap_ici=0.45, overlap_dcn=0.2))


# The generative version runs where hypothesis is installed (CI's
# requirements-dev tier); the fixed cases above keep local coverage.
try:
    from hypothesis import given, settings, strategies as st

    _frac = st.one_of(st.none(), st.floats(min_value=0.02, max_value=1.0,
                                           allow_nan=False))
    _mxu = st.dictionaries(
        st.sampled_from(["bfloat16", "float32", "float64", "int8"]),
        st.dictionaries(st.sampled_from(list(SHAPE_CLASSES)),
                        st.floats(min_value=0.02, max_value=1.0,
                                  allow_nan=False), max_size=3),
        max_size=3)

    @settings(max_examples=60, deadline=None)
    @given(mxu=_mxu, hbm=_frac, ici=_frac, dcn=_frac, oi=_frac, od=_frac)
    def test_profile_serialization_roundtrip(mxu, hbm, ici, dcn, oi, od):
        _roundtrip(CalibrationProfile(
            chip_name="chip", mxu=mxu, hbm_fraction=hbm, ici_fraction=ici,
            dcn_fraction=dcn, overlap_ici=oi, overlap_dcn=od))
except ImportError:      # pragma: no cover - exercised on bare containers
    @pytest.mark.skip(reason="property round-trip needs hypothesis "
                      "(pip install -r requirements-dev.txt)")
    def test_profile_serialization_roundtrip():
        pass

"""Operator fusion as a costed plan dimension (ISSUE 9).

Four families of guarantees:

  * the two op-profile bugfixes — ssd_scan's inter-chunk state traffic
    ceils instead of flooring to zero, and windowed-causal attention gets
    the exact averaged keys-per-query discount (the legacy path granted
    frac=0.5 only at eff_kv == skv == sq);
  * fused <= unfused HBM bytes on every emitted variant, at the op level
    and over whole generated plans (deterministic sweeps here; the
    hypothesis-randomized versions run when hypothesis is installed);
  * ``fusion="off"`` (the default everywhere) stays bit-identical to the
    frozen PRE_FUSION golden cells — the knob cannot move old numbers;
  * the batched/vectorized coster is bit-exact across fusion structure
    groups, and the ``PlanCostCache`` fingerprint separates fusion
    settings (no cross-contamination through a shared cache).
"""
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.configs import SHAPES, get_config
from repro.core.cluster import multi_pod_config, single_pod_config
from repro.core.costmodel import PlanCostCache, estimate
from repro.core.linalg_ops import avg_keys_per_query, profile
from repro.core.planner import (SearchStats, _cost_candidate,
                                _cost_group_vectorized, _structure_key,
                                build_step_program, choose_plan,
                                enumerate_plans)
from repro.core.symbols import TensorStat
from repro.core.sweep import CLUSTERS

POD = single_pod_config()
MULTI = multi_pod_config()

# ---------------------------------------------------------------------------
# Frozen pre-fusion baseline: beam choose_plan with every default, captured
# before the fusion knob landed.  Fusion work must never change these — the
# knob defaults to "off" and "off" is the legacy program tree bit for bit.
# They move only when re-anchored against the compiler or the chip (the
# train cells moved with the compiled-memory HBM terms).
# ---------------------------------------------------------------------------
PRE_FUSION_STEP_TIMES = {
    ("qwen1.5-0.5b", "train_4k", "pod"): 0.1252018982518281,
    ("qwen1.5-0.5b", "decode_32k", "pod"): 0.0027855075299145302,
    ("qwen1.5-0.5b", "decode_32k", "v5p-pod"): 0.002752198992027129,
    ("gemma3-12b", "train_4k", "v5p-pod"): 5.649483478591187,
    ("gemma3-12b", "decode_32k", "v5p-pod"): 0.011174433533523029,
    ("mamba2-1.3b", "train_4k", "pod"): 0.3716940020409412,
    ("mamba2-1.3b", "decode_32k", "v6e-pod"): 2.833234691535151e-05,
    ("qwen1.5-110b", "train_4k", "v5p-dcn"): 21.582674763321936,
}


def test_fusion_off_bit_identical_to_pre_fusion_golden():
    cache = PlanCostCache()
    for (arch_id, shape_id, cl), want in PRE_FUSION_STEP_TIMES.items():
        best = choose_plan(get_config(arch_id), SHAPES[shape_id],
                           CLUSTERS[cl], cache=cache)[0]
        assert best.cost.total == want, (arch_id, shape_id, cl)
        assert best.plan.fusion == "off"


# ---------------------------------------------------------------------------
# Bugfix 1: ssd_scan inter-chunk state traffic
# ---------------------------------------------------------------------------
def _ssd_state_bytes(s, chunk=256, b=2, h=8, p=64, n=128):
    prof = profile("ssd_scan", [TensorStat((b, s, h, p), "bfloat16")],
                   state=n, chunk=chunk)
    x_bytes = b * s * h * p * 2
    return prof.read_bytes - x_bytes


def test_ssd_short_sequence_still_pays_state_traffic():
    """s < chunk used to floor to ZERO state bytes; now exactly one chunk."""
    one_chunk = 2 * 8 * 64 * 128 * 2          # b*h*p*n * bf16
    assert _ssd_state_bytes(100, chunk=256) == one_chunk
    assert _ssd_state_bytes(1, chunk=256) == one_chunk    # decode step
    # divisible sequences are unchanged by the ceil (floor == ceil there)
    assert _ssd_state_bytes(512, chunk=256) == 2 * one_chunk
    # and a ragged tail rounds UP, not down
    assert _ssd_state_bytes(700, chunk=256) == 3 * one_chunk


# ---------------------------------------------------------------------------
# Bugfix 2: windowed-causal attention discount
# ---------------------------------------------------------------------------
def test_avg_keys_per_query_closed_form():
    # full causal self-attention: classic (n+1)/2
    assert avg_keys_per_query(4096, 4096, None, True) == (1 + 4096) / 2.0
    # window overhanging the sequence start: mixed regime, exact average
    assert avg_keys_per_query(4096, 4096, 1024, True) == 896.125
    # window never binding (w >= skv): same as unwindowed
    assert avg_keys_per_query(4096, 4096, 8192, True) == (1 + 4096) / 2.0
    # decode suffix (sq=1 of a long context): window fully binding
    assert avg_keys_per_query(1, 32768, 1024, True) == 1024.0
    # non-causal: plain window size
    assert avg_keys_per_query(4096, 4096, 1024, False) == 1024.0
    # brute-force cross-check of the mixed regime
    sq = skv = 64
    w = 16
    brute = sum(min(skv - sq + i + 1, w) for i in range(sq)) / sq
    assert avg_keys_per_query(sq, skv, w, True) == brute


def test_windowed_causal_attention_now_discounted():
    """Legacy path charges the full window everywhere (frac=1 since
    eff_kv != skv); the fused variant pays only the averaged visible keys."""
    q = TensorStat((1, 8, 4096, 128), "bfloat16")
    k = v = TensorStat((1, 8, 4096, 128), "bfloat16")
    legacy = profile("attention", [q, k, v], causal=True, window=1024)
    fused = profile("attention", [q, k, v], causal=True, window=1024,
                    fused=True)
    assert legacy.flops > fused.flops
    # exact ratio: legacy charges eff_kv=1024 per query, fused 896.125
    assert fused.flops == pytest.approx(legacy.flops * 896.125 / 1024.0)
    # unwindowed full causal is unchanged in flops (0.5 == (n+1)/2n asympt.)
    full_legacy = profile("attention", [q, k, v], causal=True)
    full_fused = profile("attention", [q, k, v], causal=True, fused=True)
    assert full_fused.flops == pytest.approx(full_legacy.flops, rel=1e-3)


# ---------------------------------------------------------------------------
# Fused <= unfused, op level
# ---------------------------------------------------------------------------
def _attn_stats(b, hq, hkv, sq, skv, d, dtype="bfloat16"):
    return [TensorStat((b, hq, sq, d), dtype),
            TensorStat((b, hkv, skv, d), dtype),
            TensorStat((b, hkv, skv, d), dtype)]


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window", [
    (1, 8, 8, 4096, 4096, 128, True, None),
    (4, 16, 4, 1, 32768, 128, True, None),         # decode
    (1, 8, 2, 4096, 4096, 64, True, 1024),         # sliding window
    (2, 4, 4, 512, 512, 64, False, None),
])
def test_attention_fused_cheaper_than_materialized(b, hq, hkv, sq, skv, d,
                                                   causal, window):
    ins = _attn_stats(b, hq, hkv, sq, skv, d)
    fused = profile("attention", list(ins), causal=causal, window=window,
                    fused=True)
    mat = profile("attention", list(ins), causal=causal, window=window,
                  fused=False)
    assert fused.flops == mat.flops          # the delta is traffic only
    assert fused.read_bytes < mat.read_bytes
    assert fused.write_bytes < mat.write_bytes
    # the delta is exactly the score matrix's round trip (fp32 scores +
    # input-width probs, written once and read once each)
    score_cells = b * hq * sq * skv
    assert mat.read_bytes - fused.read_bytes == score_cells * (4 + 2)
    assert mat.write_bytes - fused.write_bytes == score_cells * (4 + 2)


@pytest.mark.parametrize("epi,ew_op", [("silu", "silu"), ("gelu", "gelu"),
                                       ("layernorm", "layernorm")])
def test_matmul_epilogue_cheaper_than_separate_op(epi, ew_op):
    m, k, n = 8192, 4096, 4096
    a = TensorStat((m, k), "bfloat16")
    w = TensorStat((k, n), "bfloat16")
    fused = profile("matmul", [a, w], epilogue=epi)
    plain = profile("matmul", [a, w])
    sep = profile(ew_op, [plain.out])
    # same arithmetic: the epilogue charge equals the standalone op's flops
    assert fused.flops == plain.flops + sep.flops
    # strictly less traffic: the m x n intermediate never round-trips
    fused_bytes = fused.read_bytes + fused.write_bytes
    unfused_bytes = (plain.read_bytes + plain.write_bytes
                     + sep.read_bytes + sep.write_bytes)
    assert fused_bytes < unfused_bytes
    inter = m * n * 2                        # bf16 intermediate
    assert unfused_bytes - fused_bytes == 2 * inter   # write + re-read


def test_matmul_cast_sinking_beats_materialized_cast():
    m, k, n = 8192, 4096, 4096
    a = TensorStat((m, k), "float32")
    w = TensorStat((k, n), "float32")
    sunk = profile("matmul", [a, w], sink_cast_bytes=2)
    plain = profile("matmul", [a, w])
    cast = profile("cast", [plain.out], from_bytes=4, to_bytes=2)
    assert sunk.write_bytes == m * n * 2
    sunk_total = sunk.read_bytes + sunk.write_bytes
    unfused_total = (plain.read_bytes + plain.write_bytes
                     + cast.read_bytes + cast.write_bytes)
    assert sunk_total < unfused_total


def test_matmul_epilogue_epi_cols_narrows_the_charge():
    a = TensorStat((1024, 512), "bfloat16")
    w = TensorStat((512, 3 * 1024), "bfloat16")   # fused gated-MLP proj
    narrow = profile("matmul", [a, w], epilogue="silu", epi_cols=1024)
    wide = profile("matmul", [a, w], epilogue="silu")
    plain = profile("matmul", [a, w])
    assert narrow.flops - plain.flops == 6.0 * 1024 * 1024
    assert wide.flops - plain.flops == 6.0 * 1024 * (3 * 1024)


# ---------------------------------------------------------------------------
# Fused <= unfused, whole generated plans
# ---------------------------------------------------------------------------
_PLAN_CELLS = [("qwen1.5-0.5b", "train_4k", POD),
               ("qwen1.5-0.5b", "decode_32k", POD),
               ("gemma3-12b", "decode_32k", POD),
               ("mamba2-1.3b", "train_4k", POD),
               ("qwen1.5-0.5b", "train_4k", MULTI)]


@pytest.mark.parametrize("arch_id,shape_id", sorted({(a, s)
                                                     for a, s, _ in _PLAN_CELLS}))
def test_plan_level_fused_hbm_never_exceeds_materialized(arch_id, shape_id):
    arch, shape = get_config(arch_id), SHAPES[shape_id]
    cc = POD
    by_fusion = {}
    for plan in enumerate_plans(arch, shape, cc, fusion="search"):
        key = (plan.name, plan.remat, plan.microbatches,
               plan.grad_reduce_dtype)
        by_fusion.setdefault(key, {})[plan.fusion] = estimate(
            build_step_program(arch, shape, plan, cc), cc).totals.hbm_bytes
    assert by_fusion
    for key, totals in by_fusion.items():
        assert set(totals) == {"off", "none", "full"}, key
        assert totals["full"] <= totals["none"], key
        # "off" is the fusion-blind legacy tree: between the two honest
        # variants it under-counts the materialized plan
        assert totals["off"] <= totals["none"], key


def test_fusion_search_widens_space_and_beam_matches_exhaustive():
    arch, shape = get_config("qwen1.5-0.5b"), SHAPES["decode_32k"]
    assert len(enumerate_plans(arch, shape, POD, fusion="search")) == \
        3 * len(enumerate_plans(arch, shape, POD))
    beam = choose_plan(arch, shape, POD, fusion="search")[0]
    exh = choose_plan(arch, shape, POD, search="exhaustive",
                      fusion="search")[0]
    assert beam.cost.total == exh.cost.total
    assert beam.plan.fusion == exh.plan.fusion


# ---------------------------------------------------------------------------
# Batched costing: bit-exact across fusion structure groups
# ---------------------------------------------------------------------------
def test_structure_key_separates_fusion_settings():
    arch, shape = get_config("qwen1.5-0.5b"), SHAPES["train_4k"]
    plans = enumerate_plans(arch, shape, POD, fusion="search")
    p = plans[0]
    keys = {f: _structure_key(
        type(p)(**{**p.__dict__, "fusion": f}), shape.mode)
        for f in ("off", "none", "full")}
    assert len(set(keys.values())) == 3


def test_batched_walk_bit_exact_across_fusion_groups():
    arch = get_config("qwen1.5-0.5b")
    for shape_id, cc in (("train_4k", POD), ("decode_32k", POD),
                         ("train_4k", MULTI)):
        shape = SHAPES[shape_id]
        groups = {}
        for p in enumerate_plans(arch, shape, cc, fusion="search"):
            groups.setdefault(_structure_key(p, shape.mode), []).append(p)
        fusions_seen = set()
        for members in groups.values():
            fusions_seen.add(members[0].fusion)
            assert len({m.fusion for m in members}) == 1   # never mixed
            if len(members) < 2:
                continue
            vec = _cost_group_vectorized(arch, shape, members, cc)
            for p, got in zip(members, vec):
                base = _cost_candidate(arch, shape, p, cc, None,
                                       SearchStats()).cost
                assert got.total == base.total, p.describe()
                assert got.totals.as_tuple() == base.totals.as_tuple(), \
                    p.describe()
        assert fusions_seen == {"off", "none", "full"}


def test_batched_search_matches_exhaustive_over_fusion_space():
    arch, shape = get_config("qwen1.5-0.5b"), SHAPES["decode_32k"]
    bat = choose_plan(arch, shape, POD, search="batched", fusion="search")[0]
    exh = choose_plan(arch, shape, POD, search="exhaustive",
                      fusion="search")[0]
    assert bat.cost.total == exh.cost.total
    assert bat.plan.fusion == exh.plan.fusion


# ---------------------------------------------------------------------------
# Cache-fingerprint separation
# ---------------------------------------------------------------------------
def test_shared_cache_never_mixes_fusion_settings():
    arch, shape = get_config("qwen1.5-0.5b"), SHAPES["train_4k"]
    base = enumerate_plans(arch, shape, POD)[0]
    cache = PlanCostCache()

    def cost(f):
        plan = type(base)(**{**base.__dict__, "fusion": f})
        return estimate(build_step_program(arch, shape, plan, POD), POD,
                        cache=cache)

    cold = {f: cost(f).total for f in ("off", "none", "full")}
    assert len(set(cold.values())) == 3       # three distinct plans
    # warm replay through the now-populated shared cache: bit-identical
    for f, want in cold.items():
        assert cost(f).total == want, f


# ---------------------------------------------------------------------------
# Hypothesis-randomized properties (skipped when hypothesis is absent; the
# deterministic sweeps above run always)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    _dim = st.integers(min_value=1, max_value=64).map(lambda x: x * 8)
    _seq = st.integers(min_value=1, max_value=512).map(lambda x: x * 8)

    @settings(max_examples=60, deadline=None)
    @given(b=st.integers(1, 8), h=st.integers(1, 16), sq=_seq, skv=_seq,
           d=_dim, causal=st.booleans(),
           window=st.one_of(st.none(), st.integers(8, 4096)))
    def test_prop_attention_fused_never_more_bytes(b, h, sq, skv, d,
                                                   causal, window):
        if sq > skv:
            sq = skv                         # suffix convention
        ins = _attn_stats(b, h, h, sq, skv, d)
        fused = profile("attention", list(ins), causal=causal,
                        window=window, fused=True)
        mat = profile("attention", list(ins), causal=causal,
                      window=window, fused=False)
        assert fused.flops == mat.flops
        assert fused.read_bytes <= mat.read_bytes
        assert fused.write_bytes <= mat.write_bytes

    @settings(max_examples=60, deadline=None)
    @given(sq=st.integers(1, 4096), extra=st.integers(0, 4096),
           w=st.one_of(st.none(), st.integers(1, 8192)),
           causal=st.booleans())
    def test_prop_avg_keys_matches_brute_force(sq, extra, w, causal):
        skv = sq + extra
        brute = sum(min(min(w, skv) if w else skv,
                        (skv - sq + i + 1) if causal else skv)
                    for i in range(sq)) / sq
        assert avg_keys_per_query(sq, skv, w, causal) == \
            pytest.approx(brute, rel=1e-12)

    @settings(max_examples=40, deadline=None)
    @given(m=_seq, k=_dim, n=_dim,
           epi=st.sampled_from(["bias", "silu", "gelu", "layernorm"]))
    def test_prop_matmul_epilogue_strictly_less_traffic(m, k, n, epi):
        a, w = TensorStat((m, k), "bfloat16"), TensorStat((k, n), "bfloat16")
        fused = profile("matmul", [a, w], epilogue=epi)
        plain = profile("matmul", [a, w])
        assert fused.read_bytes + fused.write_bytes <= \
            plain.read_bytes + plain.write_bytes + 4 * n
        assert fused.flops > plain.flops

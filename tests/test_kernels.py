"""Per-kernel allclose validation against the pure-jnp oracles —
shape/dtype sweeps, interpret mode (kernel bodies execute on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import ops, ref
from repro.models.mamba import ssd_decode_step

RNG = np.random.default_rng(7)


def randn(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# --------------------------------------------------------------- tsmm
@pytest.mark.parametrize("m,n,bm,bn", [
    (512, 256, 256, 128),
    (1024, 512, 512, 256),
    (768, 384, 256, 128),       # non-power-of-two multiples
    (2048, 128, 512, 128),      # single block column
])
def test_tsmm_shapes(m, n, bm, bn):
    x = randn((m, n))
    out = ops.tsmm(x, bm=bm, bn=bn)
    expect = ref.tsmm_ref(x)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_tsmm_dtypes(dtype, tol):
    x = randn((512, 256), dtype)
    out = ops.tsmm(x, bm=256, bn=128)
    expect = np.asarray(ref.tsmm_ref(x), np.float32)
    np.testing.assert_allclose(np.asarray(out, np.float32), expect,
                               rtol=tol, atol=tol * 30)


def test_tsmm_symmetry():
    x = randn((512, 256))
    out = np.asarray(ops.tsmm(x, bm=256, bn=128))
    np.testing.assert_allclose(out, out.T, rtol=1e-6)


def test_tsmm_ridge_epilogue():
    """Fused G = X^T X + reg*I shifts exactly the diagonal, all blocks."""
    x = randn((512, 256))
    reg = 7.25
    out = ops.tsmm(x, bm=256, bn=128, reg=reg)      # 2 block cols: tests
    plain = ops.tsmm(x, bm=256, bn=128)             # on/off-diagonal tiles
    np.testing.assert_allclose(
        np.asarray(out) - np.asarray(plain),
        reg * np.eye(256, dtype=np.float32), rtol=0, atol=1e-4)
    np.testing.assert_allclose(out, ref.tsmm_ref(x, reg=reg),
                               rtol=2e-5, atol=2e-4)


# ------------------------------------------------------ matmul epilogue
@pytest.mark.parametrize("epilogue", [None, "bias", "silu", "gelu"])
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (512, 256, 256, 256, 128, 128),
    (256, 512, 384, 128, 256, 128),     # non-square, 3 k-steps
])
def test_matmul_epilogue_sweep(epilogue, m, n, k, bm, bn, bk):
    x, w = randn((m, k)), randn((k, n))
    bias = randn((n,)) if epilogue == "bias" else None
    out = ops.matmul_epilogue(x, w, bias, epilogue=epilogue,
                              bm=bm, bn=bn, bk=bk)
    expect = ref.matmul_epilogue_ref(x, w, bias, epilogue=epilogue)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-4)


def test_matmul_epilogue_layernorm_full_row():
    x, w = randn((256, 256)), randn((256, 256))
    out = ops.matmul_epilogue(x, w, epilogue="layernorm",
                              bm=128, bn=256, bk=128)
    expect = ref.matmul_epilogue_ref(x, w, epilogue="layernorm")
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-4)
    rows = np.asarray(out, np.float32)
    np.testing.assert_allclose(rows.mean(axis=-1), 0.0, atol=1e-4)


@pytest.mark.parametrize("out_dtype,tol", [
    (jnp.bfloat16, 3e-2), (jnp.float32, 2e-4)])
def test_matmul_epilogue_cast_sinking(out_dtype, tol):
    """out_dtype narrows during the single flush write (fp32 accumulate)."""
    x, w = randn((256, 256)), randn((256, 256))
    out = ops.matmul_epilogue(x, w, epilogue="silu", out_dtype=out_dtype,
                              bm=128, bn=128, bk=128)
    assert out.dtype == out_dtype
    expect = ref.matmul_epilogue_ref(x, w, epilogue="silu",
                                     out_dtype=out_dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_matmul_epilogue_bf16_inputs():
    x = randn((256, 256), jnp.bfloat16)
    w = randn((256, 256), jnp.bfloat16)
    out = ops.matmul_epilogue(x, w, epilogue="gelu", bm=128, bn=128, bk=128)
    expect = ref.matmul_epilogue_ref(x, w, epilogue="gelu")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


# ----------------------------------------------------------- flash attn
@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window", [
    (2, 4, 2, 256, 64, True, None),
    (1, 4, 4, 256, 32, False, None),
    (2, 8, 2, 512, 64, True, 128),
    (1, 2, 1, 512, 128, True, None),
    (1, 4, 1, 256, 64, False, 64),
])
def test_flash_attention_sweep(b, hq, hkv, s, d, causal, window):
    q, k, v = randn((b, hq, s, d)), randn((b, hkv, s, d)), randn((b, hkv, s, d))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=128, bk=128)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    q = randn((1, 2, 256, 64), jnp.bfloat16)
    k = randn((1, 2, 256, 64), jnp.bfloat16)
    v = randn((1, 2, 256, 64), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, bq=128, bk=128)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_block_shape_invariance():
    q, k, v = randn((1, 2, 512, 64)), randn((1, 2, 512, 64)), randn((1, 2, 512, 64))
    o1 = ops.flash_attention(q, k, v, bq=64, bk=64)
    o2 = ops.flash_attention(q, k, v, bq=256, bk=128)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ decode attention
def _slot_positions(slots, pos, ring):
    """kpos of a cache after the query at ``pos`` was written: slots
    0..pos in order, or (``ring``) each slot holding the latest position
    that maps to it."""
    s = np.arange(slots)
    if ring:
        return (pos - (pos - s) % slots).astype(np.int32)
    return np.where(s <= pos, s, -1).astype(np.int32)


def _decode_case(g, hd, b, slots, pos, window=None, ring=False,
                 dtype=jnp.bfloat16, nkv=2, layers=3, layer=1):
    kv = randn((layers, slots, nkv, b, 2 * hd), dtype)
    q = randn((b, nkv * g, hd), dtype)
    kpos = _slot_positions(slots, pos, ring)
    return q, kv, kpos, layer, window


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 128 KiB, so the test caches span many grid steps; the
    kernel's traces are dropped on both sides, since the block is read
    when it is traced."""
    da.decode_attention.clear_cache()
    monkeypatch.setattr(da, "BLOCK_BYTES", 128 << 10)
    yield
    da.decode_attention.clear_cache()


@pytest.mark.parametrize("g,hd,b,slots,pos,window,ring,dtype", [
    (1, 64, 16, 64, 20, None, False, jnp.bfloat16),    # mid-block
    (2, 64, 16, 64, 15, None, False, jnp.bfloat16),    # last slot of a block
    (2, 64, 16, 64, 16, None, False, jnp.bfloat16),    # first of the next
    (2, 128, 32, 64, 63, None, False, jnp.bfloat16),   # cap - 1, hd 128
    (2, 128, 8, 64, 41, None, False, jnp.float32),     # float32
    (2, 64, 16, 48, 130, 20, True, jnp.bfloat16),      # ring after wrap
    (1, 64, 8, 64, 100, 64, True, jnp.float32),        # window = cap
    (2, 256, 16, 64, 90, 32, True, jnp.bfloat16),      # hd 256 ring
    (4, 128, 64, 32, 20, None, False, jnp.bfloat16),   # two lane chunks
])
def test_decode_attention_sweep(small_blocks, g, hd, b, slots, pos, window,
                                ring, dtype):
    q, kv, kpos, layer, window = _decode_case(g, hd, b, slots, pos, window,
                                              ring, dtype)
    assert da.fits(kv.shape, kv.dtype, g)
    args = (q, kv, jnp.asarray(kpos), layer, pos)
    out = ops.decode_attention(*args, window=window)
    expect = ref.decode_attention_ref(*args, window=window)
    assert out.dtype == jnp.float32 and out.shape == q.shape
    tol = 2e-5 if dtype == jnp.float32 else 2e-4
    np.testing.assert_allclose(out, expect, rtol=tol, atol=tol)


@pytest.mark.parametrize("pos,window,ring", [(37, None, False),
                                             (130, 20, True)])
def test_decode_attention_reads_only_live_slots(small_blocks, pos, window,
                                                ring):
    """Every slot past ``pos`` and every slot whose ``kpos`` the mask drops
    holds NaN: the output is finite and equals the one over a clean cache."""
    q, kv, kpos, layer, window = _decode_case(2, 64, 16, 64, pos, window,
                                              ring)
    live = (kpos >= 0) & (kpos <= pos)
    if window is not None:
        live &= kpos > pos - window
    assert not live.all()
    dead = np.flatnonzero(~live)
    poisoned = kv.at[layer, dead].set(jnp.nan)
    args = (jnp.asarray(kpos), layer, pos)
    out = ops.decode_attention(q, poisoned, *args, window=window)
    clean = ops.decode_attention(q, kv, *args, window=window)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(out, clean)
    np.testing.assert_allclose(
        out, ref.decode_attention_ref(q, kv, *args, window=window),
        rtol=2e-4, atol=2e-4)


def test_decode_attention_block_slots():
    """A block divides the slots, puts its scores on whole lanes and stays
    within the block budget."""
    row = 16 * 32 * 128 * 2                     # qwen1.5-0.5b at batch 32
    bs = da.block_slots(1280, 32, row)
    assert 1280 % bs == 0 and bs * 32 % 128 == 0
    assert bs * row <= da.BLOCK_BYTES < 2 * bs * row


@pytest.mark.parametrize("shape,dtype,g,takes", [
    ((24, 1280, 16, 32, 128), jnp.bfloat16, 1, True),   # qwen1.5-0.5b cell
    ((24, 1283, 16, 32, 128), jnp.bfloat16, 1, False),  # slots tile no block
    ((24, 1280, 16, 8, 128), jnp.bfloat16, 1, False),   # batch: half a tile
    ((24, 1280, 16, 12, 128), jnp.bfloat16, 1, False),  # batch not 8k
    ((24, 1280, 16, 5, 128), jnp.float32, 1, False),    # batch not 8k
    ((24, 1280, 16, 32, 160), jnp.bfloat16, 1, False),  # hd 80
    ((40, 1280, 20, 32, 256), jnp.bfloat16, 1, False),  # qwen1.5-4b: XLA's
    ((80, 1280, 8, 32, 256), jnp.bfloat16, 8, True),    # qwen1.5-110b
    ((8, 1024, 8, 32, 512), jnp.bfloat16, 2, True),     # gemma3-12b
])
def test_decode_attention_fits(shape, dtype, g, takes):
    """The kernel takes a cache where it tiles it and measured faster
    than the XLA products; the model keeps those for the rest."""
    assert da.fits(shape, dtype, g) is takes


@pytest.mark.parametrize("b,g,tile,lanes", [
    (32, 1, 16, 32), (64, 1, 16, 32), (32, 2, 16, 16), (32, 8, 16, 16),
    (12, 1, 16, 0), (8, 2, 8, 8), (16, 1, 8, 16)])
def test_decode_attention_chunk_lanes(b, g, tile, lanes):
    """A product takes whole tiles of lanes, at most ``ROWS`` query rows
    unless one tile has more."""
    assert da.chunk_lanes(b, g, tile) == lanes


# ------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 4, 16, 32, 32),
    (1, 256, 2, 64, 128, 64),
    (2, 64, 8, 32, 16, 16),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    g = 1
    x = randn((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A_log = jnp.asarray(RNG.uniform(-1, 1, (h,)), jnp.float32)
    B = randn((b, s, g, n))
    C = randn((b, s, g, n))
    D = randn((h,))
    y_k, st_k = ops.ssd_scan(x, dt, A_log, B, C, D, chunk=chunk)
    y_r, st_r = ref.ssd_scan_ref(x, dt, A_log, B, C, D, chunk=chunk)
    np.testing.assert_allclose(y_k, y_r, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(st_k, st_r, rtol=2e-4, atol=2e-4)


def test_ssd_scan_matches_sequential_decode():
    b, s, h, p, n = 1, 32, 2, 8, 16
    x = randn((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A_log = jnp.asarray(RNG.uniform(-1, 1, (h,)), jnp.float32)
    B, C = randn((b, s, 1, n)), randn((b, s, 1, n))
    D = randn((h,))
    y_k, st_k = ops.ssd_scan(x, dt, A_log, B, C, D, chunk=8)
    st = jnp.zeros((b, h, p, n))
    for t in range(s):
        y_t, st = ssd_decode_step(st, x[:, t], dt[:, t], A_log,
                                  B[:, t], C[:, t], D)
        np.testing.assert_allclose(y_k[:, t], y_t, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(st_k, st, rtol=2e-4, atol=2e-4)

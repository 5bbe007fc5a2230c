"""Pallas TPU kernel: one-token decode attention over the stacked
slot-major K|V cache (flash-decoding).

The cache is ``kv`` [L, slots, Hkv, B, 2*hd], each slot's K and V side by
side in one row (``models/transformer.py`` ``init_cache``).  The kernel
takes the whole stack and the layer index; the layer is picked in the
index map from scalar prefetch, so no layer is sliced out of the cache.

The grid runs over blocks of ``bs`` slots (``BLOCK_BYTES`` of cache) and
reads each row once.  The batch lanes are taken ``c`` at a time
(:func:`chunk_lanes`).  For one kv head and one chunk of lanes a block is
the matrix X [(slot, c), 2*hd], and both products run on the MXU against
the whole of X, so K and V are never sliced apart:

* scores: ``[q | 0]`` [(g, c), 2*hd] times X^T gives every query of the
  chunk against every row, [(g, c), (slot, c')]; only ``c == c'`` is kept
  (the rest leaves the softmax through a -inf bias), which puts the
  scores of a block on lanes, dense;
* output: the probabilities [(g, c), (slot, c')] — zero off ``c == c'`` —
  times X sum each query's live rows; lanes ``hd:`` of the result are
  V's, lanes ``:hd`` are discarded.

A product streams g * c query rows against each tile of X, so chunks
(``ROWS``) keep its work from growing with the batch; :func:`fits`
leaves the kernel out where the XLA products are faster.

The products are exact in float32: the query is taken in the cache's
dtype (the 1/sqrt(hd) scale folded into it where it is a power of two,
else applied to the float32 scores), and the float32 probabilities enter
a bf16 product as ``PIECES`` bf16 pieces that sum to them.  The running
max, normaliser and accumulator are float32.  A loop step takes
``HEADS`` (kv head, chunk) pairs, whose chains of product, max,
exponential and product are independent, so the scheduler overlaps
them.

Blocks past the last one that can hold a live key (``min(pos, slots - 1)
// bs``) map to that block again, so they issue no DMA, and skip their
compute.  A slot is live where its ``kpos`` is set, at most ``pos``, and
inside the window; a block with a dead slot zeroes that slot's row in
VMEM and biases its scores to -inf, so whatever a dead slot holds never
reaches the output.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

# Pallas is imported where the kernel is built: the model imports this
# module for ``fits`` on every path, training included.

NAME = "decode_attention"     # the pallas_call's name, in HLO and traces
NEG_INF = -1e30               # the running max before any live key
BLOCK_BYTES = 8 << 20         # bytes of cache a grid step reads
HEADS = 4                     # (kv head, chunk) pairs a loop step works on
PIECES = 3                    # bf16 pieces that carry a float32 exactly
ROWS = 32                     # most query rows (g x lanes) a product takes
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b


def chunk_lanes(batch: int, groups: int, tile: int) -> int:
    """Batch lanes one product takes: the most, in whole sublane tiles of
    ``tile`` lanes dividing ``batch``, whose ``groups`` query heads each
    stay within ``ROWS`` rows, and one tile at least; 0 where the batch is
    not whole tiles."""
    if batch % tile:
        return 0
    return max(c for c in range(tile, batch + 1, tile)
               if batch % c == 0 and (groups * c <= ROWS or c == tile))


def block_slots(slots: int, lanes: int, row_bytes: int) -> int:
    """Slots a grid step reads: the largest count that divides ``slots``,
    lays a block's scores out on whole lanes (``bs * lanes`` a multiple of
    128) and keeps a block within ``BLOCK_BYTES``; 0 where none does."""
    best = 0
    for bs in range(1, slots + 1):
        if (slots % bs == 0 and bs * lanes % 128 == 0
                and (bs * row_bytes <= BLOCK_BYTES or not best)):
            best = bs
    return best


def fits(kv_shape, dtype, groups: int) -> bool:
    """Whether the kernel takes a stacked cache of ``kv_shape``
    ([L, slots, Hkv, B, 2*hd]) and ``dtype`` read by ``groups`` query
    heads per kv head: B whole sublane tiles, K and V whole lanes
    together, blocks of slots on whole lanes; and not one query head per
    kv head over K and V of whole lane tiles each (hd 128, 256), where
    the XLA products read each byte once at near peak and ran faster."""
    _, slots, hkv, b, two_hd = kv_shape
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return False
    itemsize = jnp.dtype(dtype).itemsize
    c = chunk_lanes(b, groups, 32 // itemsize)
    return (two_hd % 128 == 0 and c > 0
            and not (groups == 1 and two_hd % 256 == 0)
            and block_slots(slots, c, hkv * b * two_hd * itemsize) > 0)


def _split(x: jax.Array, n: int, dtype) -> list:
    """float32 ``x`` as ``n`` pieces in ``dtype`` whose sum is ``x`` (to
    float32's precision from three bf16 pieces); ``x`` itself where
    ``dtype`` is float32."""
    if dtype == jnp.float32:
        return [x]
    pieces = []
    for _ in range(n):
        piece = x.astype(dtype)
        pieces.append(piece)
        x = x - piece.astype(jnp.float32)
    return pieces


def _dot(pieces: list, x: jax.Array, dims) -> jax.Array:
    """sum_k pieces[k] . x, as one product with the pieces stacked on
    rows; float32."""
    rows = pieces[0].shape[0]
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    out = jax.lax.dot_general(
        jnp.concatenate(pieces, axis=0) if len(pieces) > 1 else pieces[0],
        x, dims, precision=exact, preferred_element_type=jnp.float32)
    return functools.reduce(jnp.add, [out[k * rows:(k + 1) * rows]
                                      for k in range(len(pieces))])


def _kernel(scal_ref, kpos_ref, q_ref, kv_ref, o_ref, m_ref, l_ref,
            acc_ref, bias_ref, mask_ref, *, bs: int, nb: int,
            window: Optional[int], scale: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    pos = scal_ref[1]
    top = jnp.minimum(pos, nb * bs - 1)           # highest live slot
    _, _, hkv, _, two_hd = kv_ref.shape
    _, nc, gc, _ = m_ref.shape                    # chunks; queries of one
    c = bias_ref.shape[1] // bs                   # lanes of a chunk
    n = bs * c                                    # rows of a block's X

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        row = jax.lax.broadcasted_iota(jnp.int32, (gc, n), 0) % c
        lane = jax.lax.broadcasted_iota(jnp.int32, (gc, n), 1) % c
        bias_ref[...] = jnp.where(row == lane, 0.0, -jnp.inf)

    def live(slot):
        kp = kpos_ref[slot]
        ok = (kp >= 0) & (kp <= pos)
        if window is not None:
            ok &= kp > pos - window
        return ok

    def rows_of(h, k):
        lanes = slice(k * c, (k + 1) * c)
        if kv_ref.dtype != jnp.bfloat16:
            return kv_ref[0, :, h, lanes].reshape(n, two_hd)
        # bf16 read as 32-bit words, two rows a word, as the cache's tiles
        # hold them: whole vregs, no repacking before the MXU
        words = slice(k * c // 2, (k + 1) * c // 2)
        w = kv_ref.bitcast(jnp.uint32)[0, :, h, words].reshape(n // 2, two_hd)
        return pltpu.bitcast(w, jnp.bfloat16)

    def block(masked: bool):
        if masked:
            mask_ref[...] = bias_ref[...]
            span = min(n, -(-(c + 127) // 128) * 128)   # lanes that hold a slot

            def drop(j, carry):
                @pl.when(~live(i * bs + j))
                def _():
                    kv_ref[0, j] = jnp.zeros(kv_ref.shape[2:], kv_ref.dtype)
                    lo = jnp.minimum(j * c // 128 * 128, n - span)
                    lanes = pl.ds(pl.multiple_of(lo, 128), span)
                    col = lo + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
                    mask_ref[:, lanes] = jnp.where(col // c == j, -jnp.inf,
                                                   mask_ref[:, lanes])
                return carry

            jax.lax.fori_loop(0, bs, drop, 0)
        bias = (mask_ref if masked else bias_ref)[...]

        def heads(step, carry):
            new = []
            for h in [step * group + j for j in range(group)]:
                for k in range(nc):
                    x = rows_of(h, k)                            # [(s, c), 2hd]
                    s = _dot([q_ref[h, k]], x, _NT)              # [(g, c), n]
                    s = (s if scale == 1.0 else s * scale) + bias
                    m = m_ref[h, k]
                    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new)
                    new.append((h, k, m_new,
                                alpha * l_ref[h, k]
                                + p.sum(axis=-1, keepdims=True),
                                alpha * acc_ref[h, k] + _dot(
                                    _split(p, PIECES, x.dtype), x, _NN)))
            for h, k, m, l, acc in new:
                m_ref[h, k], l_ref[h, k], acc_ref[h, k] = m, l, acc
            return carry

        group = math.gcd(hkv, max(1, HEADS // nc))
        jax.lax.fori_loop(0, hkv // group, heads, 0)

    reached = i * bs <= top
    clean = jax.lax.fori_loop(0, bs, lambda j, ok: ok & live(i * bs + j),
                              reached, unroll=True)
    pl.when(clean)(functools.partial(block, masked=False))
    pl.when(reached & ~clean)(functools.partial(block, masked=True))

    @pl.when(i == nb - 1)
    def _flush():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def decode_attention(q: jax.Array, kv: jax.Array, kpos: jax.Array,
                     layer: jax.Array, pos: jax.Array, *,
                     window: Optional[int] = None,
                     interpret: bool) -> jax.Array:
    """One query token per lane against layer ``layer`` of a stacked cache.

    q: [B, Hq, hd], taken in the cache's dtype; kv: [L, S, Hkv, B, 2*hd]
    (each slot's K | V), a shape :func:`fits` takes; kpos: [S] the position each slot of this layer
    holds (-1: empty); pos: the query's position.  Returns float32
    [B, Hq, hd]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, hd = q.shape
    _, slots, hkv, _, two_hd = kv.shape
    g = hq // hkv
    c = chunk_lanes(b, g, 32 // kv.dtype.itemsize)
    nc = b // c
    bs = block_slots(slots, c, hkv * b * two_hd * kv.dtype.itemsize)
    nb = slots // bs
    # [Hkv, chunk, (g, c), 2hd]: each kv head's queries by chunk of lanes,
    # facing K's lanes; V's zero
    qg = q.reshape(nc, c, hkv, g, hd).transpose(2, 0, 3, 1, 4)
    qg = qg.reshape(hkv, nc, g * c, hd).astype(jnp.float32)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, two_hd - hd)))
    # the scale folded in where that is exact (a power of two: hd 64 or
    # 256), else applied to the scores
    scale = hd ** -0.5
    fold = math.log2(scale).is_integer()
    qg = (qg * scale if fold else qg).astype(kv.dtype)
    scal = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(pos, jnp.int32)])

    def kv_block(i, scal_ref, kpos_ref):
        last = jnp.minimum(scal_ref[1], slots - 1) // bs
        return scal_ref[0], jnp.minimum(i, last), 0, 0, 0

    state = (hkv, nc, g * c)
    fixed = lambda i, *_: (0, 0, 0, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, nb=nb, window=window,
                          scale=1.0 if fold else scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec(qg.shape, fixed),
                pl.BlockSpec((1, bs, hkv, b, two_hd), kv_block),
            ],
            out_specs=pl.BlockSpec(state + (two_hd,), fixed),
            scratch_shapes=[pltpu.VMEM(state + (1,), jnp.float32),
                            pltpu.VMEM(state + (1,), jnp.float32),
                            pltpu.VMEM(state + (two_hd,), jnp.float32),
                            pltpu.VMEM((g * c, bs * c), jnp.float32),
                            pltpu.VMEM((g * c, bs * c), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(state + (two_hd,), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=3 * BLOCK_BYTES + (16 << 20)),
        name=NAME,
        interpret=interpret,
    )(scal, kpos.astype(jnp.int32), qg, kv)
    # [Hkv, chunk, (g, c), hd] -> [B, Hq, hd]
    out = out[..., hd:].reshape(hkv, nc, g, c, hd)
    return out.transpose(1, 3, 0, 2, 4).reshape(b, hq, hd)

"""Pallas TPU kernel: blockwise online-softmax attention (flash-style).

Compute hot-spot of the prefill_32k / train_4k cells.  Grid is
(B, Hq, nq, nk) with the KV axis minormost & sequential; the running
max / normalizer / accumulator live in fp32 VMEM scratch across KV steps.
Causal and sliding-window bands skip whole KV blocks via ``pl.when``
(the TPU analogue of warp-level early-exit in GPU flash kernels).

GQA is handled in the index maps (kv head = q head // group), so no
K/V replication is materialized in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * bq
    q_hi = q_lo + bq - 1                      # highest query position
    k_lo = ki * bk
    k_hi = k_lo + bk - 1

    may_contribute = True
    if causal:
        may_contribute &= k_lo <= q_hi
    if window is not None:
        may_contribute &= k_hi > q_lo - window

    @pl.when(may_contribute)
    def _update():
        q = q_ref[0, 0]                        # [bq, d]
        k = k_ref[0, 0]                        # [bk, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                    # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new) * mask
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    bq: int = 512, bk: int = 512,
                    interpret: bool) -> jax.Array:
    """q: [B,Hq,S,D], k/v: [B,Hkv,S,D] -> [B,Hq,S,D]."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = scale if scale is not None else d ** -0.5

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, h, qi, ki: (bi, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, h, qi, ki: (bi, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out

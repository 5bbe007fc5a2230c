"""Shared model primitives (pure JAX, GSPMD-friendly einsum formulations).

Everything here is written so the XLA partitioner can shard it cleanly
under the plans produced by :mod:`repro.core.planner`:

  * attention is *chunked* (online-softmax over KV blocks) so prefill at
    32k/500k never materializes an S x S score tensor — the pure-jnp
    analogue of the Pallas flash kernel in :mod:`repro.kernels`;
  * sliding-window layers visit a statically-bounded band of KV chunks,
    so local layers cost O(S * (W + C)) flops, not O(S^2);
  * MoE uses GShard-style capacity dispatch einsums (all-to-all friendly);
  * MLA implements DeepSeek's low-rank q/kv compression with the absorbed
    (MQA-over-latent) decode path (see transformer.py).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import costing_mode

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Small pieces
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps))
            * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, D]; positions broadcastable to x.shape[:-1]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., S, D/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _band_mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: Optional[int]) -> jax.Array:
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    return mask


def attention_dense(q, k, v, *, causal: bool = True,
                    q_offset=0, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    out_dim: Optional[int] = None) -> jax.Array:
    """Direct softmax attention (oracle + small-S path).

    q: [B, Hq, Sq, Dk], k: [B, Hkv, Skv, Dk], v: [B, Hkv, Skv, Dv].
    ``q_offset``: absolute position of q[0] relative to k[0] (decode).
    Supports Dk != Dv (MLA absorbed path).
    """
    b, hq, sq, dk = q.shape
    _, hkv, skv, dv = v.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    qg = q.reshape(b, hkv, g, sq, dk)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(sq) + q_offset
    k_pos = jnp.arange(skv)
    mask = _band_mask(q_pos, k_pos, causal, window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m) * mask[None, None, None]
    l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p / l, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, dv).astype(q.dtype)


def attention_chunked(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention over KV chunks (flash-style, pure jnp).

    Query chunks are a static python loop; each visits only the KV chunks
    its causal/window band can intersect, via a lax.scan with dynamic
    slicing.  Peak memory O(q_chunk * kv_chunk) scores per head.
    """
    b, hq, sq, dk = q.shape
    _, hkv, skv, dv = v.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    if sq <= 2048 and skv <= 2048:
        return attention_dense(q, k, v, causal=causal, window=window, scale=scale)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    # pad ragged tails (e.g. MTP's S-1 sequences); padded keys are masked
    # off via the k_pos < skv check, padded queries are sliced off.
    pad_q = (-sq) % q_chunk
    pad_k = (-skv) % kv_chunk
    sq_p, skv_p = sq + pad_q, skv + pad_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    valid_kv = skv
    nq, nk = sq_p // q_chunk, skv_p // kv_chunk
    g = hq // hkv
    qr = q.reshape(b, hkv, g, nq, q_chunk, dk)

    def kv_step(qi, qc, carry, j, masked: bool):
        """masked=False for blocks fully inside the causal/window band —
        skips mask broadcast/select/compare entirely (they dominated the
        per-layer HBM bytes; see EXPERIMENTS.md §Perf)."""
        m, l, acc = carry
        kj = jax.lax.dynamic_slice_in_dim(k, j * kv_chunk, kv_chunk, axis=2)
        vj = jax.lax.dynamic_slice_in_dim(v, j * kv_chunk, kv_chunk, axis=2)
        # bf16 operands -> fp32 accumulation on the MXU: no convert ops
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qc, kj,
                       preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = qi * q_chunk + jnp.arange(q_chunk)
            k_pos = j * kv_chunk + jnp.arange(kv_chunk)
            mask = _band_mask(q_pos, k_pos, causal, window)
            mask &= (k_pos < valid_kv)[None, :]          # padded keys
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if masked:
            p = p * mask[None, None, None]
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(q.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    def _interior_range(qi, lo, kv_hi):
        """KV-chunk indices fully inside the band (no masking needed)."""
        q_lo, q_hi = qi * q_chunk, (qi + 1) * q_chunk - 1
        int_lo, int_hi = lo, kv_hi
        if causal:
            # block fully past? need k_hi = j*kc+kc-1 <= q_lo
            int_hi = min(int_hi, (q_lo + 1) // kv_chunk)
        if window is not None:
            # fully inside window: k_lo = j*kc >= q_hi - window + 1
            int_lo = max(int_lo, -(-(q_hi - window + 1) // kv_chunk))
        if pad_k:
            int_hi = min(int_hi, skv // kv_chunk)   # padded tail needs mask
        return int_lo, max(int_hi, int_lo)

    outs = []
    for qi in range(nq):                       # static unroll over q blocks
        kv_hi = min(nk, -(-((qi + 1) * q_chunk) // kv_chunk)) if causal else nk
        lo = max(0, (qi * q_chunk - (window or 0)) // kv_chunk) if window else 0
        qc = qr[:, :, :, qi]
        m0 = jnp.full((b, hkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, dv), jnp.float32)
        carry = (m0, l0, a0)
        int_lo, int_hi = _interior_range(qi, lo, kv_hi)
        # boundary blocks BEFORE the interior (window edge)
        for j in range(lo, int_lo):
            carry, _ = kv_step(qi, qc, carry, j, True)
        if int_hi > int_lo:                    # unmasked interior sweep
            carry, _ = jax.lax.scan(
                lambda c, j, _qi=qi, _qc=qc: kv_step(_qi, _qc, c, j, False),
                carry, jnp.arange(int_lo, int_hi),
                unroll=True if costing_mode.unroll_scans() else 1)
        for j in range(max(int_hi, int_lo), kv_hi):   # diagonal/tail blocks
            carry, _ = kv_step(qi, qc, carry, j, True)
        m, l, acc = carry
        outs.append(acc / jnp.maximum(l[..., None], 1e-30))
    o = jnp.stack(outs, axis=3)                # [b,hkv,g,nq,qc,dv]
    o = o.reshape(b, hkv, g, sq_p, dv).reshape(b, hq, sq_p, dv)
    return o[:, :, :sq].astype(q.dtype)


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              q_offset=0, scale: Optional[float] = None) -> jax.Array:
    """Dispatch: dense for small/decode, chunked for long prefill/train."""
    sq, skv = q.shape[2], k.shape[2]
    if sq == 1 or (sq <= 2048 and skv <= 2048):
        return attention_dense(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, scale=scale)
    # flash-style recompute-in-backward: without this, the kv-chunk scan
    # saves every per-step softmax residual for the backward pass and the
    # compiled plan's temp memory explodes (observed 73 GB/device at
    # train_4k — see EXPERIMENTS.md §Perf iteration 1).
    chunked = jax.checkpoint(
        functools.partial(attention_chunked, causal=causal, window=window,
                          scale=scale))
    return chunked(q, k, v)


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


@jax.named_scope("mlp")
def ffn(x: jax.Array, params: Dict[str, jax.Array], gated: bool,
        act: str = "silu") -> jax.Array:
    """Dense MLP. gated: SwiGLU (w_gate, w_up, w_down); else (w_up, w_down)."""
    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    if gated:
        h = actf(dense(x, params["w_gate"])) * dense(x, params["w_up"])
    else:
        h = actf(dense(x, params["w_up"], params.get("b_up")))
    out = dense(h, params["w_down"], params.get("b_down"))
    return out


@jax.named_scope("moe")
def moe_ffn(x: jax.Array, params: Dict[str, jax.Array], *, top_k: int,
            capacity_factor: float, gated: bool,
            group_size: int = 4096) -> Tuple[jax.Array, jax.Array]:
    """GShard-style capacity-based MoE with token grouping.

    x: [T, d].  params: w_router [d, E]; w_gate/w_up [E, d, ff]; w_down
    [E, ff, d].  Returns (out [T, d], aux_loss scalar).

    Tokens are split into groups of ``group_size`` (per-device blocks in
    GShard) so the dispatch one-hot is [G, Tg, E, Cg] with Cg ~ Tg*k/E —
    linear in T, and the form GSPMD turns into all-to-alls under expert
    sharding.  Capacity (and hence dropping) is per-group.
    """
    t, d = x.shape
    e = params["w_router"].shape[-1]
    tg = min(group_size, t)
    if t % tg != 0:                                  # fall back: one group
        tg = t
    g = t // tg
    capacity = max(int(capacity_factor * top_k * tg / e), 1)
    xg = x.reshape(g, tg, d)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        params["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # [G, Tg, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, slot) within its expert queue (per group)
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)    # [G, Tg, k, E]
    flat = onehot.reshape(g, tg * top_k, e)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(g, tg, top_k, e)
    pos = jnp.einsum("gtke,gtke->gtk", pos, onehot)            # [G, Tg, k]
    keep = pos < capacity
    gate_vals = gate_vals * keep

    pos_cap = jnp.where(keep, pos, 0).astype(jnp.int32)
    disp = (onehot * keep[..., None]).astype(jnp.float32)      # [G, Tg, k, E]
    pos_onehot = jax.nn.one_hot(pos_cap, capacity, dtype=jnp.float32)
    dispatch = jnp.einsum("gtke,gtkc->gtec", disp, pos_onehot)  # [G,Tg,E,C]
    combine = jnp.einsum("gtk,gtke,gtkc->gtec", gate_vals, disp, pos_onehot)

    xe = jnp.einsum("gtd,gtec->gecd", xg.astype(jnp.float32), dispatch)
    xe = xe.astype(x.dtype)                                    # [G, E, C, d]
    actf = jax.nn.silu
    if gated:
        h = actf(jnp.einsum("gecd,edf->gecf", xe, params["w_gate"].astype(x.dtype)))
        h = h * jnp.einsum("gecd,edf->gecf", xe, params["w_up"].astype(x.dtype))
    else:
        h = actf(jnp.einsum("gecd,edf->gecf", xe, params["w_up"].astype(x.dtype)))
    ye = jnp.einsum("gecf,efd->gecd", h, params["w_down"].astype(x.dtype))
    out = jnp.einsum("gecd,gtec->gtd", ye.astype(jnp.float32), combine)

    # load-balance aux loss (Switch-style), averaged over groups
    density = onehot.sum(2).mean(1)                            # [G, E]
    density_proxy = probs.mean(1)
    aux = (density * density_proxy).sum(-1).mean() * e
    return out.reshape(t, d).astype(x.dtype), aux.astype(jnp.float32)

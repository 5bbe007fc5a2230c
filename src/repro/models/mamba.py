"""Mamba2 (SSD — state-space duality) block, pure JAX.

Implements the chunked SSD algorithm of arXiv:2405.21060 §6 (the "fully
recomputed" dual form): intra-chunk quadratic attention-like term + an
inter-chunk state recurrence (lax.scan over chunks).  A Pallas kernel for
the same computation lives in repro.kernels.ssd_scan; this module is the
oracle and the GSPMD path.

Shapes follow the paper: x [B,S,H,P], dt [B,S,H], A_log [H], B/C [B,S,G,N].
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import costing_mode


def segsum(log_a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} log_a[..., k].

    log_a: [..., L] -> [..., L, L] lower-triangular (j <= i), -inf above.
    """
    L = log_a.shape[-1]
    x = jnp.cumsum(log_a, axis=-1)
    diff = x[..., :, None] - x[..., None, :]          # sum_{j+1..i} for i>=j
    ii = jnp.arange(L)
    mask = ii[:, None] >= ii[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A_log: jax.Array,
                B: jax.Array, C: jax.Array, D: jax.Array,
                chunk: int = 256,
                init_state: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g

    dt = jnp.maximum(dt.astype(jnp.float32), 1e-6)
    A = -jnp.exp(A_log.astype(jnp.float32))           # [H], negative
    log_a = (dt * A)                                   # [B,S,H] log decay
    xbar = x.astype(jnp.float32) * dt[..., None]       # dt-scaled input

    # chunked views
    xc = xbar.reshape(b, nc, chunk, h, p)
    lac = log_a.reshape(b, nc, chunk, h)
    Bc = B.astype(jnp.float32).reshape(b, nc, chunk, g, n)
    Cc = C.astype(jnp.float32).reshape(b, nc, chunk, g, n)
    Bh = jnp.repeat(Bc, rep, axis=3)                   # [b,nc,l,h,n]
    Ch = jnp.repeat(Cc, rep, axis=3)

    # ---- intra-chunk (quadratic, attention-like) ----
    Lmat = jnp.exp(segsum(lac.transpose(0, 1, 3, 2)))  # [b,nc,h,l,l]
    scores = jnp.einsum("bclhn,bcshn->bchls", Ch, Bh)  # [b,nc,h,l,s]
    y_diag = jnp.einsum("bchls,bchls,bcshp->bclhp",
                        scores, Lmat, xc)

    # ---- chunk states ----
    a_cum = jnp.cumsum(lac, axis=2)                    # [b,nc,l,h]
    decay_to_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum)
    states = jnp.einsum("bclhn,bclh,bclhp->bchpn", Bh, decay_to_end, xc)

    # ---- inter-chunk recurrence ----
    chunk_decay = jnp.exp(a_cum[:, :, -1, :])          # [b,nc,h]
    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((b, h, p, n), jnp.float32))

    def step(prev, inp):
        dec, st = inp                                  # [b,h], [b,h,p,n]
        new = prev * dec[..., None, None] + st
        return new, prev                               # emit state ENTERING chunk

    final_state, prev_states = jax.lax.scan(
        step, s0, (chunk_decay.transpose(1, 0, 2),
                   states.transpose(1, 0, 2, 3, 4)),
        unroll=True if costing_mode.unroll_scans() else 1)
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # [b,nc,h,p,n]

    # ---- off-diagonal (cross-chunk) output ----
    state_decay_in = jnp.exp(a_cum)                    # decay from chunk start
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", Ch, prev_states, state_decay_in)

    y = (y_diag + y_off).reshape(b, s, h, p)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), final_state


def ssd_decode_step(state: jax.Array, x_t: jax.Array, dt_t: jax.Array,
                    A_log: jax.Array, B_t: jax.Array, C_t: jax.Array,
                    D: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD update.

    state [B,H,P,N]; x_t [B,H,P]; dt_t [B,H]; B_t/C_t [B,G,N].
    Returns (y [B,H,P], new_state).
    """
    b, h, p, n = state.shape
    g = B_t.shape[1]
    rep = h // g
    dt_t = jnp.maximum(dt_t.astype(jnp.float32), 1e-6)
    a = jnp.exp(dt_t * -jnp.exp(A_log.astype(jnp.float32)))       # [B,H]
    Bh = jnp.repeat(B_t.astype(jnp.float32), rep, axis=1)         # [B,H,N]
    Ch = jnp.repeat(C_t.astype(jnp.float32), rep, axis=1)
    xb = x_t.astype(jnp.float32) * dt_t[..., None]                # [B,H,P]
    new_state = state * a[..., None, None] + xb[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    y = y + x_t.astype(jnp.float32) * D.astype(jnp.float32)[None, :, None]
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# Full Mamba2 block (in_proj -> conv -> SSD -> gate -> out_proj)
# ---------------------------------------------------------------------------


def mamba_block_init(rng, d_model: int, ssm, dtype) -> Dict[str, jax.Array]:
    di = ssm.d_inner(d_model)
    h = ssm.n_heads(d_model)
    g, n, w = ssm.n_groups, ssm.state_size, ssm.conv_width
    conv_ch = di + 2 * g * n
    k1, k2, k3 = jax.random.split(rng, 3)
    std = d_model ** -0.5
    return {
        "w_in": (jax.random.normal(k1, (d_model, 2 * di + 2 * g * n + h)) * std).astype(dtype),
        "conv_w": (jax.random.normal(k2, (w, conv_ch)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm_scale": jnp.zeros((di,), jnp.float32),
        "w_out": (jax.random.normal(k3, (di, d_model)) * di ** -0.5).astype(dtype),
    }


def _causal_conv(xc: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d.  xc [B,S,C]; w [W,C]; state [B,W-1,C]."""
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((xc.shape[0], width - 1, xc.shape[2]), xc.dtype)
    xpad = jnp.concatenate([state, xc], axis=1)
    out = sum(xpad[:, i:i + xc.shape[1], :] * w[i][None, None, :]
              for i in range(width))
    new_state = xpad[:, -(width - 1):, :]
    return jax.nn.silu(out + b[None, None, :]), new_state


def mamba_block_apply(params: Dict[str, jax.Array], x: jax.Array, ssm,
                      cache: Optional[Dict[str, jax.Array]] = None,
                      ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """x: [B, S, d_model].  cache: {"conv": [B,W-1,C], "state": [B,H,P,N]}."""
    bsz, s, d = x.shape
    di = ssm.d_inner(d)
    h = ssm.n_heads(d)
    g, n = ssm.n_groups, ssm.state_size

    with jax.named_scope("in_proj"):
        proj = dense_(x, params["w_in"])               # [B,S,2di+2gn+h]
    with jax.named_scope("conv"):
        z, xin, Bx, Cx, dt = jnp.split(
            proj, [di, 2 * di, 2 * di + g * n, 2 * di + 2 * g * n], axis=-1)
        conv_in = jnp.concatenate([xin, Bx, Cx], axis=-1)
        conv_state = cache.get("conv") if cache else None
        conv_out, new_conv = _causal_conv(conv_in, params["conv_w"],
                                          params["conv_b"], conv_state)
        xin, Bx, Cx = jnp.split(conv_out, [di, di + g * n], axis=-1)
        xh = xin.reshape(bsz, s, h, ssm.head_dim)
        Bh = Bx.reshape(bsz, s, g, n)
        Ch = Cx.reshape(bsz, s, g, n)

    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"][None, None, :])
        if cache is not None and s == 1:
            y, new_state = ssd_decode_step(cache["state"], xh[:, 0], dt[:, 0],
                                           params["A_log"], Bh[:, 0], Ch[:, 0],
                                           params["D"])
            y = y[:, None]                              # [B,1,H,P]
        else:
            init = cache["state"] if cache is not None else None
            y, new_state = ssd_chunked(xh, dt, params["A_log"], Bh, Ch,
                                       params["D"], chunk=ssm.chunk_size,
                                       init_state=init)
    with jax.named_scope("gate_norm"):
        y = y.reshape(bsz, s, di)
        from repro.models.layers import rms_norm
        y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                     params["norm_scale"])
    with jax.named_scope("out_proj"):
        out = dense_(y, params["w_out"])
    new_cache = ({"conv": new_conv, "state": new_state}
                 if cache is not None else None)
    return out, new_cache


def dense_(x, w):
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))

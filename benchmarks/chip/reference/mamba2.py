"""Plain float32 reference of Mamba2 (arXiv:2405.21060; hf:state-spaces/
mamba2-1.3b): token embedding, pre-norm layers of RMSNorm -> Mamba2 mixer
-> residual, a final RMSNorm and an untied head.

The mixer: one input projection to (z, x, B, C, dt); a depthwise causal
convolution of width ``d_conv`` with bias and SiLU over (x, B, C);
dt = softplus(dt + dt_bias); A = -exp(A_log); the selective state-space
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t h_t + D x_t
per head; the gated RMSNorm rmsnorm(y * silu(z)); one output projection.

The recurrence is evaluated exactly in its blocked quadratic (dual) form,
the listing ``ssd_minimal_discrete`` of the paper, at a block length of
its own (``BLOCK``, not the configuration's chunk): within a block the
masked [L, L] product, across blocks a decay matrix over all block states
(no scan).  Every matmul goes through ``Matmul``; layers run under
``jax.checkpoint``.

Departures, in how parameters are held: RMSNorm weights are stored as
(weight - 1); parameters arrive in the benchmark's tree (``blocks/...``
stacked over a leading layer axis, matrices as [in, out], the convolution
as [width, channels]).  Sizes come from the configuration file.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference.common import Matmul, cross_entropy, rms_norm

BLOCK = 128


def _segsum(a: jax.Array) -> jax.Array:
    """out[..., i, j] = sum_{j < k <= i} a[..., k] for j <= i, -inf above."""
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], a.shape + (t,))
    below = jnp.tril(jnp.ones((t, t), bool), -1)
    x = jnp.where(below, x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)


def ssd(x, a, b, c, mm: Matmul):
    """x [B, T, H, P] (already dt-scaled), a [B, T, H] (dt * A), b and c
    [B, T, H, N]: y [B, T, H, P] of the recurrence from a zero state."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    L = min(BLOCK, t)
    nb = t // L
    x = x.reshape(bsz, nb, L, h, p)
    b = b.reshape(bsz, nb, L, h, n)
    c = c.reshape(bsz, nb, L, h, n)
    a = a.reshape(bsz, nb, L, h).transpose(0, 3, 1, 2)            # b h c l
    a_cum = jnp.cumsum(a, axis=-1)
    decay = jnp.exp(_segsum(a))                                     # b h c l s
    cb = mm.ein("bclhn,bcshn->bhcls", c, b)
    y_diag = mm.ein("bhcls,bcshp->bclhp", cb * decay, x)
    to_end = jnp.exp(a_cum[..., -1:] - a_cum)                       # b h c l
    states = mm.ein("bclhn,bclhp->bchpn",
                    b * to_end.transpose(0, 2, 3, 1)[..., None], x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = mm.ein("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    from_start = jnp.exp(a_cum).transpose(0, 2, 3, 1)               # b c l h
    y_off = mm.ein("bclhn,bchpn->bclhp", c * from_start[..., None], states)
    return (y_diag + y_off).reshape(bsz, t, h, p)


def _conv(u: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal convolution: out[t] = sum_k w[k] u[t - W + 1 + k]."""
    width = w.shape[0]
    up = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    t = u.shape[1]
    return sum(up[:, k:k + t] * w[k] for k in range(width)) + bias


def _layer(cfg: Dict, mm: Matmul, x: jax.Array, p: Dict) -> jax.Array:
    bsz, t, d = x.shape
    di = cfg["expand"] * d
    hp = cfg["headdim"]
    nh = di // hp
    g, n = cfg["ngroups"], cfg["d_state"]
    eps = cfg["norm_eps"]
    q = p["mamba"]
    h = rms_norm(x, 1.0 + p["ln"], eps)
    zxbcdt = mm.ein("bsd,df->bsf", h, q["w_in"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * g * n]
    dt = zxbcdt[..., 2 * di + 2 * g * n:]
    xbc = jax.nn.silu(_conv(xbc, q["conv_w"], q["conv_b"]))
    xs = xbc[..., :di].reshape(bsz, t, nh, hp)
    bm = jnp.repeat(xbc[..., di:di + g * n].reshape(bsz, t, g, n), nh // g, axis=2)
    cm = jnp.repeat(xbc[..., di + g * n:].reshape(bsz, t, g, n), nh // g, axis=2)
    dt = jax.nn.softplus(dt + q["dt_bias"])                         # b t h
    A = -jnp.exp(q["A_log"])
    y = ssd(xs * dt[..., None], dt * A, bm, cm, mm)
    y = y + xs * q["D"][:, None]
    y = rms_norm(y.reshape(bsz, t, di) * jax.nn.silu(z), 1.0 + q["norm_scale"], eps)
    return x + mm.ein("bsf,fd->bsd", y, q["w_out"])


def hidden(params: Dict, tokens: jax.Array, cfg: Dict, mm: Matmul) -> jax.Array:
    """Final-normed hidden states [B, S, d] of token ids [B, S]."""
    x = params["embed"].astype(jnp.float32)[tokens]
    layer = jax.checkpoint(lambda h, p: (_layer(cfg, mm, h, p), None))
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms_norm(x, 1.0 + params["final_norm"], cfg["norm_eps"])


def head(params: Dict, h: jax.Array, mm: Matmul) -> jax.Array:
    return mm.ein("bsd,dv->bsv", h, params["lm_head"])


def loss(params: Dict, tokens: jax.Array, cfg: Dict, mm: Matmul) -> jax.Array:
    """Mean next-token cross-entropy over B x (S - 1) positions."""
    h = hidden(params, tokens, cfg, mm)
    return cross_entropy(h[:, :-1], params["lm_head"], tokens[:, 1:], mm)

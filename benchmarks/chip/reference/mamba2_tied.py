"""Plain float32 reference of Mamba2 as hf:state-spaces/mamba2-1.3b
publishes it: token embedding, pre-norm layers of RMSNorm -> Mamba2 mixer
-> residual added in float32 (``residual_in_fp32``), a final RMSNorm and a
head tied to the embedding over the vocabulary padded to a multiple of 16
(50277 -> 50288 rows).

The mixer is that of ``reference/mamba2.py``, whose exact blocked dual
form of the SSD recurrence (``ssd``) and causal convolution (``_conv``)
this module imports: one input projection to (z, x, B, C, dt), the
depthwise causal convolution with bias and SiLU over (x, B, C),
dt = softplus(dt + dt_bias), A = -exp(A_log), the recurrence per head plus
D x, the gated RMSNorm rmsnorm(y * silu(z)) and one output projection.

The in-projection and the convolution are read through :func:`in_proj`,
which takes them fused (``w_in`` [d, 2di+2gn+h], ``conv_w`` [width, di+2gn],
``conv_b``) or in parts named per component and assembles the published
[z | x | B | C | dt] order, so a program that splits them is compared
without a change here.

Everything is float32 and every matmul goes through ``Matmul`` under
``jax.default_matmul_precision("highest")``; layers run under
``jax.checkpoint``.

Departures, in how parameters are held: RMSNorm weights are stored as
(weight - 1); parameters arrive in the benchmark's tree (``blocks/...``
stacked over a leading layer axis, matrices as [in, out], the convolution
as [width, channels], the embedding as [vocab, d]).  The leaves that the
configuration's ``stored_bfloat16`` patterns name are read on bfloat16's
grid (:func:`as_stored`): the weights arrive as the program stores them,
widened to float32, but a compiler that may keep excess precision (XLA
on the TPU) drops a float32 -> bfloat16 -> float32 round trip, so the
rounding is made here with ``reduce_precision``, which it keeps.  The
gradient passes through the rounding unchanged.  The vocabulary's
padding rows are ordinary rows: their logits enter the softmax, as the
published model's do.  Sizes come from the configuration file.
"""
from __future__ import annotations

import fnmatch
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from reference.common import Matmul, cross_entropy, rms_norm
from reference.mamba2 import _conv, ssd

IN_PARTS = ("z", "x", "B", "C", "dt")      # the published order of w_in
CONV_PARTS = ("x", "B", "C")               # of conv_w and conv_b


def _assemble(q: Dict, fused: str, parts: Tuple[str, ...]) -> jax.Array:
    """``q[fused]``, or the parts ``q[f"{fused}_{part}"]`` joined on the
    last axis in the published order."""
    if fused in q:
        return q[fused].astype(jnp.float32)
    return jnp.concatenate([q[f"{fused}_{p}"].astype(jnp.float32)
                            for p in parts], axis=-1)


def in_proj(q: Dict) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(w_in [d, z|x|B|C|dt], conv_w [width, x|B|C], conv_b [x|B|C]) of one
    layer's mixer parameters, fused or split by component."""
    return (_assemble(q, "w_in", IN_PARTS), _assemble(q, "conv_w", CONV_PARTS),
            _assemble(q, "conv_b", CONV_PARTS))


@jax.custom_jvp
def _bf16_grid(x: jax.Array) -> jax.Array:
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@_bf16_grid.defjvp
def _bf16_grid_jvp(primals, tangents):
    return _bf16_grid(primals[0]), tangents[0]


def as_stored(params: Dict, cfg: Dict) -> Dict:
    """``params`` with each leaf whose path (``blocks/mamba/w_in``) matches
    one of ``cfg["stored_bfloat16"]`` rounded to bfloat16's grid, in
    float32; the others as they are."""
    pats = cfg.get("stored_bfloat16", ())

    def one(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if any(fnmatch.fnmatchcase(name, pat) for pat in pats):
            return _bf16_grid(x.astype(jnp.float32))
        return x
    return jax.tree_util.tree_map_with_path(one, params)


def _layer(cfg: Dict, mm: Matmul, x: jax.Array, p: Dict) -> jax.Array:
    bsz, t, d = x.shape
    di = cfg["expand"] * d
    hp = cfg["headdim"]
    nh = di // hp
    g, n = cfg["ngroups"], cfg["d_state"]
    eps = cfg["norm_eps"]
    q = p["mamba"]
    w_in, conv_w, conv_b = in_proj(q)
    h = rms_norm(x, 1.0 + p["ln"], eps)
    zxbcdt = mm.ein("bsd,df->bsf", h, w_in)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * g * n]
    dt = zxbcdt[..., 2 * di + 2 * g * n:]
    xbc = jax.nn.silu(_conv(xbc, conv_w, conv_b))
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
    params = as_stored(params, cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[tokens]
        layer = jax.checkpoint(lambda h, p: (_layer(cfg, mm, h, p), None))
        x, _ = jax.lax.scan(layer, x, params["blocks"])
        return rms_norm(x, 1.0 + params["final_norm"], cfg["norm_eps"])


def head(params: Dict, h: jax.Array, mm: Matmul) -> jax.Array:
    """Logits over every embedding row: the head is the embedding's
    transpose.  It has no configuration to read, so it takes the
    embedding as it comes (the serving comparison's call; no cell of this
    configuration serves)."""
    with jax.default_matmul_precision("highest"):
        return mm.ein("bsd,vd->bsv", h, params["embed"])


def loss(params: Dict, tokens: jax.Array, cfg: Dict, mm: Matmul) -> jax.Array:
    """Mean next-token cross-entropy over B x (S - 1) positions."""
    params = as_stored(params, cfg)
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, cfg, mm)
        w_head = params["embed"].astype(jnp.float32).T
        return cross_entropy(h[:, :-1], w_head, tokens[:, 1:], mm)

"""Plain float32 reference of Qwen1.5 (the Qwen2 architecture of
hf:Qwen/Qwen1.5-0.5B): token embedding, pre-norm decoder layers of
RMSNorm -> causal multi-head attention with QKV bias and rotary positions
(rotate-half form, inverse frequencies theta^(-2i/d)) -> residual, RMSNorm
-> SwiGLU MLP -> residual, a final RMSNorm and an untied head.

Straight jax.numpy in float32, every matmul through ``Matmul`` (HIGHEST
precision, or the fp8 control).  The attention is the full [S, S] softmax
with a causal mask, no cache and no blocking.  Layers run one after
another under ``jax.checkpoint`` so that a training step fits the chip.

Departures from the published model, all in how parameters are held:
each RMSNorm weight is stored as (weight - 1); parameters arrive in the
benchmark's tree (``blocks/...`` stacked over a leading layer axis,
matrices as [in, out]).  Sizes come from the configuration file.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from reference.common import Matmul, cross_entropy, rms_norm


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [B, S, H, hd] rotated by position (rotate-half form)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _layer(cfg: Dict, mm: Matmul, x: jax.Array, p: Dict) -> jax.Array:
    b, s, d = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    eps = cfg["rms_norm_eps"]
    a = p["attn"]
    h = rms_norm(x, 1.0 + p["ln1"], eps)
    q = (mm.ein("bsd,df->bsf", h, a["w_q"]) + a["b_q"]).reshape(b, s, nh, hd)
    k = (mm.ein("bsd,df->bsf", h, a["w_k"]) + a["b_k"]).reshape(b, s, nkv, hd)
    v = (mm.ein("bsd,df->bsf", h, a["w_v"]) + a["b_v"]).reshape(b, s, nkv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = mm.ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm.ein("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * hd)
    x = x + mm.ein("bsf,fd->bsd", o, a["w_o"])
    m = p["mlp"]
    h = rms_norm(x, 1.0 + p["ln2"], eps)
    g = jax.nn.silu(mm.ein("bsd,df->bsf", h, m["w_gate"]))
    u = mm.ein("bsd,df->bsf", h, m["w_up"])
    return x + mm.ein("bsf,fd->bsd", g * u, m["w_down"])


def hidden(params: Dict, tokens: jax.Array, cfg: Dict, mm: Matmul) -> jax.Array:
    """Final-normed hidden states [B, S, d] of token ids [B, S]."""
    x = params["embed"].astype(jnp.float32)[tokens]
    layer = jax.checkpoint(lambda h, p: (_layer(cfg, mm, h, p), None))
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms_norm(x, 1.0 + params["final_norm"], cfg["rms_norm_eps"])


def head(params: Dict, h: jax.Array, mm: Matmul) -> jax.Array:
    return mm.ein("bsd,dv->bsv", h, params["lm_head"])


def loss(params: Dict, tokens: jax.Array, cfg: Dict, mm: Matmul) -> jax.Array:
    """Mean next-token cross-entropy over B x (S - 1) positions."""
    h = hidden(params, tokens, cfg, mm)
    return cross_entropy(h[:, :-1], params["lm_head"], tokens[:, 1:], mm)

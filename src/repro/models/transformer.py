"""Decoder-LM / enc-dec / MoE / MLA / hybrid transformer assembly.

One implementation covers all 10 assigned architectures, driven entirely by
:class:`repro.configs.base.ArchConfig`:

  * homogeneous decoder stacks run as ONE ``lax.scan`` over stacked layer
    params (compact HLO — essential for the 512-device dry-run compiles);
  * gemma3's 5-local:1-global pattern scans over *cycles* (pattern period)
    so every layer keeps a static window — local layers get ring-buffer KV
    caches of size W, global layers full-length caches;
  * deepseek: MLA attention (low-rank q/kv, decoupled rope) with the
    absorbed MQA-over-latent decode path, 3 dense + 58 MoE layers as two
    scans, optional MTP head;
  * zamba2: 9 segments of (6 scanned mamba2 layers + shared attention
    block, params alternating between 2 shared sets);
  * whisper: encoder (non-causal) + decoder (causal self + cross) with the
    audio frontend stubbed as precomputed frame embeddings.

Params are plain pytrees of jnp arrays; leaves of scanned stacks carry a
leading layer axis.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import costing_mode
from repro.models import layers as L
from repro.models import mamba as M

Params = Dict[str, Any]
Cache = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _norm_init(rng, shape, scale, dtype):
    return (jax.random.normal(rng, shape) * scale).astype(dtype)


def attn_init(rng, cfg: ArchConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.head_dim_
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(rng, 8)
    std = d ** -0.5
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "w_dq": _norm_init(ks[0], (d, m.q_lora_rank), std, dtype),
            "q_norm": jnp.zeros((m.q_lora_rank,), jnp.float32),
            "w_uq": _norm_init(ks[1], (m.q_lora_rank, nh * m.qk_head_dim),
                               m.q_lora_rank ** -0.5, dtype),
            "w_dkv": _norm_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                                std, dtype),
            "kv_norm": jnp.zeros((m.kv_lora_rank,), jnp.float32),
            "w_ukv": _norm_init(ks[3], (m.kv_lora_rank,
                                        nh * (m.qk_nope_head_dim + m.v_head_dim)),
                                m.kv_lora_rank ** -0.5, dtype),
            "w_o": _norm_init(ks[4], (nh * m.v_head_dim, d),
                              (nh * m.v_head_dim) ** -0.5, dtype),
        }
    p = {
        "w_q": _norm_init(ks[0], (d, nh * hd), std, dtype),
        "w_k": _norm_init(ks[1], (d, nkv * hd), std, dtype),
        "w_v": _norm_init(ks[2], (d, nkv * hd), std, dtype),
        "w_o": _norm_init(ks[3], (nh * hd, d), (nh * hd) ** -0.5, dtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((nh * hd,), dtype)
        p["b_k"] = jnp.zeros((nkv * hd,), dtype)
        p["b_v"] = jnp.zeros((nkv * hd,), dtype)
    return p


def mlp_init(rng, cfg: ArchConfig, d_ff: int, dtype) -> Params:
    d = cfg.d_model
    ks = jax.random.split(rng, 3)
    std = d ** -0.5
    p = {"w_up": _norm_init(ks[0], (d, d_ff), std, dtype),
         "w_down": _norm_init(ks[1], (d_ff, d), d_ff ** -0.5, dtype)}
    if cfg.gated_mlp:
        p["w_gate"] = _norm_init(ks[2], (d, d_ff), std, dtype)
    return p


def moe_init(rng, cfg: ArchConfig, dtype) -> Params:
    mc = cfg.moe
    d, e, f = cfg.d_model, mc.n_experts, mc.d_ff_expert
    ks = jax.random.split(rng, 5)
    std = d ** -0.5
    p = {
        "w_router": _norm_init(ks[0], (d, e), std, jnp.float32),
        "w_up": _norm_init(ks[1], (e, d, f), std, dtype),
        "w_down": _norm_init(ks[2], (e, f, d), f ** -0.5, dtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = _norm_init(ks[3], (e, d, f), std, dtype)
    if mc.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, mc.n_shared_experts * f, dtype)
    return p


def block_init(rng, cfg: ArchConfig, *, moe: bool, cross: bool, dtype) -> Params:
    ks = jax.random.split(rng, 4)
    d = cfg.d_model
    p: Params = {"ln1": jnp.zeros((d,), jnp.float32),
                 "ln2": jnp.zeros((d,), jnp.float32),
                 "attn": attn_init(ks[0], cfg, dtype)}
    if moe:
        p["moe"] = moe_init(ks[1], cfg, dtype)
    else:
        d_ff = cfg.d_ff if cfg.d_ff else 4 * d
        if cfg.moe is not None and cfg.moe.d_ff_dense:
            d_ff = cfg.moe.d_ff_dense
        p["mlp"] = mlp_init(ks[1], cfg, d_ff, dtype)
    if cross:
        p["ln_cross"] = jnp.zeros((d,), jnp.float32)
        p["cross"] = attn_init(ks[2], cfg, dtype)
    return p


def _stack(rng, n: int, init_fn) -> Params:
    # one vmapped init per parameter, not n unrolled copies: the same
    # values, and a program the TPU compiler takes seconds (not minutes) on
    return jax.vmap(init_fn)(jax.random.split(rng, n))


def init_params(cfg: ArchConfig, rng) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 10)
    d = cfg.d_model
    params: Params = {
        "embed": _norm_init(ks[0], (cfg.vocab_size, d), 1.0, dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _norm_init(ks[1], (d, cfg.vocab_size), d ** -0.5, dtype)

    fam = cfg.family
    if fam == "ssm":
        params["blocks"] = _stack(ks[2], cfg.n_layers,
                                  lambda k: dict(
                                      ln=jnp.zeros((d,), jnp.float32),
                                      mamba=M.mamba_block_init(k, d, cfg.ssm, dtype)))
    elif fam == "hybrid":
        params["blocks"] = _stack(ks[2], cfg.n_layers,
                                  lambda k: dict(
                                      ln=jnp.zeros((d,), jnp.float32),
                                      mamba=M.mamba_block_init(k, d, cfg.ssm, dtype)))
        params["shared_attn"] = [
            block_init(k, cfg, moe=False, cross=False, dtype=dtype)
            for k in jax.random.split(ks[3], cfg.hybrid.n_shared_attn_blocks)]
    elif cfg.enc_dec is not None:
        params["enc_blocks"] = _stack(
            ks[2], cfg.enc_dec.n_encoder_layers,
            lambda k: block_init(k, cfg, moe=False, cross=False, dtype=dtype))
        params["enc_norm"] = jnp.zeros((d,), jnp.float32)
        params["blocks"] = _stack(
            ks[3], cfg.n_layers,
            lambda k: block_init(k, cfg, moe=False, cross=True, dtype=dtype))
    elif cfg.moe is not None:
        nd = cfg.moe.first_dense_layers
        if nd:
            params["dense_blocks"] = _stack(
                ks[2], nd, lambda k: block_init(k, cfg, moe=False, cross=False,
                                                dtype=dtype))
        params["blocks"] = _stack(
            ks[3], cfg.n_layers - nd,
            lambda k: block_init(k, cfg, moe=True, cross=False, dtype=dtype))
        if cfg.mtp_depth:
            params["mtp"] = {
                "proj": _norm_init(ks[4], (2 * d, d), (2 * d) ** -0.5, dtype),
                "block": block_init(ks[5], cfg, moe=False, cross=False, dtype=dtype),
                "norm": jnp.zeros((d,), jnp.float32),
            }
    elif cfg.window_pattern is not None:
        period = len(cfg.window_pattern)
        n_cycles = cfg.n_layers // period
        assert n_cycles * period == cfg.n_layers
        params["cycles"] = _stack(
            ks[2], n_cycles,
            lambda k: [block_init(kk, cfg, moe=False, cross=False, dtype=dtype)
                       for kk in jax.random.split(k, period)])
    else:
        params["blocks"] = _stack(
            ks[2], cfg.n_layers,
            lambda k: block_init(k, cfg, moe=False, cross=False, dtype=dtype))
    return params


# ---------------------------------------------------------------------------
# Attention sublayer apply (dense QKV path + caches)
# ---------------------------------------------------------------------------


@jax.named_scope("attention")
def gqa_attention(cfg: ArchConfig, p: Params, x: jax.Array, *,
                  positions: jax.Array, window: Optional[int],
                  causal: bool = True,
                  kv_cache: Optional[Dict[str, jax.Array]] = None,
                  kv_source: Optional[jax.Array] = None,
                  use_kernel: bool = False,
                  ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Standard GQA attention.  x: [B,S,d].

    kv_cache: {"kv": [S_c,Hkv,B,2*hd], "kpos": [S_c]} — ring or full; each
    slot's K and V side by side (see :func:`init_cache`).
    kv_source: cross-attention source (whisper); disables rope+cache-write.
    """
    b, s, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = L.dense(x, p["w_q"], p.get("b_q")).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    src = kv_source if kv_source is not None else x
    sk = src.shape[1]
    k = L.dense(src, p["w_k"], p.get("b_k")).reshape(b, sk, nkv, hd).transpose(0, 2, 1, 3)
    v = L.dense(src, p["w_v"], p.get("b_v")).reshape(b, sk, nkv, hd).transpose(0, 2, 1, 3)

    new_cache = None
    if kv_source is not None:
        out = L.attention_dense(q, k, v, causal=False)
    else:
        q = L.apply_rope(q, positions[:, None, :].repeat(nh, 1), cfg.rope_theta)
        k = L.apply_rope(k, positions[:, None, :].repeat(nkv, 1), cfg.rope_theta)
        if kv_cache is None:
            out = L.attention(q, k, v, causal=causal, window=window,
                              use_kernel=use_kernel)
        else:
            kv, kpos = kv_cache["kv"], kv_cache["kpos"]
            # "layer": the whole stacked cache rides the layers
            # (_at_layer); this layer's rows are kv[layer]
            layer = kv_cache.get("layer")
            at = () if layer is None else (layer,)
            cap = kv.shape[len(at)]
            # one slot's row holds K and V side by side: [Hkv, B, 2*hd]
            rows = jnp.concatenate([k, v], axis=-1).transpose(
                2, 1, 0, 3).astype(kv.dtype)
            if s == 1:                                     # decode
                pos = positions[0, 0]
                slot = pos % cap
                kv = _put(kv, rows, at + (slot, 0, 0, 0))
                kpos = _put(kpos, pos[None].astype(kpos.dtype), at + (slot,))
                new_cache = {"kv": kv, "kpos": kpos}
                if layer is not None:
                    kpos = jax.lax.dynamic_index_in_dim(kpos, layer,
                                                        keepdims=False)
                valid = (kpos >= 0) & (kpos <= pos)
                if window is not None:
                    valid &= kpos > pos - window
                scores_mask = valid[None, None, None, :]
                ck, cv = (_cached_heads(kv, at, i * hd, hd) for i in (0, 1))
                out = _masked_dense_attention(q, ck, cv, scores_mask)
            else:                                          # prefill
                if s >= cap:
                    kv = rows[s - cap:]
                    kpos = positions[0, s - cap:].astype(jnp.int32)
                else:
                    kv = jax.lax.dynamic_update_slice_in_dim(kv, rows, 0,
                                                             axis=0)
                    kpos = jax.lax.dynamic_update_slice_in_dim(
                        kpos, positions[0].astype(jnp.int32), 0, axis=0)
                out = L.attention(q, k, v, causal=causal, window=window,
                                  use_kernel=use_kernel)
                new_cache = {"kv": kv, "kpos": kpos}
    out = out.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    return L.dense(out, p["w_o"]), new_cache


def _put(buf: jax.Array, row: jax.Array, idx: Tuple) -> jax.Array:
    """``buf`` with ``row`` (lacking ``buf``'s leading dims) written at
    ``idx``."""
    row = row.reshape((1,) * (buf.ndim - row.ndim) + row.shape)
    return jax.lax.dynamic_update_slice(buf, row, idx)


def _cached_heads(kv: jax.Array, at: Tuple, lane: int, hd: int) -> jax.Array:
    """K (``lane`` 0) or V (``lane`` hd) of the cache's layer ``at`` as
    [B,Hkv,S_c,hd].  K and V are sliced apart, so each slice fuses into the
    one attention product that reads it instead of being materialized."""
    size = (1,) * len(at) + kv.shape[len(at):-1] + (hd,)
    x = jax.lax.dynamic_slice(kv, at + (0, 0, 0, lane), size)
    return x.reshape(x.shape[len(at):]).transpose(2, 1, 0, 3)


def _masked_dense_attention(q, k, v, mask) -> jax.Array:
    b, hq, sq, dk = q.shape
    _, hkv, skv, dv = v.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(dk)
    qg = q.reshape(b, hkv, g, sq, dk)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, :, None], s, L.NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m) * mask[:, :, None]
    l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p / l, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, dv).astype(q.dtype)


@jax.named_scope("attention")
def mla_attention(cfg: ArchConfig, p: Params, x: jax.Array, *,
                  positions: jax.Array,
                  kv_cache: Optional[Dict[str, jax.Array]] = None,
                  use_kernel: bool = False,
                  ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """DeepSeek MLA.  Cache holds the compressed latent (c_kv + k_rope)."""
    m = cfg.mla
    b, s, d = x.shape
    nh = cfg.n_heads
    r, rd = m.kv_lora_rank, m.qk_rope_head_dim
    dn, dv_ = m.qk_nope_head_dim, m.v_head_dim

    cq = L.rms_norm(L.dense(x, p["w_dq"]), p["q_norm"], cfg.norm_eps)
    q = L.dense(cq, p["w_uq"]).reshape(b, s, nh, m.qk_head_dim).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, positions[:, None, :].repeat(nh, 1), cfg.rope_theta)

    ckv_full = L.dense(x, p["w_dkv"])                      # [B,S,r+rd]
    c_kv = L.rms_norm(ckv_full[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = ckv_full[..., None, r:]                       # [B,S,1,rd]
    k_rope = L.apply_rope(k_rope.transpose(0, 2, 1, 3),
                          positions[:, None, :], cfg.rope_theta)  # [B,1,S,rd]

    scale = 1.0 / math.sqrt(m.qk_head_dim)
    new_cache = None
    if kv_cache is not None and s == 1:
        # ---- absorbed decode: MQA over the latent cache ----
        pos = positions[0, 0]
        cc, ckr = kv_cache["ckv"], kv_cache["krope"]       # [B,S_c,r],[B,S_c,rd]
        cc = jax.lax.dynamic_update_slice_in_dim(cc, c_kv, pos, axis=1)
        ckr = jax.lax.dynamic_update_slice_in_dim(
            ckr, k_rope[:, 0], pos, axis=1)
        w_ukv = p["w_ukv"].reshape(r, nh, dn + dv_)
        w_uk, w_uv = w_ukv[..., :dn], w_ukv[..., dn:]
        q_lat = jnp.einsum("bhsd,rhd->bhsr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32)).astype(x.dtype)
        q_full = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B,H,1,r+rd]
        k_full = jnp.concatenate([cc, ckr], axis=-1)[:, None]  # [B,1,S,r+rd]
        v_lat = cc[:, None]                                 # [B,1,S,r]
        kmask = (jnp.arange(cc.shape[1]) <= pos)[None, None, None, :]
        # _masked_dense_attention scales by 1/sqrt(r+rd); MLA's true scale is
        # 1/sqrt(qk_head_dim) — fold the correction into q.
        corr = math.sqrt(q_full.shape[-1]) * scale
        o_lat = _masked_dense_attention(q_full * corr, k_full, v_lat, kmask)
        out = jnp.einsum("bhsr,rhd->bshd", o_lat.astype(jnp.float32),
                         w_uv.astype(jnp.float32))
        out = out.reshape(b, s, nh * dv_).astype(x.dtype)
        new_cache = {"ckv": cc, "krope": ckr}
    else:
        kv = L.dense(c_kv, p["w_ukv"]).reshape(b, s, nh, dn + dv_)
        k_nope = kv[..., :dn].transpose(0, 2, 1, 3)
        v = kv[..., dn:].transpose(0, 2, 1, 3)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, (b, nh, s, rd)).astype(k_nope.dtype)], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = L.attention(qf, k, v, causal=True, scale=scale, use_kernel=use_kernel)
        out = o.transpose(0, 2, 1, 3).reshape(b, s, nh * dv_)
        if kv_cache is not None:                           # prefill fills cache
            cc = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["ckv"], c_kv, 0, axis=1)
            ckr = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["krope"], k_rope[:, 0], 0, axis=1)
            new_cache = {"ckv": cc, "krope": ckr}
    return L.dense(out, p["w_o"]), new_cache


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def cross_attention(cfg: ArchConfig, p: Params, x: jax.Array,
                    k: jax.Array, v: jax.Array) -> jax.Array:
    """Cross-attn with precomputed K/V [B,Hkv,S_enc,hd]."""
    b, s, d = x.shape
    nh, hd = cfg.n_heads, cfg.head_dim_
    q = L.dense(x, p["w_q"], p.get("b_q")).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    o = L.attention_dense(q, k, v, causal=False)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    return L.dense(o, p["w_o"])


def cross_kv(cfg: ArchConfig, p: Params, src: jax.Array):
    b, sk, _ = src.shape
    nkv, hd = cfg.n_kv_heads, cfg.head_dim_
    k = L.dense(src, p["w_k"], p.get("b_k")).reshape(b, sk, nkv, hd).transpose(0, 2, 1, 3)
    v = L.dense(src, p["w_v"], p.get("b_v")).reshape(b, sk, nkv, hd).transpose(0, 2, 1, 3)
    return k, v


def block_apply(cfg: ArchConfig, p: Params, x: jax.Array, *,
                positions: jax.Array, window: Optional[int],
                causal: bool = True, moe: bool = False,
                kv_cache: Optional[Dict] = None,
                cross_state: Optional[Tuple] = None,
                capacity_factor: Optional[float] = None,
                use_kernel: bool = False):
    """One transformer block. Returns (x, new_cache, aux_loss)."""
    b, s, d = x.shape
    h_in = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        attn_out, new_cache = mla_attention(cfg, p["attn"], h_in,
                                            positions=positions,
                                            kv_cache=kv_cache,
                                            use_kernel=use_kernel)
    else:
        attn_out, new_cache = gqa_attention(cfg, p["attn"], h_in,
                                            positions=positions, window=window,
                                            causal=causal, kv_cache=kv_cache,
                                            use_kernel=use_kernel)
    x = x + attn_out
    if cross_state is not None:
        ck, cv = cross_state
        x = x + cross_attention(cfg, p["cross"],
                                L.rms_norm(x, p["ln_cross"], cfg.norm_eps), ck, cv)
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if moe:
        mc = cfg.moe
        out2d, aux = L.moe_ffn(h2.reshape(b * s, d), p["moe"],
                               top_k=mc.top_k,
                               capacity_factor=capacity_factor or mc.capacity_factor,
                               gated=cfg.gated_mlp)
        out = out2d.reshape(b, s, d)
        if mc.n_shared_experts:
            out = out + L.ffn(h2, p["moe"]["shared"], cfg.gated_mlp)
    else:
        out = L.ffn(h2, p["mlp"], cfg.gated_mlp,
                    act="silu" if cfg.gated_mlp else "gelu")
    return x + out, new_cache, aux


@jax.named_scope("ssd")
def mamba_layer_apply(cfg: ArchConfig, p: Params, x: jax.Array,
                      cache: Optional[Dict] = None, use_kernel: bool = False):
    """Pre-norm Mamba2 layer.  The residual ``x`` keeps its own dtype (float32
    under ``cfg.residual_in_fp32``); the mixer sees the normed input in
    ``cfg.dtype``."""
    h = L.rms_norm(x, p["ln"], cfg.norm_eps).astype(cfg.dtype)
    out, new_cache = M.mamba_block_apply(p["mamba"], h, cfg.ssm, cache,
                                         use_kernel=use_kernel)
    return x + out.astype(x.dtype), new_cache, jnp.zeros((), jnp.float32)


def _remat_wrap(fn, remat: str):
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "selective":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def scan_stack(stacked: Params, x: jax.Array, body_fn, cache=None,
               remat: str = "none"):
    """Scan a homogeneous layer stack.  body_fn(p, h, c) -> (h, c, aux).

    A one-token step carries stacked K/V caches (a ``{"kv", "kpos"}`` dict
    or a tuple of them) whole (:func:`_scan_in_place`); other caches are
    scanned as ``xs`` and restacked as ``ys``."""
    if cache is None:
        def body(h, p):
            h2, _, aux = body_fn(p, h, None)
            return h2, aux
        body = _remat_wrap(body, remat)
        x, auxs = jax.lax.scan(body, x, stacked)
        return x, None, auxs.sum()

    caches = cache if isinstance(cache, tuple) else (cache,)
    if x.shape[1] == 1 and all(set(c) == {"kv", "kpos"} for c in caches):
        return _scan_in_place(stacked, x, body_fn, cache)

    def body(h, pc):
        p, c = pc
        h2, c2, aux = body_fn(p, h, c)
        return h2, (c2, aux)

    x, (cache2, auxs) = jax.lax.scan(body, x, (stacked, cache))
    return x, cache2, auxs.sum()


def decode_carries_cache(cfg: ArchConfig) -> bool:
    """Whether a one-token decode of ``cfg`` writes K/V rows into stacked
    caches in place (:func:`_at_layer`): every family with attention
    caches; MLA keeps latents and the SSM its state."""
    return cfg.family != "ssm" and cfg.mla is None


def _at_layer(cache, i):
    """Layer ``i``'s view of stacked K/V caches (one ``{"kv", "kpos"}``
    dict or a tuple of them): the whole stack, which gqa_attention reads
    at ``[i]`` and writes one row of in place."""
    if isinstance(cache, tuple):
        return tuple(dict(c, layer=i) for c in cache)
    return dict(cache, layer=i)


def _scan_in_place(stacked: Params, x: jax.Array, body_fn, cache):
    """One-token step over stacked K/V caches: the scan carries them whole
    and each layer writes its row at ``[layer, slot]``, so a cache is
    neither sliced out as ``xs`` nor restacked as ``ys``."""
    n = jax.tree.leaves(stacked)[0].shape[0]

    def body(carry, pi):
        h, c = carry
        p, i = pi
        h2, c2, aux = body_fn(p, h, _at_layer(c, i))
        return (h2, c2), aux

    (x, cache2), auxs = jax.lax.scan(body, (x, cache),
                                     (stacked, jnp.arange(n)))
    return x, cache2, auxs.sum()


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Cache:
    """Concrete zero-filled decode cache (eval_shape-able for the dry-run).

    An attention cache is slot-major, ``"kv"`` [layers, slots, Hkv, B,
    2*hd] with each slot's K and V side by side, so a one-token step writes
    one contiguous row per layer, and the chip's default layout (slots
    major, batch x K|V minor; lane-dense at hd 64) is the one the decode's
    attention reads: no relayout copy, no custom layout to keep."""
    dtype = jnp.dtype(cfg.dtype)
    nkv, hd = cfg.n_kv_heads, cfg.head_dim_

    def kvc(n_layers, cap):
        return {"kv": jnp.zeros((n_layers, cap, nkv, batch, 2 * hd), dtype),
                "kpos": jnp.full((n_layers, cap), -1, jnp.int32)}

    cache: Cache = {"pos": jnp.zeros((), jnp.int32)}
    fam = cfg.family
    if fam in ("ssm", "hybrid"):
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        conv_ch = di + 2 * s.n_groups * s.state_size
        cache["mamba"] = {
            "conv": jnp.zeros((cfg.n_layers, batch, s.conv_width - 1, conv_ch), dtype),
            "state": jnp.zeros((cfg.n_layers, batch, s.n_heads(cfg.d_model),
                                s.head_dim, s.state_size), jnp.float32),
        }
        if fam == "hybrid":
            n_app = cfg.n_layers // cfg.hybrid.attn_every
            cache["attn"] = kvc(n_app, max_len)
    elif cfg.enc_dec is not None:
        cache["self"] = kvc(cfg.n_layers, max_len)
        cache["cross_k"] = jnp.zeros((cfg.n_layers, batch, nkv,
                                      cfg.enc_dec.encoder_seq, hd), dtype)
        cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    elif cfg.mla is not None:
        m = cfg.mla
        nd = cfg.moe.first_dense_layers if cfg.moe else 0
        for name, n in (("dense", nd), ("moe", cfg.n_layers - nd)):
            if n:
                cache[name] = {
                    "ckv": jnp.zeros((n, batch, max_len, m.kv_lora_rank), dtype),
                    "krope": jnp.zeros((n, batch, max_len, m.qk_rope_head_dim), dtype),
                }
    elif cfg.window_pattern is not None:
        period = len(cfg.window_pattern)
        n_cycles = cfg.n_layers // period
        for i, w in enumerate(cfg.window_pattern):
            cache[f"p{i}"] = kvc(n_cycles,
                                 max_len if w is None else min(w, max_len))
    elif cfg.moe is not None:
        nd = cfg.moe.first_dense_layers
        if nd:
            cache["dense"] = kvc(nd, max_len)
        cache["moe"] = kvc(cfg.n_layers - nd, max_len)
    else:
        cache["self"] = kvc(cfg.n_layers, max_len)
    return cache


# ---------------------------------------------------------------------------
# Forward / prefill / decode
# ---------------------------------------------------------------------------


def _stack_runner(cfg: ArchConfig, params: Params, x: jax.Array,
                  positions: jax.Array, cache: Optional[Cache],
                  remat: str, use_kernel: bool, capacity_factor=None):
    """Run the arch-specific layer stack. Returns (x, new_cache, aux)."""
    fam = cfg.family
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Cache = {} if cache is not None else None

    if fam == "ssm":
        def body(p, h, c):
            return mamba_layer_apply(cfg, p, h, c, use_kernel)
        x, c2, aux = scan_stack(params["blocks"], x, body,
                                cache["mamba"] if cache else None, remat)
        if cache is not None:
            new_cache["mamba"] = c2
        aux_total += aux

    elif fam == "hybrid":
        every = cfg.hybrid.attn_every
        n_seg = cfg.n_layers // every
        mamba_stack = jax.tree.map(
            lambda a: a.reshape((n_seg, every) + a.shape[1:]), params["blocks"])
        mcaches, acaches = [], []
        attn = cache["attn"] if cache is not None else None
        # a one-token step writes its rows into the shared-attention stack
        # in place (_at_layer); a prompt fills each application's slice
        in_place = cache is not None and x.shape[1] == 1

        def body(p, h, c):
            return mamba_layer_apply(cfg, p, h, c, use_kernel)
        for seg in range(n_seg):
            seg_params = jax.tree.map(lambda a: a[seg], mamba_stack)
            seg_cache = (jax.tree.map(lambda a: a[seg * every:(seg + 1) * every],
                                      cache["mamba"]) if cache else None)
            x, c2, aux = scan_stack(seg_params, x, body, seg_cache, remat)
            aux_total += aux
            if cache is not None:
                mcaches.append(c2)
            shared = params["shared_attn"][seg % len(params["shared_attn"])]
            blk = _remat_wrap(
                lambda h_, ac_, _sh=shared: block_apply(
                    cfg, _sh, h_, positions=positions, window=None,
                    kv_cache=ac_, use_kernel=use_kernel)[:2],
                remat if cache is None else "none")
            if cache is None:
                x, _ = blk(x, None)
            elif in_place:
                x, attn = blk(x, _at_layer(attn, seg))
            else:
                x, ac2 = blk(x, jax.tree.map(lambda a: a[seg], attn))
                acaches.append(ac2)
        if cache is not None:
            new_cache["mamba"] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *mcaches)
            new_cache["attn"] = attn if in_place else jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=0), *acaches)

    elif cfg.enc_dec is not None:
        # decoder over x; cross K/V must already be in `cross_state`
        raise RuntimeError("enc_dec handled in forward()/decode_step directly")

    elif cfg.moe is not None:
        nd = cfg.moe.first_dense_layers
        cname = {True: "dense", False: "moe"}
        for moe_flag, pname in ((False, "dense_blocks"), (True, "blocks")):
            if pname not in params:
                continue
            key = cname[not moe_flag] if False else ("moe" if moe_flag else "dense")
            def body(p, h, c, _moe=moe_flag):
                return block_apply(cfg, p, h, positions=positions, window=None,
                                   moe=_moe, kv_cache=c,
                                   capacity_factor=capacity_factor,
                                   use_kernel=use_kernel)
            x, c2, aux = scan_stack(params[pname], x, body,
                                    cache[key] if cache else None, remat)
            aux_total += aux
            if cache is not None:
                new_cache[key] = c2

    elif cfg.window_pattern is not None:
        period = len(cfg.window_pattern)
        kv_len = positions.shape[-1] if cache is None else None

        def cycle_body(h, pc):
            cyc_params, cyc_caches = pc
            new_c = []
            aux = jnp.zeros((), jnp.float32)
            for i, w in enumerate(cfg.window_pattern):
                p_i = [jax.tree.map(lambda a: a, cp) for cp in [cyc_params]][0][i]
                c_i = cyc_caches[i] if cyc_caches is not None else None
                h, c2, a = block_apply(cfg, p_i, h, positions=positions,
                                       window=w, kv_cache=c_i,
                                       use_kernel=use_kernel)
                aux += a
                new_c.append(c2 if c2 is not None else 0)
            return h, (tuple(new_c) if cyc_caches is not None else None, aux)

        def body(p, h, cs):
            h2, (cs2, aux) = cycle_body(h, (p, cs))
            return h2, cs2, aux
        caches_in = (tuple(cache[f"p{i}"] for i in range(period))
                     if cache is not None else None)
        x, cs_out, aux = scan_stack(params["cycles"], x, body, caches_in,
                                    remat)
        aux_total += aux
        if cache is not None:
            for i in range(period):
                new_cache[f"p{i}"] = cs_out[i]
    else:
        def body(p, h, c):
            return block_apply(cfg, p, h, positions=positions, window=None,
                               kv_cache=c, use_kernel=use_kernel)
        x, c2, aux = scan_stack(params["blocks"], x, body,
                                cache["self"] if cache else None, remat)
        aux_total += aux
        if cache is not None:
            new_cache["self"] = c2
    return x, new_cache, aux_total


@jax.named_scope("ce_head")
def _head(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", h, params["embed"]).astype(jnp.float32)
    return jnp.einsum("bsd,dv->bsv", h, params["lm_head"]).astype(jnp.float32)


@jax.named_scope("embed")
def _embed(cfg: ArchConfig, params: Params, tokens: jax.Array) -> jax.Array:
    """Token embeddings: the residual stream the layer stack carries."""
    x = jnp.take(params["embed"], tokens, axis=0)
    return x.astype(jnp.float32) if cfg.residual_in_fp32 else x


def run_encoder(cfg: ArchConfig, params: Params, frontend: jax.Array,
                remat: str = "none", use_kernel: bool = False) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings [B,F,d]."""
    b, f, _ = frontend.shape
    positions = jnp.broadcast_to(jnp.arange(f), (b, f))

    def body(p, h, c):
        return block_apply(cfg, p, h, positions=positions, window=None,
                           causal=False, use_kernel=use_kernel)
    x, _, _ = scan_stack(params["enc_blocks"], frontend, body, None, remat)
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward_hidden(cfg: ArchConfig, params: Params, tokens: jax.Array,
                   frontend: Optional[jax.Array] = None, *,
                   remat: str = "none", use_kernel: bool = False,
                   capacity_factor: Optional[float] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Trunk only: returns (pre-head hidden [B,S_total,d], aux_loss)."""
    b, s = tokens.shape
    x = _embed(cfg, params, tokens)
    if cfg.enc_dec is not None:
        assert frontend is not None, "enc-dec arch needs frontend embeddings"
        enc_out = run_encoder(cfg, params, frontend, remat, use_kernel)
    elif cfg.frontend != "none" and frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    stot = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(stot), (b, stot))

    if cfg.enc_dec is not None:
        def body(p, h, c):
            ck, cv = cross_kv(cfg, p["cross"], enc_out)
            return block_apply(cfg, p, h, positions=positions, window=None,
                               kv_cache=c, cross_state=(ck, cv),
                               use_kernel=use_kernel)
        x, _, aux = scan_stack(params["blocks"], x, body, None, remat)
    else:
        x, _, aux = _stack_runner(cfg, params, x, positions, None, remat,
                                  use_kernel, capacity_factor)
    return x, aux


def forward(cfg: ArchConfig, params: Params, tokens: jax.Array,
            frontend: Optional[jax.Array] = None, *, remat: str = "none",
            use_kernel: bool = False,
            capacity_factor: Optional[float] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits [B,S_total,V], aux_loss)."""
    x, aux = forward_hidden(cfg, params, tokens, frontend, remat=remat,
                            use_kernel=use_kernel,
                            capacity_factor=capacity_factor)
    logits = _head(cfg, params, x)
    return logits, aux


def mtp_hidden(cfg: ArchConfig, params: Params, h_main: jax.Array,
               tokens: jax.Array) -> jax.Array:
    """DeepSeek MTP trunk: hidden predicting t+2 from h[t] + emb(token[t+1])."""
    p = params["mtp"]
    b, s = tokens.shape
    h = L.rms_norm(h_main[:, :-1], p["norm"], cfg.norm_eps)
    nxt = jnp.take(params["embed"], tokens[:, 1:], axis=0)
    x = jnp.einsum("bsd,df->bsf", jnp.concatenate([h, nxt], -1),
                   p["proj"].astype(h.dtype))
    positions = jnp.broadcast_to(jnp.arange(s - 1), (b, s - 1))
    x, _, _ = block_apply(cfg, p["block"], x, positions=positions, window=None)
    return x


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array], *,
            remat: str = "none", use_kernel: bool = False,
            aux_weight: float = 0.01, mtp_weight: float = 0.1,
            capacity_factor: Optional[float] = None,
            ce_chunk: int = 2048):
    """Next-token CE (+ MoE aux + MTP).  batch: tokens [B,S] (+frontend).

    The CE head is **chunked + rematerialized**: logits are computed per
    token-chunk inside jax.checkpoint, so the [T, vocab] fp32 tensor never
    materializes — peak head memory is [ce_chunk, vocab].  (This fixed a
    73 GB/device temp the compiled-plan memory analysis exposed; see
    EXPERIMENTS.md §Perf.)
    """
    tokens = batch["tokens"]
    frontend = batch.get("frontend")
    hidden, aux = forward_hidden(cfg, params, tokens, frontend, remat=remat,
                                 use_kernel=use_kernel,
                                 capacity_factor=capacity_factor)
    offset = 0
    if cfg.frontend != "none" and cfg.enc_dec is None and frontend is not None:
        offset = frontend.shape[1]
    h = hidden[:, offset:offset + tokens.shape[1] - 1]
    tgt = tokens[:, 1:]
    ce = _chunked_ce(cfg, params, h, tgt, ce_chunk)
    total = ce + aux_weight * aux
    metrics = {"ce": ce, "aux": aux}
    if cfg.mtp_depth and "mtp" in params:
        h_m = hidden[:, offset:offset + tokens.shape[1]]
        mtp_h = mtp_hidden(cfg, params, h_m, tokens)      # [B, S-1, d]
        mtp_ce = _chunked_ce(cfg, params, mtp_h[:, :-1], tokens[:, 2:],
                             ce_chunk)
        total = total + mtp_weight * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    return total, metrics


@jax.named_scope("ce_head")
def _chunked_ce(cfg: ArchConfig, params: Params, h: jax.Array,
                targets: jax.Array, chunk: int) -> jax.Array:
    """Mean next-token CE with a rematerialized, time-chunked head.

    Chunks along the TIME axis with batch kept leading, so every chunk
    stays batch-sharded under GSPMD.  (The first version reshaped the
    sharded token dim into the scan axis — the partitioner then had to
    replicate each chunk, generating two [T, vocab]-sized all-reduces of
    637 GB each at train_4k/multi-pod.  See EXPERIMENTS.md §Perf.)
    """
    b, s, d = h.shape
    c = max(min(chunk // max(b, 1), s), 1)
    pad = (-s) % c
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    n = (s + pad) // c
    hr = h.reshape(b, n, c, d).transpose(1, 0, 2, 3)      # [n, b, c, d]
    tr = targets.reshape(b, n, c).transpose(1, 0, 2)      # [n, b, c]

    def chunk_loss(hc, tc):
        logits = _head(cfg, params, hc)                   # [b, c, V] fp32
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(tc, 0)[..., None],
                                 axis=-1)[..., 0]
        return jnp.where(tc >= 0, logz - ll, 0.0).sum()

    def body(acc, xt):
        hc, tc = xt
        return acc + jax.checkpoint(chunk_loss)(hc, tc), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32), (hr, tr),
        unroll=True if costing_mode.unroll_scans() else 1)
    return total / (b * s)


def prefill(cfg: ArchConfig, params: Params, tokens: jax.Array,
            cache: Cache, frontend: Optional[jax.Array] = None, *,
            use_kernel: bool = False,
            capacity_factor: Optional[float] = None) -> Tuple[jax.Array, Cache]:
    """Fill the decode cache from a prompt; returns (last-token logits, cache)."""
    b, s = tokens.shape
    x = _embed(cfg, params, tokens)
    offset = 0
    if cfg.enc_dec is not None:
        assert frontend is not None
        enc_out = run_encoder(cfg, params, frontend, "none", use_kernel)
    elif cfg.frontend != "none" and frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
        offset = frontend.shape[1]
    stot = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(stot), (b, stot))
    new_cache: Cache = {"pos": jnp.asarray(stot, jnp.int32)}

    if cfg.enc_dec is not None:
        # compute & store cross K/V once
        def body(h, pc):
            p, c = pc
            ck, cv = cross_kv(cfg, p["cross"], enc_out)
            h2, c2, _ = block_apply(cfg, p, h, positions=positions, window=None,
                                    kv_cache=c, cross_state=(ck, cv),
                                    use_kernel=use_kernel)
            return h2, (c2, ck, cv)
        x, (self_c, cks, cvs) = jax.lax.scan(
            body, x, (params["blocks"], cache["self"]))
        new_cache["self"] = self_c
        new_cache["cross_k"], new_cache["cross_v"] = cks, cvs
    else:
        x, c2, _ = _stack_runner(cfg, params, x, positions, cache, "none",
                                 use_kernel, capacity_factor)
        new_cache.update(c2)
    logits = _head(cfg, params, x[:, -1:])
    return logits[:, 0], new_cache


def decode_step(cfg: ArchConfig, params: Params, token: jax.Array,
                cache: Cache, *, use_kernel: bool = False,
                capacity_factor: Optional[float] = None
                ) -> Tuple[jax.Array, Cache]:
    """One decoding step.  token: [B] int32.  Returns (logits [B,V], cache)."""
    b = token.shape[0]
    pos = cache["pos"]
    x = _embed(cfg, params, token[:, None])
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    new_cache: Cache = {"pos": pos + 1}

    if cfg.enc_dec is not None:
        def body(pc, h, c):
            p, ck, cv = pc
            return block_apply(cfg, p, h, positions=positions, window=None,
                               kv_cache=c, cross_state=(ck, cv),
                               use_kernel=use_kernel)
        x, self_c, _ = scan_stack(
            (params["blocks"], cache["cross_k"], cache["cross_v"]), x, body,
            cache["self"])
        new_cache["self"] = self_c
        new_cache["cross_k"], new_cache["cross_v"] = cache["cross_k"], cache["cross_v"]
    else:
        x, c2, _ = _stack_runner(cfg, params, x, positions, cache, "none",
                                 use_kernel, capacity_factor)
        new_cache.update(c2)
    logits = _head(cfg, params, x)
    return logits[:, 0], new_cache

"""Component-level costing of the generated plan (fixes scan undercount).

XLA's ``cost_analysis()`` visits a while/scan body ONCE, so a model scanned
over layers reports ~1/n_layers of its true FLOPs and collective bytes.
The paper's own methodology is the fix: cost each *instruction* of the
runtime program and aggregate over the program structure (Eq 1).  Here the
"instructions" are compiled XLA executables:

    step_cost = sum_i  count_i * CompiledCost(component_i)

Components per architecture family:
  * dense/moe/mla/vlm : decoder block  x n_layers (dense + moe stacks split)
  * ssm               : mamba block    x n_layers
  * hybrid            : mamba block x n_layers + shared attn x n_apply
  * enc-dec           : encoder block x n_enc + decoder block x n_dec
  * window-pattern    : one component per distinct window value
  plus a tail (embed + chunked-CE head + optimizer update + cross-replica
  grad reduce for train; lm head for serve).  Decode components carry their
  per-layer KV/state cache so cache-read traffic is costed.

Each component is lowered+compiled under the SAME mesh/shardings as the
full step, so GSPMD generates the per-layer collectives (TP psums, EP
all-to-alls, DP grad reduces) and they are counted exactly count_i times.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core import hlo_cost
from repro.core.cluster import ClusterConfig
from repro.core.planner import ShardingPlan
from repro.launch import shardings as S
from repro.models import transformer as T
from repro.models.model import build_model
from repro.optim import adamw


@dataclasses.dataclass
class Component:
    name: str
    count: int
    cost: hlo_cost.CompiledCost


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype,
                                sharding=sharding)


def _sz(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1)
    return n


def _guarded(mesh, dim, axes):
    axes = tuple(a for a in axes if a in mesh.shape)
    if not axes or dim % _sz(mesh, axes) != 0 or _sz(mesh, axes) <= 1:
        return None
    return axes if len(axes) > 1 else axes[0]


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def _param_specs(mesh, plan, shapes_tree, path_prefix: str, drop_stack: bool):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes_tree)
    out = []
    for path, leaf in flat:
        key = path_prefix + "/" + "/".join(S._pstr(p) for p in path)
        full = S.param_sharding(mesh, plan, key, tuple(leaf.shape))
        spec = list(full.spec) + [None] * (len(leaf.shape) - len(full.spec))
        if drop_stack:
            spec, shape = spec[1:], leaf.shape[1:]
        else:
            shape = leaf.shape
        out.append(_sds(shape, leaf.dtype, _ns(mesh, *spec)))
    return jax.tree_util.tree_unflatten(treedef, out)


def _act_spec(mesh, plan, batch, seq, d, dtype):
    b = _guarded(mesh, batch, plan.batch_axes)
    s = _guarded(mesh, seq, plan.seq_axes)
    return _sds((batch, seq, d), dtype, _ns(mesh, b, s, None))


def _cache_slice_specs(mesh, plan, shapes: Dict[str, Any]):
    """Shardings for one layer's cache slice (no leading stack dim)."""
    out = {}
    for key, sds in shapes.items():
        shp = sds.shape
        nd = len(shp)
        if key == "kpos":
            out[key] = _sds(shp, sds.dtype, _ns(mesh))
            continue
        if key == "kv":  # [cap, H, B, 2*hd]
            b = _guarded(mesh, shp[2], plan.batch_axes)
            s = _guarded(mesh, shp[0], plan.batch_axes) if b is None else None
            out[key] = _sds(shp, sds.dtype, _ns(
                mesh, s, _guarded(mesh, shp[1], plan.tp_axes), b, None))
            continue
        b = _guarded(mesh, shp[0], plan.batch_axes)
        if nd == 4:      # [B, H, P, N] ssm state
            h = _guarded(mesh, shp[1], plan.tp_axes)
            out[key] = _sds(shp, sds.dtype, _ns(mesh, b, h, None, None))
        elif nd == 3:    # [B, S, r] mla latent / [B, W-1, C] conv
            s = None
            if b is None and key in ("ckv", "krope"):
                s = _guarded(mesh, shp[1], plan.batch_axes)
            out[key] = _sds(shp, sds.dtype, _ns(mesh, b, s, None))
        else:
            out[key] = _sds(shp, sds.dtype, _ns(mesh, b, *([None] * (nd - 1))))
    return out


def _train_wrap(fn, remat: str):
    inner = fn
    if remat == "full":
        inner = jax.checkpoint(fn)
    elif remat == "selective":
        inner = jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    def wrapped(p, x):
        y, vjp = jax.vjp(inner, p, x)
        dp, dx = vjp(jnp.ones_like(y))
        return y.sum(), dp, dx
    return wrapped


def _compile(name, fn, specs, mesh) -> hlo_cost.CompiledCost:
    from repro.models import costing_mode
    with costing_mode.costing_unroll():
        with mesh:
            compiled = jax.jit(fn).lower(*specs).compile()
    return hlo_cost.from_compiled(name, compiled, mesh.devices.size)


def component_costs(arch: ArchConfig, shape: ShapeConfig, plan: ShardingPlan,
                    mesh) -> List[Component]:
    cfg = arch
    model = build_model(cfg)
    mode = shape.mode
    dtype = jnp.dtype(cfg.dtype)
    micro = max(plan.microbatches, 1) if mode == "train" else 1
    batch = max(shape.global_batch // micro, 1)
    q_len = 1 if mode == "decode" else shape.seq_len
    kv_len = shape.seq_len
    d = cfg.d_model

    pshapes = model.init_shapes()
    # Layer components are compiled as ONE data-parallel replica: the batch
    # is pre-sliced by the dp degree and dp axes dropped, so GSPMD does not
    # emit per-layer param-grad psums (the real program accumulates grads
    # locally and reduces ONCE — counted by the grad_reduce component).
    # TP/EP axes (and their collectives) are kept.
    dp_deg = max(_sz(mesh, tuple(a for a in plan.batch_axes
                                 if a in mesh.shape)), 1)
    sp_deg = max(_sz(mesh, tuple(a for a in plan.seq_axes
                                 if a in mesh.shape)), 1)
    local_plan = dataclasses.replace(plan, batch_axes=(), seq_axes=())
    batch = max(batch // dp_deg, 1)
    if mode != "decode":
        q_len = max(q_len // sp_deg, 1)
    x_spec = _act_spec(mesh, local_plan, batch, q_len, d, dtype)
    cache_shapes_full = (model.cache_shapes(batch, kv_len)
                         if mode == "decode" else None)
    plan_for_caches = local_plan
    comps: List[Component] = []

    def layer_cache_slice(group_key: str):
        grp = cache_shapes_full[group_key]
        sliced = {k: _sds(v.shape[1:], v.dtype) for k, v in grp.items()}
        return _cache_slice_specs(mesh, plan_for_caches, sliced)

    def block_fwd(window, moe, cache_group):
        def fwd_nocache(p, x):
            pos = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                   (x.shape[0], x.shape[1]))
            out, _, _ = T.block_apply(cfg, p, x, positions=pos,
                                      window=window, moe=moe)
            return out

        def fwd_cache(p, x, c):
            pos = jnp.full((x.shape[0], 1), kv_len - 1, jnp.int32)
            out, c2, _ = T.block_apply(cfg, p, x, positions=pos,
                                       window=window, moe=moe, kv_cache=c)
            return out, c2
        return fwd_cache if cache_group else fwd_nocache

    def add_block(name, count, stacked, *, kind="attn", window=None,
                  moe=False, cache_group=None, stacked_is_layer=False):
        count = count * micro          # layers run once per microbatch
        lay_specs = (_param_specs(mesh, plan, stacked, "blocks", False)
                     if stacked_is_layer else
                     _param_specs(mesh, plan, stacked, "blocks", True))
        if kind == "mamba":
            if mode == "decode":
                cache_specs = layer_cache_slice("mamba")

                def fn(p, x, c):
                    return T.mamba_layer_apply(cfg, p, x, c)[:2]
                specs = (lay_specs, x_spec, cache_specs)
            else:
                def fwd(p, x):
                    return T.mamba_layer_apply(cfg, p, x, None)[0]
                fn = _train_wrap(fwd, plan.remat) if mode == "train" else fwd
                specs = (lay_specs, x_spec)
        else:
            if mode == "decode":
                cache_specs = layer_cache_slice(cache_group)
                fn = block_fwd(window, moe, True)
                specs = (lay_specs, x_spec, cache_specs)
            else:
                fwd = block_fwd(window, moe, False)
                fn = _train_wrap(fwd, plan.remat) if mode == "train" else fwd
                specs = (lay_specs, x_spec)
        comps.append(Component(name, count, _compile(name, fn, specs, mesh)))

    fam = cfg.family
    if fam == "ssm":
        add_block("mamba_layer", cfg.n_layers, pshapes["blocks"], kind="mamba")
    elif fam == "hybrid":
        add_block("mamba_layer", cfg.n_layers, pshapes["blocks"], kind="mamba")
        n_app = cfg.n_layers // cfg.hybrid.attn_every
        shared = pshapes["shared_attn"][0]
        lay_specs = _param_specs(mesh, plan, shared, "shared", False)
        if mode == "decode":
            grp = cache_shapes_full["attn"]
            sliced = {k: _sds(v.shape[1:], v.dtype) for k, v in grp.items()}
            cache_specs = _cache_slice_specs(mesh, plan, sliced)
            fn = block_fwd(None, False, True)
            comps.append(Component("shared_attn", n_app * micro,
                                   _compile("shared_attn", fn,
                                            (lay_specs, x_spec, cache_specs),
                                            mesh)))
        else:
            fwd = block_fwd(None, False, False)
            fn = _train_wrap(fwd, plan.remat) if mode == "train" else fwd
            comps.append(Component("shared_attn", n_app * micro,
                                   _compile("shared_attn", fn,
                                            (lay_specs, x_spec), mesh)))
    elif cfg.enc_dec is not None:
        enc_len = cfg.enc_dec.encoder_seq
        enc_x = _act_spec(mesh, local_plan, batch, enc_len, d, dtype)
        enc_specs = _param_specs(mesh, plan, pshapes["enc_blocks"],
                                 "enc_blocks", True)

        def enc_fwd(p, x):
            pos = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                   (x.shape[0], x.shape[1]))
            return T.block_apply(cfg, p, x, positions=pos, window=None,
                                 causal=False)[0]
        # encoder runs only at prefill/train (decode reuses cached cross-KV)
        if mode != "decode":
            fn = _train_wrap(enc_fwd, plan.remat) if mode == "train" else enc_fwd
            comps.append(Component("encoder_layer",
                                   cfg.enc_dec.n_encoder_layers * micro,
                                   _compile("encoder_layer", fn,
                                            (enc_specs, enc_x), mesh)))

        dec_specs = _param_specs(mesh, plan, pshapes["blocks"], "blocks", True)
        nkv, hd = cfg.n_kv_heads, cfg.head_dim_
        ck_spec = _sds((batch, nkv, enc_len, hd), dtype,
                       _ns(mesh, None,
                           _guarded(mesh, nkv, plan.tp_axes), None, None))
        if mode == "decode":
            sliced = {k: _sds(v.shape[1:], v.dtype)
                      for k, v in cache_shapes_full["self"].items()}
            cache_specs = _cache_slice_specs(mesh, plan_for_caches, sliced)

            def fn(p, x, c, ck, cv):
                pos = jnp.full((x.shape[0], 1), kv_len - 1, jnp.int32)
                out, c2, _ = T.block_apply(cfg, p, x, positions=pos,
                                           window=None, kv_cache=c,
                                           cross_state=(ck, cv))
                return out, c2
            comps.append(Component("decoder_layer", cfg.n_layers * micro,
                                   _compile("decoder_layer", fn,
                                            (dec_specs, x_spec, cache_specs,
                                             ck_spec, ck_spec), mesh)))
        else:
            def dec_fwd3(p, x, e):
                pos = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                       (x.shape[0], x.shape[1]))
                ck, cv = T.cross_kv(cfg, p["cross"], e)
                return T.block_apply(cfg, p, x, positions=pos, window=None,
                                     cross_state=(ck, cv))[0]
            if mode == "train":
                def fn(p, x, e):
                    y, vjp = jax.vjp(dec_fwd3, p, x, e)
                    dp, dx, de = vjp(jnp.ones_like(y))
                    return y.sum(), dp, dx
            else:
                fn = dec_fwd3
            comps.append(Component("decoder_layer", cfg.n_layers * micro,
                                   _compile("decoder_layer", fn,
                                            (dec_specs, x_spec, enc_x), mesh)))
    elif cfg.moe is not None:
        nd = cfg.moe.first_dense_layers
        if nd and "dense_blocks" in pshapes:
            add_block("dense_layer", nd, pshapes["dense_blocks"],
                      cache_group="dense")
        add_block("moe_layer", cfg.n_layers - nd, pshapes["blocks"],
                  moe=True, cache_group="moe")
    elif cfg.window_pattern is not None:
        period = len(cfg.window_pattern)
        n_cycles = cfg.n_layers // period
        counts = Counter(cfg.window_pattern)
        for w, cnt in counts.items():
            stacked = pshapes["cycles"][cfg.window_pattern.index(w)]
            eff_w = None if w is None else min(w, kv_len)
            add_block(f"layer_w{w or 'global'}", n_cycles * cnt, stacked,
                      window=eff_w,
                      cache_group=f"p{cfg.window_pattern.index(w)}")
    else:
        add_block("decoder_layer", cfg.n_layers, pshapes["blocks"],
                  cache_group="self")

    # ------------------------------------------------------------- tail
    embed_specs = {
        "embed": _sds(pshapes["embed"].shape, dtype,
                      S.param_sharding(mesh, plan, "embed",
                                       tuple(pshapes["embed"].shape))),
        "final_norm": _sds((d,), jnp.float32),
    }
    if "lm_head" in pshapes:
        embed_specs["lm_head"] = _sds(
            pshapes["lm_head"].shape, dtype,
            S.param_sharding(mesh, plan, "lm_head",
                             tuple(pshapes["lm_head"].shape)))
    if mode == "train":
        tok_spec = _sds((batch, q_len), jnp.int32, _ns(mesh, None, None))

        # CE head costed UNCHUNKED over the microbatch: same FLOPs and
        # logits write+read traffic as the real chunked scan, but head-
        # weight grads reduce once (as in the real step, where the scan
        # accumulates locally) instead of once per chunk.
        ce_tokens = batch * max(q_len - 1, 1)
        hce_spec = _sds((ce_tokens, d), dtype, _ns(mesh, None, None))
        tce_spec = _sds((ce_tokens,), jnp.int32, _ns(mesh, None))

        def ce_fn(ep, hc, tc):
            def inner(ep, hc):
                logits = T._head(cfg, ep, hc[None])[0]
                logz = jax.nn.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
                return (logz - ll).sum()
            ce, vjp = jax.vjp(inner, ep, hc)
            dp, dh = vjp(jnp.ones_like(ce))
            return ce, dp, dh
        comps.append(Component("ce_head", micro,
                               _compile("ce_head", ce_fn,
                                        (embed_specs, hce_spec, tce_spec),
                                        mesh)))

        def embed_fn(ep, tokens):
            def inner(e):
                return jnp.take(e, tokens, axis=0)
            y, vjp = jax.vjp(inner, ep["embed"])
            (de,) = vjp(jnp.ones_like(y))
            return y.sum(), de
        comps.append(Component("embed", micro,
                               _compile("embed", embed_fn,
                                        (embed_specs, tok_spec), mesh)))

        psh = S.params_shardings(mesh, plan, pshapes)
        pspecs = jax.tree.map(lambda sds, sh: _sds(sds.shape, sds.dtype, sh),
                              pshapes, psh)
        opt_shapes = jax.eval_shape(partial(adamw.init, adamw.AdamWConfig()),
                                    pshapes)
        osh = S.opt_state_shardings(mesh, plan, psh, opt_shapes)
        ospecs = jax.tree.map(lambda sds, sh: _sds(sds.shape, sds.dtype, sh),
                              opt_shapes, osh)
        ocfg = adamw.AdamWConfig()

        def opt_fn(params, opt_state, grads):
            p2, o2, _ = adamw.apply(ocfg, opt_state, grads, params)
            return p2, o2
        comps.append(Component("optimizer", 1,
                               _compile("optimizer", opt_fn,
                                        (pspecs, ospecs, pspecs), mesh)))

        dp_axes = tuple(a for a in plan.batch_axes if a in mesh.shape)
        if _sz(mesh, dp_axes) > 1 and not plan.fsdp_axes:
            gd = jnp.dtype(plan.grad_reduce_dtype)

            def psum_fn(g):
                return jax.tree.map(lambda x: jax.lax.psum(x, dp_axes), g)
            in_specs = jax.tree.map(lambda s: s.spec, psh)
            fn = jax.shard_map(psum_fn, mesh=mesh, in_specs=(in_specs,),
                               out_specs=in_specs)
            gspecs = jax.tree.map(
                lambda sds, sh: _sds(sds.shape, gd, sh), pshapes, psh)
            comps.append(Component("grad_reduce", 1,
                                   _compile("grad_reduce", fn, (gspecs,),
                                            mesh)))
    else:
        def head_fn(ep, h):
            return T._head(cfg, ep, h)
        comps.append(Component("lm_head", 1,
                               _compile("lm_head", head_fn,
                                        (embed_specs, x_spec), mesh)))
    return comps


def aggregate(comps: List[Component], cc: ClusterConfig) -> Dict[str, Any]:
    """Eq (1): weighted sum of component costs -> step roofline terms."""
    flops = bytes_ = coll_bytes = 0.0
    coll_time = 0.0
    per = []
    for c in comps:
        r = c.cost.roofline(cc)
        flops += c.count * c.cost.flops_per_device
        bytes_ += c.count * c.cost.bytes_per_device
        coll_bytes += c.count * c.cost.collective_bytes
        coll_time += c.count * r["collective_s"]
        per.append({"name": c.name, "count": c.count,
                    "flops_per_device": c.cost.flops_per_device,
                    "bytes_per_device": c.cost.bytes_per_device,
                    "collective_bytes": c.cost.collective_bytes,
                    "collectives": c.cost.collective_bytes_by_kind()})
    compute_s = flops / cc.chip.peak("bfloat16")
    memory_s = bytes_ / cc.chip.hbm_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_time}
    dominant = max(terms, key=terms.get)
    return {
        **terms,
        "dominant": dominant,
        "roofline_bound_s": max(terms.values()),
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "collective_bytes_per_device": coll_bytes,
        "components": per,
    }

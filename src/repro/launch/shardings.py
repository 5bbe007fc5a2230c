"""ShardingPlan -> NamedSharding trees for params / optimizer / batch / cache.

This is where the planner's abstract decision vector becomes concrete
PartitionSpecs.  GSPMD then *generates* the collectives, and
``repro.core.hlo_cost`` costs what was generated — the paper's pipeline.

Rules are path-based with divisibility guards: an axis is only assigned to
a tensor dimension it divides; otherwise that dimension stays replicated
(never fail a compile over a sharding mismatch — fall back and let the
cost model show the replication cost).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.planner import ShardingPlan


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


def _guard(mesh: Mesh, dim: int, axes: Tuple[str, ...]):
    """axes if they divide dim, else None (replicated)."""
    if not axes:
        return None
    n = _axis_size(mesh, axes)
    if n <= 1 or dim % n != 0:
        return None
    return axes if len(axes) > 1 else axes[0]


def _ns(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def param_sharding(mesh: Mesh, plan: ShardingPlan, path: str,
                   shape: Tuple[int, ...]) -> NamedSharding:
    tp, fsdp, ep = plan.tp_axes, plan.fsdp_axes, plan.ep_axes
    nd = len(shape)
    stacked = ("blocks" in path or "cycles" in path or "enc_blocks" in path
               or "dense_blocks" in path)
    off = 1 if (stacked and nd >= 2) else 0   # leading layer-stack axis

    def spec_with(dims):  # dims: {dim_index: axes tuple}; first-come wins
        out = [None] * nd
        used: set = set()
        for di, axes in dims.items():
            axes = tuple(a for a in axes if a not in used)
            g = _guard(mesh, shape[di], axes)
            if g is not None:
                out[di] = g
                used.update(axes)
        return _ns(mesh, *out)

    leaf = path.split("/")[-1]
    is_moe = "/moe/" in path or path.endswith("w_router")

    if leaf == "embed":
        return spec_with({0: tp, 1: fsdp})
    if leaf == "lm_head":
        return spec_with({nd - 1: tp, 0: fsdp})
    if leaf == "w_router":
        return spec_with({nd - 1: ()})
    if is_moe and leaf in ("w_up", "w_gate") and nd - off == 3:
        return spec_with({off: ep, nd - 1: tp, nd - 2: fsdp})   # ep wins ties
    if is_moe and leaf == "w_down" and nd - off == 3:
        return spec_with({off: ep, nd - 2: tp, nd - 1: fsdp})
    if leaf in ("w_q", "w_k", "w_v", "w_uq", "w_ukv", "w_gate", "w_up",
                "w_in", "w_dq", "w_dkv", "proj"):
        dims = {nd - 1: tp}
        if nd - off >= 2:
            dims[nd - 2] = fsdp
        return spec_with(dims)
    if leaf in ("w_o", "w_down", "w_out"):
        dims = {nd - 2: tp} if nd - off >= 2 else {}
        dims[nd - 1] = fsdp
        return spec_with(dims)
    if leaf in ("b_q", "b_k", "b_v", "conv_w", "conv_b"):
        return spec_with({nd - 1: tp})
    if leaf in ("A_log", "D", "dt_bias") and nd - off >= 1:
        return spec_with({nd - 1: tp})
    # norm scales, small vectors: replicated
    return _ns(mesh)


def params_shardings(mesh: Mesh, plan: ShardingPlan, params_shapes: Any) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    out = []
    for path, leaf in flat:
        key = "/".join(_pstr(p) for p in path)
        out.append(param_sharding(mesh, plan, key, tuple(leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)


def _pstr(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"[{p.idx}]"
    return str(p)


def batch_shardings(mesh: Mesh, plan: ShardingPlan, batch_shapes: Any) -> Any:
    b_axes = tuple(a for a in plan.batch_axes if a in mesh.shape)
    s_axes = tuple(a for a in plan.seq_axes if a in mesh.shape)

    def one(path, leaf):
        nd = len(leaf.shape)
        spec = [None] * nd
        spec[0] = _guard(mesh, leaf.shape[0], b_axes)
        if nd >= 2 and s_axes:
            spec[1] = _guard(mesh, leaf.shape[1], s_axes)
        return _ns(mesh, *spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


def cache_shardings(mesh: Mesh, plan: ShardingPlan, cache_shapes: Any) -> Any:
    """Decode caches: batch over data, heads over tp.  Self-attention caches
    are slot-major [L, S, H, B, 2D] (``kv``)."""
    b_axes = tuple(a for a in plan.batch_axes if a in mesh.shape)
    tp = tuple(a for a in plan.tp_axes if a in mesh.shape)

    def one(path, leaf):
        key = "/".join(_pstr(p) for p in path)
        nd = len(leaf.shape)
        shape = leaf.shape
        if key.endswith("pos") or "kpos" in key:
            return _ns(mesh)
        if _pstr(path[-1]) == "kv":     # [L, S, H, B, 2D]
            bg = _guard(mesh, shape[3], b_axes)
            # batch not shardable (e.g. long_500k B=1): shard KV length
            sg = _guard(mesh, shape[1], b_axes) if bg is None else None
            return _ns(mesh, None, sg, _guard(mesh, shape[2], tp), bg, None)
        if nd == 5:        # [L, B, H, S, D] cross K/V / [L, B, H, P, N] ssm state
            bg = _guard(mesh, shape[1], b_axes)
            sg = None
            if bg is None and "state" not in key:
                # batch not shardable (e.g. long_500k B=1): shard KV length
                sg = _guard(mesh, shape[3], b_axes)
            return _ns(mesh, None, bg, _guard(mesh, shape[2], tp), sg, None)
        if nd == 4:        # [L, B, S, r] mla latent / [L, B, W, C] conv
            bg = _guard(mesh, shape[1], b_axes)
            sg = None
            if bg is None and "conv" not in key:
                sg = _guard(mesh, shape[2], b_axes)
            last = _guard(mesh, shape[3], tp) if "conv" in key else None
            return _ns(mesh, None, bg, sg, last)
        if nd >= 2:
            return _ns(mesh, None, _guard(mesh, shape[1], b_axes),
                       *([None] * (nd - 2)))
        return _ns(mesh)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


def opt_state_shardings(mesh: Mesh, plan: ShardingPlan, params_sh: Any,
                        opt_shapes: Any) -> Any:
    """AdamW m/v shard like params, plus ZeRO-1: when ``plan.zero1`` the
    moments additionally shard over the data axes on the first dimension
    they divide (GSPMD then reduce-scatters grads into the update and
    all-gathers the delta — optimizer state never replicates over DP)."""
    from repro.optim.adamw import AdamWState
    if not getattr(plan, "zero1", False):
        return AdamWState(step=_ns(mesh), m=params_sh, v=params_sh)
    b_axes = tuple(a for a in plan.batch_axes if a in mesh.shape)

    def zero1_spec(psh: NamedSharding, shapes) -> NamedSharding:
        spec = list(psh.spec) + [None] * (len(shapes.shape) - len(psh.spec))
        used = set()
        for entry in spec:
            if entry is None:
                continue
            used.update(entry if isinstance(entry, tuple) else (entry,))
        axes = tuple(a for a in b_axes if a not in used)
        if not axes:
            return psh
        n = _axis_size(mesh, axes)
        for i, entry in enumerate(spec):
            if entry is None and shapes.shape[i] % n == 0 and n > 1:
                spec[i] = axes if len(axes) > 1 else axes[0]
                return _ns(mesh, *spec)
        return psh

    m_sh = jax.tree.map(zero1_spec, params_sh, opt_shapes.m)
    return AdamWState(step=_ns(mesh), m=m_sh, v=m_sh)

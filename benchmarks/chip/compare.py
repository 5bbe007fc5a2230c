"""The comparisons that decide ``correct``, and the reference runs they need.

Training: the program's first three steps against the plain reference's,
by three numbers:

* ``loss_gap``: the largest |loss - reference loss| over the three steps;
* ``grad_gap``: over every leaf (one per layer for stacked leaves), the gap
  between the norm of the program's first clipped gradient, read back from
  AdamW's first moment after one step (m / (1 - b1)), and the reference's,
  over the larger of the reference's leaf norm and its median leaf norm;
* ``change_gap``: the same for the norm of each leaf's change over three
  steps, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (moved by round-off alone).

Serving: ``logit_gap``, the widest gap by which a served token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

EXCLUDE_BELOW = 1e-3


def ref_module(name: str):
    import importlib
    return importlib.import_module(f"reference.{name}")


def precision():
    import jax
    return jax.default_matmul_precision("highest")


def stacked_flags(paths: Sequence[str]) -> List[bool]:
    return [p.startswith("blocks/") for p in paths]


def norm_gap(prog: np.ndarray, ref: np.ndarray,
             keep: Optional[np.ndarray] = None) -> float:
    """Worst leaf of |prog - ref| / max(ref, median ref)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    denom = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / denom
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def ref_shardings(tree: Any, devices: Sequence) -> Any:
    """Spread a tree over ``devices``: each leaf's largest dimension that
    they divide, otherwise replicated."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)

    def one(x):
        dims = sorted(range(len(x.shape)), key=lambda i: -x.shape[i])
        for i in dims:
            if n > 1 and x.shape[i] % n == 0:
                spec = [None] * len(x.shape)
                spec[i] = "x"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())
    return jax.tree.map(one, tree)


def weights_f32(make_plain, seed: int, shardings: Any) -> Any:
    """The seed's weights (``weights.make_weights_fn``) in float32, placed
    by ``shardings``."""
    import jax
    import jax.numpy as jnp
    from weights import seed_words
    fn = jax.jit(lambda a, b: jax.tree.map(lambda x: x.astype(jnp.float32),
                                           make_plain(a, b)),
                 out_shardings=shardings)
    return fn(*seed_words(seed))


def reference_training(ref, cfg: Dict, make_plain, seed: int, shapes: Any,
                       batches: Sequence[np.ndarray], hp: Dict,
                       devices: Sequence, mode: str = "float32",
                       loss_fn=None) -> Dict[str, Any]:
    """Three AdamW steps of the reference from the seed's weights: per-step
    losses, the first clipped gradient's leaf norms and the leaf norms of
    the change over the steps.  ``shapes`` is the program's parameter tree
    (its dtypes are how the configuration stores each leaf);
    ``loss_fn(params, tokens, cfg, mm)`` stands in for ``ref.loss`` where a
    variant is read."""
    import jax
    import jax.numpy as jnp
    from reference.common import Matmul, adamw_coeffs, adamw_step, leaf_norms
    from weights import leaf_paths

    mm = Matmul(mode)
    loss_fn = loss_fn or ref.loss
    flags = stacked_flags(leaf_paths(shapes))
    dtypes = jax.tree.map(lambda s: s.dtype, shapes)
    sh = ref_shardings(shapes, devices)
    tok_sh = ref_shardings(jax.ShapeDtypeStruct(batches[0].shape, jnp.int32), devices)
    with precision():
        grad_fn = jax.jit(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg, mm)),
                          out_shardings=(None, sh))
        step_fn = jax.jit(lambda p, m, v, g, c: adamw_step(p, m, v, g, c, hp, dtypes),
                          out_shardings=(sh, sh, sh, None), donate_argnums=(0, 1, 2))
        norms = jax.jit(lambda t: leaf_norms(t, flags))
        p = weights_f32(make_plain, seed, sh)
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t), out_shardings=sh)
        m, v = zeros(p), zeros(p)
        losses, grad_norms = [], None
        for k, toks in enumerate(batches):
            loss, g = grad_fn(p, jax.device_put(jnp.asarray(toks), tok_sh))
            losses.append(float(loss))
            p, m, v, clip = step_fn(p, m, v, g, adamw_coeffs(k + 1, hp))
            if k == 0:
                grad_norms = np.concatenate([np.asarray(x) for x in norms(g)]) * float(clip)
            del g
        del m, v
        change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b), flags))
        p0 = weights_f32(make_plain, seed, sh)
        change_norms = np.concatenate([np.asarray(x) for x in change(p, p0)])
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


def worst_leaves(prog: np.ndarray, ref: np.ndarray, names: Sequence[str],
                 top: int = 3) -> List[str]:
    """The leaves with the widest gaps, as ``name: program/reference``."""
    denom = np.maximum(ref, np.median(ref))
    gap = np.abs(np.asarray(prog) - ref) / denom
    order = np.argsort(-gap)[:top]
    return [f"{names[i]}: {prog[i]:.6g}/{ref[i]:.6g} ({gap[i]:.4f})" for i in order]


def leaf_names(shapes: Any) -> List[str]:
    """One name per compared norm: ``path[layer]`` for stacked leaves."""
    import jax
    from weights import leaf_paths
    out = []
    for path, s in zip(leaf_paths(shapes), jax.tree.leaves(shapes)):
        if path.startswith("blocks/"):
            out.extend(f"{path}[{i}]" for i in range(s.shape[0]))
        else:
            out.append(path)
    return out


def training_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    keep = ref["grad_norms"] >= EXCLUDE_BELOW * np.median(ref["grad_norms"])
    return {
        "loss_gap": float(max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))),
        "grad_gap": norm_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": norm_gap(prog["change_norms"], ref["change_norms"], keep),
    }


def serve_logit_gaps(ref, cfg: Dict, params: Any, seqs: np.ndarray,
                     reads: List[List[tuple]], mode: str = "float32",
                     block: int = 256) -> Dict[str, Any]:
    """For each sequence of ``seqs`` [k, T] and each (position, token) of
    ``reads[i]``: the reference's best logit at that position less its logit
    of the token.  ``mode`` sets the precision of the forward pass whose
    tokens are judged; with ``mode="fp8"`` the judged token is that pass's
    own first choice (the control), judged by the float32 reference."""
    import jax
    import jax.numpy as jnp
    from reference.common import Matmul

    with precision():
        hidden = jax.jit(lambda p, t: ref.hidden(p, t, cfg, Matmul("float32")))
        h = hidden(params, jnp.asarray(seqs))
        if mode != "float32":
            h_low = jax.jit(lambda p, t: ref.hidden(p, t, cfg, Matmul(mode)))(
                params, jnp.asarray(seqs))

        def head_stats(params, hb, wanted):
            lg = ref.head(params, hb, Matmul("float32"))
            hit = jnp.take_along_axis(lg, wanted[..., None], -1)[..., 0]
            return lg.max(-1), hit

        def low_argmax(params, hb):
            return ref.head(params, hb, Matmul(mode)).argmax(-1).astype(jnp.int32)

        head_stats = jax.jit(head_stats)
        low_argmax = jax.jit(low_argmax)
        k, t = seqs.shape
        wanted = np.zeros((k, t), np.int32)
        for i, rd in enumerate(reads):
            for pos, tok in rd:
                wanted[i, pos] = tok
        best = np.zeros((k, t), np.float32)
        got = np.zeros((k, t), np.float32)
        for s in range(0, t, block):
            if mode != "float32":
                wanted[:, s:s + block] = np.asarray(low_argmax(params, h_low[:, s:s + block]))
            b, g = head_stats(params, h[:, s:s + block], jnp.asarray(wanted[:, s:s + block]))
            best[:, s:s + block] = np.asarray(b)
            got[:, s:s + block] = np.asarray(g)
    gaps = [float(best[i, pos] - got[i, pos]) for i, rd in enumerate(reads)
            for pos, _ in rd]
    return {"logit_gap": max(gaps), "tokens": len(gaps)}

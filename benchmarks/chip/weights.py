"""Weights from the seed, made on the device in one jitted call.

The values come from a counter-based integer hash of (seed, leaf, element),
so any program that calls :func:`make_weights_fn` with the same tree and
seed gets the same bits, and the reference can make the weights again
after the window without keeping a copy.  The tree's structure, shapes and
dtypes are the program's own (``model.init_shapes()``); the value of each
leaf follows the configuration's ``init`` rules, matched by path.
"""
from __future__ import annotations

import fnmatch
import math
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

_GOLDEN = 0x9E3779B9


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two 32-bit words (low, high)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def leaf_paths(tree) -> List[str]:
    """The slash-joined key path of every leaf, in flattening order."""
    import jax
    paths = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for k in path:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        paths.append("/".join(parts))
    return paths


def rule_for(path: str, rules: Dict[str, Dict]) -> Dict:
    for pattern, spec in rules.items():
        if fnmatch.fnmatchcase(path, pattern):
            return spec
    raise KeyError(f"no init rule matches parameter {path!r}")


def _fmix(x):
    """murmur3's 32-bit finalizer."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform(shape, leaf: int, seed_lo, seed_hi):
    """Uniform [0, 1) float32 of ``shape``, a pure function of the seed words,
    the leaf index and the element index."""
    import jax
    import jax.numpy as jnp
    n = math.prod(shape)
    idx = jax.lax.iota(jnp.uint32, n)
    k = _fmix(seed_lo ^ np.uint32((leaf * _GOLDEN) & 0xFFFFFFFF))
    h = _fmix(idx * np.uint32(_GOLDEN) + k)
    h = _fmix(h ^ _fmix(seed_hi + np.uint32(leaf)))
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    return u.reshape(shape)


def leaf_value(spec: Dict, shape, leaf: int, seed_lo, seed_hi):
    """One leaf in float32 by its rule."""
    import jax.numpy as jnp
    kind = spec["kind"]
    if kind == "const":
        return jnp.full(shape, spec["value"], jnp.float32)
    u = _uniform(shape, leaf, seed_lo, seed_hi)
    if kind == "uniform":                          # zero mean, given std
        return (2.0 * u - 1.0) * np.float32(spec["std"] * math.sqrt(3.0))
    lo, hi = spec["low"], spec["high"]
    if kind == "range":
        return lo + (hi - lo) * u
    if kind == "log_range":                        # log of U[low, high]
        return jnp.log(lo + (hi - lo) * u)
    if kind == "inv_softplus_log_range":           # dt_bias: softplus^-1(dt)
        dt = jnp.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * u)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init kind {kind!r}")


def make_weights_fn(shapes: Any, rules: Dict[str, Dict]) -> Callable:
    """``fn(seed_lo, seed_hi) -> tree`` of ``shapes``' structure and dtypes.
    Jit it (with the program's shardings as ``out_shardings``); the seed
    words are arguments, so every seed runs one compiled program."""
    import jax
    flat, treedef = jax.tree_util.tree_flatten(shapes)
    specs = [rule_for(p, rules) for p in leaf_paths(shapes)]

    def fn(seed_lo, seed_hi):
        leaves = [leaf_value(spec, s.shape, i, seed_lo, seed_hi).astype(s.dtype)
                  for i, (spec, s) in enumerate(zip(specs, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return fn

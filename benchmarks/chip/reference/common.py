"""Pieces the plain references share: matmuls in a stated precision, RMS
normalisation, cross-entropy in blocks of positions, and AdamW.

Nothing here imports the program.  ``Matmul("float32")`` is the reference:
float32 operands at ``jax.lax.Precision.HIGHEST``.  ``Matmul("fp8")`` is the
control: each operand rounded to float8_e4m3fn after scaling its largest
magnitude to the format's largest (per tensor), then multiplied and summed
in float32 (forward and backward products alike), the step below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    """x rounded to float8_e4m3fn after scaling its largest magnitude to the
    format's largest (one scale per tensor), and scaled back."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ein_hp(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_ein(spec, a, b):
    return _ein_hp(spec, _fp8(a), _fp8(b))


def _fp8_ein_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _ein_hp(spec, qa, qb), (qa, qb)


def _fp8_ein_bwd(spec, res, g):
    """The backward products in fp8 as well: the rounded operands against
    the rounded cotangent."""
    _, vjp = jax.vjp(functools.partial(_ein_hp, spec), *res)
    return vjp(_fp8(g))


_fp8_ein.defvjp(_fp8_ein_fwd, _fp8_ein_bwd)


class Matmul:
    """``ein(spec, a, b)``: an einsum of two operands in one precision, its
    backward products in the same precision."""

    def __init__(self, mode: str = "float32"):
        if mode not in ("float32", "fp8"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode

    def ein(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if self.mode == "float32":
            return _ein_hp(spec, a, b)
        return _fp8_ein(spec, a, b)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """x / rms(x) * weight, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def cross_entropy(hidden: jax.Array, w_head: jax.Array, targets: jax.Array,
                  mm: Matmul, block: int = 512) -> jax.Array:
    """Mean next-token cross-entropy of hidden [B, T, d] against targets
    [B, T] through the head [d, V], ``block`` positions at a time (each
    block recomputed in the backward pass, so [B, T, V] never exists)."""
    b, t, d = hidden.shape
    block = min(block, t)
    pad = (-t) % block
    h = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    y = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    n = (t + pad) // block
    h = h.reshape(b, n, block, d).transpose(1, 0, 2, 3)
    y = y.reshape(b, n, block).transpose(1, 0, 2)

    @jax.checkpoint
    def one(hc, yc):
        logits = mm.ein("bsd,dv->bsv", hc, w_head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, jnp.maximum(yc, 0)[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(yc >= 0, lse - hit, 0.0))

    def body(acc, xs):
        return acc + one(*xs), None

    s, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, y))
    return s / (b * t)


def adamw_coeffs(step: int, hp: Dict[str, float]) -> jax.Array:
    """(learning rate, 1 - b1^t, 1 - b2^t) of step ``step`` (from 1): linear
    warm-up, then cosine decay to ``min_lr_ratio`` of the peak."""
    import math
    warm = min(step / max(hp["warmup_steps"], 1), 1.0)
    frac = min(max((step - hp["warmup_steps"])
                   / max(hp["total_steps"] - hp["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    lr = hp["lr"] * warm * (hp["min_lr_ratio"] + (1 - hp["min_lr_ratio"]) * cos)
    return jnp.asarray([lr, 1 - hp["b1"] ** step, 1 - hp["b2"] ** step],
                       jnp.float32)


def adamw_step(params: Any, m: Any, v: Any, grads: Any, coeffs: jax.Array,
               hp: Dict[str, float], dtypes: Any) -> Tuple[Any, Any, Any, jax.Array]:
    """One AdamW step (decoupled weight decay, global-norm clipping) with
    ``adamw_coeffs``.  The new parameters are rounded to each leaf's stored
    dtype, as the configuration stores them, and handed back in float32."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    lr, b1c, b2c = coeffs[0], coeffs[1], coeffs[2]
    b1, b2 = hp["b1"], hp["b2"]

    def upd(p, g, m_, v_, dt):
        g = g * clip
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        u = (m_ / b1c) / (jnp.sqrt(v_ / b2c) + hp["eps"])
        p = p - lr * (u + hp["weight_decay"] * p)
        return p.astype(dt).astype(jnp.float32), m_, v_

    out = jax.tree.map(upd, params, grads, m, v, dtypes)
    is_t = lambda x: isinstance(x, tuple)
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=is_t)
    new_m = jax.tree.map(lambda o: o[1], out, is_leaf=is_t)
    new_v = jax.tree.map(lambda o: o[2], out, is_leaf=is_t)
    return new_p, new_m, new_v, clip


def leaf_norms(tree: Any, stacked: List[bool]) -> List[jax.Array]:
    """Norm of every leaf, one per layer for stacked leaves (layer axis 0)."""
    out = []
    for x, st in zip(jax.tree.leaves(tree), stacked):
        x = x.astype(jnp.float32)
        if st:
            out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x))[None])
    return out

"""Pallas TPU kernel: tsmm — transpose-self matmul  G = X^T X.

The paper's flagship physical operator: "exploit the unary input
characteristic and the known result symmetry which allows to do only half
the computation" (§2).  SystemML's CPU tsmm skips the lower triangle
element-wise; the TPU-native adaptation skips **whole MXU output tiles**:

  * the (n/bn x n/bn) grid of output blocks is linearized to only the
    upper-triangular pairs (i <= j) — T = nb(nb+1)/2 grid steps instead of
    nb^2.  The (i, j) pair for each step is scalar-prefetched (the splash-
    attention trick), so BlockSpec index_maps stay O(1);
  * each step accumulates X_i^T X_j over the m-dimension grid axis into an
    fp32 VMEM scratch tile, writing the bf16/f32 result tile once at the
    last m-step (HBM write traffic = half the Gram matrix, once);
  * the strict lower triangle is never computed nor written — the ops.py
    wrapper mirrors it in one cheap transpose.

Grid layout: (T, K) with K = m/bm minormost & sequential ("arbitrary"), so
the output tile revisit pattern is legal; T is parallel.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _upper_pairs(nb: int) -> Tuple[np.ndarray, np.ndarray]:
    """Linearized upper-triangular block pairs (i <= j)."""
    ii, jj = [], []
    for i in range(nb):
        for j in range(i, nb):
            ii.append(i)
            jj.append(j)
    return np.asarray(ii, np.int32), np.asarray(jj, np.int32)


def _tsmm_kernel(i_ref, j_ref, xi_ref, xj_ref, out_ref, acc_ref, *,
                 k_steps: int, reg: float):
    s = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xi = xi_ref[...]                       # [bm, bn]
    xj = xj_ref[...]                       # [bm, bn]
    acc_ref[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())),  # contract over m: X_i^T @ X_j
        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        if reg != 0.0:                     # static: compiled away when 0
            bn = acc.shape[0]
            eye = (jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1))
            on_diag = i_ref[s] == j_ref[s]
            acc = acc + jnp.where(eye & on_diag, jnp.float32(reg), 0.0)
        out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "reg", "interpret"))
def tsmm_upper(x: jax.Array, *, bm: int = 512, bn: int = 256,
               reg: float = 0.0, interpret: bool) -> jax.Array:
    """Upper-triangular blocks of X^T X + reg*I (lower-left tiles zero).

    x: [m, n] with m % bm == 0 and n % bn == 0.  ``reg`` is the ridge
    epilogue of the paper's LinReg DS solve (G = X^T X + lambda*I): the
    diagonal shift is fused into the accumulator flush of the diagonal
    blocks, so G is still written exactly once.
    """
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (x.shape, bm, bn)
    nb, kk = n // bn, m // bm
    ii, jj = _upper_pairs(nb)
    t = len(ii)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, kk),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda s, k, ii, jj: (k, ii[s])),
            pl.BlockSpec((bm, bn), lambda s, k, ii, jj: (k, jj[s])),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda s, k, ii, jj: (ii[s], jj[s])),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_tsmm_kernel, k_steps=kk, reg=reg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(jnp.asarray(ii), jnp.asarray(jj), x, x)

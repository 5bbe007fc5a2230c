"""Pallas TPU kernel: Mamba2 SSD chunked scan.

Compute hot-spot of the mamba2/zamba2 cells.  One grid step processes one
(batch, head, chunk) tile of head-major operands; the chunk axis is
minormost & sequential so the inter-chunk SSM state lives in an fp32 VMEM
scratch tile [P, N] carried across chunk steps — the TPU analogue of the
register-resident state in the CUDA SSD kernel.

The chunk-local mixing is expressed as MXU matmuls (the log-decay cumsum
is a masked f32 reduction):
  * intra-chunk mixing  Y_diag = ((C B^T) * L) Xbar,
  * state emission       S_c    = (decay_to_end * Xbar)^T B,
  * state consumption    Y_off  = decay_in * (C S_prev^T).

Inputs are pre-scaled outside (xbar = x * dt, log_a = dt * A), and the
D-residual is applied in the wrapper — the kernel is the pure scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xbar_ref, la_row_ref, la_col_ref, b_ref, c_ref, y_ref,
                state_out_ref, state_ref, *, chunk: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xb = xbar_ref[0, 0].astype(jnp.float32)            # [l, p]
    la_row = la_row_ref[0, 0].astype(jnp.float32)      # [1, l]
    la_col = la_col_ref[0, 0].astype(jnp.float32)      # [l, 1]
    bm = b_ref[0, 0].astype(jnp.float32)               # [l, n]
    cm = c_ref[0, 0].astype(jnp.float32)               # [l, n]

    # inclusive cumsum of the log-decays, once down the sublanes and once
    # along the lanes (exact f32 VPU reductions, no relayout)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = jj <= ii
    cum_col = jnp.sum(jnp.where(lower, la_row, 0.0), axis=1,
                      keepdims=True)                   # [l, 1]
    cum_row = jnp.sum(jnp.where(ii <= jj, la_col, 0.0), axis=0,
                      keepdims=True)                   # [1, l]

    # decay matrix L[i,j] = exp(sum_{j+1..i} log_a), zero above the diagonal
    lmat = jnp.where(lower, jnp.exp(jnp.where(lower, cum_col - cum_row, 0.0)),
                     0.0)

    # intra-chunk
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(scores * lmat, xb, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # cross-chunk: consume state entering this chunk
    state = state_ref[...]                             # [p, n] fp32
    y_off = jnp.exp(cum_col) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),           # [l, n] x [p, n]^T
        preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # update state: S = exp(total) * S + (decay_to_end * xbar)^T B
    total = jnp.sum(la_row, axis=1, keepdims=True)     # [1, 1]
    emit = jax.lax.dot_general(xb * jnp.exp(total - cum_col), bm,
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [p, n]
    state_ref[...] = jnp.exp(total) * state + emit

    @pl.when(ci == nc - 1)
    def _flush():
        state_out_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(xbar: jax.Array, log_a: jax.Array, bmat: jax.Array,
                    cmat: jax.Array, *, chunk: int = 256,
                    interpret: bool):
    """Head-major operands: xbar [B,H,S,P] (dt-scaled); log_a [B,H,S];
    bmat/cmat [B,G,S,N] (head h reads group h // (H/G)).  Returns (y_core
    [B,H,S,P], final_state [B,H,P,N] fp32) — caller adds the D*x residual.

    Every block's last two dimensions are (chunk, P), (chunk, N), (1,
    chunk), (chunk, 1) or (P, N), which the TPU tiling accepts for any
    chunk that is a multiple of 128 (or the whole sequence)."""
    b, h, s, p = xbar.shape
    g, n = bmat.shape[1], bmat.shape[-1]
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // rep, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), xbar.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xbar, log_a[:, :, None, :], log_a[..., None], bmat, cmat)
    return y, state

"""Jit'd public wrappers around the Pallas kernels.

The platform picks the mode: kernels compile for the TPU when JAX's
default backend is a TPU and run in Pallas interpret mode everywhere else
(the CPU tests).  Compile-only checks for a described chip call the kernel
modules directly with ``interpret=False``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import matmul_epilogue as _mme
from repro.kernels import ssd_scan as _ssd
from repro.kernels import tsmm as _tsmm


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def tsmm(x: jax.Array, *, bm: int = 512, bn: int = 256,
         reg: float = 0.0) -> jax.Array:
    """Symmetric Gram matrix X^T X (+ reg*I) via the half-compute kernel.

    The kernel writes only upper-triangular tiles; the strict lower
    triangle is mirrored here (diagonal blocks are internally symmetric).
    ``reg`` fuses the LinReg DS ridge shift into the diagonal-tile flush.
    """
    up = _tsmm.tsmm_upper(x, bm=bm, bn=bn, reg=reg,
                          interpret=_interpret())
    upper = jnp.triu(up)
    return upper + jnp.triu(up, 1).T


def matmul_epilogue(x: jax.Array, w: jax.Array, bias=None, *,
                    epilogue: Optional[str] = None, out_dtype=None,
                    bm: int = 256, bn: int = 256,
                    bk: int = 256) -> jax.Array:
    """``epilogue(x @ w)`` with the elementwise tail fused into the flush.

    Realizes the planner's ``fusion="full"`` matmul variants: epilogue in
    {None, "bias", "silu", "gelu", "layernorm"}, with ``out_dtype`` cast
    sinking (the fp32 accumulator is narrowed during the single write).
    """
    return _mme.matmul_epilogue(
        x, w, bias, epilogue=epilogue, out_dtype=out_dtype,
        bm=bm, bn=bn, bk=bk, interpret=_interpret())


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    bq: int = 512, bk: int = 512) -> jax.Array:
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale, bq=bq, bk=bk,
        interpret=_interpret())


def decode_attention(q, kv, kpos, layer, pos, *,
                     window: Optional[int] = None) -> jax.Array:
    """One-token attention of q [B,Hq,hd] over layer ``layer`` of the
    stacked slot-major cache kv [L,S,Hkv,B,2*hd], whose slots hold the
    positions kpos [S]; float32 [B,Hq,hd]."""
    return _da.decode_attention(q, kv, kpos, layer, pos, window=window,
                                interpret=_interpret())


def ssd_scan(x, dt, A_log, B, C, D, *,
             chunk: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Mamba2 SSD scan via the Pallas kernel (matches models.mamba API).

    x: [B,S,H,P]; dt: [B,S,H]; A_log: [H]; B/C: [B,S,G,N]; D: [H].
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    dt32 = jnp.maximum(dt.astype(jnp.float32), 1e-6)
    a = -jnp.exp(A_log.astype(jnp.float32))
    log_a = (dt32 * a).transpose(0, 2, 1)              # [B,H,S]
    xbar = (x * dt32[..., None].astype(x.dtype)).transpose(0, 2, 1, 3)
    y, state = _ssd.ssd_scan_kernel(
        xbar, log_a, B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3),
        chunk=chunk, interpret=_interpret())
    y = y.transpose(0, 2, 1, 3) + x * D.astype(x.dtype)[None, None, :, None]
    return y, state

"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import attention_dense
from repro.models.mamba import ssd_chunked


def tsmm_ref(x: jax.Array, reg: float = 0.0) -> jax.Array:
    """Full Gram matrix X^T X (+ reg*I)."""
    g = jnp.einsum("mk,mn->kn", x.astype(jnp.float32),
                   x.astype(jnp.float32))
    if reg:
        g = g + reg * jnp.eye(x.shape[1], dtype=jnp.float32)
    return g.astype(x.dtype)


def matmul_epilogue_ref(x, w, bias=None, *, epilogue: Optional[str] = None,
                        out_dtype=None) -> jax.Array:
    """Unfused oracle: fp32 matmul, then the elementwise epilogue."""
    acc = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    if epilogue == "bias":
        acc = acc + bias.astype(jnp.float32)[None, :]
    elif epilogue == "silu":
        acc = jax.nn.silu(acc)
    elif epilogue == "gelu":
        acc = jax.nn.gelu(acc)
    elif epilogue == "layernorm":
        mu = jnp.mean(acc, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(acc - mu), axis=-1, keepdims=True)
        acc = (acc - mu) * jax.lax.rsqrt(var + 1e-6)
    elif epilogue is not None:
        raise ValueError(f"unknown epilogue {epilogue!r}")
    return acc.astype(x.dtype if out_dtype is None else out_dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None) -> jax.Array:
    return attention_dense(q, k, v, causal=causal, window=window, scale=scale)


def decode_attention_ref(q, kv, kpos, layer, pos, *,
                         window: Optional[int] = None) -> jax.Array:
    """The model's dense decode product over layer ``layer`` of the stacked
    cache (``transformer.dense_cached_attention``), float32 [B,Hq,hd]."""
    from repro.models.transformer import dense_cached_attention
    out = dense_cached_attention(q.astype(jnp.float32)[:, :, None], kv, kpos,
                                 layer, pos, window)
    return out[:, :, 0]


def ssd_scan_ref(x, dt, A_log, B, C, D, *, chunk: int = 256,
                 ) -> Tuple[jax.Array, jax.Array]:
    """Chunked-SSD oracle (validated against the sequential recurrence)."""
    return ssd_chunked(x, dt, A_log, B, C, D, chunk=chunk)

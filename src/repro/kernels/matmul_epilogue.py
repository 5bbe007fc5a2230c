"""Pallas TPU kernel: blocked matmul with a fused epilogue.

This is the kernel realization of the planner's ``fusion="full"`` matmul
variants (see ``linalg_ops._matmul`` and COST_MODEL.md "Costing fusion
plans"): the epilogue — bias add, SiLU/GELU activation, or row layernorm
— is applied to the fp32 accumulator tile *before* the single HBM write,
so the B·M·N intermediate never round-trips through HBM.  The analytical
profile charges exactly the traffic this kernel performs: the fused plan
saves ``cells x (write + read)`` bytes versus materializing the matmul
output and running the elementwise op as a second pass.

Cast sinking rides the same flush: ``out_dtype`` narrows (or widens) the
result during the accumulator write, which is how the serving head's
"fp32 logits" materialization is folded away under ``fusion="full"``.

Grid layout: (M/bm, N/bn, K/bk) with K minormost and sequential
("arbitrary") so the fp32 VMEM scratch accumulator is revisited legally;
the M and N axes are parallel.  The layernorm epilogue normalizes over
the full N row and therefore requires a single block along N (bn == n).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPILOGUES = (None, "bias", "silu", "gelu", "layernorm")
_LN_EPS = 1e-6


def _epilogue_f32(acc: jax.Array, epilogue: Optional[str],
                  bias: Optional[jax.Array]) -> jax.Array:
    """Apply the epilogue in fp32 (mirrors ``ref.matmul_epilogue_ref``)."""
    if epilogue is None:
        return acc
    if epilogue == "bias":
        return acc + bias
    if epilogue == "silu":
        return jax.nn.silu(acc)
    if epilogue == "gelu":
        return jax.nn.gelu(acc)
    if epilogue == "layernorm":
        mu = jnp.mean(acc, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(acc - mu), axis=-1, keepdims=True)
        return (acc - mu) * jax.lax.rsqrt(var + _LN_EPS)
    raise ValueError(f"unknown epilogue {epilogue!r}")


def _mm_epi_kernel(*refs, k_steps: int, epilogue: Optional[str]):
    if epilogue == "bias":
        x_ref, w_ref, b_ref, out_ref, acc_ref = refs
    else:
        x_ref, w_ref, out_ref, acc_ref = refs
        b_ref = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        bias = b_ref[...].astype(jnp.float32) if b_ref is not None else None
        out_ref[...] = _epilogue_f32(acc, epilogue, bias).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "epilogue", "out_dtype", "bm", "bn", "bk", "interpret"))
def matmul_epilogue(x: jax.Array, w: jax.Array,
                    bias: Optional[jax.Array] = None, *,
                    epilogue: Optional[str] = None,
                    out_dtype: Optional[jnp.dtype] = None,
                    bm: int = 256, bn: int = 256, bk: int = 256,
                    interpret: bool) -> jax.Array:
    """``epilogue(x @ w)`` written once, in ``out_dtype``.

    x: [m, k]; w: [k, n]; bias: [n] (required iff epilogue == "bias").
    Block sizes must tile the operands exactly; layernorm needs bn == n.
    """
    m, kdim = x.shape
    k2, n = w.shape
    assert kdim == k2, (x.shape, w.shape)
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}")
    if epilogue == "layernorm":
        assert bn == n, ("layernorm epilogue normalizes the full row; "
                         f"need bn == n, got bn={bn} n={n}")
    if (epilogue == "bias") != (bias is not None):
        raise ValueError("bias operand required iff epilogue == 'bias'")
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        (x.shape, w.shape, bm, bn, bk)
    mb, nb, kk = m // bm, n // bn, kdim // bk
    out_dtype = x.dtype if out_dtype is None else out_dtype

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if epilogue == "bias":
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias.reshape(1, n))

    fn = pl.pallas_call(
        functools.partial(_mm_epi_kernel, k_steps=kk, epilogue=epilogue),
        grid=(mb, nb, kk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(*args)

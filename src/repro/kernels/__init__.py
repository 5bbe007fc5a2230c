"""Pallas TPU kernels for the perf-critical compute layers.

  * tsmm            — the paper's transpose-self matmul (half-compute Gram,
                      optional fused ridge epilogue X^T X + reg*I)
  * flash_attention — blockwise online-softmax attention (prefill hot-spot)
  * ssd_scan        — Mamba2 SSD chunked scan (ssm/hybrid hot-spot)
  * matmul_epilogue — blocked matmul with fused bias/silu/gelu/layernorm
                      epilogue + cast sinking (the fusion="full" variants)

Each has a jit'd wrapper in ops.py and a pure-jnp oracle in ref.py; the
wrappers compile for a TPU backend and interpret elsewhere.  Validated in
interpret mode on CPU (tests/test_kernels.py), compiled for a described v5e
(tests/test_tpu_compile.py), and checked against ref.py on the chip
(chip_smoke.py).
"""
from repro.kernels import ops, ref

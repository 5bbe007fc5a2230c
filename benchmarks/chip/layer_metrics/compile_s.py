"""compile_s: seconds JAX spent compiling or loading programs from the
persistent cache during set-up (``jax.monitoring`` backend-compile events).
Moves ``setup_s``."""


def read(rec):
    return rec["compile_s"]

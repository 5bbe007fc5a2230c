"""Serving driver: ``python -m repro.launch.serve --arch <id> --reduced``.

Batched prefill+decode with the ServeEngine; production-shape serving
plans are exercised (lowered+compiled) via dryrun.py's decode cells.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.runtime.serve_engine import EngineConfig, Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    print(f"compile cache: {enable_compile_cache()}")
    arch = get_config(args.arch)
    if args.reduced:
        arch = dataclasses.replace(arch.reduced(), dtype="float32")
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         EngineConfig(max_len=args.max_len,
                                      temperature=args.temperature))

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, arch.vocab_size,
                                             size=args.prompt_len)),
                    max_new_tokens=args.max_new)
            for _ in range(args.batch)]
    frontend = None
    fs = model.frontend_shape(args.batch)
    if fs is not None:
        frontend = jax.numpy.asarray(rng.standard_normal(fs),
                                     jax.numpy.float32)
    outs = engine.generate(reqs, frontend)
    for i, c in enumerate(outs):
        print(f"req{i}: prompt[:8]={c.prompt[:8]} -> tokens={c.tokens}")
    print(f"prefill {outs[0].prefill_time_s*1e3:.1f}ms, "
          f"decode {outs[0].decode_time_s*1e3:.1f}ms "
          f"({args.max_new} steps, batch {args.batch})")


if __name__ == "__main__":
    main()

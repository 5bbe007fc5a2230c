"""Chip smoke test: the system's main paths at full width on a TPU.

    python chip_smoke.py            # one chip: train, serve, kernels
    python chip_smoke.py --chips 4  # four chips: the 2x2-mesh training step
                                    # against a one-device run of it

One process holds the chip(s) for every phase.  Each phase prints what it
ran and measured; a phase that fails makes the script exit non-zero.  The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Without a TPU, or without the
repository's ``src/`` beside this file, it exits non-zero and prints no
result: it never falls back to the CPU.

Phases (one chip):
  * train   — qwen1.5-0.5b at its published width (24 layers, d_model
              1024, vocab 151936, bf16, random weights from a seed) for a
              few steps through ``repro.launch.train.make_trainer``, with
              the plan ``choose_plan`` picks for the detected chip.  Checks
              that every loss is finite and the first is near ln(vocab).
  * serve   — the same model through ``ServeEngine``: 8 prompts of 128
              tokens, 32 greedy new tokens each.  Checks that decode
              logits match a fresh prefill of the same prefix.
  * kernels — ``flash_attention``, ``decode_attention`` and ``ssd_scan``
              compiled for the chip at real widths, each against its
              ``kernels/ref.py`` oracle.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH_ID = "qwen1.5-0.5b"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 5
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW = 8, 128, 32
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def compile_snapshot():
    """Where the program's compile record stands (``repro.runtime.tracing``:
    backend compiles or persistent-cache loads, and cache hits)."""
    from repro.runtime import tracing
    return time.perf_counter(), tracing.cache_hits()


def compiled_since(snap) -> str:
    from repro.runtime import tracing
    seconds = sum(c.seconds for c in tracing.compiles(snap[0]))
    return (f"compile_s={seconds:.3f} "
            f"cache_hits={tracing.cache_hits() - snap[1]}")


def _train_shape():
    from repro.configs import SHAPES
    return dataclasses.replace(SHAPES["train_4k"], global_batch=TRAIN_BATCH,
                               seq_len=TRAIN_SEQ)


def run_training(arch, shape, devices, label: str):
    """Plan, build and run the trainer on a host mesh over ``devices``;
    returns (losses, run result, plan)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_trainer, print_ranking

    mesh = make_host_mesh(devices)
    trainer, decisions = make_trainer(arch, shape, mesh, steps=TRAIN_STEPS,
                                      log_every=1)
    print_ranking(decisions, trainer.cc)
    best = decisions[0]
    log(f"[{label}] plan={best.plan.describe()} chip={trainer.cc.chip.name} "
        f"mesh={trainer.cc.mesh_shape} estimate_hbm={best.hbm_est:.0f}B "
        f"feasible={best.feasible}")
    snap = compile_snapshot()
    t0 = time.perf_counter()
    result = trainer.run()
    wall = time.perf_counter() - t0
    hist = result["history"]
    losses = [h["loss"] for h in hist]
    steps = [h["time_s"] for h in hist]
    log(f"[{label}] steps={len(hist)} losses={losses}")
    log(f"[{label}] first_step_s={steps[0]:.4f} (trace+compile+run) "
        f"step_s={steps[1:]} {compiled_since(snap)} wall_s={wall:.3f}")
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise AssertionError(f"{label}: non-finite or missing losses {losses}")
    return losses, result, best.plan


def _peaks(devices):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def phase_train(jax):
    from repro.configs import get_config
    arch = get_config(ARCH_ID)
    losses, _, _ = run_training(arch, _train_shape(), jax.devices()[:1],
                                "train")
    log(f"[train] peak_bytes_in_use={_peaks(jax.devices()[:1])[0]} "
        f"memory_stats={jax.devices()[0].memory_stats()}")
    expect = math.log(arch.vocab_size)
    if abs(losses[0] - expect) > 1.0:
        raise AssertionError(f"first loss {losses[0]:.4f} is not near "
                             f"ln(vocab)={expect:.4f}")


def phase_serve(jax):
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.runtime.serve_engine import EngineConfig, Request, ServeEngine

    arch = get_config(ARCH_ID)
    model = build_model(arch)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    max_len = SERVE_PROMPT + SERVE_NEW
    engine = ServeEngine(model, params, EngineConfig(max_len=max_len))
    rng = np.random.default_rng(SEED)
    reqs = [Request(prompt=[int(t) for t in rng.integers(
                1, arch.vocab_size, SERVE_PROMPT)],
                    max_new_tokens=SERVE_NEW)
            for _ in range(SERVE_REQUESTS)]
    for run in ("cold", "warm"):
        snap = compile_snapshot()
        t0 = time.perf_counter()
        outs = engine.generate(reqs)
        wall = time.perf_counter() - t0
        c = outs[0]
        log(f"[serve] {run}: requests={len(outs)} wall_s={wall:.4f} "
            f"prefill_s={c.prefill_time_s:.4f} decode_s={c.decode_time_s:.4f} "
            f"new_tokens/s={len(outs) * SERVE_NEW / wall:.1f} "
            f"{compiled_since(snap)}")
    log(f"[serve] engine stats={engine.stats}")
    for c in outs:
        if len(c.tokens) != SERVE_NEW or not all(
                0 <= t < arch.vocab_size for t in c.tokens):
            raise AssertionError(f"bad completion {c.rid}: {c.tokens}")

    # decode logits after the last generated prefix vs a fresh prefill
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    prompt, toks = reqs[0].prompt, outs[0].tokens
    logits, cache = prefill(params, jnp.asarray([prompt], jnp.int32),
                            model.init_cache(1, max_len))
    first_ok = int(jnp.argmax(logits[0])) == toks[0]
    for t in toks[:-1]:
        logits, cache = decode(params, jnp.asarray([t], jnp.int32), cache)
    fresh, _ = prefill(params, jnp.asarray([prompt + toks[:-1]], jnp.int32),
                       model.init_cache(1, max_len))
    dec, ref = np.asarray(logits[0]), np.asarray(fresh[0])
    err = float(np.max(np.abs(dec - ref)))
    scale = float(np.max(np.abs(ref)))
    log(f"[serve] decode-vs-prefill logits: max_abs_err={err:.5f} "
        f"max_abs_logit={scale:.4f} argmax_equal={dec.argmax() == ref.argmax()} "
        f"first_token_matches_batched_engine={first_ok}")
    if not np.all(np.isfinite(dec)) or err > 0.05 * max(scale, 1.0):
        raise AssertionError("decode logits disagree with a fresh prefill")


def phase_kernels(jax):
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    key = jax.random.split(jax.random.PRNGKey(SEED), 8)
    # flash attention: qwen1.5-0.5b heads at a 2048-token prefill
    shp = (4, 16, 2048, 64)
    q, k, v = (jax.random.normal(key[i], shp, jnp.bfloat16) for i in range(3))
    snap = compile_snapshot()
    out = ops.flash_attention(q, k, v, bq=512, bk=512).block_until_ready()
    want = ref.flash_attention_ref(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    log(f"[kernels] flash_attention {shp} bf16: max_abs_err={err:.5f} "
        f"{compiled_since(snap)}")
    if not err <= 3e-2:
        raise AssertionError(f"flash_attention error {err}")

    # decode attention: qwen1.5-0.5b's stacked cache, 32 lanes, 1280 slots
    kv = jax.random.normal(key[3], (24, 1280, 16, 32, 128), jnp.bfloat16)
    q = jax.random.normal(key[4], (32, 16, 64), jnp.bfloat16)
    kpos = jnp.where(jnp.arange(1280) <= 1100, jnp.arange(1280), -1)
    args = (q, kv, kpos.astype(jnp.int32), 7, 1100)
    snap = compile_snapshot()
    out = ops.decode_attention(*args).block_until_ready()
    err = float(jnp.max(jnp.abs(out - ref.decode_attention_ref(*args))))
    log(f"[kernels] decode_attention {tuple(kv.shape)} bf16 pos=1100: "
        f"max_abs_err={err:.6f} {compiled_since(snap)}")
    if not err <= 2e-2:
        raise AssertionError(f"decode_attention error {err}")
    del kv

    # SSD scan: mamba2-1.3b widths (64 heads x 64, state 128, chunk 256)
    b, s, h, p, n = 1, 2048, 64, 64, 128
    x = jax.random.normal(key[3], (b, s, h, p), jnp.float32)
    dt = jax.random.uniform(key[4], (b, s, h), jnp.float32, 0.01, 0.2)
    a_log = jax.random.uniform(key[5], (h,), jnp.float32, -1.0, 1.0)
    bm = jax.random.normal(key[6], (b, s, 1, n), jnp.float32)
    cm = jax.random.normal(key[7], (b, s, 1, n), jnp.float32)
    d = jnp.ones((h,), jnp.float32)
    snap = compile_snapshot()
    y, st = ops.ssd_scan(x, dt, a_log, bm, cm, d, chunk=256)
    y.block_until_ready()
    y_ref, st_ref = ref.ssd_scan_ref(x, dt, a_log, bm, cm, d, chunk=256)
    for name, got, exp in (("y", y, y_ref), ("state", st, st_ref)):
        err = float(jnp.max(jnp.abs(got - exp)))
        scale = float(jnp.max(jnp.abs(exp)))
        log(f"[kernels] ssd_scan {name} {tuple(got.shape)}: "
            f"max_abs_err={err:.5f} max_abs_ref={scale:.4f} "
            f"{compiled_since(snap)}")
        if not (np.isfinite(err) and err <= 2e-2 * max(scale, 1.0)):
            raise AssertionError(f"ssd_scan {name} error {err}")


def phase_four_chips(jax):
    from repro.configs import get_config

    arch = get_config(ARCH_ID)
    devs = jax.devices()[:4]
    losses4, res, plan = run_training(arch, _train_shape(), devs, "2x2")
    log(f"[2x2] peak_bytes_in_use per device={_peaks(devs)} "
        f"memory_stats[0]={devs[0].memory_stats()}")
    leaves = jax.tree.leaves(res["params"])
    held = {dv.id: 0 for dv in devs}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    sharded = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
    log(f"[2x2] param bytes per device={held} sharded_leaves={sharded}/"
        f"{len(leaves)}")
    if any(v == 0 for v in held.values()) or (
            (plan.tp_axes or plan.fsdp_axes) and not sharded):
        raise AssertionError("parameters are not spread over the 2x2 mesh")
    del res, leaves

    losses1, _, _ = run_training(arch, _train_shape(), devs[:1], "1dev")
    diff = max(abs(a - b) for a, b in zip(losses4, losses1))
    log(f"[2x2] max |loss_2x2 - loss_1dev| = {diff:.5f}")
    if not diff <= 2e-2:
        raise AssertionError(f"2x2 losses {losses4} differ from one-device "
                             f"losses {losses1}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir}")

    phases = ([("four_chips", phase_four_chips)] if args.chips == 4 else
              [("train", phase_train), ("serve", phase_serve),
               ("kernels", phase_kernels)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(jax)
            log(f"== {name}: ok ({time.perf_counter() - t0:.1f}s)")
        except Exception:
            traceback.print_exc()
            log(f"== {name}: FAILED ({time.perf_counter() - t0:.1f}s)")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

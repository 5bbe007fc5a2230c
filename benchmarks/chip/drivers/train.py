"""Driver ``train``: the planner-picked training step through the program's
``Trainer``.

The rows are uniform token ids (``traffic.UniformCorpus``), fed through
the program's own prefetching pipeline: ``Trainer.run`` builds it with
``make_pipeline``, which set-up points at the benchmark's corpus for the
rest of the run.

Set-up ranks the plans for the cell's mesh (``launch.train.rank_plans``,
which refuses a winner that does not fit), builds one ``Trainer`` with the
traffic's AdamW settings, makes the weights from the seed, and drives the
first three steps through ``Trainer.run`` (the first compiles).  It keeps
what the comparison needs from them: the losses, AdamW's first moment
after step one, and each leaf's change over the three steps, read before
the fourth step takes the parameters.  The window hands the same state to
one more ``Trainer.run`` and stops it at the first step that ends past
``--seconds``: ``train_tokens_per_s`` is all its tokens over all its time.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import numpy as np

import compare
import traffic
from common import log, span
from weights import leaf_paths, make_weights_fn, seed_words

WARMUP_STEPS = 3


def setup(ctx) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.data import pipeline
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import mesh_cluster, rank_plans
    from repro.optim import adamw, compress
    from repro.runtime import train_loop
    from repro.runtime.train_loop import Trainer, TrainerConfig

    tr = ctx.traffic
    b, s = tr["global_batch"], tr["seq_len"]

    def make_pipeline(vocab_size, seq_len, global_batch, *, seed=0, start_step=0, **_):
        corpus = traffic.UniformCorpus(vocab_size, seq_len, global_batch, seed)
        return pipeline.PrefetchIterator(corpus, start_step=start_step)
    train_loop.make_pipeline = make_pipeline
    shape = ShapeConfig(ctx.name, s, b, "train")
    mesh = make_host_mesh(ctx.devices)
    cc = mesh_cluster(mesh)
    with span("plan"):
        t = time.perf_counter()
        decisions = rank_plans(ctx.arch, shape, cc)
        ctx.record["plan_s"] = time.perf_counter() - t
    best = decisions[0]
    ctx.record.update(plan=best.plan.describe(), plan_est_step_s=best.time,
                      plan_est_hbm_bytes=best.hbm_est)
    log(f"plan {best.plan.describe()} est_step_s={best.time:.4f} "
        f"est_hbm={best.hbm_est / 1e9:.2f}GB mesh={cc.mesh_shape} "
        f"plan_s={ctx.record['plan_s']:.3f}")
    opt = adamw.AdamWConfig(**tr["optimizer"])
    trainer = Trainer(ctx.arch, shape, cc, mesh, plan=best.plan, opt_cfg=opt,
                      tcfg=TrainerConfig(steps=0, log_every=1, seed=ctx.seed))
    shapes = trainer.model.init_shapes()
    flags = compare.stacked_flags(leaf_paths(shapes))
    lo, hi = seed_words(ctx.seed)
    with mesh, span("weights"):
        make = jax.jit(make_weights_fn(shapes, ctx.config["init"]),
                       out_shardings=trainer.param_sh)
        params = make(lo, hi)
        opt_state = jax.jit(partial(adamw.init, opt),
                            out_shardings=trainer.opt_sh)(params)
        ef = jax.jit(lambda ps: compress.EFState(residual=jax.tree.map(
            lambda p: jnp.zeros((), jnp.float32), ps)),
            out_shardings=trainer.ef_sh)(params)
    from reference.common import leaf_norms
    norms = jax.jit(lambda t: leaf_norms(t, flags))
    change = jax.jit(lambda a, c: leaf_norms(
        jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                     a, c), flags))

    def run(k0, k1, params, opt_state, on_metrics=None):
        trainer.tcfg = dataclasses.replace(trainer.tcfg, steps=k1)
        res = trainer.run(start_step=k0, params=params, opt_state=opt_state,
                          ef=ef, on_metrics=on_metrics)
        return res["params"], res["opt_state"], res["history"]

    losses = []
    with span("warmup"):
        for k in range(WARMUP_STEPS):
            params, opt_state, hist = run(k, k + 1, params, opt_state)
            losses.append(hist[-1]["loss"])
            log(f"warm-up step {k}: loss={hist[-1]['loss']:.6f} "
                f"grad_norm={hist[-1]['grad_norm']:.6f} time_s={hist[-1]['time_s']:.4f}")
            if k == 0:       # m = (1 - b1) * clipped gradient after one step
                grad_norms = np.concatenate(
                    [np.asarray(x) for x in norms(opt_state.m)]) / (1 - opt.b1)
        with mesh:
            p0 = make(lo, hi)
            change_norms = np.concatenate([np.asarray(x) for x in change(params, p0)])
            del p0
    ctx.state.update(trainer=trainer, params=params, opt_state=opt_state,
                     run=run, shapes=shapes, make_plain=make_weights_fn(
                         shapes, ctx.config["init"]))
    ctx.record["program"] = {"losses": losses, "grad_norms": grad_norms,
                             "change_norms": change_norms}


def window(ctx, opened) -> None:
    """``opened()`` opens the window span and returns its closer."""
    tr = ctx.traffic
    tokens_per_step = tr["global_batch"] * tr["seq_len"]
    ends, losses, times = [], [], []
    trainer = ctx.state["trainer"]
    close = None
    t0 = None

    def on_metrics(m):
        nonlocal close
        now = time.perf_counter()
        ends.append(now)
        losses.append(m["loss"])
        times.append(m["time_s"])
        if now - t0 >= ctx.seconds and close is not None:
            close()
            close = None
            trainer.tcfg = dataclasses.replace(trainer.tcfg, steps=m["step"] + 1)

    close = opened()
    t0 = time.perf_counter()
    ctx.record["t_window_start"] = time.time()
    params, opt_state, _ = ctx.state["run"](WARMUP_STEPS, 10 ** 9,
                                            ctx.state.pop("params"),
                                            ctx.state.pop("opt_state"), on_metrics)
    window_s = ends[-1] - t0
    ctx.state.update(params=params, opt_state=opt_state)
    steps = len(ends)
    ctx.record.update(window_s=window_s, steps=steps, tokens=steps * tokens_per_step,
                      step_s=window_s / steps,
                      attempted=steps,
                      failed=sum(1 for x in losses if not np.isfinite(x)))
    ctx.record["metrics"] = {"train_tokens_per_s": steps * tokens_per_step / window_s}
    log(f"window: {steps} steps in {window_s:.4f}s, step_s={window_s / steps:.5f}, "
        f"losses {losses[0]:.5f} .. {losses[-1]:.5f}")
    q = np.percentile(times, [0, 25, 50, 75, 100])
    log("window step times (Trainer.run): min/q1/median/q3/max "
        + " ".join(f"{x:.5f}" for x in q) + "; by tenths: "
        + " ".join(f"{np.mean(c):.4f}" for c in np.array_split(np.asarray(times), min(10, len(times)))))


def check(ctx) -> dict:
    """The reference's three steps from the same weights and rows."""
    for k in ("params", "opt_state", "trainer", "run"):
        ctx.state.pop(k, None)
    tr = ctx.traffic
    corpus = traffic.UniformCorpus(ctx.arch.vocab_size, tr["seq_len"],
                                   tr["global_batch"], ctx.seed)
    batches = [corpus.batch_at(k)["tokens"] for k in range(WARMUP_STEPS)]
    ref = compare.reference_training(
        compare.ref_module(ctx.config["reference"]), ctx.config,
        ctx.state["make_plain"], ctx.seed, ctx.state["shapes"], batches,
        tr["optimizer"], ctx.devices)
    prog = ctx.record["program"]
    log(f"losses program={prog['losses']} reference={ref['losses']}")
    names = compare.leaf_names(ctx.state["shapes"])
    for key in ("grad_norms", "change_norms"):
        log(f"widest {key}: " + "; ".join(
            compare.worst_leaves(prog[key], ref[key], names)))
    return compare.training_numbers(prog, ref)

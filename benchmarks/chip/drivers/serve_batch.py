"""Driver ``serve_batch``: closed-loop batch generation through the
program's ``ServeEngine`` (static batching, greedy).

Set-up makes the weights from the seed, builds one engine and serves one
batch of the traffic's prompts with two new tokens each, which compiles
the prefill at the batch's padded length, the decode step and the eager
sampling ops.  The window submits batch after batch (the next when the
last one returned, through the engine's ``submit`` and ``step``) and ends
with the first batch that returns past ``--seconds``.  Each ``step`` is
timed: a request's first token arrives with its prefill, token j >= 1 at
the end of step j.
"""
from __future__ import annotations

import time

import numpy as np

import compare
import flops
import traffic
from common import log, span
from weights import make_weights_fn, seed_words

SAMPLE_REQUESTS = 4
WARMUP_BATCH = 1 << 30          # a batch index no window reaches


def setup(ctx) -> None:
    import jax
    from repro.models.model import build_model
    from repro.runtime.serve_engine import EngineConfig, Request, ServeEngine

    tr = ctx.traffic
    model = build_model(ctx.arch)
    shapes = model.init_shapes()
    make_plain = make_weights_fn(shapes, ctx.config["init"])
    with span("weights"):
        params = jax.jit(make_plain)(*seed_words(ctx.seed))
    engine = ServeEngine(model, params, EngineConfig(
        batching="static", max_len=tr["max_len"], temperature=0.0))
    with span("warmup"):
        reqs = traffic.serve_batch(tr, ctx.arch.vocab_size, ctx.seed, WARMUP_BATCH)
        for prompt, _ in reqs:
            engine.submit(Request(prompt=prompt, max_new_tokens=2))
        while engine.pending_requests:
            engine.step()
    ctx.state.update(engine=engine, make_plain=make_plain, shapes=shapes,
                     Request=Request)


def window(ctx, opened) -> None:
    tr = ctx.traffic
    engine, Request = ctx.state["engine"], ctx.state["Request"]
    plen = tr["prompt_len"]
    stats0 = dict(engine.stats)
    done, gaps, contexts = [], [], []
    close = opened()
    t0 = time.perf_counter()
    ctx.record["t_window_start"] = time.time()
    index = 0
    while True:
        reqs = traffic.serve_batch(tr, ctx.arch.vocab_size, ctx.seed, index)
        with span("batch"):
            rids = {engine.submit(Request(prompt=p, max_new_tokens=n)): (p, n)
                    for p, n in reqs}
            ends, starts, finished = [], [], []
            while engine.pending_requests:
                with span("step"):
                    s = time.perf_counter()
                    finished.extend(engine.step())
                    ends.append(time.perf_counter())
                starts.append(s)
        wants = [n for _, n in reqs]
        # decode step j serves the requests wanting more than j tokens,
        # each query over plen + j keys
        for j in range(1, max(wants)):
            contexts.append([plen + j] * sum(1 for w in wants if w > j))
        for c in finished:
            n = len(c.tokens)
            first = starts[0] + c.prefill_time_s
            arrive = [first] + ends[:n - 1]
            gaps.extend(np.diff(arrive).tolist())
            p, want = rids[c.rid]
            done.append({"prompt": p, "tokens": list(c.tokens), "want": want})
        index += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    t1 = time.perf_counter()
    close()
    window_s = t1 - t0
    generated = sum(len(d["tokens"]) for d in done)
    decode_steps = engine.stats["decode_steps"] - stats0["decode_steps"]
    wasted = engine.stats["wasted_slot_steps"] - stats0["wasted_slot_steps"]
    batch = tr["batch"]
    work = (len(done) * flops.prefill_flops(ctx.config, plen)
             + sum(flops.decode_flops(ctx.config, c) for step in contexts for c in step))
    ctx.state["done"] = done
    ctx.record.update(
        window_s=window_s, batches=index, requests=len(done),
        generated_tokens=generated, decode_steps=decode_steps,
        wasted_slot_steps=wasted, slot_steps=decode_steps * batch,
        decode_contexts=contexts, model_flops=work,
        attempted=len(done),
        failed=sum(1 for d in done if len(d["tokens"]) != d["want"]))
    ctx.record["metrics"] = {
        "serve_tokens_per_s": generated / window_s,
        "serve_itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
    }
    log(f"window: {index} batches, {len(done)} requests, {generated} tokens in "
        f"{window_s:.4f}s; {len(gaps)} gaps, median {1e3 * np.median(gaps):.3f} ms")


def check(ctx) -> dict:
    """The reference over a seeded sample of the window's requests, the
    longest among them, each prompt with its served tokens."""
    ctx.state.pop("engine", None)
    done = ctx.state["done"]
    rng = np.random.default_rng([ctx.seed, 1])
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    others = [i for i in rng.permutation(len(done)) if i != longest]
    pick = [longest] + others[:SAMPLE_REQUESTS - 1]
    t = ctx.traffic["max_len"]
    seqs = np.zeros((len(pick), t), np.int32)
    reads = []
    for row, i in enumerate(pick):
        full = done[i]["prompt"] + done[i]["tokens"]
        seqs[row, :len(full)] = full
        plen = len(done[i]["prompt"])
        reads.append([(plen - 1 + j, tok) for j, tok in enumerate(done[i]["tokens"])])
    ctx.state["sample"] = {"seqs": seqs, "reads": reads}
    params = compare.weights_f32(ctx.state["make_plain"], ctx.seed, None)
    out = compare.serve_logit_gaps(compare.ref_module(ctx.config["reference"]),
                                   ctx.config, params, seqs, reads)
    log(f"compared {out['tokens']} served tokens of {len(pick)} requests")
    return {"logit_gap": out["logit_gap"]}

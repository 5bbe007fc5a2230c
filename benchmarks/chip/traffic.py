"""Seeded traffic: training batches and batches of generation requests.

One generator per kind reads its parameters from ``traffic/<name>.json``.
Every seed gets the same set of sizes in another order and other token
ids, so the seed changes what is computed but not how much.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np


def uniform_batch(vocab: int, seq_len: int, batch: int, seed: int,
                  step: int) -> np.ndarray:
    """Token rows of one training step, each id uniform over the vocabulary
    and drawn anew for every (seed, step): rows that all differ, with no id
    repeated more than chance repeats it."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, size=(batch, seq_len), dtype=np.int64).astype(np.int32)


class UniformCorpus:
    """``batch_at(step)``: one training step's rows of ``uniform_batch``,
    in the form the program's prefetching pipeline takes from its source."""

    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int):
        self.args = (vocab, seq_len, batch, seed)

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        return {"tokens": uniform_batch(*self.args, step)}


def stratified_lengths(spec: Dict, n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles of a lognormal (``median``,
    ``sigma``), clipped to [``min``, ``max``], the longest set to ``max``:
    the same heavy-tailed set for every seed."""
    dist = NormalDist(math.log(spec["median"]), spec["sigma"])
    out = [int(round(math.exp(dist.inv_cdf((i + 0.5) / n)))) for i in range(n)]
    out = [min(max(x, spec["min"]), spec["max"]) for x in out]
    out[-1] = spec["max"]
    return sorted(out)


def serve_batch(traffic: Dict, vocab: int, seed: int,
                index: int) -> List[Tuple[List[int], int]]:
    """Batch ``index`` of a closed-loop generation run: (prompt ids,
    new tokens) per request.  Prompts share one length (a length bucket);
    output lengths are ``stratified_lengths`` in a seeded order."""
    n = traffic["batch"]
    rng = np.random.default_rng([seed, index])
    outs = [stratified_lengths(traffic["output"], n)[i] for i in rng.permutation(n)]
    plen = traffic["prompt_len"]
    prompts = rng.integers(1, vocab, size=(n, plen))
    return [([int(t) for t in prompts[i]], int(outs[i])) for i in range(n)]

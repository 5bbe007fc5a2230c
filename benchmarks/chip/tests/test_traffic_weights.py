"""Seeded inputs: the same seed gives the same weights and traffic, every
seed the same sizes, and seeds past 32 bits work."""
import os

import jax
import numpy as np
import pytest

import traffic
from common import bench_file, load_json, resolve_cell
from weights import make_weights_fn, seed_words

BIG = 2 ** 33 + 5


def test_seed_words_cover_64_bits():
    assert seed_words(BIG) == (np.uint32(5), np.uint32(2))
    with pytest.raises(ValueError):
        seed_words(-1)


def test_weights_repeat_per_seed_and_differ_across_seeds():
    shapes = {"embed": jax.ShapeDtypeStruct((64, 8), np.float32),
              "blocks": {"ln": jax.ShapeDtypeStruct((2, 8), np.float32),
                         "w": jax.ShapeDtypeStruct((2, 8, 8), jax.numpy.bfloat16)}}
    rules = {"blocks/ln": {"kind": "const", "value": 0.0},
             "*": {"kind": "uniform", "std": 0.02}}
    fn = jax.jit(make_weights_fn(shapes, rules))
    a, b, c = fn(*seed_words(BIG)), fn(*seed_words(BIG)), fn(*seed_words(BIG + 1))
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    assert a["blocks"]["w"].dtype == jax.numpy.bfloat16
    assert float(np.std(np.asarray(a["embed"]))) == pytest.approx(0.02, rel=0.2)
    assert not np.array_equal(a["embed"], a["blocks"]["w"][0].astype(np.float32))
    assert np.all(np.asarray(a["blocks"]["ln"]) == 0)


def test_uniform_corpus_rows_differ_and_repeat():
    a = traffic.uniform_batch(151936, 2048, 2, BIG, 0)
    assert np.array_equal(a, traffic.uniform_batch(151936, 2048, 2, BIG, 0))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a, traffic.uniform_batch(151936, 2048, 2, BIG, 1))
    assert a.min() >= 0 and a.max() < 151936


def test_serving_batches_have_one_set_of_sizes():
    tr = load_json(bench_file("traffic", "batch32_p1024_out16to256.json"))
    sizes = None
    for seed in (1, BIG):
        for index in range(3):
            reqs = traffic.serve_batch(tr, 151936, seed, index)
            assert len(reqs) == tr["batch"]
            assert {len(p) for p, _ in reqs} == {tr["prompt_len"]}
            outs = sorted(n for _, n in reqs)
            assert outs[-1] == tr["output"]["max"] and outs[0] >= tr["output"]["min"]
            sizes = sizes or outs
            assert outs == sizes
    assert tr["prompt_len"] + tr["output"]["max"] == tr["max_len"]


def test_every_cell_resolves():
    bench = load_json(os.path.join(os.path.dirname(os.path.dirname(bench_file())),
                                   "BENCHMARK.json"))
    for w in bench["workloads"]:
        spec = resolve_cell(w["name"], bench)
        assert spec["cell"]["limits"]
        assert os.path.exists(bench_file("drivers", spec["traffic"]["driver"] + ".py"))
        for m in spec["per_layer"]:
            assert os.path.exists(bench_file("layer_metrics", m["name"] + ".py"))

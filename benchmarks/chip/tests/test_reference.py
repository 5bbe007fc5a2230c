"""The plain references against the program's models at the reduced size,
in float32 on the CPU, from the same seeded weights."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
from common import load_json
from harness import program_arch
from reference.common import Matmul
from weights import make_weights_fn

DATA = os.path.join(os.path.dirname(__file__), "data")


def _setup(name, seq):
    cfg = load_json(os.path.join(DATA, name))
    arch = dataclasses.replace(program_arch(cfg), dtype="float32")
    from repro.models.model import build_model
    model = build_model(arch)
    params = jax.jit(make_weights_fn(model.init_shapes(), cfg["init"]))(
        np.uint32(7), np.uint32(1))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, seq), 0, arch.vocab_size)
    return cfg, model, params, tokens


@pytest.mark.parametrize("name,seq", [("tiny_qwen.json", 48), ("tiny_mamba2.json", 64)])
def test_reference_logits_and_loss_match_program(name, seq):
    cfg, model, params, tokens = _setup(name, seq)
    ref = compare.ref_module(cfg["reference"])
    mm = Matmul("float32")
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, tokens)
        got = ref.head(params, ref.hidden(params, tokens, cfg, mm), mm)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        loss_prog, _ = model.loss(params, {"tokens": tokens})
        loss_ref = ref.loss(params, tokens, cfg, mm)
    assert abs(float(loss_prog) - float(loss_ref)) < 1e-4


@pytest.mark.parametrize("name,seq", [("tiny_qwen.json", 48), ("tiny_mamba2.json", 64)])
def test_reference_gradients_match_program(name, seq):
    cfg, model, params, tokens = _setup(name, seq)
    ref = compare.ref_module(cfg["reference"])
    mm = Matmul("float32")
    with jax.default_matmul_precision("highest"):
        g_prog = jax.grad(lambda p: model.loss(p, {"tokens": tokens})[0])(params)
        g_ref = jax.grad(lambda p: ref.loss(p, tokens, cfg, mm))(params)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * scale


def test_fp8_control_differs_from_reference():
    cfg, model, params, tokens = _setup("tiny_qwen.json", 48)
    ref = compare.ref_module(cfg["reference"])
    with jax.default_matmul_precision("highest"):
        a = ref.loss(params, tokens, cfg, Matmul("float32"))
        b = ref.loss(params, tokens, cfg, Matmul("fp8"))
    assert 0 < abs(float(a) - float(b)) < 0.5

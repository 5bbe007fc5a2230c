"""The tied, float32-residual Mamba2 reference (``reference/mamba2_tied.py``)
against the program at a tiny size on the CPU, from the same seeded
weights; the cell's path through the harness; and the reader of
``collective_exposed_pct`` on hand-made device intervals."""
import dataclasses
import fnmatch
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import trace_reduce
from common import BENCH_DIR, load_json
from harness import load_file_module, program_arch, run_cell
from reference import mamba2_tied
from reference.common import Matmul
from test_faults import PEAK, SEED, train_spec
from weights import make_weights_fn

DATA = os.path.join(os.path.dirname(__file__), "data")
CONFIG = "tiny_mamba2_tied.json"
# the program's loss and gradients are float32 throughout here, as the
# reference's; what is left is the order of the sums (the chunk length of
# the program's scan, 32, against the reference's block of 128 and its
# decay matrix over all blocks; XLA's fusion of the norms), a few units of
# float32's 1.2e-7 relative step over 4 layers of 64-wide sums
LOGIT_TOL = 2e-4             # absolute and relative, logits of order 0.1
LOSS_TOL = 1e-4              # on a loss of ln(256) = 5.5
GRAD_TOL = 1e-3              # of each leaf's largest gradient entry


def _setup(seq):
    cfg = load_json(os.path.join(DATA, CONFIG))
    arch = dataclasses.replace(program_arch(cfg), dtype="float32")
    from repro.models.model import build_model
    model = build_model(arch)
    params = jax.jit(make_weights_fn(model.init_shapes(), cfg["init"]))(
        np.uint32(7), np.uint32(1))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, seq), 0, arch.vocab_size)
    return cfg, arch, model, params, tokens


def test_configuration_is_the_published_one():
    cfg = load_json(os.path.join(DATA, CONFIG))
    arch = program_arch(cfg)
    assert arch.tie_embeddings and arch.residual_in_fp32
    assert arch.vocab_size % cfg["pad_vocab_size_multiple"] == 0
    assert arch.vocab_size - cfg["vocab_size"] < cfg["pad_vocab_size_multiple"]
    full = load_json(os.path.join(BENCH_DIR, "configs", "mamba2_1p3b_2x2.json"))
    assert full["reduced"] == [] and full["n_layer"] == 48
    assert full["arch_overrides"]["vocab_size"] == 50288


def test_logits_and_loss_match_program():
    cfg, arch, model, params, tokens = _setup(64)
    assert "lm_head" not in params
    mm = Matmul("float32")
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, tokens)
        got = mamba2_tied.head(params, mamba2_tied.hidden(params, tokens, cfg, mm), mm)
        np.testing.assert_allclose(got, want, rtol=LOGIT_TOL, atol=LOGIT_TOL)
        loss_prog, _ = model.loss(params, {"tokens": tokens})
        loss_ref = mamba2_tied.loss(params, tokens, cfg, mm)
    assert want.shape[-1] == arch.vocab_size
    assert abs(float(loss_prog) - float(loss_ref)) < LOSS_TOL


def test_gradients_match_program():
    cfg, _, model, params, tokens = _setup(64)
    mm = Matmul("float32")
    with jax.default_matmul_precision("highest"):
        g_prog = jax.grad(lambda p: model.loss(p, {"tokens": tokens})[0])(params)
        g_ref = jax.grad(lambda p: mamba2_tied.loss(p, tokens, cfg, mm))(params)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) <= GRAD_TOL * scale


def test_in_projection_read_fused_or_split():
    """A tree whose in-projection and convolution are split by component
    gives the reference the same layer as the fused one."""
    cfg, _, _, params, tokens = _setup(32)
    di = cfg["expand"] * cfg["d_model"]
    gn = cfg["ngroups"] * cfg["d_state"]
    q = params["blocks"]["mamba"]
    cuts = {"w_in": [di, 2 * di, 2 * di + gn, 2 * di + 2 * gn],
            "conv_w": [di, di + gn], "conv_b": [di, di + gn]}
    names = {"w_in": mamba2_tied.IN_PARTS, "conv_w": mamba2_tied.CONV_PARTS,
             "conv_b": mamba2_tied.CONV_PARTS}
    split = {k: v for k, v in q.items() if k not in cuts}
    for key, at in cuts.items():
        for name, part in zip(names[key], jnp.split(q[key], at, axis=-1)):
            split[f"{key}_{name}"] = part
    params2 = dict(params, blocks=dict(params["blocks"], mamba=split))
    mm = Matmul("float32")
    a = mamba2_tied.hidden(params, tokens, cfg, mm)
    b = mamba2_tied.hidden(params2, tokens, cfg, mm)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_reference_reads_bf16_stored_leaves_on_their_grid():
    """With ``stored_bfloat16``, weights a little off bfloat16's grid (what
    arrives where the compiler dropped the harness's rounding) give the
    loss and gradients of the weights on the grid; without it they do not."""
    cfg, _, _, params, tokens = _setup(32)
    full = load_json(os.path.join(BENCH_DIR, "configs", "mamba2_1p3b_2x2.json"))
    pats = full["stored_bfloat16"]
    stored = dict(cfg, stored_bfloat16=pats)
    on = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)

    def nudge(path, x):      # a relative 2**-10: under half of bfloat16's spacing
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        hit = any(fnmatch.fnmatchcase(name, pat) for pat in pats)
        return x * (1 + 2.0 ** -10) if hit else x
    off = jax.tree_util.tree_map_with_path(nudge, on)
    mm = Matmul("float32")
    loss = lambda c: jax.value_and_grad(lambda p, t: mamba2_tied.loss(p, t, c, mm))
    (l_on, g_on), (l_off, g_off) = loss(stored)(on, tokens), loss(stored)(off, tokens)
    assert float(l_on) == float(l_off)
    for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(loss(cfg)(on, tokens)[0]) != float(loss(cfg)(off, tokens)[0])


def test_cell_path_is_correct_at_a_tiny_size():
    """The cell's configuration through the harness and the training
    driver (bf16 weights, float32 residual), judged against the reference
    by the limits the tiny SSM runs are judged by in ``test_faults``."""
    out = run_cell(train_spec(CONFIG, "tiny-ssm", 4), SEED, 0.3, False,
                   jax.devices()[:1], PEAK, time.time())
    assert out["correct"], out["compared"]


def _planes(*ops):
    return {"devices": [{"name": "/device:TPU:0", "ops": list(ops),
                         "modules": []}],
            "spans": [(0.0, 10.0, "bench.window")]}


AR = "all-reduce.1 (all-reduce)"


@pytest.mark.parametrize("ops,want", [
    # hidden: the collective runs wholly under a fusion
    ([(0.0, 4.0, "fusion.1 (fusion)"), (1.0, 3.0, AR)], 0.0),
    # partly exposed: of the collective's 3.0-5.0, 4.0-4.5 runs alone
    ([(0.0, 4.0, "fusion.1 (fusion)"), (3.0, 5.0, AR),
      (4.5, 6.0, "fusion.2 (fusion)")], 100.0 * 0.5 / 10.0),
    # none: no collective at all
    ([(0.0, 4.0, "fusion.1 (fusion)")], 0.0),
])
def test_collective_exposed_pct_reads_the_exposed_share(ops, want):
    reader = load_file_module(os.path.join(BENCH_DIR, "layer_metrics",
                                           "collective_exposed_pct.py"))
    rec = {"trace": trace_reduce.reduce(_planes(*ops)), "tokens": 1}
    assert reader.read(rec) == pytest.approx(want)
    assert reader.read({"trace": None, "tokens": 1}) is None
    assert reader.read({"trace": rec["trace"]}) is None

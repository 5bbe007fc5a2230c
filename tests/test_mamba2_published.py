"""Mamba2 as published (hf:state-spaces/mamba2-1.3b): the float32 residual
stream (``ArchConfig.residual_in_fp32``) at a tiny size, and a training
step of the tied-head model under the planner's tensor-parallel pick on a
2x2 mesh of CPU devices against the same step on one device."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import transformer as T
from repro.models.model import build_model

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(**kw):
    """The reduced Mamba2 in bf16, its head tied, as the published model."""
    return dataclasses.replace(get_config("mamba2-1.3b").reduced(),
                               tie_embeddings=True, **kw)


def _weights(cfg):
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    # a tied head over unit-variance rows gives logits of std ~8; scale the
    # table as a trained model's is, so the softmax is not saturated
    params["embed"] = (params["embed"] * 0.05).astype(params["embed"].dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    return params, tokens


def _plain_forward(cfg, params, tokens, residual_dtype):
    """Embedding, then per layer rmsnorm -> mixer -> residual add in
    ``residual_dtype``, the final norm and the tied head: written out layer
    by layer, without the program's scan."""
    x = params["embed"][tokens].astype(residual_dtype)
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        h = L.rms_norm(x, p["ln"], cfg.norm_eps).astype(cfg.dtype)
        out, _ = M.mamba_block_apply(p["mamba"], h, cfg.ssm)
        x = x + out.astype(residual_dtype)
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.einsum("bsd,vd->bsv", h, params["embed"]).astype(jnp.float32)


def test_float32_residual_is_added_in_float32():
    cfg = _tiny(residual_in_fp32=True)
    params, tokens = _weights(cfg)
    got, _ = jax.jit(build_model(cfg).forward)(params, tokens)
    want = jax.jit(lambda p, t: _plain_forward(cfg, p, t, jnp.float32))(params, tokens)
    bf16 = jax.jit(lambda p, t: _plain_forward(cfg, p, t, jnp.bfloat16))(params, tokens)
    # the same bf16 mixer arithmetic in both: only XLA's fusion differs
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and the stream's dtype shows: a bf16 residual lands elsewhere
    assert float(jnp.max(jnp.abs(want - bf16))) > 100 * float(jnp.max(jnp.abs(got - want)))


def _todays_layer(cfg, p, x, cache=None):
    """``mamba_layer_apply`` as it was before the float32 residual."""
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    out, new_cache = M.mamba_block_apply(p["mamba"], h, cfg.ssm, cache)
    return x + out, new_cache, jnp.zeros((), jnp.float32)


def _todays_head(cfg, params, x):
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,vd->bsv", h, params["embed"]).astype(jnp.float32)


def test_without_the_flag_the_program_is_todays(monkeypatch):
    """Flag off, the forward and the loss's gradient trace to the same
    program as before the flag existed, and give the same bits."""
    cfg = _tiny()
    assert not cfg.residual_in_fp32
    model = build_model(cfg)
    params, tokens = _weights(cfg)

    def run():
        fwd = jax.jit(model.forward)
        grad = jax.jit(jax.grad(lambda p: model.loss(p, {"tokens": tokens})[0]))
        return (str(jax.make_jaxpr(model.forward)(params, tokens)),
                fwd(params, tokens)[0], grad(params))

    jaxpr, logits, grads = run()
    monkeypatch.setattr(T, "mamba_layer_apply", _todays_layer)
    monkeypatch.setattr(T, "_head", _todays_head)
    monkeypatch.setattr(T, "_embed", lambda cfg, params, tokens: jnp.take(
        params["embed"], tokens, axis=0))
    jaxpr0, logits0, grads0 = run()
    assert jaxpr == jaxpr0
    assert np.array_equal(np.asarray(logits), np.asarray(logits0))
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_float32_residual_decode_follows_the_forward():
    """Prefill and one-token steps carry the float32 stream too."""
    cfg = _tiny(residual_in_fp32=True)
    model = build_model(cfg)
    params, tokens = _weights(cfg)
    tokens = tokens[:, :12]
    full, _ = model.forward(params, tokens)
    logits, cache = model.prefill(params, tokens[:, :8], model.init_cache(2, 16))
    steps = [logits]
    for t in range(8, 12):
        logits, cache = model.decode_step(params, tokens[:, t], cache)
        steps.append(logits)
    got = jnp.stack(steps[:-1], axis=1)
    # bf16 mixer arithmetic, chunked in the forward and stepwise in decode
    np.testing.assert_allclose(got, full[:, 7:11], rtol=0.05, atol=0.05)


def test_float32_residual_is_for_the_ssm_family_only():
    with pytest.raises(ValueError, match="residual_in_fp32"):
        dataclasses.replace(get_config("qwen1.5-0.5b"), residual_in_fp32=True)


TP_STEP = r"""
import dataclasses, json
import jax, numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.cluster import local_cluster_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import rank_plans
from repro.runtime.train_loop import Trainer, TrainerConfig

full = dataclasses.replace(get_config("mamba2-1.3b"), tie_embeddings=True,
                           vocab_size=50288, residual_in_fp32=True)
plan = rank_plans(full, ShapeConfig("t", 2048, 8, "train"), local_cluster_config(
    "TPU v5 lite", (2, 2), ("data", "model")))[0].plan
arch = dataclasses.replace(full.reduced(), vocab_size=256, tie_embeddings=True,
                           residual_in_fp32=True, dtype="float32")
shape = ShapeConfig("t", 64, 8, "train")
cc = local_cluster_config("cpu", (2, 2), ("data", "model"))
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 256)
out = {"plan": plan.describe(), "tp": list(plan.tp_axes),
       "microbatches": plan.microbatches}
for n in (4, 1):
    mesh = make_host_mesh(jax.devices()[:n])
    tr = Trainer(arch, shape, cc, mesh, plan=plan,
                 tcfg=TrainerConfig(steps=1, donate=False))
    params, opt, ef = tr.init_state(jax.random.PRNGKey(0))
    params = dict(params, embed=params["embed"] * 0.05)
    with mesh:
        params = jax.device_put(params, tr.param_sh)
        _, opt2, _, m = tr.train_step(
            params, opt, ef, {"tokens": jax.device_put(tokens, tr.batch_sh["tokens"])})
    if n == 4:
        out["sharded"] = sum(not s.is_fully_replicated
                             for s in jax.tree.leaves(tr.param_sh))
    # AdamW's first moment after one step is (1 - b1) x the clipped gradient
    out[str(n)] = {"loss": float(m["loss"]),
                   "grads": [np.asarray(x).ravel().tolist()
                             for x in jax.tree.leaves(opt2.m)]}
print("RESULT=" + json.dumps(out))
"""


def test_tensor_parallel_step_matches_one_device():
    """The planner's pick for the published model on a v5e 2x2 (data x
    tensor parallel, microbatched) at a tiny size on four CPU devices gives
    one device's loss and gradients."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", TP_STEP], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [x for x in res.stdout.splitlines() if x.startswith("RESULT=")]
    assert line, res.stdout[-2000:] + res.stderr[-4000:]
    import json
    out = json.loads(line[0][len("RESULT="):])
    assert out["tp"] == ["model"] and out["microbatches"] > 1, out["plan"]
    assert out["sharded"] > 0
    four, one = out["4"], out["1"]
    # float32 throughout: the shards change only the order of the sums
    assert four["loss"] == pytest.approx(one["loss"], rel=1e-5)
    for a, b in zip(four["grads"], one["grads"]):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-4 * (np.max(np.abs(b)) + 1e-12)

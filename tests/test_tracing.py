"""The program's own tracing (``repro.runtime.tracing``): host spans in the
record and in the profiler's trace, the compile record, the spans of
``Trainer.run`` and ``ServeEngine.step``, and the device scopes of the
model's layers in the lowered training step."""
import dataclasses
import glob
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.planner import ShardingPlan
from repro.runtime import tracing

TRAIN_STEPS = 3
TRAIN_CHILDREN = ["train.input_wait", "train.device_put", "train.dispatch",
                  "train.device_wait", "train.observe"]


def _tiny(arch_id):
    return dataclasses.replace(get_config(arch_id).reduced(), dtype="float32")


def _children(spans, parent):
    return [s for s in spans if s.parent == parent.sid]


def test_no_record_outside_a_profiler_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    t0 = time.perf_counter()
    with tracing.span("test.outer", k=1):
        with tracing.step_span("test.inner", step_num=0):
            pass
    assert tracing.window(t0, time.perf_counter()) == []


def test_nested_spans_parents_self_times_and_clipping(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.perf_counter()
        with tracing.span("test.outer", k=7):
            time.sleep(0.02)
            with tracing.span("test.inner"):
                time.sleep(0.03)
            time.sleep(0.01)
        t1 = time.perf_counter()
    spans = tracing.window(t0, t1)
    assert [s.name for s in spans] == ["test.outer", "test.inner"]
    outer, inner = spans
    assert outer.ids == {"k": 7} and outer.parent is None
    assert inner.parent == outer.sid
    own = tracing.self_times(spans)
    assert own[1] == pytest.approx(inner.end - inner.start)
    assert own[0] == pytest.approx((outer.end - outer.start) - own[1])
    assert own[0] >= 0.03 and own[1] >= 0.03
    mid = 0.5 * (inner.start + inner.end)
    clipped = tracing.window(mid, t1)
    assert [s.start for s in clipped] == [mid, mid]
    assert [s.end for s in clipped] == [outer.end, inner.end]
    assert tracing.window(t1, t1 + 1.0) == []


def test_compile_is_recorded_with_its_function_name():
    def tracing_probe(x):
        return x * 3.0 + 1.0

    t0 = time.perf_counter()
    jax.jit(tracing_probe)(jnp.arange(7.0)).block_until_ready()
    got = [c for c in tracing.compiles(t0) if "tracing_probe" in c.fun_name]
    assert got and all(c.seconds > 0 for c in got)
    assert tracing.cache_hits() >= 0


@pytest.fixture(scope="module")
def traced_training(tmp_path_factory):
    """Three steps of ``Trainer.run`` (tiny dense arch, a checkpoint after
    every step past the first) under one profiler session."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import mesh_cluster
    from repro.optim import adamw
    from repro.runtime.train_loop import Trainer, TrainerConfig

    mesh = make_host_mesh()
    tmp = tmp_path_factory.mktemp("traced_training")
    trainer = Trainer(
        _tiny("qwen1.5-0.5b"), ShapeConfig("tiny", 32, 4, "train"),
        mesh_cluster(mesh), mesh, plan=ShardingPlan(batch_axes=("data",)),
        opt_cfg=adamw.AdamWConfig(total_steps=TRAIN_STEPS),
        tcfg=TrainerConfig(steps=TRAIN_STEPS, log_every=1, checkpoint_every=1,
                           ckpt_dir=str(tmp / "ckpt")))
    with jax.profiler.trace(str(tmp / "trace")):
        t0 = time.perf_counter()
        hist = trainer.run()["history"]
        t1 = time.perf_counter()
    xplane, = glob.glob(str(tmp / "trace" / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    return {"spans": tracing.window(t0, t1), "history": hist, "xplane": xplane}


@pytest.mark.slow
def test_trainer_run_gives_each_step_its_children_in_order(traced_training):
    spans, hist = traced_training["spans"], traced_training["history"]
    steps = [s for s in spans if s.name == "train.step"]
    assert [s.ids["step_num"] for s in steps] == list(range(TRAIN_STEPS))
    assert [h["step"] for h in hist] == list(range(TRAIN_STEPS))
    for s in steps:
        kids = _children(spans, s)
        want = TRAIN_CHILDREN + (["train.checkpoint"] if s.ids["step_num"] else [])
        assert [k.name for k in kids] == want
        assert all(s.start <= k.start <= k.end <= s.end for k in kids)
    # time_s runs from the end of input_wait to the end of device_wait
    for s, h in zip(steps, hist):
        kids = {k.name: k for k in _children(spans, s)}
        inner = kids["train.device_wait"].end - kids["train.input_wait"].end
        assert inner == pytest.approx(h["time_s"], abs=5e-3)


@pytest.mark.slow
def test_trainer_spans_in_the_profile_match_the_record(traced_training):
    pd = jax.profiler.ProfileData.from_file(traced_training["xplane"])
    host = [ev for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    in_trace = [ev for ev in host if ev.name == "repro.train.step"]
    in_record = [s for s in traced_training["spans"] if s.name == "train.step"]
    assert len(in_trace) == len(in_record) == TRAIN_STEPS
    waits = [ev for ev in host if ev.name == "repro.train.device_wait"]
    assert len(waits) == TRAIN_STEPS


@pytest.mark.slow
def test_serve_engine_step_spans(tmp_path):
    from repro.models.model import build_model
    from repro.runtime.serve_engine import EngineConfig, Request, ServeEngine

    model = build_model(_tiny("qwen1.5-0.5b"))
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, EngineConfig(max_len=32))
    reqs = [Request(prompt=[1, 2, 3, 4], max_new_tokens=3),
            Request(prompt=[5, 6], max_new_tokens=2)]
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.perf_counter()
        outs = engine.generate(reqs)
        t1 = time.perf_counter()
    assert [len(c.tokens) for c in outs] == [3, 2]
    spans = tracing.window(t0, t1)
    steps = [s for s in spans if s.name == "serve.step"]
    names = [s.name for s in spans]
    assert names.count("serve.admit") == names.count("serve.prefill_wait") == 1
    admit = next(s for s in spans if s.name == "serve.admit")
    wait = next(s for s in spans if s.name == "serve.prefill_wait")
    assert admit.parent == steps[0].sid and wait.parent == admit.sid
    decodes = engine.stats["decode_steps"]
    assert decodes == 2 and len(steps) == decodes + 1
    for k, s in enumerate(steps):
        kids = [c.name for c in _children(spans, s)]
        assert s.ids == {"step": k}
        want = ["serve.commit"] + (["serve.decode_dispatch", "serve.decode_wait"]
                                   if k < decodes else [])
        assert kids == (["serve.admit"] if k == 0 else []) + want


@pytest.mark.slow
def test_serving_programs_have_stable_names():
    from repro.models.model import build_model
    from repro.runtime.serve_engine import EngineConfig, Request, ServeEngine

    model = build_model(_tiny("qwen1.5-0.5b"))
    engine = ServeEngine(model, model.init(jax.random.PRNGKey(1)),
                         EngineConfig(max_len=16))
    t0 = time.perf_counter()
    engine.generate([Request(prompt=[3, 1, 4], max_new_tokens=2)])
    names = {c.fun_name for c in tracing.compiles(t0)}
    assert {"jit(serve_prefill)", "jit(serve_decode)"} <= names


def _lowered_train_step_text(arch_id):
    from repro.models.model import build_model
    from repro.optim import adamw, compress
    from repro.runtime.train_loop import make_train_step

    model = build_model(_tiny(arch_id))
    opt = adamw.AdamWConfig()
    step = make_train_step(model, opt, ShardingPlan(batch_axes=("data",)))
    params = model.init_shapes()
    opt_state = jax.eval_shape(partial(adamw.init, opt), params)
    ef = compress.EFState(residual=jax.tree.map(
        lambda _: jax.ShapeDtypeStruct((), jnp.float32), params))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    return jax.jit(step).lower(params, opt_state, ef, batch).as_text(
        debug_info=True)


@pytest.mark.slow
@pytest.mark.parametrize("arch_id,scopes", [
    ("qwen1.5-0.5b", ["embed", "attention", "mlp", "ce_head", "optimizer"]),
    ("mamba2-1.3b", ["ssd"]),
    ("deepseek-v3-671b", ["attention", "moe"]),
])
def test_lowered_train_step_names_each_scope(arch_id, scopes):
    text = _lowered_train_step_text(arch_id)
    for scope in scopes:
        # a location reads "<scope>/..." inside a scanned layer,
        # ".../jvp(<scope>)/..." in the forward, "transpose(jvp(<scope>))"
        # in the backward and ".../<scope>/..." in the optimizer
        assert re.search(rf'["/(]{scope}[/)]', text), scope


def test_self_times_of_a_hand_made_record():
    S = tracing.Span
    spans = [S(0, "a", 0.0, 10.0, None, {}), S(1, "b", 1.0, 4.0, 0, {}),
             S(2, "c", 2.0, 3.0, 1, {}), S(3, "d", 5.0, 6.0, 0, {})]
    assert tracing.self_times(spans) == [6.0, 2.0, 1.0, 1.0]
    assert np.isclose(sum(tracing.self_times(spans)), 10.0)

"""The readers of the program's own span record (``train_host_pct``,
``serve_host_step_ms``): known values from a hand-made record, nothing
from an empty record or from a program without the record, and a value
from a traced tiny run of each cell's driver on the CPU."""
import collections
import os
import sys
import time

import jax
import pytest

from common import BENCH_DIR
from harness import load_file_module, run_cell
from repro.runtime import tracing
from test_faults import PEAK, SEED, serve_spec, train_spec

S = tracing.Span


def reader(name):
    return load_file_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"))


TRAIN = [S(0, "train.step", 9.0, 12.0, None, {}),      # clipped to 10-12
         S(1, "train.device_wait", 11.0, 12.0, 0, {}),
         S(2, "train.step", 12.0, 15.0, None, {}),
         S(3, "train.input_wait", 12.0, 12.5, 2, {}),
         S(4, "train.device_wait", 13.0, 15.0, 2, {}),
         S(5, "train.step", 15.0, 21.0, None, {}),      # clipped to 15-20
         S(6, "train.device_wait", 16.0, 19.0, 5, {}),
         S(7, "train.step", 21.0, 22.0, None, {})]      # after the window

SERVE = [S(0, "serve.step", 0.00, 0.10, None, {}),
         S(1, "serve.admit", 0.00, 0.06, 0, {}),
         S(2, "serve.prefill_wait", 0.01, 0.05, 1, {}),
         S(3, "serve.decode_dispatch", 0.06, 0.07, 0, {}),
         S(4, "serve.decode_wait", 0.07, 0.098, 0, {}),   # host 0.032
         S(5, "serve.step", 0.10, 0.13, None, {}),
         S(6, "serve.decode_wait", 0.101, 0.126, 5, {}),  # host 0.005
         S(7, "serve.step", 0.13, 0.14, None, {}),        # no decode: left out
         S(8, "serve.step", 0.14, 0.17, None, {}),
         S(9, "serve.decode_wait", 0.15, 0.16, 8, {})]    # host 0.020


@pytest.mark.parametrize("metric,spans,t_open,t_close,want", [
    ("train_host_pct", TRAIN, 10.0, 20.0, 100.0 * (1 + 1 + 2) / 10),
    ("serve_host_step_ms", SERVE, 0.0, 1.0, 20.0),
])
def test_reader_of_a_hand_made_record(monkeypatch, metric, spans, t_open,
                                      t_close, want):
    monkeypatch.setattr(tracing, "_spans", collections.deque(spans))
    rec = {"t_open": t_open, "t_close": t_close}
    assert reader(metric).read(rec) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["train_host_pct", "serve_host_step_ms"])
def test_reader_of_an_empty_record_or_no_record(monkeypatch, metric):
    rec = {"t_open": 0.0, "t_close": 1.0}
    monkeypatch.setattr(tracing, "_spans", collections.deque())
    assert reader(metric).read(rec) is None
    # a program without the record, as before it was added
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert reader(metric).read(rec) is None


@pytest.mark.parametrize("spec,metric,unit", [
    (lambda: train_spec("tiny_qwen.json", "qwen05b-train-1chip", 2),
     "train_host_pct", "%"),
    (serve_spec, "serve_host_step_ms", "ms"),
])
def test_traced_tiny_run_reports_the_metric(spec, metric, unit):
    s = dict(spec(), per_layer=[{"name": metric, "unit": unit}])
    out = run_cell(s, SEED, 0.3, True, jax.devices()[:1], PEAK, time.time())
    value = out["metrics"][metric]["value"]
    assert value > 0 and (metric != "train_host_pct" or value <= 100)

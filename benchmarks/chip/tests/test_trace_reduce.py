"""The trace reduction on interval arithmetic, on hand-made planes, and on a
small trace recorded on one TPU v5e chip (``data/probe_v5e.xplane.pb``:
three calls of a jitted 4-step scan of 512x512 bf16 matmuls, each under a
``bench.step`` span inside one ``bench.window`` span)."""
import os

import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_subtract():
    assert T.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert T.subtract([(0, 10)], [(1, 2), (1.5, 3), (8, 12)]) == [(0, 1), (3, 8)]
    assert T.total(T.union([(0, 1), (0.5, 2)])) == 2


def test_self_time_of_nested_events():
    got = T.self_times([(0, 10, "while"), (1, 3, "fusion"), (4, 5, "copy"),
                        (11, 12, "copy")])
    assert got == {"while": 7, "fusion": 2, "copy": 2}


def test_op_label():
    assert T.op_label("%copy.39 = bf16[1,32]{4,1:T(8,128)(2,1)} copy(bf16[1,32] %x)") \
        == "copy.39 (copy)"
    assert T.op_label("%while = (s32[]{:T(128)}, bf16[2]{0}) while((s32[]) %t)") \
        == "while (while)"
    assert T.op_label("jit_step(123)") == "jit_step(123)"


def test_reduce_hand_made_planes():
    planes = {
        "devices": [
            {"name": "/device:TPU:0",
             "ops": [(1.0, 2.0, "a"), (2.5, 3.0, "all-reduce.1 (all-reduce)"),
                     (2.6, 2.8, "b")],
             "modules": [(1.0, 3.0, "jit__unknown(1)")]},
            {"name": "/device:TPU:1",
             "ops": [(1.0, 3.0, "a")], "modules": [(1.0, 3.0, "jit__unknown(1)")]},
        ],
        "spans": [(0.0, 4.0, "bench.window"), (2.0, 2.5, "bench.step"),
                  (3.0, 4.0, "bench.tail")],
    }
    r = T.reduce(planes)
    assert r["window_s"] == 4.0
    assert r["busy_s"] == pytest.approx((1.5 + 2.0) / 2)
    # device 0: the collective runs 2.5-3.0, of which 2.6-2.8 overlaps b
    assert r["collective_exposed_s"] == pytest.approx(0.3 / 2)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.window"] == pytest.approx(1.0)
    assert gaps["bench.step"] == pytest.approx(0.5)
    assert gaps["bench.tail"] == pytest.approx(1.0)
    assert T.program_run_n_times(r, 1) == (1.0, 2.0)
    assert T.program_run_n_times(r, 2) == (0.0, 0.0)


def test_reduce_recorded_v5e_trace():
    planes = T.load(os.path.join(DATA, "probe_v5e.xplane.pb"))
    assert [d["name"] for d in planes["devices"]] == ["/device:TPU:0"]
    names = {n for _, _, n in planes["spans"]}
    assert {"bench.window", "bench.step"} <= names
    r = T.reduce(planes)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # the device clock reads about 1.4 ms ahead of the host's here, so the
    # first call's program starts before the 37 ms window span opens
    runs, secs = T.program_run_n_times(r, 2)
    assert runs == 2 and 0 < secs < r["window_s"]
    assert r["collective_exposed_s"] == 0
    assert any(name.startswith("while") for name, _ in r["device_ops"])
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)

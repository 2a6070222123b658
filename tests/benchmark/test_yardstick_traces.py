"""The trace reduction: busy union, idle share, top operations and the
labelling of idle gaps, on hand-made events and on a small trace
recorded on a v5e chip (`fixtures/tpu_small.xplane.pb`: three calls of
a 512 x 512 jitted matmul inside `bench.window`, with `bench.call` and
`bench.wait` host spans)."""

import os

import pytest

from benchmark import traces
from benchmark.traces import Event

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tpu_small.xplane.pb")
DEV = "/device:TPU:0"


def _ev(plane, line, name, s, e):
    return Event(plane, line, name, float(s), float(e - s))


def test_busy_union_idle_gaps_and_top_ops():
    events = [
        _ev("/host:CPU", "python", "bench.window", 0, 100),
        _ev("/host:CPU", "python", "bench.call", 5, 45),
        _ev("/host:CPU", "python", "bench.wait", 45, 100),
        _ev("/host:CPU", "python", "jit_unrelated", 0, 100),
        _ev(DEV, "XLA Ops", "a", 10, 30),
        _ev(DEV, "XLA Ops", "b", 20, 40),   # overlaps a: counted once
        _ev(DEV, "XLA Ops", "a", 60, 70),
        _ev(DEV, "XLA Ops", "c", 95, 120),  # clipped at the window's end
        _ev(DEV, "XLA Ops", "d", -20, -10),  # before the window
        _ev(DEV, "XLA Modules", "jit_f(123)", 0, 50),  # not an op line
    ]
    out = traces.reduce(events)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(45e-9)  # [10,40] [60,70] [95,100]
    # ops inside a program's module event are named "<program>/<op>"
    assert out["device_ops"][0] == ["jit_f/a", pytest.approx(20e-9)]
    assert [n for n, _ in out["device_ops"]] == ["jit_f/a", "jit_f/b", "a",
                                                 "c"]
    gaps = dict(out["idle_gaps"])
    # gaps [0,10] (mid 5: bench.call), [40,60] and [70,95] (bench.wait)
    assert gaps == {"bench.call": pytest.approx(10e-9),
                    "bench.wait": pytest.approx(45e-9)}


def test_no_window_or_no_device_op_reads_nothing():
    assert traces.reduce([_ev(DEV, "XLA Ops", "a", 0, 1)]) is None
    assert traces.reduce([_ev("/host:CPU", "t", "bench.window", 0, 9)]) is None


def test_recorded_v5e_trace():
    out = traces.reduce(traces.load(FIXTURE))
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"] and all(t > 0 for _, t in out["device_ops"])
    assert sum(t for _, t in out["device_ops"]) >= out["busy_s"] * 0.999
    assert all(n.startswith("jit__lambda/%") for n, _ in out["device_ops"])
    labels = dict(out["idle_gaps"])
    assert "bench.wait" in labels
    idle = sum(labels.values())
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)

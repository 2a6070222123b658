"""Device self time by stage scope, the program's host spans, and the
readers of the per-layer metrics built on them: on hand-made events, and
on `fixtures/scoped_small.xplane.pb`, recorded on a v5e chip by
`fixtures/make_scoped_fixture.py` (three calls of one program inside
`bench.window`: a matmul under `fixture.matmul`, a `while` whose body
is under `fixture.loop`, and an unscoped add)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import scopes, spec, traces
from benchmark.traces import Event
from raft_tpu import obs

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixtures", "scoped_small.xplane.pb")
SMALL = os.path.join(HERE, "fixtures", "tpu_small.xplane.pb")
FIXTURE_STAGES = ("fixture.matmul", "fixture.loop")
DEV = "/device:TPU:0"


def _ev(plane, line, name, s, e):
    return Event(plane, line, name, float(s), float(e - s))


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(f)/ivf_pq.scan/while/body/closed_call/ivf_pq.trim/approx_top_k:",
     "ivf_pq.trim"),
    ("jit(f)/ivf_pq.scan/while/body/closed_call/gather:", "ivf_pq.scan"),
    ("jit(_refine_impl)/refine/while/body/closed_call/gather:", "refine"),
    ("jit(f)/dot_general:", scopes.UNSCOPED),
    ("dataset:", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_innermost_stage_scope_wins(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_self_time_leaves_out_nested_ops():
    # a while [0, 100] holding two ops, one holding another; then an op
    # after it, and one touching the while's end (not nested)
    spans = [(0, 100), (10, 40), (15, 25), (50, 90), (100, 120)]
    assert scopes._self_times(spans) == [30, 20, 10, 40, 20]


def test_device_scopes_on_hand_made_events():
    events = [
        _ev("/host:CPU", "python", "bench.window", 0, 100),
        _ev(DEV, "XLA Modules", "jit_f(1)", 0, 100),
        _ev(DEV, "XLA Ops", "%while = w", 10, 60),
        _ev(DEV, "XLA Ops", "%fusion.1 = a", 12, 30),
        _ev(DEV, "XLA Ops", "%fusion.2 = b", 30, 58),
        _ev(DEV, "XLA Ops", "%copy = c", 90, 130),  # clipped at 100
    ]
    md = {DEV: {"%fusion.1 = a": "jit(f)/ivf_pq.scan/while/body/dot:",
                "%fusion.2 = b": "jit(f)/ivf_pq.scan/while/body/"
                                 "ivf_pq.trim/approx_top_k:",
                "%while = w": "jit(f)/ivf_pq.scan/while:"}}
    ops = {}
    out = scopes.device_scopes(events, md, traces._window(events), ops=ops)
    # scan: fusion.1's 18 ns and the while's own 4 (50 less 18 and 28)
    assert out == {"jit_f": {"ivf_pq.scan": pytest.approx(22e-9),
                             "ivf_pq.trim": pytest.approx(28e-9),
                             "unscoped": pytest.approx(10e-9)}}
    assert ops["jit_f/%copy"] == ["unscoped", "", "%copy = c", 10.0]
    assert ops["jit_f/%while"][:2] == ["ivf_pq.scan", md[DEV]["%while = w"]]
    assert scopes.totals({"a": {"x": 1.0}, "b": {"x": 2.0, "y": 1.0}}) == {
        "x": 3.0, "y": 1.0}


def test_compiler_made_op_takes_its_operands_scope():
    events = [
        _ev("/host:CPU", "python", "bench.window", 0, 100),
        _ev(DEV, "XLA Modules", "jit_f(1)", 0, 100),
        _ev(DEV, "XLA Ops", "%pad.1 = f32[8]{0} pad(f32[6]{0} %p)", 0, 10),
        _ev(DEV, "XLA Ops", "%copy.2 = f32[8]{0:T(8)} copy(f32[8]{0} %p, "
            "f32[8]{0} %pad.1), calls=%fused_merge", 10, 30),
        _ev(DEV, "XLA Ops", "%copy.3 = f32[8]{0} copy(f32[8]{0} %p)", 30, 35),
        _ev(DEV, "XLA Ops", "%copy.4 = f32[8]{0} copy(f32[8]{0} %p)", 35, 45),
        _ev(DEV, "XLA Ops", "%top.5 = f32[8]{0} sort(f32[8]{0} %copy.4)",
            45, 50),
    ]
    md = {DEV: {events[2].name: "jit(f)/brute_force.distance/pad:",
                events[3].name: "",
                events[6].name: "jit(f)/brute_force.select/sort:",
                "%fused_merge = f32[1]{0} fusion()":
                    "jit(f)/brute_force.select/add:"}}
    # copy.2 moves pad.1's value; copy.4 feeds the select's sort; copy.3
    # moves a parameter nothing scoped reads
    assert scopes.device_scopes(events, md, (0, 100)) == {"jit_f": {
        "brute_force.distance": pytest.approx(30e-9),
        "brute_force.select": pytest.approx(15e-9),
        "unscoped": pytest.approx(5e-9)}}


def test_host_spans_inside_the_window():
    events = [
        _ev("/host:CPU", "python", "bench.window", 100, 200),
        _ev("/host:CPU", "worker", "raft_tpu.serve.take", 90, 110),  # clipped
        _ev("/host:CPU", "worker", "raft_tpu.serve.take", 150, 160),
        _ev("/host:CPU", "worker", "raft_tpu.serve.take", 300, 310),  # after
        _ev("/host:CPU", "worker", "raft_tpu.gc", 120, 120),  # zero length
        _ev("/host:CPU", "python", "bench.wait", 100, 200),  # not a span
        _ev(DEV, "XLA Ops", "raft_tpu.odd", 100, 200),  # not a host event
    ]
    assert scopes.host_spans(events, (100, 200)) == {
        "gc": (1, 0.0), "serve.take": (2, pytest.approx(20e-9))}


def test_python_under_a_span_on_a_cpu_trace(tmp_path):
    import glob
    import threading
    import time

    import jax

    def step():
        time.sleep(0.002)

    def worker():
        with jax.profiler.TraceAnnotation("raft_tpu.serve.scatter"):
            for _ in range(3):
                step()
        step()  # outside the span

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    window = traces._window(traces.load(path))
    out = scopes.python_under(path, window, "raft_tpu.serve.scatter")
    assert out["seconds"] >= 0.006 and out["python_s"] <= out["seconds"]
    top = dict(out["top"])
    assert top["$time sleep"] == pytest.approx(out["seconds"], rel=0.25)
    assert scopes.python_under(path, window, "raft_tpu.none") is None


def test_metadata_reader_on_recorded_trace():
    md = scopes.tf_ops(FIXTURE)
    ops = md[DEV]
    by_tf = {v: k.split(" = ")[0] for k, v in ops.items()}
    assert by_tf["jit(program)/fixture.matmul/dot_general:"] == "%fusion"
    assert (by_tf["jit(program)/while/body/fixture.loop/dot_general:"]
            == "%convolution_tanh_fusion.2")
    assert by_tf["jit(program)/add:"] == "%broadcast_add_fusion"
    # the tpu_small trace's one scoped op, read the same way
    (small,) = scopes.tf_ops(SMALL)[DEV].values()
    assert small == "jit(<lambda>)/dot_general:"


def test_recorded_trace_by_stage_scope():
    events = traces.load(FIXTURE)
    window = traces._window(events)
    ops = {}
    out = scopes.device_scopes(events, scopes.tf_ops(FIXTURE), window,
                               FIXTURE_STAGES, ops=ops)
    # the matmul's 564 ns and the 6 ns copy-done that moves its input in
    assert out == {"jit_program": {
        "fixture.loop": pytest.approx(2.289e-06),
        "fixture.matmul": pytest.approx(5.70e-07),
        "unscoped": pytest.approx(1.718e-06)}}
    assert ops["jit_program/%copy-done"][:2] == ["fixture.matmul", ""]
    # one line, ops nested or in sequence: self times add up to busy
    busy = traces.reduce(events)["busy_s"]
    assert sum(out["jit_program"].values()) == pytest.approx(busy)
    # the while keeps only its own time: 87 ns of its 4.1 us, the rest
    # is its body's; the unscoped add and the loop's copies remain
    whole = sum(e.dur_ns for e in events if e.name.startswith("%while"))
    assert ops["jit_program/%while"][0] == scopes.UNSCOPED
    assert ops["jit_program/%while"][3] == pytest.approx(87.0)
    assert whole > 40 * 87.0
    assert ops["jit_program/%broadcast_add_fusion"][1:2] == [
        "jit(program)/add:"]
    assert ops["jit_program/%broadcast_add_fusion"][3] == pytest.approx(1246.0)


def test_reduce_on_recorded_traces():
    out = scopes.reduce(FIXTURE)
    # no stage of the library's programs in the fixture: all unscoped
    assert out["scopes"] == {"unscoped": pytest.approx(4.577e-06)}
    assert out["spans"] == {}
    assert out["unscoped_ops"][0][0] == (
        "jit_program/%convolution_tanh_fusion.2")
    assert out["unscoped_ops"][0][1].startswith(
        "%convolution_tanh_fusion.2 = f32[256,256]")
    assert out["top_ops"][0][:2] == [
        "jit_program/%convolution_tanh_fusion.2", scopes.UNSCOPED]
    small = scopes.reduce(SMALL)
    assert small["scopes"]["unscoped"] == pytest.approx(
        traces.reduce(traces.load(SMALL))["busy_s"])
    assert scopes.reduce(FIXTURE, [_ev(DEV, "XLA Ops", "a", 0, 1)]) is None


# -- the per-layer readers -------------------------------------------------

def _run(**fields):
    base = dict(calls=[10000] * 4, trace={"busy_s": 1.0, "window_s": 1.2})
    base.update(fields)
    return SimpleNamespace(**base)


SCOPED = {"ivf_pq.scan": 0.8, "ivf_pq.trim": 2.0, "ivf_pq.merge": 0.4,
          "brute_force.select": 1.2, "unscoped": 0.01}
SERVE = {"serve.take": (4, 0.004), "serve.merge": (4, 0.008),
         "serve.dispatch": (4, 0.02), "serve.scatter": (4, 0.04),
         "serve.maintain": (5, 0.001), "serve.batch": (4, 0.9),
         "gc": (2, 0.05)}


@pytest.mark.parametrize("name, run, want", [
    ("ivf_pq_search.scan_ms",
     _run(trace={"busy_s": 1.0, "scopes": SCOPED}), 200.0),
    ("ivf_pq_search.select_ms",
     _run(trace={"busy_s": 1.0, "scopes": SCOPED}), 600.0),
    ("brute_force.select_ms",
     _run(trace={"busy_s": 1.0, "scopes": SCOPED}), 300.0),
    ("serve.host_ms_per_batch", _run(spans=SERVE), 18.25),
    ("host.gc_ms", _run(spans=SERVE), 50.0),
    ("host.gc_ms", _run(spans={"serve.batch": (4, 0.9)}), 0.0),
    ("ivf_pq_build.kmeans_s",
     _run(setup_spans={"ivf_pq.build.coarse": (1, 6.5)}), 6.5),
])
def test_reader_on_hand_made_run(name, run, want):
    got = spec.module(REPO, "layers", name).read(run)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "ivf_pq_search.scan_ms", "ivf_pq_search.select_ms",
    "brute_force.select_ms", "serve.host_ms_per_batch", "host.gc_ms",
    "ivf_pq_build.kmeans_s"])
@pytest.mark.parametrize("run", [
    _run(),  # a parent's traced run: no scopes, spans or set-up spans
    _run(trace=None, calls=[]),
    _run(trace={"busy_s": 1.0, "scopes": {"unscoped": 1.0}}, spans=None,
         setup_spans={}),
], ids=["no-scopes", "no-trace", "unscoped-only"])
def test_reader_reads_nothing_without_its_input(name, run):
    assert spec.module(REPO, "layers", name).read(run) is None


@pytest.mark.parametrize("traffic, spans, read", [
    ("tiny-batch", "neighbors.ivf_pq.search", "ivf_pq_build.kmeans_s"),
    ("tiny-open", "serve.batch", "serve.host_ms_per_batch"),
])
def test_scoped_run_end_to_end(tiny_root, monkeypatch, capsys, traffic,
                               spans, read):
    """`benchmark.scoped` at a CPU size, the look for a chip skipped; a
    CPU trace holds no device line, so the trace's summary is stood in."""
    from benchmark import run, scoped

    root = tiny_root([{"name": "t.cell", "config": "tiny-ivfpq",
                       "traffic": traffic}])
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "preflight", lambda chips: None)
    monkeypatch.setattr(spec, "peaks", lambda root, kind: {})
    monkeypatch.setattr(traces, "reduce", lambda events: {
        "busy_s": 1.0, "window_s": 1.0, "devices": 1, "device_ops": [],
        "idle_gaps": []})
    obs.reset()
    try:
        assert scoped.main(["--workload", "t.cell", "--seed",
                            str(2**31 + 7), "--seconds", "1"]) == 0
    finally:
        obs.disable()
        obs.reset()
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert out["correct"] and spans in out["spans"]
    assert out["metrics"][read] > 0
    assert set(out["setup_spans"]) >= {"neighbors.ivf_pq.build",
                                       "ivf_pq.build.coarse",
                                       "ivf_pq.build.codebook"}

"""Observability subsystem tests.

The load-bearing contract here is DETERMINISM: the same seeded chaos
drill must produce the same span/event snapshot (modulo clock fields),
because that is what makes obs snapshots assertable in CI and
comparable across runs in an incident. The drill test below runs a full
scenario twice — comms collectives with an injected drop, serving with
warmup + compile-cache hits + an injected slow batch and a flaky
submit, host corruption, rank-health transitions — and pins the exact
event sequence, collective byte counts, and compile-cache hits.
"""

import json
import threading

import numpy as np
import jax.numpy as jnp
import pytest

import importlib

from raft_tpu import obs

# the core package re-binds the attribute `logger` to the Logger object,
# shadowing the module for attribute-based import forms
logger_mod = importlib.import_module("raft_tpu.core.logger")
from raft_tpu.core import faults, tracing
from raft_tpu.obs import report as obs_report
from raft_tpu.obs.registry import Registry


@pytest.fixture
def obs_on():
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_instruments():
    reg = Registry()
    c = reg.counter("a.calls")
    c.inc()
    c.inc(4)
    assert reg.counter("a.calls") is c  # get-or-create is idempotent
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3)
    g.add(2.5)
    assert g.value == 5.5
    h = reg.histogram("lat")
    for v in (2.0, 1.0, 4.0):
        h.observe(v)
    agg = h.aggregate()
    assert agg == {"count": 3, "total": 7.0, "min": 1.0, "max": 4.0,
                   "mean": 7.0 / 3, "last": 4.0}
    # one name, one instrument kind
    with pytest.raises(ValueError):
        reg.gauge("a.calls")


def test_registry_snapshot_deterministic_and_reset():
    reg = Registry()
    reg.counter("z").inc(1)
    reg.counter("a").inc(2)
    reg.gauge("m").set(7)
    snap = reg.snapshot()
    assert list(snap["counters"]) == ["a", "z"]  # sorted
    assert snap["counters"] == {"a": 2, "z": 1}
    reg.reset()
    snap2 = reg.snapshot()
    assert snap2["counters"] == {"a": 0, "z": 0}  # values zeroed, names kept
    assert snap2["gauges"]["m"] == 0.0


def test_registry_collector_sections():
    reg = Registry()
    reg.add_collector("svc", lambda: {"x": 1})
    reg.add_collector("bad", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["collectors"]["svc"] == {"x": 1}
    assert "error" in snap["collectors"]["bad"]  # failure never raises
    reg.remove_collector("bad")
    assert "bad" not in reg.snapshot().get("collectors", {})


def test_registry_thread_safety():
    reg = Registry()
    c = reg.counter("n")

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


# ---------------------------------------------------------------------------
# bus
# ---------------------------------------------------------------------------

def test_bus_ordering_ring_and_subscribers():
    from raft_tpu.obs.bus import EventBus

    bus = EventBus(maxlen=4)
    seen = []
    bus.subscribe(seen.append)
    bus.subscribe(lambda e: 1 / 0)  # broken subscriber must not poison
    for i in range(6):
        bus.publish("k", i=i)
    evs = bus.events()
    assert [e["seq"] for e in evs] == [3, 4, 5, 6]  # ring kept the tail
    assert len(seen) == 6  # subscribers saw everything, in order
    assert [e["i"] for e in seen] == list(range(6))
    assert bus.events(kind="nope") == []
    bus.clear()
    assert len(bus) == 0
    assert bus.publish("k") == 1  # sequence restarted


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_aggregation(obs_on):
    with obs.span("outer"):
        with obs.span("inner", k=7) as sp:
            sp.set(extra="x")
    evs = obs.bus().events(kind="span")
    # close order: inner first, then outer
    assert [(e["name"], e["depth"], e["parent"]) for e in evs] == [
        ("inner", 1, "outer"), ("outer", 0, None)]
    assert evs[0]["k"] == 7 and evs[0]["extra"] == "x"
    assert evs[0]["dur_s"] >= 0.0
    agg = obs.registry().snapshot()["histograms"]["span.outer"]
    assert agg["count"] == 1


def test_span_capture_totals(obs_on):
    with obs.capture_spans() as cap:
        for _ in range(3):
            with obs.span("phase.a"):
                pass
        with obs.span("phase.b"):
            pass
    with obs.span("phase.a"):  # outside the capture window
        pass
    totals = cap.totals()
    assert totals["phase.a"]["calls"] == 3
    assert totals["phase.b"]["calls"] == 1
    assert set(totals) == {"phase.a", "phase.b"}


def test_disabled_is_inert():
    obs.disable()
    obs.reset()
    with obs.span("nope") as sp:
        sp.set(a=1)
    obs.event("fault", site="x")
    obs.collective("allreduce", np.zeros((4,), np.float32))
    assert obs.bus().events() == []
    snap = obs.registry().snapshot()
    # reset() keeps instrument definitions from earlier tests; disabled
    # hooks must not have moved any of them off zero
    assert all(v == 0 for v in snap["counters"].values())
    assert all(agg["count"] == 0 for agg in snap["histograms"].values())


def test_span_decorator_and_current_span(obs_on):
    @obs.spanned("deco.fn", tag=1)
    def fn():
        assert obs.current_span().name == "deco.fn"
        return 42

    assert fn() == 42
    assert obs.current_span() is None
    ev = obs.bus().events(kind="span")[-1]
    assert ev["name"] == "deco.fn" and ev["tag"] == 1


# ---------------------------------------------------------------------------
# tracing satellites
# ---------------------------------------------------------------------------

def test_trace_range_disabled_accepts_kwargs():
    tracing.enable(False)
    try:
        with tracing.trace_range("x", foo=1):  # must not TypeError
            pass

        @tracing.annotate("y", foo=2)
        def f():
            return 7

        assert f() == 7
    finally:
        tracing.enable(True)


def test_obs_reexports_tracing():
    assert obs.trace_range is tracing.trace_range
    assert obs.annotate is tracing.annotate


# ---------------------------------------------------------------------------
# logger bridge
# ---------------------------------------------------------------------------

def test_logger_routes_to_bus_when_enabled(obs_on):
    logger_mod.set_level(logger_mod.RAFT_LEVEL_INFO)
    try:
        logger_mod.logger.info("bridged %d", 1)
        evs = obs.bus().events(kind="log")
        assert len(evs) == 1
        assert evs[0]["msg"] == "bridged 1" and evs[0]["level"] == "INFO"
        obs.disable()
        logger_mod.logger.info("not bridged")
        assert len(obs.bus().events(kind="log")) == 1  # handler removed
    finally:
        logger_mod.set_level(logger_mod.RAFT_LEVEL_WARN)


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def test_collective_accounting_exact(obs_on):
    from raft_tpu.comms.comms import Comms, op_t

    comms = Comms()
    x = np.arange(32, dtype=np.float32).reshape(8, 4)

    def prog(ac, xs):
        return ac.allreduce(jnp.sum(xs, axis=0))[None, :]

    out = comms.run(prog, x)  # (8, 4): one replicated result row per rank
    np.testing.assert_allclose(np.asarray(out), np.tile(x.sum(axis=0), (8, 1)))
    counters = obs.registry().snapshot()["counters"]
    # one allreduce traced; per-rank payload is the (4,) f32 row sum
    assert counters["comms.allreduce.calls"] == 1
    assert counters["comms.allreduce.bytes"] == 16
    evs = obs.bus().events(kind="collective")
    assert [(e["op"], e["bytes"]) for e in evs] == [("allreduce", 16)]


def test_barrier_counts_itself_and_its_allreduce(obs_on):
    from raft_tpu.comms.comms import Comms

    comms = Comms()

    def prog(ac, xs):
        return jnp.reshape(ac.barrier(jnp.sum(xs)), (1,))

    comms.run(prog, np.ones(8, np.float32))
    counters = obs.registry().snapshot()["counters"]
    assert counters["comms.barrier.calls"] == 1
    assert counters["comms.allreduce.calls"] == 1  # delegation layer


# ---------------------------------------------------------------------------
# exporters + report
# ---------------------------------------------------------------------------

_PROM_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
# exposition series: bare `name value`, or `name{le="<bound>"} value`
# (the histogram bucket label the exporter emits)
_PROM_LINE = _PROM_NAME + r'(?:\{le="(?:[0-9.e+-]+|\+Inf)"\})? (\S+)'


def _assert_prometheus(text: str):
    import re

    lines = text.strip().split("\n")
    assert lines
    for line in lines:
        m = re.fullmatch(_PROM_LINE, line)
        assert m, f"not exposition format: {line!r}"
        float(m.group(1))  # value must parse as a float (nan/inf ok)


def test_render_prometheus_format(obs_on):
    obs.counter("a.b.calls").inc(3)
    obs.gauge("depth").set(1.5)
    obs.histogram("span.x").observe(0.25)
    text = obs.render_registry_prometheus()
    _assert_prometheus(text)
    assert "raft_tpu_a_b_calls 3" in text.split("\n")
    assert "raft_tpu_span_x_count 1" in text.split("\n")
    # None aggregates (empty histogram min/max) are skipped, not "None"
    obs.histogram("span.empty")
    assert "None" not in obs.render_registry_prometheus()


def test_render_prometheus_histogram_buckets(obs_on):
    """Real `le`-bucketed exposition: cumulative _bucket series with a
    +Inf terminal equal to _count, plus the _sum series — not just
    aggregate gauges."""
    h = obs.histogram("span.x")
    for v in (0.004, 0.004, 0.3, 99.0):
        h.observe(v)
    lines = obs.render_registry_prometheus().split("\n")
    _assert_prometheus("\n".join(l for l in lines if l))
    assert 'raft_tpu_span_x_bucket{le="0.005"} 2' in lines
    assert 'raft_tpu_span_x_bucket{le="0.5"} 3' in lines
    assert 'raft_tpu_span_x_bucket{le="10"} 3' in lines  # 99.0 only in +Inf
    assert 'raft_tpu_span_x_bucket{le="+Inf"} 4' in lines
    assert "raft_tpu_span_x_count 4" in lines
    assert any(l.startswith("raft_tpu_span_x_sum ") for l in lines)
    # cumulative counts must be monotone in bound order
    counts = [h.bucket_counts()]
    for seq in counts:
        vals = [n for _, n in seq]
        assert vals == sorted(vals) and vals[-1] == 4


def test_snapshot_save_and_report_cli(obs_on, tmp_path, capsys):
    obs.counter("comms.allreduce.calls").inc(2)
    obs.counter("comms.allreduce.bytes").inc(4096)
    obs.counter("serve.compile_cache.hit").inc(5)
    obs.counter("serve.compile_cache.miss").inc(1)
    with obs.span("neighbors.ivf_flat.search"):
        pass
    obs.event("fault", site="serve.batch", action="slow")
    path = tmp_path / "snap.json"
    snap = obs.save_snapshot(str(path))
    assert json.loads(path.read_text())["metrics"]["counters"] == \
        snap["metrics"]["counters"]
    rc = obs_report.main([str(path), "--title", "drill"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "# drill" in out
    assert "allreduce" in out and "4.0 KiB" in out
    assert "neighbors.ivf_flat.search" in out
    assert "bucket-program hits: 5/6" in out
    assert "serve.batch" in out  # fault timeline


def test_server_metrics_joins_global_snapshot(obs_on):
    from raft_tpu.serve.metrics import ServerMetrics

    m = ServerMetrics(latency_window=8)
    m.observe_submit()
    sections = obs.snapshot()["metrics"]["collectors"]
    mine = [v for k, v in sections.items() if k.startswith("serve#")]
    assert any(sec.get("submitted") == 1 for sec in mine)


# ---------------------------------------------------------------------------
# the chaos-drill determinism contract (ISSUE 3 acceptance)
# ---------------------------------------------------------------------------

def _normalize(snap: dict) -> dict:
    """Strip clock-derived fields; keep everything a replay must pin."""
    events = [
        {k: v for k, v in e.items() if k not in ("t", "dur_s", "marks")}
        for e in snap["events"]
    ]
    hist_counts = {name: agg["count"]
                   for name, agg in snap["metrics"]["histograms"].items()}
    return {
        "counters": snap["metrics"]["counters"],
        "events": events,
        "hist_counts": hist_counts,
    }


def _chaos_drill():
    """One full instrumented scenario; returns the normalized snapshot.

    Built exclusively from per-call-traced programs (`comms.run`
    re-traces; the serve bucket ladder is warmed explicitly) so a
    second run of the same seeded plan reproduces the event sequence
    bit-for-bit.
    """
    from raft_tpu.comms.comms import Comms
    from raft_tpu.comms.resilience import RankHealth
    from raft_tpu.serve.engine import SearchServer, ServerConfig

    obs.reset()
    plan = faults.FaultPlan([
        faults.Fault("drop_collective", site="comms.allreduce", rank=3),
        faults.Fault("slow_rank", site="serve.batch", latency_s=0.002),
        faults.Fault("flaky_bootstrap", site="serve.submit", count=1),
        faults.Fault("corrupt_shard", site="batch_loader.load", rank=-1,
                     fraction=0.5),
    ], seed=77)

    comms = Comms()
    x = np.ones((8, 4), np.float32)

    def prog(ac, xs):
        return ac.allreduce(jnp.sum(xs, axis=0))

    # healthy collective, then the same program under the chaos plan
    # (the drop event lands at trace time, the jaxpr changes)
    comms.run(prog, x, out_specs=None)
    with plan.install():
        comms.run(prog, x, out_specs=None)

        # host-side corruption: seeded, so the cell count replays
        block = np.ones((16, 4), np.float32)
        faults.corrupt_host("batch_loader.load", block)

        # rank-health transitions (duplicate marks emit no event)
        health = RankHealth.all_healthy(8)
        health.mark_unhealthy(3)
        health.mark_unhealthy(3)
        health.mark_healthy(3)

        # serving: warmup compiles both buckets, then three batches —
        # two compile-cache hits and one miss (new k)
        rng = np.random.default_rng(0)
        server = SearchServer(
            rng.standard_normal((64, 16)).astype(np.float32),
            ServerConfig(buckets=(4, 8), max_wait_ms=0.0),
        )
        server.warmup(3)
        with pytest.raises(faults.FaultInjected):
            server.submit(rng.standard_normal((2, 16)).astype(np.float32), k=3)
        server.submit(rng.standard_normal((2, 16)).astype(np.float32), k=3)
        server.step()
        server.submit(rng.standard_normal((6, 16)).astype(np.float32), k=3)
        server.step()
        server.submit(rng.standard_normal((2, 16)).astype(np.float32), k=5)
        server.step()
    return _normalize(obs.snapshot())


def test_chaos_drill_snapshot_exact(obs_on):
    snap = _chaos_drill()

    # -- collective accounting: 2 traced allreduces, (4,) f32 payloads
    assert snap["counters"]["comms.allreduce.calls"] == 2
    assert snap["counters"]["comms.allreduce.bytes"] == 32

    # -- compile cache: warmup seeds (4,3) and (8,3); k=3 batches hit,
    #    the k=5 batch misses
    assert snap["counters"]["serve.compile_cache.hit"] == 2
    assert snap["counters"]["serve.compile_cache.miss"] == 1
    assert snap["hist_counts"]["serve.warmup_compile_s"] == 2

    # -- fault timeline, in order
    fault_evs = [e for e in snap["events"] if e["kind"] == "fault"]
    assert [(e["site"], e["action"]) for e in fault_evs] == [
        ("comms.allreduce", "drop"),
        ("batch_loader.load", "corrupt_host"),
        ("serve.submit", "flaky"),
        ("serve.batch", "slow"),
        ("serve.batch", "slow"),
        ("serve.batch", "slow"),
    ]
    corrupt = fault_evs[1]
    assert corrupt["cells"] == 25  # seeded draw: fixed forever by seed=77

    # -- health transitions: only real flips, in order
    health_evs = [e for e in snap["events"] if e["kind"] == "health"]
    assert [(e["rank"], e["healthy"]) for e in health_evs] == [
        (3, False), (3, True)]

    # -- compile events: two warmups then hit/hit/miss
    compile_evs = [e for e in snap["events"] if e["kind"] == "compile"]
    assert [(e["phase"], e["bucket"], e["k"], e.get("cached"))
            for e in compile_evs] == [
        ("warmup", 4, 3, None), ("warmup", 8, 3, None),
        ("serve", 4, 3, True), ("serve", 8, 3, True),
        ("serve", 4, 5, False),
    ]

    # -- spans: the serving path nests under serve.batch's dispatch
    span_evs = [e for e in snap["events"] if e["kind"] == "span"]
    serve_batches = [e for e in span_evs if e["name"] == "serve.batch"]
    assert len(serve_batches) == 3
    dispatches = [e for e in span_evs if e["name"] == "serve.dispatch"]
    assert [e["parent"] for e in dispatches] == ["serve.batch"] * 3
    knn_spans = [e for e in span_evs
                 if e["name"] == "neighbors.brute_force.knn"]
    assert len(knn_spans) == 5  # 2 warmup + 3 batches
    assert {e["parent"] for e in knn_spans} == {"serve.warmup",
                                                "serve.dispatch"}


@pytest.mark.parametrize("runs", [2])
def test_chaos_drill_replays_identically(obs_on, runs):
    snaps = [_chaos_drill() for _ in range(runs)]
    assert snaps[0] == snaps[1]

"""Request-scope tracing, flight recorder, and SLO watchtower suite
(ISSUE 18): exact deterministic trace pins over a step-mode serve run,
telescoping stage accounting against measured e2e latency, flight-ring eviction and
atomic dumps (including a real SIGKILLed watchdog child), the two new
chaos sites (`obs.flight.dump`, `serve.trace.stamp`) with their
degrade-not-die contracts, and multi-window burn-rate math (fast trips
before slow; recover hysteresis)."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from raft_tpu import obs, serve
from raft_tpu.core import faults
from raft_tpu.jobs.watchdog import StageTimeout, run_supervised
from raft_tpu.obs import flight, slo, trace

SEED = int(os.environ.get(faults.ENV_SEED, "1234"))


@pytest.fixture
def obs_on():
    obs.reset()
    trace.reset(seed=0)  # a prior test may have re-seeded the mint
    obs.enable()
    yield
    flight.uninstall()
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(0)
    return rng.standard_normal((64, 16)).astype(np.float32)


def _normalize_trace(e: dict) -> dict:
    """Strip clock-derived fields; keep everything a replay must pin."""
    return {k: v for k, v in e.items() if k not in ("seq", "t", "marks")}


def _serve_three_batches(dataset):
    """Three single-request batches in step mode (deterministic worker
    thread = MainThread); returns the bus trace events."""
    rng = np.random.default_rng(1)
    server = serve.SearchServer(
        dataset, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    for _ in range(3):
        fut = server.submit(rng.standard_normal((2, 16)).astype(np.float32),
                            k=3)
        assert server.step() == 1
        assert fut.result(timeout=1.0).ids.shape == (2, 3)
    return [e for e in obs.snapshot()["events"] if e["kind"] == "trace"]


# ---------------------------------------------------------------------------
# trace ids: pure, seeded, pinned
# ---------------------------------------------------------------------------

def test_trace_id_pure_and_pinned():
    # splitmix64 of (seed 0, n 1..3): fixed forever — a replayed drill
    # must mint these exact ids
    assert trace.trace_id(0, 1) == 10451216379200822465
    assert trace.trace_id(0, 2) == trace.trace_id(0, 2)
    assert len({trace.trace_id(0, n) for n in range(1, 100)}) == 99
    assert len({trace.trace_id(s, 1) for s in range(100)}) == 100
    for s, n in ((0, 1), (7, 3), (2**63, 12)):
        assert 0 <= trace.trace_id(s, n) < 2**64


def test_mint_matches_pure_function_and_resets(obs_on):
    got = [trace.begin().trace_id for _ in range(3)]
    assert got == [trace.trace_id(0, n) for n in (1, 2, 3)]
    trace.reset(seed=5)
    assert trace.begin().trace_id == trace.trace_id(5, 1)
    obs.reset()  # resets the count, keeps the seed
    assert trace.begin().trace_id == trace.trace_id(5, 1)


def test_begin_returns_none_when_disabled():
    obs.reset()
    assert trace.begin() is None


# ---------------------------------------------------------------------------
# exact deterministic trace pin (ISSUE 18 acceptance)
# ---------------------------------------------------------------------------

def test_three_batch_trace_pin_exact(obs_on, dataset):
    events = _serve_three_batches(dataset)
    want = [
        {
            "kind": "trace",
            "trace_id": trace.trace_id(0, i + 1),
            "outcome": "ok",
            "stages": ["admitted", "coalesced", "dispatched", "fenced",
                       "scattered"],
            "worker": "MainThread",
            "rows": 2,
            "k": 3,
            "bucket": 8,
            "cached": i > 0,  # first batch compiles, the rest hit
            "probe": "None",  # exact searcher: probe plan is a no-op
            "coverage": 1.0,
        }
        for i in range(3)
    ]
    assert [_normalize_trace(e) for e in events] == want
    # every mark is a monotonic timestamp in pipeline order
    for e in events:
        marks = e["marks"]
        ts = [marks[s] for s in want[0]["stages"]]
        assert ts == sorted(ts)
    # each completed request observed every stage histogram once
    hists = obs.snapshot()["metrics"]["histograms"]
    for name in ("serve.stage.queue_wait_s", "serve.stage.linger_s",
                 "serve.stage.device_s", "serve.stage.scatter_s"):
        assert hists[name]["count"] == 3
    counters = obs.snapshot()["metrics"]["counters"]
    assert counters["serve.outcome.ok"] == 3


def test_trace_pin_replays_identically(obs_on, dataset):
    runs = []
    for _ in range(2):
        obs.reset()
        runs.append([_normalize_trace(e)
                     for e in _serve_three_batches(dataset)])
    assert runs[0] == runs[1]


def test_stage_sum_covers_measured_e2e(obs_on, dataset):
    """Acceptance: summed per-stage times >= 95% of the measured e2e
    latency per request. An injected 80 ms slow dispatch makes the
    traced window dominate whatever sub-ms slack sits outside it."""
    server = serve.SearchServer(
        dataset, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    plan = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="serve.batch",
                      latency_s=0.08)],
        seed=SEED)
    rng = np.random.default_rng(2)
    t_sub = []
    futs = []
    for _ in range(4):
        q = rng.standard_normal((2, 16)).astype(np.float32)
        t_sub.append(time.monotonic())
        futs.append(server.submit(q, k=3))
    with plan.install():
        assert server.step() == 4
    t_done = time.monotonic()
    for fut in futs:
        assert fut.result(timeout=1.0).coverage == 1.0
    events = [e for e in obs.snapshot()["events"] if e["kind"] == "trace"]
    assert len(events) == 4
    for e, t0 in zip(events, t_sub):
        marks = e["marks"]
        stage_sum = sum(
            marks[b] - marks[a]
            for (a, b) in zip(trace.STAGES, trace.STAGES[1:]))
        e2e = t_done - t0
        assert stage_sum == pytest.approx(
            marks["scattered"] - marks["admitted"])  # deltas telescope
        assert stage_sum >= 0.95 * e2e, (stage_sum, e2e)


# ---------------------------------------------------------------------------
# terminal outcomes
# ---------------------------------------------------------------------------

def test_outcome_counters_and_drop_wait(obs_on, dataset):
    server = serve.SearchServer(
        dataset, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    ok = server.submit(np.zeros((2, 16), np.float32), k=3)
    dead = server.submit(np.zeros((2, 16), np.float32), k=3, deadline_s=0.0)
    assert server.step() == 2
    assert ok.result(timeout=1.0).coverage == 1.0
    with pytest.raises(serve.DeadlineExceeded):
        dead.result(timeout=0.1)
    snap = obs.snapshot()
    counters = snap["metrics"]["counters"]
    assert counters["serve.outcome.ok"] == 1
    assert counters["serve.outcome.expired"] == 1
    assert "serve.outcome.rejected" not in counters
    # the killed request's queue wait landed in the drop histogram
    assert snap["metrics"]["histograms"]["serve.drop_wait_s"]["count"] == 1
    # and its trace closed with the expired outcome (admitted only —
    # it never reached a later stage)
    traces = {e["outcome"]: e for e in snap["events"]
              if e["kind"] == "trace"}
    assert traces["expired"]["stages"] == ["admitted"]
    assert traces["ok"]["stages"][-1] == "scattered"


def test_rejected_request_closes_its_trace(obs_on, dataset):
    server = serve.SearchServer(
        dataset,
        serve.ServerConfig(
            buckets=(8,),
            admission=serve.AdmissionConfig(max_pending_rows=2,
                                            policy="reject")))
    server.submit(np.zeros((2, 16), np.float32), k=3)
    with pytest.raises(serve.RejectedError):
        server.submit(np.zeros((2, 16), np.float32), k=3)
    counters = obs.snapshot()["metrics"]["counters"]
    assert counters["serve.outcome.rejected"] == 1
    rejected = [e for e in obs.snapshot()["events"]
                if e["kind"] == "trace" and e["outcome"] == "rejected"]
    assert len(rejected) == 1


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_evicts_oldest_first(obs_on):
    rec = flight.FlightRecorder(maxlen=4).install()
    try:
        for i in range(10):
            obs.event("tick", i=i)
        assert [e["i"] for e in rec.events()] == [6, 7, 8, 9]
    finally:
        rec.uninstall()
    obs.event("tick", i=99)  # uninstalled: the ring stops recording
    assert [e["i"] for e in rec.events()] == [6, 7, 8, 9]


def test_flight_dump_atomic_and_readable(obs_on, tmp_path):
    flight.install(maxlen=64, dump_dir=str(tmp_path))
    obs.counter("drill.widgets").inc(3)
    obs.event("tick", i=0)
    with obs.span("drill.open"):
        path = flight.maybe_dump("unit_test", detail="abc")
    assert path is not None and os.path.exists(path)
    # atomic_write leaves no temp droppings behind
    assert [p for p in os.listdir(tmp_path) if ".tmp." in p] == []
    with open(path) as f:
        snap = json.load(f)
    assert snap["reason"] == "unit_test"
    assert snap["detail"] == "abc"
    assert snap["pid"] == os.getpid()
    assert snap["registry_delta"]["drill.widgets"] == 3
    assert any(e["kind"] == "tick" for e in snap["events"])
    # the dump ran inside an open span; the stack was captured
    assert any(s["name"] == "drill.open" for s in snap["open_spans"])


def test_flight_dump_disarmed_is_noop(obs_on):
    assert flight.installed() is None
    assert flight.maybe_dump("nobody_home") is None


def test_open_spans_capture(obs_on):
    with obs.span("outer"):
        with obs.span("inner", depth_attr=1):
            stacks = obs.open_spans()
    names = [(s["name"], s["depth"]) for s in stacks
             if s["thread"] == threading.current_thread().name]
    assert names == [("outer", 0), ("inner", 1)]
    assert obs.open_spans() == []  # closed spans leave no residue


# ---------------------------------------------------------------------------
# chaos drills: the two new sites degrade, never kill (ISSUE 18 sat. 3)
# ---------------------------------------------------------------------------

def test_sites_registered():
    known = faults.known_sites()
    assert flight.DUMP_SITE == "obs.flight.dump" and flight.DUMP_SITE in known
    assert trace.STAMP_SITE == "serve.trace.stamp" and trace.STAMP_SITE in known


def test_flaky_dump_is_swallowed(obs_on, tmp_path):
    flight.install(dump_dir=str(tmp_path))
    obs.event("tick", i=1)
    plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap", site="obs.flight.dump",
                      count=1)],
        seed=SEED)
    with plan.install():
        assert flight.maybe_dump("drill") is None  # failed, did not raise
        path = flight.maybe_dump("drill")  # armed once: retry succeeds
    assert path is not None and os.path.exists(path)
    actions = [e["action"] for e in obs.snapshot()["events"]
               if e["kind"] == "flight"]
    assert actions == ["dump_failed", "dump"]


def test_flaky_dump_never_kills_worker_loop(obs_on, dataset, tmp_path):
    """A batcher bug inside the threaded worker loop triggers a flight
    dump; with the dump ALSO failing (injected), the worker must still
    survive both and keep serving."""
    flight.install(dump_dir=str(tmp_path))
    server = serve.SearchServer(
        dataset, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    real_collect = server.batcher.collect
    boom = threading.Event()

    def collect_once_broken(timeout_s=None):
        if not boom.is_set():
            boom.set()
            raise ValueError("injected batcher bug")
        return real_collect(timeout_s=timeout_s)

    server.batcher.collect = collect_once_broken
    plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap", site="obs.flight.dump",
                      count=1)],
        seed=SEED)
    with plan.install():
        server.start()
        try:
            fut = server.submit(np.zeros((2, 16), np.float32), k=3)
            assert fut.result(timeout=5.0).coverage == 1.0  # still serving
        finally:
            server.stop()
    events = obs.snapshot()["events"]
    assert any(e["kind"] == "serve_worker_error" for e in events)
    assert any(e["kind"] == "flight" and e["action"] == "dump_failed"
               for e in events)


def test_corrupt_stamp_degrades_to_untraced_bit_identical(obs_on, dataset):
    """An injected stamp corruption kills request 1's trace; the request
    itself is served with results bit-identical to an uninjected run."""
    from raft_tpu.neighbors import brute_force

    rng = np.random.default_rng(3)
    qs = [rng.standard_normal((2, 16)).astype(np.float32) for _ in range(2)]
    server = serve.SearchServer(
        dataset, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap", site="serve.trace.stamp",
                      count=1)],
        seed=SEED)
    results = []
    with plan.install():
        for q in qs:
            fut = server.submit(q, k=3)
            server.step()
            results.append(fut.result(timeout=1.0))
    for q, got in zip(qs, results):
        want_v, want_i = brute_force.knn(dataset, q, 3)
        np.testing.assert_array_equal(np.asarray(want_v), got.values)
        np.testing.assert_array_equal(np.asarray(want_i), got.ids)
    traces = [e for e in obs.snapshot()["events"] if e["kind"] == "trace"]
    # request 1 degraded to untraced (its first stamp died and the ctx
    # stopped consuming arms); request 2 traced normally, and because
    # the dead ctx minted first, its id is still trace_id(0, 1)
    assert [e["trace_id"] for e in traces] == [trace.trace_id(0, 2)]
    assert traces[0]["outcome"] == "ok"
    fault_evs = [e for e in obs.snapshot()["events"]
                 if e["kind"] == "fault"]
    assert [(e["site"], e["action"]) for e in fault_evs] == [
        ("serve.trace.stamp", "flaky")]


# ---------------------------------------------------------------------------
# watchdog-armed dump, end to end (real SIGKILLed child)
# ---------------------------------------------------------------------------

def test_watchdog_kill_leaves_readable_flight_dump(obs_on, tmp_path):
    flight.install(maxlen=128, dump_dir=str(tmp_path))
    child = ("import sys, time; print('up', flush=True); "
             "time.sleep(60)")
    with pytest.raises(StageTimeout):
        run_supervised([sys.executable, "-c", child], describe="stall-child",
                       stall_timeout_s=0.3, echo=False)
    dumps = sorted(p for p in os.listdir(tmp_path)
                   if p.startswith("flight-") and p.endswith(".json"))
    assert len(dumps) == 1
    assert [p for p in os.listdir(tmp_path) if ".tmp." in p] == []
    with open(os.path.join(tmp_path, dumps[0])) as f:
        snap = json.load(f)
    assert snap["reason"] == "watchdog_kill"
    assert snap["stage"] == "stall-child"
    # the ring CONTAINS the kill's own event (dump runs before SIGKILL)
    kills = [e for e in snap["events"]
             if e["kind"] == "fault" and e["action"] == "watchdog_kill"]
    assert len(kills) == 1 and kills[0]["stage"] == "stall-child"


# ---------------------------------------------------------------------------
# SLO watchtower
# ---------------------------------------------------------------------------

def test_objective_validation():
    with pytest.raises(ValueError, match="kind"):
        slo.Objective("x", "latencyy", target=0.99)
    with pytest.raises(ValueError, match="target"):
        slo.Objective("x", "latency", target=1.0)
    with pytest.raises(ValueError, match="hysteresis"):
        slo.Watchtower([slo.Objective("x", "error", target=0.99)],
                       breach_burn=2.0, recover_burn=2.0)
    assert slo.Objective("x", "error", target=0.99).budget == pytest.approx(0.01)


def test_burn_rate_fast_trips_before_slow_then_breach_then_recover(obs_on):
    """The multi-window guard, driven by explicit synthetic time: the
    fast window trips first but a breach needs the slow window's
    confirmation; recovery needs BOTH burns under the (lower) recover
    threshold — hysteresis against flapping."""
    wt = slo.Watchtower([slo.Objective("error_rate", "error", target=0.99)],
                        fast_s=300.0, slow_s=3600.0,
                        breach_burn=14.0, recover_burn=1.0)
    # budget = 0.01, so burn = 100 * bad_fraction; breach needs
    # bad_fraction >= 0.14 in BOTH windows
    for _ in range(900):
        wt.observe("error_rate", bad=False, t=100.0)
    for _ in range(50):
        wt.observe("error_rate", bad=True, t=1000.0)
    for _ in range(50):
        wt.observe("error_rate", bad=False, t=1000.0)
    fast, slow = wt.burns("error_rate", t=1000.0)
    assert fast == pytest.approx(50.0)   # 50/100 bad in the fast window
    assert slow == pytest.approx(5.0)    # 50/1000 bad in the slow window
    assert fast >= wt.breach_burn and slow < wt.breach_burn
    assert wt.evaluate(t=1000.0) == []   # fast tripped, slow vetoed
    assert not wt.state(t=1000.0)["error_rate"]["breached"]

    # the error keeps burning: now the slow window confirms -> breach
    for _ in range(150):
        wt.observe("error_rate", bad=True, t=1010.0)
    [tr] = wt.evaluate(t=1010.0)
    assert tr["objective"] == "error_rate" and tr["transition"] == "breach"
    assert tr["fast_burn"] >= 14.0 and tr["slow_burn"] >= 14.0

    # fast window drains below breach_burn -> still breached
    # (recover needs BOTH burns < recover_burn)
    assert wt.evaluate(t=1400.0) == []
    fast, slow = wt.burns("error_rate", t=1400.0)
    assert fast < wt.recover_burn <= slow
    assert wt.state(t=1400.0)["error_rate"]["breached"]

    # slow window drains too -> recover
    [tr] = wt.evaluate(t=5000.0)
    assert tr["transition"] == "recover"
    counters = obs.snapshot()["metrics"]["counters"]
    assert counters["slo.breach"] == 1
    assert counters["slo.recover"] == 1
    kinds = [e["kind"] for e in obs.snapshot()["events"]
             if e["kind"].startswith("slo.")]
    assert kinds == ["slo.breach", "slo.recover"]


def test_watchtower_attached_to_server(obs_on, dataset):
    """The serve integration: terminal outcomes feed the watchtower via
    ServerMetrics; an all-expired burst breaches the error objective on
    both windows at once (same synthetic clock instant)."""
    t_fake = [1000.0]
    wt = slo.Watchtower(slo.serve_objectives(), clock=lambda: t_fake[0])
    server = serve.SearchServer(
        dataset, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    server.attach_watchtower(wt)
    futs = [server.submit(np.zeros((2, 16), np.float32), k=3,
                          deadline_s=0.0) for _ in range(3)]
    assert server.step() == 3
    for fut in futs:
        with pytest.raises(serve.DeadlineExceeded):
            fut.result(timeout=0.1)
    assert wt.state()["error_rate"]["breached"]
    assert obs.snapshot()["metrics"]["counters"]["slo.breach"] == 1
    # healthy traffic at a later instant recovers it
    t_fake[0] += 4000.0
    for _ in range(3):
        fut = server.submit(np.zeros((2, 16), np.float32), k=3)
        server.step()
        assert fut.result(timeout=1.0).coverage == 1.0
    assert not wt.state()["error_rate"]["breached"]
    assert obs.snapshot()["metrics"]["counters"]["slo.recover"] == 1


def test_judge_serve_verdicts():
    good = {"submitted": 100, "expired": 0, "rejected": 0, "failed": 0,
            "latency_ms_p99": 12.0, "coverage_min": 1.0,
            "batch_occupancy": 0.5}
    v = slo.judge_serve(good, p99_ms=250.0)
    assert v["slo_ok"] and v["slo_p99_ok"] and v["slo_error_ok"]
    assert v["slo_error_rate"] == 0.0
    # two expiries out of 100 blow a 1% error budget
    v = slo.judge_serve({**good, "expired": 2})
    assert not v["slo_error_ok"] and not v["slo_ok"]
    assert v["slo_error_rate"] == pytest.approx(0.02)
    # an empty run cannot claim its SLOs held (NaN stats judge failing)
    v = slo.judge_serve({"submitted": 0, "latency_ms_p99": float("nan"),
                         "batch_occupancy": float("nan")})
    assert not v["slo_ok"] and not v["slo_p99_ok"] and not v["slo_error_ok"]


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------

def test_disabled_serve_is_untraced(dataset):
    obs.reset()
    assert not obs.enabled()
    server = serve.SearchServer(
        dataset, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    fut = server.submit(np.zeros((2, 16), np.float32), k=3)
    server.step()
    assert fut.result(timeout=1.0).coverage == 1.0
    obs.enable()
    try:
        snap = obs.snapshot()
        assert [e for e in snap["events"] if e["kind"] == "trace"] == []
        # instrument NAMES may linger from earlier tests (the global
        # registry resets values, not names); the disabled run must not
        # have moved any of them
        assert snap["metrics"]["counters"].get("serve.outcome.ok", 0) == 0
        device = snap["metrics"]["histograms"].get("serve.stage.device_s")
        assert device is None or device["count"] == 0
    finally:
        obs.disable()
        obs.reset()

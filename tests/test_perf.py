"""Perf-watchtower suite (ISSUE 7): the analytic cost model pinned
against XLA's own cost analysis, span cost accounting, MFU attribution
in captures and reports, the cross-rank merge renderer (exact
snapshots), the append-only bench ledger, and the per-rank MNMG capture
hook."""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu import obs
from raft_tpu.obs import ledger, perf
from raft_tpu.obs import report as obs_report


@pytest.fixture
def obs_on():
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# cost model: formulas, peaks, MFU
# ---------------------------------------------------------------------------

def test_canon_dtype_and_bytes():
    assert perf.canon_dtype("float32") == "f32"
    assert perf.canon_dtype(np.dtype(np.float32)) == "f32"
    assert perf.canon_dtype("bfloat16") == "bf16"
    assert perf.canon_dtype(jnp.bfloat16) == "bf16"
    assert perf.canon_dtype("uint8") == "int8"
    assert perf.canon_dtype("weird") == "f32"  # conservative default
    assert perf.dtype_bytes("bf16") == 2 and perf.dtype_bytes("int8") == 1


def test_formulas_scale():
    a = perf.pairwise_l2(1000, 100, 64)
    b = perf.pairwise_l2(2000, 100, 64)
    assert 1.9 < b["flops"] / a["flops"] < 2.1  # matmul-dominated
    # list-major streams every list: cost grows with n_lists/n_probes
    qm = perf.ivf_pq_scan(nq=64, n_probes=8, n_lists=64, n_rows=64_000,
                          dim=32, pq_dim=16, k=10)
    lm = perf.ivf_pq_scan(nq=64, n_probes=8, n_lists=64, n_rows=64_000,
                          dim=32, pq_dim=16, k=10, scanned_lists=64)
    assert lm["flops"] > 4 * qm["flops"]
    one = perf.kmeans_step(10_000, 64, 128)
    ten = perf.kmeans_step(10_000, 64, 128, iters=10)
    assert ten["flops"] == 10 * one["flops"]
    # rerank adds exact-distance work on top of the integer scan
    plain = perf.rabitq_scan(nq=64, n_probes=8, n_lists=64, n_rows=64_000,
                             dim=64, k=10)
    rer = perf.rabitq_scan(nq=64, n_probes=8, n_lists=64, n_rows=64_000,
                           dim=64, k=10, rerank_mult=8)
    assert rer["flops"] > plain["flops"]
    # popcount ops are their own rate class since ISSUE 11: the scan is
    # charged as uint32 VPU "int" ops, never against a matmul peak
    assert plain["dtype"] == "int"
    assert plain["flops_by_dtype"]["int"] > 0
    assert plain["flops_by_dtype"]["f32"] > 0  # the coarse stage


def test_cost_registry_per_span_name():
    # every instrumented span resolves a formula; a typo fails loudly
    for name in ("neighbors.brute_force.knn", "neighbors.ivf_flat.search",
                 "neighbors.ivf_pq.search", "neighbors.ivf_rabitq.search",
                 "mnmg.knn", "mnmg.kmeans_fit", "mnmg.ivf_flat_search",
                 "mnmg.ivf_pq_search", "mnmg.ivf_rabitq_search"):
        assert name in perf.SPAN_COST_MODEL
    c = perf.cost_for("neighbors.brute_force.knn", n=100, nq=10, d=8, k=3)
    assert c["flops"] > 0 and c["bytes"] > 0
    with pytest.raises(KeyError):
        perf.cost_for("no.such.span")
    perf.register("custom.span", lambda n: {"flops": n, "bytes": 0,
                                            "dtype": "f32"})
    try:
        assert perf.cost_for("custom.span", n=7)["flops"] == 7
    finally:
        del perf.SPAN_COST_MODEL["custom.span"]


def test_platform_info_cpu_is_nominal():
    info = perf.platform_info()
    assert info["platform"] == "cpu"  # conftest pins the CPU mesh
    assert info["nominal"] is True
    assert info["peak_flops"]["bf16"] > 0


@pytest.mark.parametrize("kind,ok", [("TPU v5 lite", True),
                                     ("TPU v9 imaginary", False)])
def test_platform_info_keys_peaks_by_device_kind(monkeypatch, kind, ok):
    """The v5e row is found by the kind the chip reports; an unknown
    accelerator kind raises instead of borrowing another chip's peaks."""
    import jax

    class Dev:
        platform = "tpu"
        device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Dev()])
    if ok:
        info = perf.platform_info()
        assert info["device_kind"] == kind and info["nominal"] is False
        assert info["peak_flops"]["bf16"] == 197e12
    else:
        with pytest.raises(KeyError, match="no peak row"):
            perf.platform_info()


def test_mfu_math():
    info = {"peak_flops": {"f32": 50e9, "bf16": 100e9}, "nominal": True}
    assert perf.mfu({"f32": 5e9}, 1.0, info) == pytest.approx(0.1)
    # mixed dtypes weight each against its own peak
    assert perf.mfu({"f32": 5e9, "bf16": 10e9}, 1.0, info) == pytest.approx(0.2)
    # a dtype the platform has no peak for yields no claim, not 0%
    assert perf.mfu({"int8": 1.0}, 1.0, info) is None
    assert perf.mfu({"f32": 1.0}, 0.0, info) is None
    assert perf.mfu({}, 1.0, info) is None


def test_integer_peak_row_and_popcount_canon():
    """ISSUE 11 satellite: uint32 popcount ops resolve onto their own
    "int" peak row on EVERY platform (v5e architectural estimate, CPU
    nominal placeholder) — before the row existed the bit-plane scan's
    flops fell to the f32 fallback and MFU weighed popcounts against a
    matmul peak; and a platform whose table genuinely misses a dtype
    still yields None, never a fabricated 0%."""
    assert perf.canon_dtype("uint32") == "int"
    assert perf.canon_dtype("int32") == "int"
    assert perf.canon_dtype("int") == "int"
    for name, row in perf.PEAK_TABLE.items():
        assert "int" in row["peak_flops"], name
    assert perf.PEAK_TABLE["cpu"]["nominal"] is True
    # mixed int8+popcount span: each component against ITS peak
    peaks = perf.PEAK_TABLE["TPU v5 lite"]["peak_flops"]
    info = {"peak_flops": peaks}
    m = perf.mfu({"int8": peaks["int8"], "int": peaks["int"]}, 2.0, info)
    assert m == pytest.approx(1.0)
    assert perf.mfu({"int": 1.0}, 1.0, {"peak_flops": {}}) is None


def test_rabitq_fused_geometry_cost():
    """The bit-plane scan charges integer-ops flops and the blocked
    intersection tensor it writes (bits x words per candidate) beside
    the packed-code stream — the dtype-correct MFU attribution; the
    int8 fused PQ scan splits its peaks by dtype."""
    kw = dict(nq=64, n_probes=8, n_lists=64, n_rows=64_000, dim=64, k=10)
    four = perf.rabitq_scan(**kw, query_bits=4)
    one = perf.rabitq_scan(**kw, query_bits=1)
    assert four["flops_by_dtype"]["int"] == 4 * one["flops_by_dtype"]["int"]
    rows = 8 * 64_000 / 64
    assert four["bytes"] - one["bytes"] == pytest.approx(
        64 * rows * 3 * 2 * 4.0)  # three more planes of two words
    # the int8 fused PQ scan splits coarse-f32 from the int8 MXU matmul
    pq = perf.ivf_pq_scan(nq=64, n_probes=8, n_lists=64, n_rows=64_000,
                          dim=32, pq_dim=16, k=10, dtype="int8",
                          scanned_lists=64, fused=True)
    assert pq["flops_by_dtype"]["int8"] > 0
    assert pq["flops_by_dtype"]["f32"] > 0


def test_collective_wire_bytes():
    assert perf.collective_wire_bytes("allreduce", 1024, 8) == \
        int(1024 * 2 * 7 / 8)
    # allgather's counted payload is the per-rank INPUT shard; a ring
    # allgather forwards w-1 foreign shards through each rank
    assert perf.collective_wire_bytes("allgather", 1024, 8) == 1024 * 7
    assert perf.collective_wire_bytes("allreduce", 1024, 1) == 0
    assert perf.collective_wire_bytes("allreduce", 1024, None) == 0


# ---------------------------------------------------------------------------
# the XLA cross-check (the acceptance pin: analytic == cost_analysis)
# ---------------------------------------------------------------------------

def test_analytic_pairwise_l2_matches_xla():
    """The pairwise-L2 formula must track XLA's own flop count tightly —
    this is the hot path ROADMAP item 1's 10x claim will be judged on."""
    from raft_tpu.distance import pairwise_distance
    from raft_tpu.distance.distance_types import DistanceType

    n, m, d = 512, 1024, 64
    x = jnp.ones((n, d))
    y = jnp.ones((m, d))
    xla = perf.xla_cost_analysis(
        lambda a, b: pairwise_distance(a, b, metric=DistanceType.L2Expanded),
        x, y)
    assert xla is not None and xla["flops"] > 0
    an = perf.pairwise_l2(n, m, d)
    assert 0.85 <= an["flops"] / xla["flops"] <= 1.15
    # bytes: XLA counts every intermediate buffer touch, the model
    # counts unavoidable operand/output traffic — same order, not equal
    assert 0.1 <= an["bytes"] / xla["bytes"] <= 10.0


@pytest.mark.slow  # one small IVF-PQ build (~20 s CPU)
def test_analytic_ivf_pq_scan_matches_xla():
    """The IVF-PQ scan formula must be the right order of magnitude and
    engine-aware: the list-major engine streams every padded list, and
    the model charged with scanned_lists=n_lists lands within 3x of
    XLA's count (a model, not a measurement — but one that can't drift
    silently by 10x)."""
    from raft_tpu.neighbors import ivf_pq

    rng = np.random.default_rng(0)
    data = rng.random((20_000, 32), dtype=np.float32)
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=64, kmeans_n_iters=3, pq_dim=16), data)
    q = jnp.asarray(rng.random((128, 32), dtype=np.float32))
    sp = ivf_pq.SearchParams(n_probes=8, score_mode="recon8_list")
    jax.block_until_ready(ivf_pq.search(sp, idx, q, 10))  # warm host caches
    xla = perf.xla_cost_analysis(lambda qq: ivf_pq.search(sp, idx, qq, 10), q)
    assert xla is not None and xla["flops"] > 0
    padded = int(idx.codes.shape[0] * idx.codes.shape[1])
    an = perf.ivf_pq_scan(nq=128, n_probes=8, n_lists=64, n_rows=padded,
                          dim=32, pq_dim=16, k=10, scanned_lists=64)
    assert 1 / 3 <= an["flops"] / xla["flops"] <= 3.0
    assert 1 / 10 <= an["bytes"] / xla["bytes"] <= 10.0


# ---------------------------------------------------------------------------
# span cost accounting
# ---------------------------------------------------------------------------

def test_span_cost_accumulates_into_counters_and_event(obs_on):
    with obs.span("pipeline.scan"):
        obs.span_cost(flops=100, bytes=10, dtype="bf16")
        obs.span_cost(flops=50, bytes=5, dtype="bf16")  # accumulates
    counters = obs.registry().snapshot()["counters"]
    assert counters["perf.pipeline.scan.flops.bf16"] == 150
    assert counters["perf.pipeline.scan.bytes"] == 15
    ev = obs.bus().events(kind="span")[-1]
    assert ev["cost_flops"] == 150 and ev["cost_bytes"] == 15
    assert ev["cost_dtype"] == "bf16"


def test_span_cost_keeps_mixed_dtypes_separate(obs_on):
    """A span charging an int8 scan and then an f32 rerank must keep
    both sums — collapsing to the last dtype would weigh all the flops
    against the wrong peak (int8 peak is 2x bf16 on v5e)."""
    with obs.span("pipeline.mixed"):
        obs.span_cost(flops=100, dtype="int8")
        obs.span_cost(flops=40, dtype="f32")
    counters = obs.registry().snapshot()["counters"]
    assert counters["perf.pipeline.mixed.flops.int8"] == 100
    assert counters["perf.pipeline.mixed.flops.f32"] == 40
    ev = obs.bus().events(kind="span")[-1]
    assert ev["cost_flops"] == 140
    assert ev["cost_flops_by_dtype"] == {"int8": 100, "f32": 40}


def test_span_cost_disabled_and_outside_span():
    obs.disable()
    obs.reset()
    assert obs.span_cost(flops=1, dtype="f32") is None
    obs.enable()
    try:
        assert obs.span_cost(flops=1, dtype="f32") is None  # no open span
        snap = obs.registry().snapshot()
        assert not any(name.startswith("perf.") and val
                       for name, val in snap["counters"].items())
    finally:
        obs.disable()
        obs.reset()


def test_capture_totals_derive_mfu(obs_on):
    with obs.capture_spans() as cap:
        with obs.span("phase.score"):
            obs.span_cost(flops=10_000_000, bytes=1_000, dtype="f32")
        with obs.span("phase.idle"):
            pass
    totals = cap.totals()
    score = totals["phase.score"]
    assert score["flops"] == 10_000_000 and score["bytes"] == 1_000
    assert score["gflops_per_s"] > 0
    assert 0.0 < score["mfu"]
    assert score["mfu_nominal"] is True  # CPU peaks are placeholders
    assert "flops" not in totals["phase.idle"]  # uncharged spans stay lean


def test_instrumented_searches_charge_cost(obs_on, rng):
    from raft_tpu.neighbors import brute_force, ivf_flat

    data = rng.random((600, 16), dtype=np.float32)
    brute_force.knn(data, data[:8], k=3)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=2),
                           data)
    ivf_flat.search(ivf_flat.SearchParams(n_probes=4), index, data[:4], 3)
    counters = obs.registry().snapshot()["counters"]
    knn_flops = [v for n, v in counters.items()
                 if n.startswith("perf.neighbors.brute_force.knn.flops.")]
    assert knn_flops and all(v > 0 for v in knn_flops)
    flat_flops = [v for n, v in counters.items()
                  if n.startswith("perf.neighbors.ivf_flat.search.flops.")]
    assert flat_flops and all(v > 0 for v in flat_flops)


def test_run_case_fenced_mfu(obs_on):
    """The bench row's headline MFU divides charged cost by the FENCED
    timed-loop wall — not the span's host dispatch window, which on an
    async backend closes before the device finishes (the per-span rates
    in `phases` carry that caveat; the row-level number must not)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench"))
    import common as bench_common

    def fn():
        with obs.span("phase.scan"):
            obs.span_cost(flops=50_000_000, dtype="f32")
        return jnp.ones((4,)) * 2

    rec = bench_common.run_case("t", "case", fn, iters=4, warmup=1)
    assert rec["phases"]["phase.scan"]["calls"] == 4
    assert rec["gflops_per_s"] > 0
    assert 0.0 < rec["mfu"] and rec["mfu_nominal"] is True
    # fenced-loop rate can never exceed the per-span dispatch-window rate
    assert rec["gflops_per_s"] <= \
        rec["phases"]["phase.scan"]["gflops_per_s"] * 1.001


def test_collective_hook_counts_wire_bytes(obs_on):
    obs.collective("allreduce", np.zeros((8,), np.float32), axis="data",
                   world=8)
    counters = obs.registry().snapshot()["counters"]
    assert counters["comms.allreduce.bytes"] == 32
    assert counters["comms.allreduce.wire_bytes"] == int(32 * 2 * 7 / 8)
    ev = obs.bus().events(kind="collective")[-1]
    assert ev["wire_bytes"] == counters["comms.allreduce.wire_bytes"]
    assert ev["world"] == 8


# ---------------------------------------------------------------------------
# report: the MFU section and the merge view (exact snapshots)
# ---------------------------------------------------------------------------

_PERF_SNAP = {
    "platform": {"platform": "tpu-v5e", "device_kind": "TPU v5e",
                 "peak_flops": {"bf16": 197e12, "f32": 197e12,
                                "int8": 394e12},
                 "hbm_Bps": 819e9, "nominal": False},
    "metrics": {
        "counters": {
            "perf.neighbors.ivf_pq.search.flops.bf16": 19_700_000_000_000,
            "perf.neighbors.ivf_pq.search.bytes": 40_960_000_000,
            "perf.neighbors.brute_force.knn.flops.f32": 985_000_000_000,
        },
        "gauges": {},
        "histograms": {
            "span.neighbors.ivf_pq.search": {
                "count": 4, "total": 1.0, "min": 0.2, "max": 0.3,
                "mean": 0.25, "last": 0.25},
            "span.neighbors.brute_force.knn": {
                "count": 1, "total": 0.5, "min": 0.5, "max": 0.5,
                "mean": 0.5, "last": 0.5},
        },
    },
    "events": [],
}

_PERF_EXPECTED = """\
# pinned

events: 0  counters: 3  gauges: 0

## Spans (wall-clock attribution)

span                       calls  total      mean       max
-------------------------  -----  ---------  ---------  ---------
neighbors.brute_force.knn  1      500.00 ms  500.00 ms  500.00 ms
neighbors.ivf_pq.search    4      1.000 s    250.00 ms  300.00 ms

## Cost attribution (analytic model over span host-time; MFU vs tpu-v5e peak)

span                       flops       dtype  GFLOP/s   MFU     bytes/s
-------------------------  ----------  -----  --------  ------  ----------
neighbors.brute_force.knn  985 GFLOP   f32    1970      1.00%   -
neighbors.ivf_pq.search    19.7 TFLOP  bf16   1.97e+04  10.00%  38.1 GiB/s
"""


def _lines(text):
    # table cells are right-padded; trailing spaces are presentation,
    # not contract — everything else is pinned byte-exact
    return [l.rstrip() for l in text.splitlines()]


def test_report_perf_section_exact_snapshot():
    """Exact render pin: 19.7 TFLOP of bf16 over 1 s against the 197
    TFLOP/s v5e peak MUST read 10.00% MFU — the arithmetic the roofline
    work (ROADMAP item 1) is judged by."""
    out = obs_report.render(_PERF_SNAP, title="pinned")
    assert _lines(out) == _lines(_PERF_EXPECTED)


def test_report_perf_section_tags_nominal_cpu():
    snap = json.loads(json.dumps(_PERF_SNAP))  # deep copy
    snap["platform"] = {"platform": "cpu", "peak_flops": {"f32": 50e9,
                                                          "bf16": 50e9},
                        "nominal": True}
    out = obs_report.render(snap)
    assert "NOMINAL peaks, not a hardware claim" in out


def _rank_snap(rank, slow):
    return {
        "rank": rank, "world": 2,
        "metrics": {
            "counters": {
                "comms.allreduce.calls": 3 if rank == 0 else 2,
                "comms.allreduce.bytes": 3072 if rank == 0 else 2048,
                "comms.allgather.calls": 1, "comms.allgather.bytes": 512,
            },
            "gauges": {},
            "histograms": {
                "span.mnmg.knn": {"count": 2, "total": slow, "min": 0.1,
                                  "max": slow, "mean": slow / 2,
                                  "last": 0.1},
            },
        },
        "events": [
            {"seq": 5, "t": 0.0, "kind": "fault", "site": "comms.allreduce",
             "action": "drop"},
            {"seq": 9, "t": 0.0, "kind": "health", "rank": 1,
             "healthy": rank != 0},
        ],
    }


_MERGE_EXPECTED = """\
# pinned merge

ranks merged: 2  world: 2

## Per-rank span attribution

span      r0         r1         skew
--------  ---------  ---------  -----
mnmg.knn  900.00 ms  200.00 ms  4.50x

straggler: span 'mnmg.knn' slowest on rank 0 (4.50x the fastest rank)

## Collective skew (per-rank calls / payload bytes)

collective  calls r0/r1  bytes
----------  -----------  ---------------
allgather   1/1          512 B/512 B
allreduce   3/2          3.0 KiB/2.0 KiB

DESYNC: collective 'allreduce' call counts differ across ranks (3/2) \
— a rank is missing collectives (hang risk)

## Merged timeline (fault, health; aligned by per-rank seq; last 60)

r0 #5     fault    action=drop site=comms.allreduce
r1 #5     fault    action=drop site=comms.allreduce
r0 #9     health   healthy=False rank=1
r1 #9     health   healthy=True rank=1
"""


def test_report_merge_exact_snapshot():
    """Exact merge pin: rank ordering comes from the snapshots' rank
    fields (inputs deliberately passed out of order), the straggler line
    names the slow rank with its skew, the call-count mismatch surfaces
    as a DESYNC, and the timeline interleaves by (seq, rank)."""
    out = obs_report.render_merged([_rank_snap(1, 0.2), _rank_snap(0, 0.9)],
                                   title="pinned merge")
    assert _lines(out) == _lines(_MERGE_EXPECTED)


def test_report_cli_merge_and_single(tmp_path, capsys):
    p0 = tmp_path / "r0.json"
    p1 = tmp_path / "r1.json"
    p0.write_text(json.dumps(_rank_snap(0, 0.9)))
    p1.write_text(json.dumps(_rank_snap(1, 0.2)))
    assert obs_report.main([str(p0), str(p1), "--merge"]) == 0
    out = capsys.readouterr().out
    assert "ranks merged: 2" in out and "straggler" in out
    # multiple files without --merge is a usage error
    with pytest.raises(SystemExit):
        obs_report.main([str(p0), str(p1)])
    capsys.readouterr()
    assert obs_report.main([str(p0)]) == 0  # single file still renders
    assert "raft_tpu run report" in capsys.readouterr().out


def test_snapshot_carries_rank_and_platform(obs_on, tmp_path):
    path = tmp_path / "snap.json"
    snap = obs.save_snapshot(str(path), rank=3, world=8, label="drill")
    assert snap["rank"] == 3 and snap["world"] == 8
    assert snap["label"] == "drill"
    assert snap["platform"]["platform"] == "cpu"
    assert json.loads(path.read_text())["rank"] == 3


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------

def test_ledger_roundtrip_and_torn_line(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    e1 = ledger.make_entry(bench="b", row={"case": "x", "value": 1.0,
                                           "unit": "qps"},
                           platform="cpu", sha="abc1234")
    ledger.append(e1, path=path)
    with open(path, "a") as f:
        f.write('{"sha": "torn')  # SIGKILL mid-append
    e2 = ledger.make_entry(bench="b", row={"case": "x", "value": 2.0,
                                           "unit": "qps"},
                           platform="cpu", sha="def5678", fallback="in_process_cpu")
    ledger.append(e2, path=path)
    rows = ledger.read(path)
    assert [e["sha"] for e in rows] == ["abc1234", "def5678"]
    assert rows[0]["bench"] == "b" and rows[0]["platform"] == "cpu"
    assert rows[0]["row"] == {"case": "x", "value": 1.0, "unit": "qps"}
    assert rows[1]["fallback"] == "in_process_cpu"
    assert "utc" in rows[0]
    assert ledger.read(str(tmp_path / "missing.jsonl")) == []


def test_ledger_env_override_and_git_sha(tmp_path, monkeypatch):
    target = str(tmp_path / "override.jsonl")
    monkeypatch.setenv(ledger.ENV_PATH, target)
    assert ledger.resolve_path("/elsewhere") == target
    monkeypatch.delenv(ledger.ENV_PATH)
    assert ledger.resolve_path(str(tmp_path)) == \
        os.path.join(str(tmp_path), ledger.DEFAULT_NAME)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sha = ledger.git_sha(repo)
    assert sha == "unknown" or all(c in "0123456789abcdef" for c in sha)
    assert ledger.git_sha(str(tmp_path)) == "unknown"  # not a repo


def test_banker_rows_reach_ledger(tmp_path, monkeypatch):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench"))
    import common

    path = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv(ledger.ENV_PATH, path)
    # a CPU run: diverted results file, cpu-tagged ledger row
    bank = common.Banker(str(tmp_path / "BENCH_x.json"), meta={})
    assert bank.path.endswith(".cpu")
    bank.add({"case": "qps", "value": 123.0, "unit": "qps"}, echo=False)
    rows = ledger.read(path)
    assert len(rows) == 1
    assert rows[0]["bench"] == "BENCH_x" and rows[0]["platform"] == "cpu"
    assert rows[0]["cpu_rehearsal"] is True and "fallback" not in rows[0]
    assert all("sha" in e for e in rows)


# ---------------------------------------------------------------------------
# serve + MNMG wiring
# ---------------------------------------------------------------------------

def test_serve_latencies_feed_bucketed_histogram(obs_on):
    from raft_tpu.serve.metrics import ServerMetrics

    m = ServerMetrics(latency_window=8)
    m.observe_batch(n_requests=2, valid_rows=2, bucket_rows=4,
                    latencies_s=[0.003, 0.2])
    h = obs.histogram("serve.latency_s")
    assert h.aggregate()["count"] == 2
    buckets = dict(h.bucket_counts())
    assert buckets["0.005"] == 1 and buckets["+Inf"] == 2
    # the exposition surface renders them as real series
    text = obs.render_registry_prometheus()
    assert 'raft_tpu_serve_latency_s_bucket{le="+Inf"} 2' in text


def test_mnmg_driver_saves_rank_snapshot(obs_on, tmp_path, monkeypatch, rng):
    from raft_tpu.comms import mnmg
    from raft_tpu.comms.comms import Comms

    monkeypatch.setenv("RAFT_TPU_OBS_RANK_DIR", str(tmp_path))
    comms = Comms()
    data = rng.random((64, 8), dtype=np.float32)
    mnmg.knn(comms, data, data[:4], k=3)
    path = tmp_path / "obs_rank000.json"
    assert path.exists()
    snap = json.loads(path.read_text())
    assert snap["rank"] == 0 and snap["world"] == 8
    assert snap["label"] == "mnmg.knn"
    # the capture includes the driver's own closed span and its cost
    span_evs = [e for e in snap["events"] if e.get("kind") == "span"
                and e.get("name") == "mnmg.knn"]
    assert span_evs and span_evs[-1]["cost_flops"] > 0
    assert any(n.startswith("perf.mnmg.knn.flops.")
               for n, v in snap["metrics"]["counters"].items() if v)


def test_mnmg_driver_no_snapshot_when_env_unset(obs_on, tmp_path, rng):
    from raft_tpu.comms import mnmg
    from raft_tpu.comms.comms import Comms

    comms = Comms()
    data = rng.random((64, 8), dtype=np.float32)
    mnmg.knn(comms, data, data[:4], k=3)
    assert not list(tmp_path.iterdir())


def test_mnmg_driver_keyword_first_arg_still_works(obs_on, tmp_path,
                                                   monkeypatch, rng):
    """rank_captured must not change the call surface: the session
    passed by KEYWORD still works and still resolves the rank file."""
    from raft_tpu.comms import mnmg
    from raft_tpu.comms.comms import Comms

    monkeypatch.setenv("RAFT_TPU_OBS_RANK_DIR", str(tmp_path))
    data = rng.random((64, 8), dtype=np.float32)
    mnmg.knn(comms=Comms(), dataset=data, queries=data[:4], k=3)
    assert (tmp_path / "obs_rank000.json").exists()

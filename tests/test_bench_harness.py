"""Bench orchestration logic (bench.py parent/child protocol).

The headline bench must always emit one JSON record even when a worker
hangs or crashes mid-run (see bench.py _run_child). These tests drive main()'s attempt
loop hermetically with stubbed children — no device, no subprocesses.
"""

import contextlib
import io
import json

import os
import sys

import numpy as np
import pytest

import bench

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench"))
import common  # noqa: E402


@pytest.fixture
def quiet(monkeypatch, tmp_path):
    monkeypatch.setattr(bench, "_PARTIAL_PATH", str(tmp_path / "partial.jsonl"))
    monkeypatch.setattr(bench, "_LAST_GOOD_PATH", str(tmp_path / "last_good.json"))
    # ledger rows from stubbed test sessions must never land in the
    # repo's real BENCH_LEDGER.jsonl
    monkeypatch.setenv("RAFT_TPU_BENCH_LEDGER", str(tmp_path / "ledger.jsonl"))
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.delenv("RAFT_TPU_BENCH_CHILD", raising=False)


def run_main():
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.main()
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_first_attempt_wins(quiet, monkeypatch):
    calls = []

    def child(kind, t):
        calls.append(kind)
        return {"metric": "m", "value": 42}, True

    monkeypatch.setattr(bench, "_run_child", child)
    rec = run_main()
    assert rec["value"] == 42
    assert calls == ["ivf"]


def test_transient_failures_retry_then_fall_back(quiet, monkeypatch):
    calls = []

    def child(kind, t):
        calls.append(kind)
        return None, True  # crash/timeout after doing real work

    monkeypatch.setattr(bench, "_run_child", child)
    rec = run_main()
    assert calls == ["ivf", "ivf", "bf"]
    assert rec["metric"] == bench._HEADLINE_METRIC
    assert rec["value"] == 0.0 and "error" in rec


def test_deterministic_failure_skips_identical_retry(quiet, monkeypatch):
    calls = []

    def child(kind, t):
        calls.append(kind)
        if kind == "ivf":
            return {"deterministic_failure": "recall gate"}, True
        return {"metric": "bf_fallback", "value": 1}, True

    monkeypatch.setattr(bench, "_run_child", child)
    rec = run_main()
    assert calls == ["ivf", "bf"], "second identical ivf attempt must be skipped"
    assert rec["metric"] == "bf_fallback"


def test_jax_runtime_errors_are_not_deterministic():
    # jax's runtime errors subclass RuntimeError; the child must not
    # classify them as deterministic (a fresh process CAN recover them)
    import jax

    assert issubclass(jax.errors.JaxRuntimeError, RuntimeError)
    assert not issubclass(jax.errors.JaxRuntimeError, bench.DeterministicBenchFailure)


def test_recall_gate_is_deterministic():
    assert issubclass(bench.DeterministicBenchFailure, RuntimeError)


def test_parent_never_initializes_a_backend(quiet, monkeypatch):
    # a chip belongs to one process: the attempt children must find it
    # free, so the parent loop may not touch a JAX backend at all
    import jax

    def touched(*a, **k):
        raise AssertionError("bench.py parent initialized a JAX backend")

    monkeypatch.setattr(jax, "devices", touched)
    monkeypatch.setattr(jax, "default_backend", touched)
    monkeypatch.setattr(bench, "_run_child",
                        lambda kind, t: ({"metric": "m", "value": 1}, True))
    assert run_main()["value"] == 1


def test_healthy_chip_keeps_full_timeout(quiet, monkeypatch):
    timeouts = []

    def child(kind, t):
        timeouts.append(t)
        return {"metric": "m", "value": 1}, True

    monkeypatch.setattr(bench, "_run_child", child)
    run_main()
    assert timeouts == [3600]



def test_hung_child_flips_to_short_leashes(quiet, monkeypatch):
    # a child that times out with NO progress signals a lost backend:
    # the remaining attempts drop to short leashes instead of burning
    # hours
    timeouts = []

    def child(kind, t):
        timeouts.append(t)
        return None, False

    monkeypatch.setattr(bench, "_run_child", child)
    rec = run_main()
    assert timeouts[0] == 3600 and all(t <= 600 for t in timeouts[1:]), timeouts
    assert rec["value"] == 0.0


def test_partial_results_recovered_after_total_failure(quiet, monkeypatch):
    # a killed child's persisted ladder entries become the final record
    def child(kind, t):
        bench._record_partial(
            {"qps": 5000.0, "recall": 0.97, "mode": "recon8_list",
             "n_probes": 8, "refine": True}
        )
        return None, True

    monkeypatch.setattr(bench, "_run_child", child)
    rec = run_main()
    assert rec["value"] == 5000.0 and rec["partial"] is True
    assert rec["recall_gate"] == bench._RECALL_GATE


def test_partial_recovery_skips_smoke_and_suspect_rows(quiet, monkeypatch):
    # the 2026-08-01 incident: a CPU smoke row and a contention artifact
    # (2.2M "qps") landed in a live chip session's partial file; tagged
    # rows must never be recoverable as that session's best
    def child(kind, t):
        bench._record_partial(
            {"qps": 2207548.0, "recall": 0.996, "mode": "recon8_list",
             "n_probes": 16, "refine": True, "suspect": True})
        bench._record_partial(
            {"qps": 16710.0, "recall": 1.0, "mode": "bf_tiled",
             "n_probes": None, "refine": False, "smoke": True})
        bench._record_partial(
            {"qps": 5000.0, "recall": 0.97, "mode": "recon8_list",
             "n_probes": 8, "refine": True})
        return None, True

    monkeypatch.setattr(bench, "_run_child", child)
    rec = run_main()
    assert rec["value"] == 5000.0 and rec["partial"] is True


def test_keep_partial_preserves_session_rows(quiet, monkeypatch):
    # the queue's end-of-session tuned-keys re-run must not erase the
    # rows the same session banked (a death mid-re-run would
    # otherwise leave the round with LESS evidence than before it ran)
    bench._record_partial(
        {"qps": 5000.0, "recall": 0.97, "mode": "recon8_list",
         "n_probes": 8, "refine": True})
    monkeypatch.setenv("RAFT_TPU_BENCH_KEEP_PARTIAL", "1")
    monkeypatch.setattr(bench, "_run_child", lambda k, t: (None, True))
    rec = run_main()
    assert rec["value"] == 5000.0 and rec["partial"] is True
    # without the flag the session reset wipes pre-existing rows
    monkeypatch.delenv("RAFT_TPU_BENCH_KEEP_PARTIAL")
    rec = run_main()
    assert rec["value"] == 0.0


def test_success_banks_last_good_but_failure_never_recycles_it(
        quiet, monkeypatch):
    # success still banks the write-only provenance record, but a later
    # total failure reports 0.0 + error — the 72 h recycling path that
    # produced BENCH_r04/r05 (an old 5315-qps row masquerading as fresh
    # trajectory across dead rounds) is gone
    good = {"metric": bench._HEADLINE_METRIC, "value": 5315.2,
            "unit": "qps", "vs_baseline": 0.532, "recall@10": 0.9965}
    monkeypatch.setattr(bench, "_run_child", lambda k, t: (dict(good), True))
    rec = run_main()
    assert rec["value"] == 5315.2
    lg = json.loads(open(bench._LAST_GOOD_PATH).read())
    assert lg["value"] == 5315.2 and "measured_unix" in lg
    monkeypatch.setattr(bench, "_run_child", lambda k, t: (None, True))
    rec = run_main()
    assert rec["value"] == 0.0 and "error" in rec
    assert "recovered_from" not in rec


def test_headline_sessions_append_to_ledger(quiet, monkeypatch):
    # every session — measured or failed — appends one honest row to the
    # append-only ledger; a 0.0 outage row is trajectory signal too
    from raft_tpu.obs import ledger

    path = os.environ["RAFT_TPU_BENCH_LEDGER"]
    good = {"metric": bench._HEADLINE_METRIC, "value": 4321.0, "unit": "qps"}
    monkeypatch.setattr(bench, "_run_child", lambda k, t: (dict(good), True))
    run_main()
    monkeypatch.setattr(bench, "_run_child", lambda k, t: (None, True))
    run_main()
    entries = ledger.read(path)
    assert [e["row"]["value"] for e in entries] == [4321.0, 0.0]
    assert all(e["bench"] == "bench_headline" and "sha" in e
               for e in entries)


def test_smoke_record_never_banks_last_good(quiet, monkeypatch):
    monkeypatch.setattr(
        bench, "_run_child",
        lambda k, t: ({"metric": bench._HEADLINE_METRIC, "value": 9e9,
                       "unit": "qps", "smoke": True}, True))
    run_main()
    assert not os.path.exists(bench._LAST_GOOD_PATH)


def test_record_partial_tags_smoke_rows(quiet, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_BENCH_SMOKE", "1")
    bench._record_partial({"qps": 1.0, "recall": 1.0, "mode": "bf_tiled"})
    row = json.loads(open(bench._PARTIAL_PATH).read().strip())
    assert row["smoke"] is True


def test_measure_protocol_flags_subfloor_walltime(quiet, monkeypatch):
    # a "measurement" faster than the dispatch floor means the
    # backend returned without doing the work: recorded, but suspect
    import jax.numpy as jnp

    monkeypatch.setenv("RAFT_TPU_BENCH_MIN_BATCH_MS", "1e9")
    truth = np.arange(4).reshape(4, 1)
    run = lambda: (jnp.zeros((4, 1)), jnp.asarray(truth))
    rec = bench._measure_protocol(run, 4, 1, truth, "bf_tiled", None,
                                  False, smoke=False)
    assert rec["suspect"] is True and rec["recall"] == 1.0
    row = json.loads(open(bench._PARTIAL_PATH).read().strip())
    assert row["suspect"] is True


def test_measure_protocol_bogus_pipelined_falls_back_to_synced(
        quiet, monkeypatch):
    # a bogus pipelined clock alone must not void the row's valid synced
    # measurement — the synced rate carries the row instead
    import jax.numpy as jnp

    monkeypatch.setattr(bench, "_dual_time",
                        lambda *a, **k: ([190.0, 190.0, 190.0], 0.002))
    truth = np.arange(4).reshape(4, 1)
    run = lambda: (jnp.zeros((4, 1)), jnp.asarray(truth))
    rec = bench._measure_protocol(run, 4, 1, truth, "recon8_list", 8,
                                  True, smoke=False)
    assert "suspect" not in rec and rec["pipelined_suspect"] is True
    assert rec["qps"] == pytest.approx(4 / 0.190, rel=1e-6)


def test_partial_floor_pool_excludes_subgate_bf(quiet, monkeypatch):
    # exact search below the gate means the engine is broken, not that
    # the config needs tuning: crash recovery must agree with the
    # in-process fallback and never report it as the floor headline
    def child(kind, t):
        bench._record_partial(
            {"qps": 17446.0, "recall": 0.90, "mode": "bf_tiled",
             "n_probes": None, "refine": False})
        bench._record_partial(
            {"qps": 6000.0, "recall": 0.85, "mode": "recon8_list",
             "n_probes": 32, "refine": False})
        return None, True

    monkeypatch.setattr(bench, "_run_child", child)
    rec = run_main()
    assert rec["value"] == 6000.0
    assert rec["recall_gate"] == bench._RECALL_FLOOR


def test_race_bf_promotes_and_keeps_ivf_best():
    ivf = {"qps": 5315.0, "recall": 0.9965, "mode": "recon8_list",
           "n_probes": 8, "refine": True}
    bf = {"qps": 17446.0, "recall": 1.0, "mode": "bf_tiled",
          "n_probes": None, "refine": False}
    extra = {}
    assert bench._race_bf(ivf, None, bf, extra) is bf
    assert extra["ivf_pq_best"]["qps"] == 5315.0
    # BF slower: IVF keeps the headline, BF recorded with its mode (the
    # racer may be the lossy bf16 variant — it must not read as exact)
    slow_bf = dict(bf, qps=4000.0, mode="bf_tiled_bf16", recall=0.99)
    extra = {}
    assert bench._race_bf(ivf, None, slow_bf, extra) is ivf
    assert extra["bf_best"] == {"qps": 4000.0, "recall": 0.99,
                                "mode": "bf_tiled_bf16"}
    # BF below the gate never wins
    lossy_bf = dict(bf, recall=0.9)
    assert bench._race_bf(ivf, None, lossy_bf, {}) is ivf


def test_race_bf_keeps_floor_ivf_signal():
    # IVF regressed below the gate but cleared the floor: the BF headline
    # must still carry the IVF number (the regression is the signal)
    floor = {"qps": 6000.0, "recall": 0.85, "mode": "recon8_list",
             "n_probes": 32, "refine": False}
    bf = {"qps": 17446.0, "recall": 1.0, "mode": "bf_tiled",
          "n_probes": None, "refine": False}
    extra = {"ladder_validation": {"overall_true_best": floor}}
    assert bench._race_bf(None, floor, bf, extra) is bf
    assert extra["ivf_pq_best"]["qps"] == 6000.0
    assert extra["ladder_validation"]["overall_true_best"] is bf


def test_grouped_crossover_fit():
    """bench_comms._fit_crossover: ring wins imply c >= ratio, planes
    wins imply c < ratio; inconsistent winners must refuse to fit."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench"))
    from bench_comms import _fit_crossover

    row = lambda r, w: {"ratio": r, "winner": w, "margin_ms": 1.0}
    # separable: midpoint of the gap
    c = _fit_crossover([row(0.25, "ring"), row(1.5, "planes")])
    assert 0.25 < c < 1.5
    # swept: bound moves past the raced ratios
    assert _fit_crossover([row(0.25, "ring"), row(1.5, "ring")]) >= 1.5
    assert _fit_crossover([row(0.25, "planes")]) < 0.25
    # inconsistent (planes won BELOW a ring win): no fit
    assert _fit_crossover([row(1.5, "ring"), row(0.25, "planes")]) is None
    assert _fit_crossover([]) is None




@pytest.fixture
def tuned_file(monkeypatch, tmp_path):
    """Point core.tuned at a scratch file; always drop the cache on both
    entry and exit so no tuned state leaks across tests."""
    from raft_tpu.core import tuned

    p = str(tmp_path / "tuned_defaults.json")
    monkeypatch.setattr(tuned, "_PATH", p)
    tuned.reload()
    yield p
    tuned.reload()


def test_tuned_defaults_absent_is_none(tuned_file):
    from raft_tpu.core import tuned

    assert tuned.get("flat_auto_engine") is None
    assert tuned.get("anything", "fallback") == "fallback"


def test_tuned_registry_wellformed():
    """TUNED_KEYS is the machine-readable contract raftlint reads by
    AST: literal entries, known kinds, choice sets where claimed, an
    existing owning bench file where named, and the canonical key
    constants spelled from it."""
    import os
    from raft_tpu.core import tuned

    assert tuned.known_keys() == tuple(sorted(tuned.TUNED_KEYS))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for key, entry in tuned.TUNED_KEYS.items():
        assert entry["kind"] in ("choice", "int", "float", "bool",
                                 "dict", "hints"), key
        if entry["kind"] == "choice":
            assert isinstance(entry["choices"], tuple) and entry["choices"], key
        else:
            assert entry["choices"] is None, key
        if entry["bench"] is not None:
            assert os.path.exists(os.path.join(repo, entry["bench"])), key
    assert tuned.INT8_SCAN_KEY in tuned.TUNED_KEYS
    assert tuned.POLICY_KEY in tuned.TUNED_KEYS
    # the RaBitQ scan engine is chosen by shape, never by a tuned key
    assert not any("bitplane" in key for key in tuned.TUNED_KEYS)
    # the dispatch modules re-export, never respell (importlib: the
    # matrix package re-exports select_k the FUNCTION, which shadows
    # the module on attribute traversal)
    import importlib

    select_k_mod = importlib.import_module("raft_tpu.matrix.select_k")
    from raft_tpu.neighbors import probe_budget

    assert select_k_mod.INT8_SCAN_KEY is tuned.INT8_SCAN_KEY
    assert probe_budget.POLICY_KEY is tuned.POLICY_KEY


def test_tuned_hints_helper_null_vs_missing(tuned_file):
    """tuned.hints() is the ONE hints access path: {} on a missing
    file, a missing key, AND a hand-edited null/corrupt value — the
    divergence the old get("hints", {}) / get("hints") or {} pair had."""
    import json
    from raft_tpu.core import tuned

    assert tuned.hints() == {}
    with open(tuned_file, "w") as f:
        json.dump({"hints": None}, f)
    tuned.reload()
    assert tuned.hints() == {}
    with open(tuned_file, "w") as f:
        json.dump({"hints": {"measured_on": "tpu"}}, f)
    tuned.reload()
    assert tuned.hints() == {"measured_on": "tpu"}


def test_apply_hints_skips_unregistered_keys(tuned_file, monkeypatch):
    """The runtime belt matching the lint-time registry check: a
    _TUNABLE entry drifting from TUNED_KEYS must not bank a winner
    where no dispatch path will ever read it."""
    import json
    import sys, os

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench"))
    import apply_profile_hints as aph

    monkeypatch.setitem(aph._TUNABLE, "bogus_hint", ("bogus_key", str))
    monkeypatch.setitem(aph._TUNABLE, "merge_schedule",
                        ("mnmg_replicated_merge_schedule", str))
    aph.apply_hints([
        {"hint": "bogus_hint", "recommend": "x", "detail": "drifted"},
        {"hint": "ivf_flat_engine_default", "recommend": "list",
         "detail": "ok"},
        # registered choice key, value outside its registered set: the
        # lint rule cannot see computed values, so the belt must
        {"hint": "merge_schedule", "recommend": "ring", "detail": "bad"},
    ])
    rec = json.load(open(tuned_file))
    assert "bogus_key" not in rec
    assert "mnmg_replicated_merge_schedule" not in rec
    assert rec["flat_auto_engine"] == "list"


@pytest.mark.slow
def test_tuned_flat_auto_engine_is_consulted(tuned_file, monkeypatch, rng):
    """engine="auto" must take the measured winner when a tuned file says
    so (a tiny batch would heuristically pick "query")."""
    import json
    from raft_tpu.core import tuned
    from raft_tpu.neighbors import ivf_flat

    data = rng.random((600, 16), dtype=np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=2), data)

    with open(tuned_file, "w") as f:
        json.dump({"flat_auto_engine": "list"}, f)
    tuned.reload()

    hit = []
    orig = ivf_flat._search_impl_listmajor

    def spy(*a, **kw):
        hit.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(ivf_flat, "_search_impl_listmajor", spy)
    ivf_flat.search(ivf_flat.SearchParams(n_probes=4, engine="auto"), index,
                    data[:2], 3)
    assert hit, "tuned flat_auto_engine=list was not consulted"


def test_apply_hints_writes_tuned_file(tuned_file):
    import json
    import sys, os

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench"))
    import apply_profile_hints as aph
    from raft_tpu.core import tuned

    hints = [
        {"hint": "ivf_flat_engine_default", "recommend": "list",
         "detail": "x"},
        {"hint": "bf_engine_default", "recommend": "inspect", "detail": "y"},
    ]
    aph.apply_hints(hints)
    rec = json.load(open(tuned_file))
    assert rec["flat_auto_engine"] == "list"
    assert "bf_engine_default" in rec["hints"]
    assert tuned.get("flat_auto_engine") == "list"


@pytest.mark.slow
def test_tuned_counting_promotion_dispatch(tuned_file, monkeypatch, rng):
    """The select_k counting auto-promotion (tuned winner + TPU backend +
    2-D f32 + VMEM fit) routes through the counting engine and stays
    exact. Off-chip the TPU gate is monkeypatched true — the kernel runs
    in interpret mode, so the DECISION logic (previously dead code until
    a chip session wrote the tuned file) is exercised in CI."""
    import importlib
    import json
    from raft_tpu.core import tuned
    from raft_tpu import matrix

    # the package re-exports the FUNCTION under the module's name; the
    # module object itself comes from importlib
    sk_module = importlib.import_module("raft_tpu.matrix.select_k")

    with open(tuned_file, "w") as f:
        json.dump({"select_k_auto_strategy": "counting"}, f)
    tuned.reload()
    import raft_tpu.core.config as cfg

    monkeypatch.setattr(cfg, "is_tpu_backend", lambda: True)

    hit = []
    orig = sk_module._select_k_counting

    def spy(*a, **kw):
        hit.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(sk_module, "_select_k_counting", spy)
    vals = rng.random((4, 512), dtype=np.float32)
    v, i = matrix.select_k(vals, 5, select_min=True)
    assert hit, "tuned counting promotion was not dispatched"
    want = np.sort(vals, axis=1)[:, :5]
    np.testing.assert_allclose(np.asarray(v), want, rtol=1e-6)

    # ineligible shapes fall back: 3-D batch keeps the default path
    hit.clear()
    v3, _ = matrix.select_k(rng.random((2, 3, 256), dtype=np.float32), 4)
    assert not hit, "counting must not take ndim != 2"


def test_merge_race_fit_rule_matches_r3_surface():
    """fit_rule reproduces the recorded round-3 surface exactly: the
    winner flips with k at fixed nq, which a single-nq threshold cannot
    express; the fitted two-key rule classifies every row."""
    import bench_mnmg_merge as bm

    rows = [
        {"nq": 512, "k": 10, "winner": "replicated",
         "replicated_ms": 8066.66, "sharded_ms": 8784.07},
        {"nq": 2048, "k": 10, "winner": "sharded",
         "replicated_ms": 20716.0, "sharded_ms": 18703.21},
        {"nq": 2048, "k": 100, "winner": "replicated",
         "replicated_ms": 20562.66, "sharded_ms": 28615.05},
    ]
    fit = bm.fit_rule(rows)
    assert fit is not None
    min_nq, per_k, err = fit
    assert err == 0.0
    for r in rows:
        pred = r["nq"] >= min_nq and r["nq"] >= r["k"] * per_k
        assert pred == (r["winner"] == "sharded"), (r, min_nq, per_k)


def test_merge_race_fit_rule_degenerate_surfaces():
    """All-replicated surfaces (and noise-only sharded wins that a
    conservative fit rejects) leave the defaults untouched."""
    import bench_mnmg_merge as bm

    all_repl = [{"nq": n, "k": k, "winner": "replicated",
                 "replicated_ms": 10.0, "sharded_ms": 20.0}
                for n in (512, 4096) for k in (10, 100)]
    assert bm.fit_rule(all_repl) is None


def test_merge_race_fit_rule_weights_by_margin():
    """A tiny noise flip must not outvote a large measured regression:
    the fit sacrifices the 5 ms row, never the 8000 ms row."""
    import bench_mnmg_merge as bm

    rows = [
        # genuine big win for sharded at high volume
        {"nq": 8192, "k": 10, "winner": "sharded",
         "replicated_ms": 9000.0, "sharded_ms": 1000.0},
        # noise-level "sharded win" at a shape the rule must keep
        # replicated because of the k=100 regression below
        {"nq": 2048, "k": 100, "winner": "sharded",
         "replicated_ms": 1000.0, "sharded_ms": 995.0},
        {"nq": 2048, "k": 10, "winner": "replicated",
         "replicated_ms": 1000.0, "sharded_ms": 1005.0},
    ]
    min_nq, per_k, err = bm.fit_rule(rows)
    assert err <= 10.0  # only noise rows misclassified
    # the big-margin row is classified correctly
    assert 8192 >= min_nq and 8192 >= 10 * per_k


def test_merge_race_fit_rule_refuses_unrepresentable_surface():
    """When no (min_nq, per_k) rule can express the winners without
    misclassifying a large share of the measured margin, the fit returns
    None and the production defaults stay untouched."""
    import bench_mnmg_merge as bm

    # sharded wins ONLY at small nq, replicated at large nq — the rule
    # family (sharded iff nq large enough) cannot represent this
    rows = [
        {"nq": 512, "k": 10, "winner": "sharded",
         "replicated_ms": 9000.0, "sharded_ms": 1000.0},
        {"nq": 8192, "k": 10, "winner": "replicated",
         "replicated_ms": 1000.0, "sharded_ms": 9000.0},
    ]
    assert bm.fit_rule(rows) is None


def test_merge_race_apply_preserves_chip_backed_keys(tmp_path, monkeypatch):
    """A CPU-measured fit must not clobber chip-backed tuned keys; a chip
    fit overwrites anything."""
    import json
    import bench_mnmg_merge as bm
    from raft_tpu.core import tuned

    p = str(tmp_path / "tuned_defaults.json")
    monkeypatch.setattr(tuned, "_PATH", p)
    tuned.reload()
    rows = [{"nq": 512, "k": 10, "winner": "replicated",
             "replicated_ms": 10.0, "sharded_ms": 500.0},
            {"nq": 4096, "k": 10, "winner": "sharded",
             "replicated_ms": 500.0, "sharded_ms": 10.0}]
    try:
        # chip-backed keys land first
        bm._apply({"backend": "tpu", "world": 8, "rows": rows})
        tuned.reload()
        assert tuned.get("mnmg_query_sharded_min_nq") == 4096
        on = tuned.get("hints")["mnmg_merge_measured_on"]
        assert on.startswith("tpu")
        # a later CPU fit (different surface) is refused
        cpu_rows = [{"nq": 128, "k": 10, "winner": "sharded",
                     "replicated_ms": 500.0, "sharded_ms": 10.0}]
        bm._apply({"backend": "cpu", "world": 16, "rows": cpu_rows})
        tuned.reload()
        assert tuned.get("mnmg_query_sharded_min_nq") == 4096
        assert tuned.get("hints")["mnmg_merge_measured_on"].startswith("tpu")
        # a chip fit overwrites
        bm._apply({"backend": "tpu", "world": 16, "rows": cpu_rows})
        tuned.reload()
        assert tuned.get("mnmg_query_sharded_min_nq") == 128
    finally:
        tuned.reload()




@pytest.mark.slow  # spawns two real sweep runs (child suite processes)
def test_run_all_resumes_completed_suites_from_job_dir(tmp_path):
    """ISSUE 8: with RAFT_TPU_RUN_ALL_JOB_DIR set, a re-run of the sweep
    SKIPS every suite the previous run committed — the mid-queue
    process-tree-loss scenario the retired run_onchip_queue_resume.sh
    used to hand-patch, now carried by the job runner's manifest."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAFT_TPU_RUN_ALL_SUITES"] = "bench_perf_smoke.py"
    env["RAFT_TPU_RUN_ALL_JOB_DIR"] = str(tmp_path / "sweep")
    env["RAFT_TPU_BENCH_LEDGER"] = str(tmp_path / "ledger.jsonl")
    env["RAFT_TPU_BENCH_OUT"] = str(tmp_path)
    cmd = [sys.executable, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench", "run_all.py")]
    r1 = subprocess.run(cmd, capture_output=True, text=True, env=env,
                        timeout=600)
    assert r1.returncode == 0, r1.stderr[-2000:]
    assert "== bench_perf_smoke.py" in r1.stderr
    from raft_tpu.obs import ledger

    n_rows = len(ledger.read(str(tmp_path / "ledger.jsonl")))
    assert n_rows > 0
    r2 = subprocess.run(cmd, capture_output=True, text=True, env=env,
                        timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    # the committed suite never relaunched: no suite banner, no fresh
    # ledger rows — the manifest skip carried it
    assert "== bench_perf_smoke.py" not in r2.stderr
    assert len(ledger.read(str(tmp_path / "ledger.jsonl"))) == n_rows


@pytest.mark.slow  # full headline ladder at smoke geometry (~1-2 min CPU)
def test_headline_bench_smoke_geometry(monkeypatch, tmp_path):
    """RAFT_TPU_BENCH_SMOKE=1 runs _bench_ivf_pq's ENTIRE control flow
    (build, truth, ladder, pipelined+synced timing, tally, tflops probe)
    on CPU at toy geometry — so no chip session ever executes this
    function's logic for the first time. The record must be headline-
    shaped with both throughput fields and a cleared gate."""
    monkeypatch.setenv("RAFT_TPU_BENCH_SMOKE", "1")
    monkeypatch.setattr(bench, "_PARTIAL_PATH", str(tmp_path / "partial.jsonl"))
    rec = bench._bench_ivf_pq()
    assert rec["metric"] == bench._HEADLINE_METRIC
    assert rec["value"] > 0
    assert rec["recall@10"] >= rec["recall_gate"] >= bench._RECALL_FLOOR
    assert "qps_synced" not in rec  # headline record carries cfg fields only
    # partial file banked at least one ladder row with both QPS flavors
    rows = [json.loads(l) for l in open(tmp_path / "partial.jsonl")]
    assert rows and all("qps_synced" in r and "qps" in r for r in rows)


# -- obs phase banking --------------------------------------------------

def test_run_case_banks_span_phases(capsys):
    """With observability on, `run_case` attaches per-phase span totals
    to its JSON record (the BENCH-row attribution contract)."""
    import common as bench_common

    from raft_tpu import obs

    import jax.numpy as jnp

    def fn():
        with obs.span("bench.phase.score"):
            out = jnp.ones((4,)) * 2
        return out

    obs.enable()
    try:
        obs.reset()
        rec = bench_common.run_case("t", "case", fn, iters=3, warmup=1)
        assert rec["phases"]["bench.phase.score"]["calls"] == 3  # timed only
        printed = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
        assert printed["phases"] == rec["phases"]
    finally:
        obs.disable()
        obs.reset()
    rec = bench_common.run_case("t", "case", fn, iters=2, warmup=1)
    assert "phases" not in rec  # disabled: records unchanged







def test_banker_resume_adopts_and_supersedes_rows(tmp_path, monkeypatch):
    """ISSUE 8 durable-job resume: a resumed Banker carries the prior
    snapshot's rows forward (skipped stages never re-bank), but a
    stage that RE-RUNS supersedes its adopted row — a mid-stage kill
    after banking must not leave duplicates — and mismatched geometry
    or a fresh run adopts nothing."""
    monkeypatch.setenv("RAFT_TPU_BENCH_LEDGER", str(tmp_path / "l.jsonl"))
    out = str(tmp_path / "BENCH_z.json")
    b1 = common.Banker(out, meta={"n": 100})
    b1.add({"stage": "make_data", "s": 1.0}, echo=False)
    b1.add({"stage": "extend", "s": 2.0}, echo=False)

    b2 = common.Banker(out, meta={"n": 100}, resume=True)
    assert [r["stage"] for r in b2.record["rows"]] == ["make_data",
                                                       "extend"]
    # the killed-mid-stage re-run: fresh row replaces the adopted one
    b2.add({"stage": "extend", "s": 9.0}, echo=False)
    assert [(r["stage"], r["s"]) for r in b2.record["rows"]] == [
        ("make_data", 1.0), ("extend", 9.0)]
    # a second add of the same stage (legit repeat) appends normally
    b2.add({"stage": "extend", "s": 3.0}, echo=False)
    assert len(b2.record["rows"]) == 3

    # geometry change -> nothing adopted
    b3 = common.Banker(out, meta={"n": 200}, resume=True)
    assert b3.record["rows"] == []
    # no resume flag -> fresh record as before
    b4 = common.Banker(out, meta={"n": 200})
    assert b4.record["rows"] == []

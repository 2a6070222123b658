"""Chaos suite: seeded fault-injection plans replayed against the
resilience layer — degraded-mode MNMG search (kill a rank, merge the
survivors, report coverage), health-check barrier + liveness probing,
bootstrap retry, and checkpoint re-hydration. Runs on a 4-rank submesh
of the virtual 8-device CPU mesh; `RAFT_TPU_FAULT_SEED` pins the chaos
seed in CI (ci/test.sh)."""

import os
import threading
import time

import numpy as np
import pytest

from raft_tpu.comms import Comms, mnmg, resilience
from raft_tpu.comms.resilience import DegradedSearchResult, RankHealth
from raft_tpu.core import faults
from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
from raft_tpu.random import make_blobs

SEED = int(os.environ.get(faults.ENV_SEED, "1234"))
WORLD = 4


@pytest.fixture(scope="module")
def comms4():
    return Comms(n_devices=WORLD)


@pytest.fixture(scope="module")
def blobs():
    data, _ = make_blobs(1600, 16, n_clusters=6, cluster_std=0.4, seed=13)
    return np.asarray(data)


@pytest.fixture(scope="module")
def flat8(comms4, blobs):
    return mnmg.ivf_flat_build(
        comms4, ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), blobs)


@pytest.fixture(scope="module")
def pq8(comms4, blobs):
    return mnmg.ivf_pq_build(
        comms4, ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4),
        blobs)


@pytest.fixture(scope="module")
def rb8(comms4, blobs):
    from raft_tpu.neighbors import ivf_rabitq

    return mnmg.ivf_rabitq_build(
        comms4, ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=4), blobs)


def _surviving_prefilter(index, dead_rank: int) -> np.ndarray:
    """Boolean keep-mask excluding every row the dead rank's shard owns
    (its slot table holds the global ids)."""
    hg = np.asarray(index.host_gids[dead_rank])
    mask = np.ones(index.n, bool)
    mask[hg[hg >= 0]] = False
    return mask


# -- FaultPlan registry -------------------------------------------------

def test_fault_plan_registry_and_determinism():
    plan = faults.FaultPlan(
        [faults.Fault(kind="kill_rank", rank=1),
         faults.Fault(kind="slow_rank", site="resilience.*", rank=2,
                      latency_s=0.5)],
        seed=SEED,
    )
    assert plan.killed_ranks() == (1,)
    assert plan.matching("resilience.barrier", "slow_rank")[0].rank == 2
    assert plan.matching("mnmg.knn.scores", "slow_rank") == ()
    # fingerprint is stable and replayable
    replay = faults.FaultPlan(plan.faults, seed=SEED)
    assert plan.trace_key() == replay.trace_key()
    assert plan.site_seed("a") == replay.site_seed("a")
    assert plan.site_seed("a") != plan.site_seed("b")
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.Fault(kind="meteor_strike")
    with pytest.raises(ValueError, match="fraction"):
        faults.Fault(kind="corrupt_shard", fraction=1.5)
    # no plan installed -> every hook is inert
    assert faults.active_plan() is None
    assert faults.trace_key() is None
    assert not faults.active_for("comms.allreduce")
    with plan.install():
        assert faults.active_plan() is plan
    assert faults.active_plan() is None


def test_rank_health_mask():
    h = RankHealth.all_healthy(WORLD)
    assert h.coverage() == 1.0 and not h.degraded
    h.mark_unhealthy(3)
    assert h.coverage() == 0.75 and h.degraded
    assert h.healthy_ranks() == (0, 1, 2)
    h.mark_healthy(3)
    assert h.coverage() == 1.0


# -- degraded-mode distributed search ----------------------------------

def test_degraded_ivf_flat_matches_survivor_merge(comms4, blobs, flat8):
    """1 of 4 ranks killed mid-serving: coverage == 0.75 and the merged
    result is EXACTLY the 3-shard reference merge (== prefiltering the
    dead shard's rows on a healthy mesh)."""
    q = blobs[:23]
    plan = faults.FaultPlan(
        [faults.Fault(kind="kill_rank", rank=1)], seed=SEED)
    with plan.install():
        health = resilience.probe_health(comms4, timeout_s=30)
        res = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8, health=health)
    assert isinstance(res, DegradedSearchResult)
    assert res.coverage == 0.75
    rv, ri = mnmg.ivf_flat_search(
        flat8, q, 5, n_probes=8, prefilter=_surviving_prefilter(flat8, 1))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
    # healthy mask returns coverage 1.0 and the undegraded result
    full = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8,
                                health=RankHealth.all_healthy(WORLD))
    assert full.coverage == 1.0
    v0, i0 = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8)
    np.testing.assert_array_equal(np.asarray(full.ids), np.asarray(i0))


def test_degraded_ivf_pq_matches_survivor_merge(comms4, blobs, pq8):
    q = blobs[:23]
    health = RankHealth.all_healthy(WORLD).mark_unhealthy(1)
    res = mnmg.ivf_pq_search(pq8, q, 5, n_probes=8, health=health)
    assert res.coverage == 0.75
    rv, ri = mnmg.ivf_pq_search(
        pq8, q, 5, n_probes=8, prefilter=_surviving_prefilter(pq8, 1))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
    # every surviving id is a real row the dead rank does not own
    dead = set(np.asarray(pq8.host_gids[1]).ravel().tolist()) - {-1}
    assert not (set(np.asarray(res.ids).ravel().tolist()) & dead)


def test_degraded_knn_matches_survivor_merge(comms4, blobs):
    q = blobs[:17]
    health = RankHealth.all_healthy(WORLD).mark_unhealthy(2)
    res = mnmg.knn(comms4, blobs, q, 10, health=health)
    assert res.coverage == 0.75
    # reference: prefilter the dead rank's contiguous row block away
    n = len(blobs)
    per = -(-n // WORLD)
    mask = np.ones(n, bool)
    mask[2 * per: min(3 * per, n)] = False
    rv, ri = mnmg.knn(comms4, blobs, q, 10, prefilter=mask)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))


def test_degraded_sharded_request_degrades_to_replicated(comms4, blobs, flat8):
    q = blobs[:32]
    health = RankHealth.all_healthy(WORLD).mark_unhealthy(0)
    with pytest.warns(UserWarning, match="REPLICATED"):
        res = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8,
                                   query_mode="sharded", health=health)
    assert res.coverage == 0.75
    assert np.asarray(res.ids).shape == (32, 5)
    # health sized for the wrong mesh is rejected loudly
    with pytest.raises(ValueError, match="health mask covers"):
        mnmg.ivf_flat_search(flat8, q, 5, n_probes=8,
                             health=RankHealth.all_healthy(8))


def test_corrupt_shard_masked_by_degraded_mode(comms4, blobs, flat8):
    """A poisoned shard (NaN scores) must not leak once the rank is
    masked: kill+corrupt rank 1 and the result still equals the 3-shard
    reference. The same corruption WITHOUT the mask visibly poisons."""
    q = blobs[:23]
    kill_and_corrupt = faults.FaultPlan(
        [faults.Fault(kind="kill_rank", rank=1),
         faults.Fault(kind="corrupt_shard", site="mnmg.ivf_flat.scores",
                      rank=1, fraction=1.0)],
        seed=SEED,
    )
    with kill_and_corrupt.install():
        health = resilience.probe_health(comms4, timeout_s=30)
        res = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8, health=health)
    rv, ri = mnmg.ivf_flat_search(
        flat8, q, 5, n_probes=8, prefilter=_surviving_prefilter(flat8, 1))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
    # unmasked corruption really fires (the drill is not a no-op)
    corrupt_only = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="mnmg.ivf_flat.scores",
                      rank=1, fraction=1.0)],
        seed=SEED,
    )
    clean_v, _ = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8)
    with corrupt_only.install():
        bad_v, _ = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8)
    assert not np.array_equal(np.asarray(bad_v), np.asarray(clean_v),
                              equal_nan=True)


def test_seeded_fault_replay_is_bit_deterministic(comms4, blobs, flat8):
    """Replaying the same seeded FaultPlan produces bit-identical
    degraded output across two runs (the chaos-drill reproducibility
    contract)."""
    q = blobs[:23]
    def run():
        plan = faults.FaultPlan(
            [faults.Fault(kind="corrupt_shard", site="mnmg.ivf_flat.scores",
                          rank=0, fraction=0.3),
             faults.Fault(kind="kill_rank", rank=3)],
            seed=SEED,
        )
        with plan.install():
            health = resilience.probe_health(comms4, timeout_s=30)
            return mnmg.ivf_flat_search(flat8, q, 5, n_probes=8,
                                        health=health)
    a, b = run(), run()
    assert a.coverage == b.coverage == 0.75
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.values), np.asarray(b.values))


# -- health barrier + probing ------------------------------------------

def test_health_barrier_and_probe(comms4):
    elapsed = resilience.health_barrier(comms4, timeout_s=30)
    assert elapsed < 30
    assert resilience.probe_health(comms4, timeout_s=30).coverage() == 1.0
    # a small injected straggler latency delays but passes
    slow = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="resilience.barrier", rank=1,
                      latency_s=0.15)],
        seed=SEED,
    )
    with slow.install():
        elapsed = resilience.health_barrier(comms4, timeout_s=30)
    assert elapsed >= 0.15
    # a straggler declared beyond the deadline is masked WITHOUT
    # sleeping the deadline out
    dead_slow = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="resilience.barrier", rank=2,
                      latency_s=9999.0)],
        seed=SEED,
    )
    t0 = time.monotonic()
    with dead_slow.install():
        health = resilience.probe_health(comms4, timeout_s=5)
    assert time.monotonic() - t0 < 5
    assert health.coverage() == 0.75 and not health.mask[2]
    # rank=-1 scopes the straggler to EVERY rank: all masked, no sleep
    all_slow = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="resilience.barrier",
                      latency_s=9999.0)],
        seed=SEED,
    )
    t0 = time.monotonic()
    with all_slow.install():
        health = resilience.probe_health(comms4, timeout_s=5)
    assert time.monotonic() - t0 < 5
    assert health.coverage() == 0.0


def test_health_barrier_deadline_covers_injected_latency(comms4):
    """The barrier deadline spans the straggler sleep at the injection
    site: latency past timeout_s raises HealthCheckTimeout instead of
    handing synchronize a fresh budget."""
    slow = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="resilience.barrier",
                      latency_s=0.2)],
        seed=SEED,
    )
    with slow.install():
        with pytest.raises(resilience.HealthCheckTimeout):
            resilience.health_barrier(comms4, timeout_s=0.1)
    # plenty of budget: the same injected latency passes
    plan = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="resilience.barrier",
                      latency_s=0.05)],
        seed=SEED,
    )
    with plan.install():
        assert resilience.health_barrier(comms4, timeout_s=30) >= 0.05


def test_probe_health_passed_plan_drives_barrier(comms4):
    """A plan passed explicitly (not installed) must drive the barrier's
    injection site exactly like an installed one — sub-deadline
    straggler latency shows up in the probe's wall time."""
    plan = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="resilience.barrier", rank=1,
                      latency_s=0.1)],
        seed=SEED,
    )
    t0 = time.monotonic()
    health = resilience.probe_health(comms4, timeout_s=30, plan=plan)
    assert time.monotonic() - t0 >= 0.1
    assert health.coverage() == 1.0  # slow but under deadline: healthy


def test_health_barrier_cancellable(comms4):
    """The barrier wait rides interruptible.synchronize: another thread
    can cancel it (the operator's escape hatch from a hung mesh)."""
    from raft_tpu.core.interruptible import InterruptedException, cancel

    tid = threading.get_ident()
    t = threading.Timer(0.05, cancel, args=(tid,))
    slow = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="resilience.barrier",
                      latency_s=0.2)],
        seed=SEED,
    )
    t.start()
    try:
        with slow.install():
            # the injected sleep holds the wait window open long enough
            # for the timer to land before/during synchronize
            resilience.health_barrier(comms4, timeout_s=30)
    except InterruptedException:
        pass  # cancel landed mid-wait — also a pass
    finally:
        t.join()
    # flag fully cleared either way: the next barrier completes
    assert resilience.health_barrier(comms4, timeout_s=30) >= 0


# -- bootstrap retry ----------------------------------------------------

def test_bootstrap_retry_recovers_from_flaky_init(monkeypatch):
    """2 injected flaky-init failures recover without operator
    intervention (the retry-with-backoff acceptance bar)."""
    from raft_tpu.comms import comms as comms_mod

    calls = {"n": 0}

    def fake_initialize(**kwargs):
        calls["n"] += 1

    monkeypatch.setattr(comms_mod.jax.distributed, "initialize",
                        fake_initialize)
    monkeypatch.setattr(comms_mod, "_MULTIHOST_INITIALIZED", False)
    plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap", site="comms.bootstrap",
                      count=2)],
        seed=SEED,
    )
    with plan.install():
        assert comms_mod.bootstrap_multihost(backoff_s=0.01) is True
    assert calls["n"] == 1  # two injected failures, then one real init
    f = plan.faults[0]
    assert plan.fire_count("comms.bootstrap", f) == 2
    # idempotent after success
    assert comms_mod.bootstrap_multihost() is False
    monkeypatch.setattr(comms_mod, "_MULTIHOST_INITIALIZED", False)


def test_bootstrap_retry_exhaustion_propagates(monkeypatch):
    from raft_tpu.comms import comms as comms_mod

    monkeypatch.setattr(
        comms_mod.jax.distributed, "initialize",
        lambda **kw: (_ for _ in ()).throw(RuntimeError("unreachable")))
    monkeypatch.setattr(comms_mod, "_MULTIHOST_INITIALIZED", False)
    with pytest.raises(RuntimeError, match="unreachable"):
        comms_mod.bootstrap_multihost(max_retries=1, backoff_s=0.01)
    assert comms_mod._MULTIHOST_INITIALIZED is False


def test_retry_with_backoff_policy():
    attempts = []

    def flaky():
        attempts.append(time.monotonic())
        if len(attempts) < 3:
            raise RuntimeError("transient")
        return "ok"

    assert resilience.retry_with_backoff(flaky, base_delay_s=0.01) == "ok"
    assert len(attempts) == 3
    with pytest.raises(ValueError):
        resilience.retry_with_backoff(
            lambda: (_ for _ in ()).throw(ValueError("genuine")),
            retry_on=(RuntimeError,), base_delay_s=0.01)


def test_retry_backoff_seeded_jitter_and_obs_events():
    """The jitter schedule is SEEDED (same seed + describe -> identical
    delays, so chaos drills replay bit-for-bit) and every retry lands a
    kind="retry" event on the obs bus."""
    from raft_tpu import obs

    def delays_for(seed, describe="op"):
        obs.reset()
        def always_fail():
            raise RuntimeError("transient")
        with pytest.raises(resilience.RetryExhausted):
            resilience.retry_with_backoff(
                always_fail, max_retries=3, base_delay_s=0.001,
                jitter=0.5, seed=seed, describe=describe)
        return [e["delay_s"] for e in obs.bus().events(kind="retry")]

    obs.enable()
    try:
        a = delays_for(11)
        b = delays_for(11)
        c = delays_for(12)
        assert len(a) == 3  # one event per retry attempt
        assert a == b  # seeded: identical schedule
        assert a != c  # a different seed jitters differently
        # jitter in [1, 1.5): every delay at least the base schedule
        assert all(d >= 0.001 * 2 ** i for i, d in enumerate(a))
        ev = obs.bus().events(kind="retry")[-1]
        assert ev["attempt"] == 3 and "transient" in ev["error"]
    finally:
        obs.reset()
        obs.disable()


def test_retry_backoff_max_elapsed_cap():
    """max_elapsed_s bounds the WHOLE retry window: a budget smaller
    than the first backoff sleep gives up immediately instead of
    retrying past it, and the exhaustion error chains the last cause."""
    attempts = []

    def always_fail():
        attempts.append(1)
        raise RuntimeError("still down")

    t0 = time.monotonic()
    with pytest.raises(resilience.RetryExhausted, match="budget spent"):
        resilience.retry_with_backoff(
            always_fail, max_retries=50, base_delay_s=10.0,
            max_elapsed_s=0.05)
    assert time.monotonic() - t0 < 5  # never slept the 10 s backoff
    assert len(attempts) == 1


def test_rehydrate_retry_exhaustion_chains_last_cause(comms4, blobs, flat8,
                                                      tmp_path):
    """Retry exhaustion surfaces as RetryExhausted CHAINING the final
    underlying failure — the last real error is never lost behind the
    retry machinery."""
    path = str(tmp_path / "flat_exhaust.ckpt")
    mnmg.ivf_flat_save(path, flat8)
    plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap", site="mnmg_ckpt.load",
                      count=10)],
        seed=SEED,
    )
    with plan.install():
        with pytest.raises(resilience.RetryExhausted,
                           match="rehydrate") as ei:
            resilience.rehydrate(comms4, path, max_retries=2)
    # the cause chain holds the LAST injected failure (attempt 3 of 10)
    assert isinstance(ei.value.__cause__, faults.FaultInjected)
    assert "3/10" in str(ei.value.__cause__)
    f = plan.faults[0]
    assert plan.fire_count("mnmg_ckpt.load", f) == 3  # 1 try + 2 retries


# -- collective + loader + kmeans drills --------------------------------

def test_drop_collective_degrades_kmeans_not_crashes(comms4, blobs):
    plan = faults.FaultPlan(
        [faults.Fault(kind="drop_collective", site="comms.allreduce",
                      rank=3)],
        seed=SEED,
    )
    with plan.install():
        centers, inertia, _ = mnmg.kmeans_fit(comms4, blobs, 6, max_iter=5,
                                              seed=0)
    assert np.isfinite(np.asarray(centers)).all()
    assert np.isfinite(inertia)


def test_batch_loader_chaos():
    from raft_tpu.neighbors.batch_loader import BatchLoadIterator

    host = np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32)
    plan = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="batch_loader.load",
                      latency_s=0.02),
         faults.Fault(kind="corrupt_shard", site="batch_loader.load",
                      fraction=0.25)],
        seed=SEED,
    )
    t0 = time.monotonic()
    with plan.install():
        blocks = [np.asarray(b) for b, _ in
                  BatchLoadIterator(host, 16, prefetch=False)]
    assert time.monotonic() - t0 >= 4 * 0.02
    assert any(np.isnan(b).any() for b in blocks)
    # successive equally-shaped blocks draw DIFFERENT corruption masks
    # (periodic corruption would blind drills to offset-dependent bugs)
    masks = [np.isnan(b) for b in blocks if np.isnan(b).any()]
    assert len(masks) >= 2 and not np.array_equal(masks[0], masks[1])
    # ...but a reset plan replays the identical sequence
    plan.reset()
    with plan.install():
        replay = [np.asarray(b) for b, _ in
                  BatchLoadIterator(host, 16, prefetch=False)]
    for a, b in zip(blocks, replay):
        np.testing.assert_array_equal(a, b)
    # rank-scoped host faults miss this controller (process_index 0)
    scoped = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="batch_loader.load",
                      rank=3, fraction=1.0)],
        seed=SEED,
    )
    with scoped.install():
        missed = [np.asarray(b) for b, _ in BatchLoadIterator(host, 16)]
    assert not any(np.isnan(b).any() for b in missed)
    # without a plan the loader is untouched
    clean = [np.asarray(b) for b, _ in BatchLoadIterator(host, 16)]
    assert not any(np.isnan(b).any() for b in clean)


def test_corrupt_pq_shard_masked_by_degraded_mode(comms4, blobs, pq8):
    """IVF-PQ twin of the flat drill: a poisoned PQ shard (NaN scores at
    site mnmg.ivf_pq.scores) must not leak once the rank is masked."""
    q = blobs[:23]
    kill_and_corrupt = faults.FaultPlan(
        [faults.Fault(kind="kill_rank", rank=1),
         faults.Fault(kind="corrupt_shard", site="mnmg.ivf_pq.scores",
                      rank=1, fraction=1.0)],
        seed=SEED,
    )
    with kill_and_corrupt.install():
        health = resilience.probe_health(comms4, timeout_s=30)
        res = mnmg.ivf_pq_search(pq8, q, 5, n_probes=8, health=health)
    assert res.coverage == 0.75
    rv, ri = mnmg.ivf_pq_search(
        pq8, q, 5, n_probes=8, prefilter=_surviving_prefilter(pq8, 1))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
    # unmasked corruption really fires (the drill is not a no-op)
    corrupt_only = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="mnmg.ivf_pq.scores",
                      rank=1, fraction=1.0)],
        seed=SEED,
    )
    clean_v, _ = mnmg.ivf_pq_search(pq8, q, 5, n_probes=8)
    with corrupt_only.install():
        bad_v, _ = mnmg.ivf_pq_search(pq8, q, 5, n_probes=8)
    assert not np.array_equal(np.asarray(bad_v), np.asarray(clean_v),
                              equal_nan=True)


def test_corrupt_rabitq_shard_masked_by_degraded_mode(comms4, blobs, rb8):
    """IVF-RaBitQ twin of the PQ drill (site mnmg.ivf_rabitq.scores): a
    poisoned estimator shard must not leak once the rank is masked —
    degraded result == survivor-prefilter reference, bit for bit."""
    q = blobs[:23]
    kill_and_corrupt = faults.FaultPlan(
        [faults.Fault(kind="kill_rank", rank=1),
         faults.Fault(kind="corrupt_shard", site="mnmg.ivf_rabitq.scores",
                      rank=1, fraction=1.0)],
        seed=SEED,
    )
    with kill_and_corrupt.install():
        health = resilience.probe_health(comms4, timeout_s=30)
        res = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8, health=health)
    assert res.coverage == 0.75
    rv, ri = mnmg.ivf_rabitq_search(
        rb8, q, 5, n_probes=8, prefilter=_surviving_prefilter(rb8, 1))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
    # unmasked corruption really fires (the drill is not a no-op)
    corrupt_only = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="mnmg.ivf_rabitq.scores",
                      rank=1, fraction=1.0)],
        seed=SEED,
    )
    clean_v, _ = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8)
    with corrupt_only.install():
        bad_v, _ = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8)
    assert not np.array_equal(np.asarray(bad_v), np.asarray(clean_v),
                              equal_nan=True)


def test_rabitq_build_encode_chaos(blobs):
    """Host site ivf_rabitq.build.encode: a slow encode pass pays the
    injected latency WITHOUT touching results (host sleeps must never
    change traced math), and a flaky encode raises FaultInjected so
    callers' retry loops see chaos distinctly from real failures."""
    from raft_tpu.neighbors import ivf_rabitq

    params = ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=4)
    clean = ivf_rabitq.build(params, blobs, seed=0)
    slow_plan = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="ivf_rabitq.build.encode",
                      latency_s=0.05)],
        seed=SEED,
    )
    t0 = time.monotonic()
    with slow_plan.install():
        slowed = ivf_rabitq.build(params, blobs, seed=0)
    assert time.monotonic() - t0 >= 0.05
    np.testing.assert_array_equal(np.asarray(slowed.codes),
                                  np.asarray(clean.codes))
    np.testing.assert_array_equal(np.asarray(slowed.aux),
                                  np.asarray(clean.aux))
    flaky_plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap",
                      site="ivf_rabitq.build.encode", count=1)],
        seed=SEED,
    )
    with flaky_plan.install():
        with pytest.raises(faults.FaultInjected):
            ivf_rabitq.build(params, blobs, seed=0)
        # the armed count is spent: the retry (same plan) succeeds
        retry = ivf_rabitq.build(params, blobs, seed=0)
    np.testing.assert_array_equal(np.asarray(retry.codes),
                                  np.asarray(clean.codes))


def test_rabitq_mnmg_encode_site_fires_per_call(comms4, blobs):
    """The distributed build's encode hook is HOST-side: it must fire on
    EVERY call, including ones served entirely by the warm jit-wrapper
    cache (a trace-time hook would silently disarm after the first
    build per cache key)."""
    from raft_tpu.neighbors import ivf_rabitq

    params = ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=2)
    mnmg.ivf_rabitq_build(comms4, params, blobs)  # warm every wrapper
    plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap",
                      site="ivf_rabitq.build.encode", count=2)],
        seed=SEED,
    )
    with plan.install():
        for _ in range(2):  # both warm-cache calls still inject
            with pytest.raises(faults.FaultInjected):
                mnmg.ivf_rabitq_build(comms4, params, blobs)
        mnmg.ivf_rabitq_build(comms4, params, blobs)  # count spent


def test_corrupt_knn_shard_masked_by_degraded_mode(comms4, blobs):
    """Distributed brute-force twin (site mnmg.knn.scores): poisoned
    shard + mask == survivor-prefilter reference, bit for bit."""
    q = blobs[:17]
    plan = faults.FaultPlan(
        [faults.Fault(kind="kill_rank", rank=2),
         faults.Fault(kind="corrupt_shard", site="mnmg.knn.scores",
                      rank=2, fraction=1.0)],
        seed=SEED,
    )
    with plan.install():
        health = resilience.probe_health(comms4, timeout_s=30)
        res = mnmg.knn(comms4, blobs, q, 10, health=health)
    assert res.coverage == 0.75
    n = len(blobs)
    per = -(-n // WORLD)
    mask = np.ones(n, bool)
    mask[2 * per: min(3 * per, n)] = False
    rv, ri = mnmg.knn(comms4, blobs, q, 10, prefilter=mask)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
    # unmasked corruption visibly poisons (the drill is not a no-op)
    corrupt_only = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="mnmg.knn.scores",
                      rank=2, fraction=1.0)],
        seed=SEED,
    )
    clean_v, _ = mnmg.knn(comms4, blobs, q, 10)
    with corrupt_only.install():
        bad_v, _ = mnmg.knn(comms4, blobs, q, 10)
    assert not np.array_equal(np.asarray(bad_v), np.asarray(clean_v),
                              equal_nan=True)


def test_corrupt_fused_scan_candidates_drill():
    """Site fused.scan.scores: corrupt_shard NaNs the fused kernel's
    candidate buffer in-trace (ops/fused_scan._maybe_corrupt). The
    fused brute-force engine must visibly poison under the plan — and
    return BIT-IDENTICAL clean results once the plan is cleared, which
    pins the fault_key-retrace contract of the fused jits (a stale
    clean trace under an installed plan, or a stale poisoned trace
    after clearing, both fail here)."""
    rng = np.random.default_rng(SEED)
    data = rng.integers(-8, 8, (1200, 16)).astype(np.float32)
    q = data[:19]
    clean_v, clean_i = brute_force.knn(data, q, 5, engine="pallas")
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="fused.scan.scores",
                      fraction=1.0)],
        seed=SEED,
    )
    with plan.install():
        bad_v, _ = brute_force.knn(data, q, 5, engine="pallas")
    assert np.isnan(np.asarray(bad_v)).all()  # fraction=1.0: total rot
    # plan cleared: bit-identical to the pre-drill clean run
    v2, i2 = brute_force.knn(data, q, 5, engine="pallas")
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(clean_v))
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(clean_i))
    # the same site guards the list-scan geometry (IVF-Flat fused
    # engine): corruption must reach it too, through the shared hook
    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), data)
    sp = ivf_flat.SearchParams(n_probes=8, engine="pallas")
    flat_clean_v, _ = ivf_flat.search(sp, index, q, 5)
    with plan.install():
        flat_bad_v, _ = ivf_flat.search(sp, index, q, 5)
    assert np.isnan(np.asarray(flat_bad_v)).all()
    flat_v2, _ = ivf_flat.search(sp, index, q, 5)
    np.testing.assert_array_equal(
        np.asarray(flat_v2), np.asarray(flat_clean_v))


def test_corrupt_probe_budget_drill():
    """Site ivf.probe_budget: corrupt_shard NaNs the traced per-query
    budget vector inside the adaptive plan; the plan clamps corrupted
    entries down to min_probes — SHRUNKEN budgets. The drill proves the
    degradation is visible (fewer lists actually scanned, results drift
    from the clean adaptive run) yet SAFE (full-shape valid results, no
    crash), and that clearing the plan restores bit-identical clean
    results (the fault_key-retrace contract of the plan jit)."""
    from raft_tpu.neighbors import probe_budget

    rng = np.random.default_rng(SEED)
    # OVERLAPPING clusters: true neighbor sets span several lists, so a
    # budget shrunk to 1 probed list visibly loses neighbors
    cent = rng.normal(size=(16, 24)) * 1.5
    data = (cent[rng.integers(0, 16, 3000)]
            + rng.normal(size=(3000, 24))).astype(np.float32)
    q = data[:32]
    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4), data)
    # saturated budgets: every query scans all 8 probes when clean
    sp = ivf_flat.SearchParams(n_probes=8, budget_tau=1.0, early_term=False)
    clean_v, clean_i = ivf_flat.search(sp, index, q, 10)
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="ivf.probe_budget",
                      fraction=1.0)],
        seed=SEED,
    )
    with plan.install():
        _, scanned_bad = probe_budget.probe_plan(
            q, index.centers, n_probes=8, min_probes=1, k=10,
            metric=index.metric, tau=1.0)
        bad_v, bad_i = ivf_flat.search(sp, index, q, 10)
    # every budget shrank to the floor: 1 list scanned per query
    assert (np.asarray(scanned_bad) == 1).all()
    # degraded recall is VISIBLE (results drift from clean) yet safe
    assert np.asarray(bad_i).shape == (32, 10)
    assert not np.array_equal(np.asarray(bad_i), np.asarray(clean_i))
    # plan cleared: bit-identical to the pre-drill clean run
    v2, i2 = ivf_flat.search(sp, index, q, 10)
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(clean_v))
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(clean_i))


def test_corrupt_fused_scan_integer_geometries_drill():
    """Site fused.scan.scores on the integer fused geometry:
    the int8 PQ-recon list scan runs the shared `_maybe_corrupt` hook on
    its candidate buffers, so a corrupt_shard plan visibly poisons it —
    and a cleared plan restores BIT-IDENTICAL clean results (the
    fault_key-retrace contract, replayed under the chaos tier's 3-seed
    RAFT_TPU_FAULT_SEED matrix)."""
    from raft_tpu.neighbors import ivf_pq

    rng = np.random.default_rng(SEED)
    data = rng.integers(-8, 8, (2000, 32)).astype(np.float32)
    q = data[:9]
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="fused.scan.scores",
                      fraction=1.0)],
        seed=SEED,
    )

    # int8 PQ-recon fused trim
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=8, kmeans_n_iters=4, pq_dim=16), data)
    sp = ivf_pq.SearchParams(n_probes=8, trim_engine="fused",
                             score_dtype="int8")
    clean_v, clean_i = ivf_pq.search(sp, idx, q, 5)
    with plan.install():
        bad_v, _ = ivf_pq.search(sp, idx, q, 5)
    assert np.isnan(np.asarray(bad_v)).all()  # fraction=1.0: total rot
    v2, i2 = ivf_pq.search(sp, idx, q, 5)
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(clean_v))
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(clean_i))


def test_drop_allgather_contribution(comms4):
    """drop_collective at comms.allgather: the faulted rank's rows come
    back as the reduction identity (zeros) on EVERY rank — the
    non-deadlocking model of 'this rank's data never arrived'."""
    import jax
    from jax.sharding import PartitionSpec as P

    ac = comms4.comms
    x = np.arange(WORLD * 3, dtype=np.float32).reshape(WORLD, 3) + 1.0

    def run():
        def body(s):
            return ac.allgather(s[0])[None]

        return np.asarray(jax.shard_map(
            body, mesh=comms4.mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        )(comms4.shard(x)))

    clean = run()
    np.testing.assert_array_equal(clean[0], x)
    plan = faults.FaultPlan(
        [faults.Fault(kind="drop_collective", site="comms.allgather",
                      rank=2)],
        seed=SEED,
    )
    with plan.install():
        dropped = run()
    assert (dropped[0][2] == 0).all()  # rank 2's rows never arrived
    np.testing.assert_array_equal(dropped[0][[0, 1, 3]], x[[0, 1, 3]])


def test_kmeans_partials_corruption_fires_and_replays(comms4, blobs):
    """corrupt_shard at mnmg.kmeans.partials (a poisoned shard's EM
    contribution BEFORE the allreduce) visibly changes the fit, and a
    replayed plan reproduces it bit-for-bit."""
    clean_c, _, _ = mnmg.kmeans_fit(comms4, blobs, 6, max_iter=5, seed=0)
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="mnmg.kmeans.partials",
                      rank=1, fraction=0.5)],
        seed=SEED,
    )
    with plan.install():
        c1, _, _ = mnmg.kmeans_fit(comms4, blobs, 6, max_iter=5, seed=0)
    replay = faults.FaultPlan(plan.faults, seed=SEED)
    with replay.install():
        c2, _, _ = mnmg.kmeans_fit(comms4, blobs, 6, max_iter=5, seed=0)
    assert not np.array_equal(np.asarray(c1), np.asarray(clean_c),
                              equal_nan=True)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_kmeans_step_straggler_slows_but_identical(comms4, blobs):
    """slow_rank at the host driver site mnmg.kmeans.step: every
    iteration pays the injected latency, and the math is untouched —
    host sleeps must never change traced results."""
    clean_c, _, clean_it = mnmg.kmeans_fit(comms4, blobs, 6, max_iter=4,
                                           seed=0)
    plan = faults.FaultPlan(
        [faults.Fault(kind="slow_rank", site="mnmg.kmeans.step",
                      latency_s=0.02)],
        seed=SEED,
    )
    t0 = time.monotonic()
    with plan.install():
        c, _, it = mnmg.kmeans_fit(comms4, blobs, 6, max_iter=4, seed=0)
    assert time.monotonic() - t0 >= it * 0.02
    assert it == clean_it
    np.testing.assert_array_equal(np.asarray(c), np.asarray(clean_c))


# -- checkpoint re-hydration --------------------------------------------

def test_rehydrate_restores_full_coverage(comms4, blobs, flat8, tmp_path):
    path = str(tmp_path / "flat.ckpt")
    mnmg.ivf_flat_save(path, flat8)
    q = blobs[:23]
    v0, i0 = mnmg.ivf_flat_search(flat8, q, 5, n_probes=8)
    degraded = mnmg.ivf_flat_search(
        flat8, q, 5, n_probes=8,
        health=RankHealth.all_healthy(WORLD).mark_unhealthy(1))
    assert degraded.coverage == 0.75
    # rehydrate through 2 injected flaky checkpoint reads
    plan = faults.FaultPlan(
        [faults.Fault(kind="flaky_bootstrap", site="mnmg_ckpt.load",
                      count=2)],
        seed=SEED,
    )
    with plan.install():
        fresh, health = resilience.rehydrate(comms4, path)
    assert health.coverage() == 1.0
    res = mnmg.ivf_flat_search(fresh, q, 5, n_probes=8, health=health)
    assert res.coverage == 1.0
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(i0))
    np.testing.assert_allclose(np.asarray(res.values), np.asarray(v0),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="checkpoint"):
        bad = str(tmp_path / "bad.ckpt")
        from raft_tpu.core.serialize import serialize_arrays

        serialize_arrays(bad, {"x": np.zeros(1)}, {"kind": "not_an_index"})
        resilience.rehydrate(comms4, bad)


def test_ivf_pq_save_local_load_chaos_roundtrip(comms4, blobs, tmp_path):
    """IVF-PQ sharded-checkpoint round-trip under a corrupt_shard fault
    plan (the flat path had the only ckpt chaos drill before): the
    seeded "ckpt.corrupt_file" sector rot hits the part files at save;
    the checksum-verified load heals from the mirror slices of a
    replicated index and the loaded search stays bit-identical."""
    pq2 = mnmg.ivf_pq_build(
        comms4, ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4),
        blobs, replication=2)
    q = blobs[:23]
    v0, i0 = mnmg.ivf_pq_search(pq2, q, 5, n_probes=8)
    path = str(tmp_path / "pq_chaos.ckpt")
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="ckpt.corrupt_file",
                      fraction=0.01)],  # a ~1%-of-file bad sector
        seed=SEED,
    )
    from raft_tpu.core.serialize import ChecksumError

    with plan.install():
        mnmg.ivf_pq_save_local(path, pq2)
    try:
        loaded = mnmg.ivf_pq_load(comms4, path)
    except ChecksumError:
        # the seeded sector landed on something unmirrored (quantizer
        # manifest): detection without heal — still never silent
        return
    assert loaded.replicas is not None and loaded.replicas.r == 2
    v1, i1 = mnmg.ivf_pq_search(loaded, q, 5, n_probes=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))
    # round-trip again through the healed load (save path unpoisoned):
    # a clean save/load of the HEALED index must also be bit-identical
    path2 = str(tmp_path / "pq_clean.ckpt")
    mnmg.ivf_pq_save_local(path2, loaded)
    again = mnmg.ivf_pq_load(comms4, path2)
    v2, i2 = mnmg.ivf_pq_search(again, q, 5, n_probes=8)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i0))


# -- quantized-transport chaos drills (comms/quantized fault surface) ---

def test_corrupt_quant_encode_degrades_but_serves(comms4, blobs):
    """Seeded scale-sidecar rot at the quantized encoder on rank 1: the
    candidate exchange visibly degrades (rank 1's candidates decode to
    NaN and fall out of the shortlist) but never crashes, and the exact
    resolve round keeps every reported score finite — quantization
    corruption can cost recall, never correctness of what IS reported."""
    q = blobs[:19]
    cv, ci = mnmg.knn(comms4, blobs, q, 10, quantization="int8")
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="comms.quant.encode",
                      rank=1, fraction=1.0)],
        seed=SEED,
    )
    with plan.install():
        bv, bi = mnmg.knn(comms4, blobs, q, 10, quantization="int8")
    assert np.isfinite(np.asarray(bv)).all()
    assert (np.any(np.asarray(bi) != np.asarray(ci))
            or np.any(np.asarray(bv) != np.asarray(cv)))
    # plan uninstalled -> the same call is clean again (trace-key hygiene)
    rv, ri = mnmg.knn(comms4, blobs, q, 10, quantization="int8")
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(ci))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(cv))


def test_corrupt_quant_decode_degrades_but_serves(comms4, blobs):
    """Decode-side scale rot on rank 0 (the rank whose buffer the host
    reads): the corrupted rank's shortlist diverges, so its merged view
    degrades visibly — but the masked exact-score psums only ever sum
    finite owner contributions, so the served payload stays finite."""
    q = blobs[:19]
    cv, ci = mnmg.knn(comms4, blobs, q, 10, quantization="int8")
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="comms.quant.decode",
                      rank=0, fraction=1.0)],
        seed=SEED,
    )
    with plan.install():
        bv, bi = mnmg.knn(comms4, blobs, q, 10, quantization="int8")
    assert np.isfinite(np.asarray(bv)).all()
    assert (np.any(np.asarray(bi) != np.asarray(ci))
            or np.any(np.asarray(bv) != np.asarray(cv)))

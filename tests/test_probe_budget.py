"""Adaptive probing suite (neighbors/probe_budget, ISSUE 12).

Pins the three contracts the feature rests on:

  1. SATURATION BIT-IDENTITY — `recall_target=1.0` (and any saturated
     budget) is bit-identical to the fixed-`n_probes` reference on all
     three engine families, every sub-engine, single-rank AND MNMG on
     the 8-device mesh.
  2. EARLY-TERMINATION SOUNDNESS (oracle) — with valid bounds and
     saturated budgets, bound-based list skipping NEVER drops a true
     top-k neighbor: IVF-Flat results equal the fixed path exactly.
  3. TRUTHFUL ACCOUNTING — `ivf.scanned_lists` / `ivf.budget_hist`
     record the actual per-batch work, and shrunken budgets shrink it.

Plus unit coverage of the budget math, policy resolution, serialization
of the stored bounds, and the serve-layer plumbing (per-request
recall_target, batch coalescing, probe_key folding, the _scaled_probes
floor rule).
"""

import os
import tempfile

import numpy as np
import jax.numpy as jnp
import pytest

from raft_tpu.core import faults
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.neighbors import ivf_flat, ivf_pq, ivf_rabitq, probe_budget

SEED = int(os.environ.get(faults.ENV_SEED, "1234"))


@pytest.fixture(scope="module")
def clustered():
    """Clustered data: the coarse gap profile has real signal, which is
    the regime adaptive budgets exist for."""
    rng = np.random.default_rng(SEED)
    cent = rng.normal(size=(16, 32)) * 8
    data = (cent[rng.integers(0, 16, 4000)]
            + rng.normal(size=(4000, 32))).astype(np.float32)
    return data


@pytest.fixture(scope="module")
def flat16(clustered):
    return ivf_flat.build(
        ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=6), clustered)


@pytest.fixture(scope="module")
def pq16(clustered):
    return ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, pq_dim=16, kmeans_n_iters=4),
        clustered)


@pytest.fixture(scope="module")
def rabitq16(clustered):
    return ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=16, kmeans_n_iters=4), clustered)


# -- unit: budget math --------------------------------------------------


def test_assign_budgets_profile_semantics():
    # sorted best-first coarse scores with one sharp gap after 2 lists
    cvals = jnp.asarray([[1.0, 1.1, 9.0, 9.1, 9.2, 9.3]])
    b_tight = int(probe_budget.assign_budgets(cvals, True, 0.2, 1)[0])
    assert b_tight == 2  # the gap cuts the profile
    b_sat = int(probe_budget.assign_budgets(cvals, True, 1.0, 1)[0])
    assert b_sat == 6  # tau >= 1 saturates
    b_floor = int(probe_budget.assign_budgets(cvals, True, 0.0, 3)[0])
    assert b_floor == 3  # clamped to min_probes


def test_assign_budgets_ip_orientation():
    # IP scores descend best-first; same gap semantics, flipped sign
    cvals = jnp.asarray([[9.0, 8.9, 1.0, 0.9]])
    assert int(probe_budget.assign_budgets(cvals, False, 0.2, 1)[0]) == 2


def test_assign_budgets_degenerate_flat_profile():
    # identical coarse scores: zero gaps everywhere -> keep everything
    cvals = jnp.full((3, 5), 2.0)
    b = probe_budget.assign_budgets(cvals, True, 0.5, 1)
    assert (np.asarray(b) == 5).all()


def test_plan_monotone_in_tau(flat16, clustered):
    q = clustered[:64]
    scans = []
    for tau in (0.1, 0.4, 0.8, 1.0):
        _, scanned = probe_budget.probe_plan(
            q, flat16.centers, n_probes=8, min_probes=1, k=10,
            metric=flat16.metric, tau=tau)
        scans.append(int(np.asarray(scanned).sum()))
    assert scans == sorted(scans), scans  # larger tau never scans less
    assert scans[-1] == 64 * 8  # tau=1.0 saturates


def test_early_term_bounds_sound_vs_oracle(flat16, clustered):
    """Every dropped list's true minimum member distance must exceed
    the query's true k-th distance within the kept set — the bound can
    never drop a true top-k neighbor."""
    q = clustered[:32]
    keep, _ = probe_budget.probe_plan(
        q, flat16.centers, n_probes=8, min_probes=1, k=10,
        metric=flat16.metric, tau=1.0,
        radii=flat16.list_radii, sizes=flat16.list_sizes)
    fixed = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8), flat16, q, 10)
    et = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, budget_tau=1.0, early_term=True),
        flat16, q, 10)
    np.testing.assert_array_equal(np.asarray(et[1]), np.asarray(fixed[1]))
    np.testing.assert_array_equal(np.asarray(et[0]), np.asarray(fixed[0]))
    # and the mask really dropped something, so the oracle is not vacuous
    assert int(np.asarray(keep).sum()) < 32 * 8


def test_policy_resolution_tuned_and_default(monkeypatch):
    from raft_tpu.core import tuned

    assert probe_budget.resolve_tau(1.0) == 1.0
    assert probe_budget.resolve_tau(None) == \
        probe_budget.DEFAULT_POLICY["default_tau"]
    # default table: a target inside the table picks its banked tau
    assert probe_budget.resolve_tau(0.9) == 0.45
    # above every banked target: saturate
    assert probe_budget.resolve_tau(0.999) == 1.0
    # a banked per-index calibration wins over the built-in
    monkeypatch.setattr(tuned, "get", lambda key, default=None: {
        "default_tau": 0.5, "targets": [[0.9, 0.11], [0.95, 0.33]],
    } if key == probe_budget.POLICY_KEY else default)
    assert probe_budget.resolve_tau(0.9) == 0.11
    assert probe_budget.resolve_tau(0.93) == 0.33
    # a corrupt tuned value degrades to the built-in, never crashes
    monkeypatch.setattr(tuned, "get", lambda key, default=None: "garbage")
    assert probe_budget.resolve_tau(0.9) == 0.45
    # ... and ONE malformed entry inside an otherwise-valid table is
    # skipped (a sort over raw entries used to crash every request)
    monkeypatch.setattr(tuned, "get", lambda key, default=None: {
        "targets": [["oops", 0.5], [0.95, 0.4]],
    } if key == probe_budget.POLICY_KEY else default)
    assert probe_budget.resolve_tau(0.9) == 0.4


def test_resolve_params_fixed_vs_adaptive():
    p = ivf_flat.SearchParams(n_probes=8)
    assert probe_budget.resolve_params(p, 8) is None
    ap = probe_budget.resolve_params(
        ivf_flat.SearchParams(n_probes=8, recall_target=0.9), 8)
    assert ap is not None and ap.tau < 1.0 and ap.early_term
    # recall_target=1.0 saturates AND disables bounds (bit-identity)
    sat = probe_budget.resolve_params(
        ivf_flat.SearchParams(n_probes=8, recall_target=1.0), 8)
    assert sat.tau == 1.0 and not sat.early_term
    # an explicit budget_tau keeps the caller's early_term choice
    et = probe_budget.resolve_params(
        ivf_flat.SearchParams(n_probes=8, budget_tau=1.0), 8)
    assert et.tau == 1.0 and et.early_term


# -- saturation bit-identity, all engines -------------------------------


@pytest.mark.parametrize("engine", ["query", "list", "pallas"])
def test_flat_saturated_bit_identical(flat16, clustered, engine):
    q = clustered[:48]
    fixed = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, engine=engine), flat16, q, 10)
    sat = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, engine=engine, recall_target=1.0),
        flat16, q, 10)
    np.testing.assert_array_equal(np.asarray(fixed[0]), np.asarray(sat[0]))
    np.testing.assert_array_equal(np.asarray(fixed[1]), np.asarray(sat[1]))


@pytest.mark.parametrize("cfg", [
    dict(score_mode="lut"),
    dict(score_mode="recon8"),
    dict(score_mode="recon8_list"),
    dict(score_mode="recon8_list", trim_engine="exact"),
    dict(score_mode="recon8_list", trim_engine="fused"),
    dict(score_mode="recon8_list", trim_engine="fused", score_dtype="int8"),
], ids=["lut", "recon8", "list", "list_exact", "fused", "fused_int8"])
def test_pq_saturated_bit_identical(pq16, clustered, cfg):
    q = clustered[:48]
    fixed = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=8, **cfg), pq16, q, 10)
    sat = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=8, recall_target=1.0, **cfg),
        pq16, q, 10)
    np.testing.assert_array_equal(np.asarray(fixed[0]), np.asarray(sat[0]))
    np.testing.assert_array_equal(np.asarray(fixed[1]), np.asarray(sat[1]))


@pytest.mark.parametrize("query_bits", [4, 8])
def test_rabitq_saturated_bit_identical(rabitq16, clustered, query_bits):
    q = clustered[:48]
    fixed = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=8, query_bits=query_bits),
        rabitq16, q, 10)
    sat = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=8, query_bits=query_bits,
                                recall_target=1.0),
        rabitq16, q, 10)
    np.testing.assert_array_equal(np.asarray(fixed[0]), np.asarray(sat[0]))
    np.testing.assert_array_equal(np.asarray(fixed[1]), np.asarray(sat[1]))


def test_mnmg_saturated_bit_identical_all_kinds(clustered):
    """Distributed saturation bit-identity on the 8-device mesh — the
    replicated coarse geometry makes one plan the every-rank plan, and
    a saturated plan must vanish entirely."""
    from raft_tpu.comms import Comms, mnmg

    comms = Comms()
    q = clustered[:16]
    fidx = mnmg.ivf_flat_build(
        comms, ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), clustered)
    pidx = mnmg.ivf_pq_build(
        comms, ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4),
        clustered)
    ridx = mnmg.ivf_rabitq_build(
        comms, ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=4),
        clustered)
    cases = [
        lambda **ad: mnmg.ivf_flat_search(fidx, q, 10, n_probes=4,
                                          engine="query", **ad),
        lambda **ad: mnmg.ivf_flat_search(fidx, q, 10, n_probes=4,
                                          engine="list", **ad),
        lambda **ad: mnmg.ivf_pq_search(pidx, q, 10, n_probes=4,
                                        engine="recon8_list", **ad),
        lambda **ad: mnmg.ivf_pq_search(pidx, q, 10, n_probes=4,
                                        engine="lut", **ad),
        lambda **ad: mnmg.ivf_rabitq_search(ridx, q, 10, n_probes=4, **ad),
    ]
    for case in cases:
        fv, fi = case()
        sv, si = case(recall_target=1.0)
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(sv))
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(si))
        # shrunken budgets still return full-shape, valid results
        av, ai = case(budget_tau=0.3)
        assert np.asarray(ai).shape == np.asarray(fi).shape
        assert (np.asarray(ai) >= 0).any()


# -- budgets do real work, recall holds ---------------------------------


@pytest.mark.parametrize("make_search", [
    lambda q, idx, **kw: ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, **kw), idx, q, 10),
], ids=["flat"])
def test_adaptive_recall_vs_scanned(flat16, clustered, make_search):
    """On clustered data a modest tau reaches the fixed-probe recall
    while scanning well under the worst case — the banked-frontier
    claim, pinned at smoke scale."""
    q = clustered[:128]
    fixed_v, fixed_i = make_search(q, flat16)
    _, scanned = probe_budget.probe_plan(
        q, flat16.centers, n_probes=8, min_probes=1, k=10,
        metric=flat16.metric, tau=0.45,
        radii=flat16.list_radii, sizes=flat16.list_sizes)
    frac = float(np.asarray(scanned).sum()) / (128 * 8)
    av, ai = make_search(q, flat16, budget_tau=0.45, early_term=True)
    recall = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / 10
        for a, b in zip(np.asarray(ai), np.asarray(fixed_i))])
    assert frac <= 0.6, frac
    assert recall >= 0.998, (recall, frac)


def test_scanned_counters_and_hist(flat16, clustered):
    from raft_tpu import obs

    q = clustered[:32]
    obs.enable()
    try:
        base = obs.counter("ivf.scanned_lists").value
        ivf_flat.search(
            ivf_flat.SearchParams(n_probes=8, budget_tau=0.3), flat16, q, 10)
        scanned = obs.counter("ivf.scanned_lists").value - base
        worst = 32 * 8
        assert 32 <= scanned < worst  # real work, less than worst case
        h = obs.histogram("ivf.budget_hist")
        assert h.count >= 32  # one observation per query
    finally:
        obs.disable()


def test_cost_model_charges_actual_scan(flat16, clustered):
    """The cost model's scanned_lists charge follows the budgets: a
    shrunken plan charges fewer flops than the fixed plan."""
    from raft_tpu import obs

    q = clustered[:64]
    obs.enable()
    try:
        with obs.span("fixed_probe_span"):
            obs.span_cost(**obs.perf.cost_for(
                "neighbors.ivf_flat.search", nq=64, n_probes=8, n_lists=16,
                n_rows=4096, dim=32, k=10, scanned_lists=8))
        with obs.span("adaptive_probe_span"):
            obs.span_cost(**obs.perf.cost_for(
                "neighbors.ivf_flat.search", nq=64, n_probes=8, n_lists=16,
                n_rows=4096, dim=32, k=10, scanned_lists=2.5))
        snap = obs.snapshot()["metrics"]["counters"]
        fixed_fl = sum(v for k, v in snap.items()
                       if k.startswith("perf.fixed_probe_span.flops"))
        adapt_fl = sum(v for k, v in snap.items()
                       if k.startswith("perf.adaptive_probe_span.flops"))
        assert adapt_fl < fixed_fl
    finally:
        obs.disable()


# -- bounds storage lifecycle -------------------------------------------


def test_flat_radii_roundtrip_and_extend(clustered, tmp_path):
    idx = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), clustered[:2000])
    r0 = np.asarray(idx.list_radii)
    ext = ivf_flat.extend(idx, clustered[2000:2500])
    r1 = np.asarray(ext.list_radii)
    assert (r1 >= r0 - 1e-6).all()  # max-fold is monotone
    # radii are genuine bounds over the extended store
    d2 = np.array(jnp.sum(
        (ext.list_data.astype(jnp.float32)
         - ext.centers[:, None, :]) ** 2, axis=2))
    d2[np.asarray(ext.slot_rows) < 0] = 0.0
    np.testing.assert_allclose(np.sqrt(d2.max(axis=1)), r1, rtol=1e-5,
                               atol=1e-5)
    p = str(tmp_path / "idx.bin")
    ivf_flat.save(p, ext)
    loaded = ivf_flat.load(p)
    np.testing.assert_array_equal(np.asarray(loaded.list_radii), r1)


def test_old_checkpoint_without_radii_falls_back(clustered, tmp_path, monkeypatch):
    """A checkpoint written without bounds loads with list_radii=None
    and adaptive searches run budgets-only (never crash)."""
    idx = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), clustered[:2000])
    idx.list_radii = None  # simulate the old format
    p = str(tmp_path / "old.bin")
    ivf_flat.save(p, idx)
    loaded = ivf_flat.load(p)
    assert loaded.list_radii is None
    q = clustered[:8]
    v, i = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=4, budget_tau=0.5, early_term=True),
        loaded, q, 5)
    assert np.asarray(i).shape == (8, 5)
    # extend on a radii-less index keeps the fallback (no fake bounds)
    ext = ivf_flat.extend(loaded, clustered[2000:2100])
    assert ext.list_radii is None


def test_adaptive_centers_invalidate_bounds(clustered):
    idx = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4,
                             adaptive_centers=True), clustered[:2000])
    # the build's own extend already ran under adaptive_centers
    assert idx.list_radii is None


def test_pq_radii_roundtrip(pq16, tmp_path):
    assert pq16.list_radii is not None
    p = str(tmp_path / "pq.bin")
    ivf_pq.save(p, pq16)
    loaded = ivf_pq.load(p)
    np.testing.assert_array_equal(
        np.asarray(loaded.list_radii), np.asarray(pq16.list_radii))


def test_rabitq_radii_derive_from_aux(rabitq16):
    r = np.asarray(rabitq16.list_radii)
    rn = np.array(rabitq16.aux[..., 0])
    rn[np.asarray(rabitq16.slot_rows) < 0] = 0.0
    np.testing.assert_allclose(r, rn.max(axis=1), rtol=1e-6)


# -- prefilter composes with budgets ------------------------------------


def test_adaptive_composes_with_prefilter(flat16, clustered):
    q = clustered[:16]
    mask = np.zeros(flat16.size, bool)
    mask[::2] = True
    fv, fi = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8), flat16, q, 10, prefilter=mask)
    av, ai = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, recall_target=1.0), flat16, q, 10,
        prefilter=mask)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ai))
    ai2 = np.asarray(ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, budget_tau=0.4), flat16, q, 10,
        prefilter=mask)[1])
    assert ((ai2 % 2 == 0) | (ai2 == -1)).all()  # filter still honored


def test_early_term_disabled_under_prefilter(flat16, clustered):
    """Bounds must NOT engage under a prefilter: list_sizes counts
    filtered members, so a bound's k-covering prefix could be entirely
    filtered out and a list holding the only ELIGIBLE neighbors would
    be skipped. With saturated budgets + early_term + a hostile filter
    the result must equal the fixed reference bit for bit (bounds
    silently fall back to budgets-only)."""
    q = clustered[:16]
    # hostile filter: keep only a thin slice of the index, so most
    # lists' "covering" members are filtered away
    mask = np.zeros(flat16.size, bool)
    mask[: flat16.size // 10] = True
    fv, fi = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8), flat16, q, 10, prefilter=mask)
    ev, ei = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, budget_tau=1.0, early_term=True),
        flat16, q, 10, prefilter=mask)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(ev))
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ei))


# -- serve plumbing -----------------------------------------------------


@pytest.mark.parametrize("n_probes,scale,want", [
    (2, 0.25, 1),   # floor(0.5) -> min 1
    (6, 0.25, 1),   # floor(1.5) = 1 (round() used to give 2)
    (8, 0.25, 2),
    (20, 0.5, 10),
    (20, 1.0, 20),
    (1, 0.1, 1),
])
def test_scaled_probes_floor_rule(n_probes, scale, want):
    from raft_tpu.serve.engine import _scaled_probes

    assert _scaled_probes(n_probes, scale) == want


def test_serve_recall_target_end_to_end(flat16, clustered):
    """Per-request recall_target flows submit -> batch -> searcher;
    recall_target=1.0 replies are bit-identical to plain requests, and
    mixed targets never share a batch."""
    from raft_tpu import serve

    q = clustered[:4]
    server = serve.SearchServer(
        flat16, serve.ServerConfig(buckets=(8,)),
        search_params=ivf_flat.SearchParams(n_probes=8, engine="query"))
    plain = server.submit(q, k=5)
    server.step()
    sat = server.submit(q, k=5, recall_target=1.0)
    server.step()
    tight = server.submit(q, k=5, recall_target=0.9)
    server.step()
    pv, sv, tv = plain.result(1), sat.result(1), tight.result(1)
    np.testing.assert_array_equal(pv.values, sv.values)
    np.testing.assert_array_equal(pv.ids, sv.ids)
    assert tv.ids.shape == (4, 5)

    # mixed-target coalescing: same k, different targets -> two batches
    a = server.submit(q, k=5, recall_target=0.9)
    b = server.submit(q, k=5, recall_target=0.95)
    served_first = server.step()
    assert served_first == 1  # only the first target's batch
    server.step()
    assert a.done() and b.done()


def test_serve_probe_key_folds_budget(flat16):
    from raft_tpu import serve

    s = serve.IvfFlatSearcher(
        flat16, ivf_flat.SearchParams(n_probes=8, engine="query"))
    fixed_key = s.probe_key(1.0)
    ad_key = s.probe_key(1.0, recall_target=0.9)
    sat_key = s.probe_key(1.0, recall_target=1.0)
    assert fixed_key != ad_key  # adaptive plan = different program
    assert ad_key != sat_key or ad_key[1] == sat_key[1]
    # overload scale still folds through as the n_probes cap
    assert s.probe_key(0.25)[0] == 2


def test_serve_recall_target_validation(flat16):
    from raft_tpu import serve

    server = serve.SearchServer(
        flat16, serve.ServerConfig(buckets=(8,)),
        search_params=ivf_flat.SearchParams(n_probes=8, engine="query"))
    with pytest.raises(ValueError, match="recall_target"):
        server.submit(np.zeros((1, 32), np.float32), k=3, recall_target=1.5)


# -- chaos: the ivf.probe_budget site -----------------------------------


def test_probe_budget_fault_site_registered():
    assert probe_budget.BUDGET_SITE in faults.known_sites()

"""IVF-RaBitQ index suite: single-chip build/search/extend/save, the
recall-with-rerank contract, prefilters, and the full production
surface — MNMG build/search, refine, degraded mode, replica failover
bit-identity, CRC checkpoint round-trip with mirror heal, and serve
batched-vs-unbatched bit-identity. (Estimator/packing property tests
live in tests/test_quantizer.py; chaos drills for the registered fault
sites in tests/test_resilience.py.)"""

import os
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.comms import Comms, mnmg
from raft_tpu.comms.resilience import DegradedSearchResult, RankHealth
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.neighbors import brute_force, ivf_rabitq
from raft_tpu.random import make_blobs

WORLD = 4


@pytest.fixture(scope="module")
def blobs():
    data, _ = make_blobs(4000, 48, n_clusters=24, cluster_std=0.8, seed=21)
    return np.asarray(data, np.float32)


@pytest.fixture(scope="module")
def index(blobs):
    return ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=32, kmeans_n_iters=6), blobs, seed=0)


@pytest.fixture(scope="module")
def exact10(blobs):
    _, ids = brute_force.knn(blobs, blobs[:50], 10)
    return np.asarray(ids)


def _recall(got, exact, k=10):
    got = np.asarray(got)
    return float(np.mean([
        len(set(got[i]) & set(exact[i])) / k for i in range(len(exact))
    ]))


# -- single-chip --------------------------------------------------------

def test_build_geometry(index, blobs):
    assert index.dim == 48
    assert index.rot_dim == 64  # rounded up to whole uint32 words
    assert index.words == 2
    assert index.codes.dtype == jnp.uint32
    assert index.aux.shape == index.codes.shape[:2] + (2,)
    assert index.size == len(blobs)
    # the rotation is column-orthonormal: norms survive the transform
    rot = np.asarray(index.rotation)
    np.testing.assert_allclose(rot.T @ rot, np.eye(48), atol=1e-5)


def test_search_with_rerank_recall(index, blobs, exact10):
    v, i = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8),
        index, blobs[:50], 10)
    assert _recall(i, exact10) >= 0.9
    # reranked distances are EXACT (squared L2 against the true rows)
    i0 = np.asarray(i)[:, 0]
    d0 = ((blobs[:50] - blobs[i0]) ** 2).sum(1)
    np.testing.assert_allclose(np.asarray(v)[:, 0], d0, rtol=1e-4,
                               atol=1e-3)


def test_rerank_depth_beats_estimator_only(index, blobs, exact10):
    no_ds = ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=32, kmeans_n_iters=6,
                               store_dataset=False), blobs, seed=0)
    assert no_ds.dataset is None
    _, est_ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16), no_ds, blobs[:50], 10)
    _, rr_ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8), index,
        blobs[:50], 10)
    assert _recall(rr_ids, exact10) > _recall(est_ids, exact10)
    # the quantized-only index reranks through an explicit dataset
    _, ref_ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8), no_ds,
        blobs[:50], 10, refine_dataset=blobs)
    np.testing.assert_array_equal(np.asarray(ref_ids), np.asarray(rr_ids))


def test_inner_product_metric(blobs):
    idx = ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=16, kmeans_n_iters=4,
                               metric=DistanceType.InnerProduct),
        blobs, seed=1)
    _, exact = brute_force.knn(blobs, blobs[:20], 10,
                               metric=DistanceType.InnerProduct)
    _, ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=8, rerank_mult=8), idx,
        blobs[:20], 10)
    assert _recall(ids, np.asarray(exact)) >= 0.8


def test_extend_appends_and_searches(blobs):
    idx = ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=16, kmeans_n_iters=4),
        blobs[:3000], seed=2)
    idx2 = ivf_rabitq.extend(idx, blobs[3000:])
    assert idx2.size == len(blobs)
    assert idx2.dataset.shape == blobs.shape
    # an appended row finds itself first after rerank
    q = blobs[3500:3510]
    _, ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8), idx2, q, 5)
    assert (np.asarray(ids)[:, 0] == np.arange(3500, 3510)).mean() >= 0.9


def test_extend_custom_indices(blobs):
    idx = ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=4),
        blobs[:1000], seed=3)
    idx2 = ivf_rabitq.extend(idx, blobs[1000:1100],
                             new_indices=np.arange(5000, 5100))
    assert idx2.id_bound == 5100
    q = blobs[1000:1010]
    _, ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=8, rerank_mult=8), idx2, q, 3)
    assert (np.asarray(ids)[:, 0] == np.arange(5000, 5010)).mean() >= 0.9


def test_rerank_depth_beyond_probed_width(blobs):
    """kk (rerank_mult * k) larger than the probed slot count must not
    crash the in-trace top-k: the scan selects everything the probes
    hold and pads the tail (worst score, id -1) — with the shipped
    tuned default rerank_mult=16, tiny-probe searches hit this path."""
    idx = ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=32, kmeans_n_iters=4), blobs[:2000],
        seed=4)
    max_list = int(idx.codes.shape[1])
    k = 10
    assert 1 * max_list < 16 * k  # the geometry that used to crash
    v, ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=1, rerank_mult=16), idx,
        blobs[:5], k)
    assert np.asarray(ids).shape == (5, k)
    assert (np.asarray(ids)[:, 0] >= 0).all()  # real candidates lead
    # estimator-only path pads too when k exceeds the probed width
    no_ds = ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=32, kmeans_n_iters=4,
                               store_dataset=False), blobs[:2000], seed=4)
    v2, ids2 = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=1), no_ds, blobs[:5], max_list + 7)
    ids2 = np.asarray(ids2)
    assert ids2.shape == (5, max_list + 7)
    assert (ids2[:, -1] == -1).all()  # beyond the probed width: -1 pad


def test_prefilter_excludes_rows(index, blobs):
    q = blobs[:10]
    _, base = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8), index, q, 5)
    base = np.asarray(base)
    mask = np.ones(index.size, bool)
    mask[base[:, 0]] = False  # ban every top-1
    _, filt = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8), index, q, 5,
        prefilter=mask)
    filt = np.asarray(filt)
    assert not np.isin(filt, base[:, 0]).any()


def test_save_load_roundtrip(index, blobs, tmp_path):
    path = str(tmp_path / "rb.idx")
    ivf_rabitq.save(path, index)
    loaded = ivf_rabitq.load(path)
    assert loaded.dataset is None  # raw rows are not serialized
    q = blobs[:20]
    v0, i0 = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8), index, q, 5)
    v1, i1 = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8), loaded, q, 5,
        refine_dataset=blobs)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))


def test_validation_errors(index, blobs):
    with pytest.raises(ValueError, match="query dim"):
        ivf_rabitq.search(ivf_rabitq.SearchParams(), index,
                          np.zeros((2, 7), np.float32), 3)
    with pytest.raises(ValueError, match="k must be positive"):
        ivf_rabitq.search(ivf_rabitq.SearchParams(), index, blobs[:2], 0)
    with pytest.raises(ValueError, match="query_bits"):
        ivf_rabitq.search(ivf_rabitq.SearchParams(query_bits=9), index,
                          blobs[:2], 3)
    with pytest.raises(ValueError, match="rerank_mult"):
        ivf_rabitq.search(ivf_rabitq.SearchParams(rerank_mult=-2), index,
                          blobs[:2], 3)
    with pytest.raises(ValueError, match="n_lists"):
        ivf_rabitq.build(ivf_rabitq.IndexParams(n_lists=64), blobs[:10])


def test_top_level_lazy_exports():
    import raft_tpu

    assert raft_tpu.ivf_rabitq_build is ivf_rabitq.build
    assert raft_tpu.ivf_rabitq_search is ivf_rabitq.search


def test_build_has_no_codebook_stage(index):
    """The structural fast-build claim: the index carries NO trained
    codebooks — encode state is the rotation alone (wall-clock race in
    bench/bench_ivf_rabitq.py)."""
    assert not hasattr(index, "pq_centers")
    quant_meta = __import__(
        "raft_tpu.neighbors.quantizer", fromlist=["RabitqQuantizer"]
    ).RabitqQuantizer(index.rot_dim).state_arrays()
    assert quant_meta == {}  # nothing trained, nothing to serialize


# -- 96 and 768 dimensions (3 and 24 code words) against plain references

def _unit_blobs(n, d, seed):
    """Unit-norm Gaussian blobs, the benchmark generator's shape."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-5.0, 5.0, (16, d))
    x = centers[rng.integers(0, 16, n)] + rng.standard_normal((n, d))
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


@pytest.fixture(scope="module", params=[96, 768])
def wide(request):
    d = request.param
    x = _unit_blobs(4032, d, seed=d)
    data, queries = x[:4000], x[4000:]
    index = ivf_rabitq.build(
        ivf_rabitq.IndexParams(n_lists=64, kmeans_n_iters=4,
                               store_dataset=False), data, seed=0)
    assert index.words == d // 32
    return data, queries, index


def test_rerank_against_numpy_exact_knn(wide):
    """Re-ranked search against exact float64 L2 k-NN in NumPy: recall
    above 0.9, and every returned distance is its id's float64 squared
    distance within 1e-5 relative."""
    data, queries, index = wide
    vals, ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=16, rerank_mult=16, query_bits=4),
        index, queries, 10, refine_dataset=data)
    vals, ids = np.asarray(vals), np.asarray(ids)
    d64 = ((queries.astype(np.float64)[:, None, :]
            - data.astype(np.float64)[None, :, :]) ** 2).sum(-1)
    exact = np.argsort(d64, axis=1, kind="stable")[:, :10]
    assert _recall(ids, exact) >= 0.9
    assert (ids >= 0).all()
    np.testing.assert_allclose(
        vals, np.take_along_axis(d64, ids, axis=1), rtol=1e-5)


def test_estimator_against_float64_formula(wide):
    """One probed list, every row: the scan's estimated distance equals
    a float64 transcription of the RaBitQ estimator. For residual r of
    a row and q_r of the query against the list's center, x_bar =
    sign(r) / sqrt(D), o = r / |r|, the query quantized per row to
    2^bits - 1 even steps of delta from its minimum lo (round to
    nearest); <q, o> ~ <q_hat, x_bar> / <o, x_bar> with <q_hat, x_bar>
    = (2 * sum of q_hat over the set bits - sum(q_r)) / sqrt(D); the
    distance is |q_r|^2 + |r|^2 - 2 |r| <q, o>."""
    data, queries, index = wide
    bits = 4
    rot = np.asarray(index.rotation, np.float64)
    centers = np.asarray(index.centers, np.float64)
    slot_rows = np.asarray(index.slot_rows)
    x_rot = data.astype(np.float64) @ rot.T
    for q in queries[:4]:
        q_rot = rot @ q.astype(np.float64)
        lst = int(np.argmin(((centers - q_rot) ** 2).sum(1)))
        pos = slot_rows[lst][slot_rows[lst] >= 0]
        vals, ids = ivf_rabitq.search(
            ivf_rabitq.SearchParams(n_probes=1, query_bits=bits),
            index, q[None], len(pos))
        got = dict(zip(np.asarray(ids)[0], np.asarray(vals)[0]))
        assert sorted(got) == sorted(pos)

        dim = rot.shape[0]
        r = x_rot[pos] - centers[lst]
        rnorm = np.linalg.norm(r, axis=1)
        o_dot = np.abs(r).sum(1) / (rnorm * np.sqrt(dim))
        qr = q_rot - centers[lst]
        lo = qr.min()
        delta = (qr.max() - lo) / (2 ** bits - 1)
        q_hat = lo + delta * np.clip(np.round((qr - lo) / delta), 0,
                                     2 ** bits - 1)
        s = np.where(r >= 0, q_hat[None, :], 0.0).sum(1)
        est = (2 * s - qr.sum()) / np.sqrt(dim) / o_dot
        want = (qr ** 2).sum() + rnorm ** 2 - 2 * rnorm * est
        np.testing.assert_allclose([got[p] for p in pos], want, rtol=1e-4,
                                   atol=1e-5)


# -- MNMG ---------------------------------------------------------------

@pytest.fixture(scope="module")
def comms4():
    return Comms(n_devices=WORLD)


@pytest.fixture(scope="module")
def mblobs():
    data, _ = make_blobs(1600, 16, n_clusters=6, cluster_std=0.4, seed=13)
    return np.asarray(data, np.float32)


@pytest.fixture(scope="module")
def rb8(comms4, mblobs):
    return mnmg.ivf_rabitq_build(
        comms4, ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=4), mblobs)


def test_mnmg_search_and_refine(comms4, mblobs, rb8):
    q = mblobs[:23]
    v, i = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8)
    assert np.asarray(v).shape == (23, 5)
    vr, ir = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8,
                                    refine_dataset=mblobs)
    ir = np.asarray(ir)
    # refined: each query row (a dataset row) finds itself at distance 0
    assert (ir[:, 0] == np.arange(23)).mean() >= 0.9
    np.testing.assert_allclose(
        np.asarray(vr)[ir[:, 0] == np.arange(23), 0], 0.0, atol=1e-4)


def test_mnmg_degraded_matches_survivor_prefilter(comms4, mblobs, rb8):
    q = mblobs[:23]
    h = RankHealth.all_healthy(WORLD)
    h.mark_unhealthy(1)
    res = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8, health=h)
    assert isinstance(res, DegradedSearchResult) and res.coverage == 0.75
    hg = np.asarray(rb8.host_gids[1])
    mask = np.ones(rb8.n, bool)
    mask[hg[hg >= 0]] = False
    rv, ri = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8, prefilter=mask)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))


def test_mnmg_replication_failover_bit_identical(comms4, mblobs):
    rb2 = mnmg.ivf_rabitq_build(
        comms4, ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=4),
        mblobs, replication=2)
    q = mblobs[:23]
    v0, i0 = mnmg.ivf_rabitq_search(rb2, q, 5, n_probes=8)
    h = RankHealth.all_healthy(WORLD)
    h.mark_unhealthy(2)
    res = mnmg.ivf_rabitq_search(rb2, q, 5, n_probes=8, health=h)
    # lossless failover: coverage stays 1.0 and the results are
    # BIT-identical to the all-healthy run
    assert res.coverage == 1.0 and res.repaired_ranks == (2,)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(v0))
    # past r-1 failures the degraded path takes over
    h2 = RankHealth.all_healthy(WORLD)
    h2.mark_unhealthy(2)
    h2.mark_unhealthy(3)
    res2 = mnmg.ivf_rabitq_search(rb2, q, 5, n_probes=8, health=h2)
    assert res2.coverage < 1.0


def test_mnmg_ckpt_roundtrip_with_corrupt_heal(comms4, mblobs, tmp_path):
    from raft_tpu.core import faults
    from raft_tpu.core.serialize import ChecksumError

    rb2 = mnmg.ivf_rabitq_build(
        comms4, ivf_rabitq.IndexParams(n_lists=8, kmeans_n_iters=4),
        mblobs, replication=2)
    q = mblobs[:23]
    v0, i0 = mnmg.ivf_rabitq_search(rb2, q, 5, n_probes=8)
    path = str(tmp_path / "rb_chaos.ckpt")
    plan = faults.FaultPlan(
        [faults.Fault(kind="corrupt_shard", site="ckpt.corrupt_file",
                      fraction=0.01)],
        seed=int(os.environ.get(faults.ENV_SEED, "1234")))
    with plan.install():
        mnmg.ivf_rabitq_save(path, rb2)
    try:
        loaded = mnmg.ivf_rabitq_load(comms4, path)
    except ChecksumError:
        # the seeded sector landed on an unmirrored field (rotation/
        # centers): detection without heal — still never silent
        return
    assert loaded.replicas is not None and loaded.replicas.r == 2
    v1, i1 = mnmg.ivf_rabitq_search(loaded, q, 5, n_probes=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))


def test_mnmg_ckpt_clean_roundtrip(comms4, mblobs, rb8, tmp_path):
    path = str(tmp_path / "rb.ckpt")
    mnmg.ivf_rabitq_save(path, rb8)
    loaded = mnmg.ivf_rabitq_load(comms4, path)
    q = mblobs[:23]
    v0, i0 = mnmg.ivf_rabitq_search(rb8, q, 5, n_probes=8)
    v1, i1 = mnmg.ivf_rabitq_search(loaded, q, 5, n_probes=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))
    # the checkpoint-based heal path dispatches rabitq checkpoints too
    # (rehydrate is what recovery.repair/heal and MnmgSearcher's
    # heal_checkpoint fall back to past r-1 failures)
    from raft_tpu.comms import resilience

    fresh, health = resilience.rehydrate(comms4, path)
    assert health.coverage() == 1.0
    v2, i2 = mnmg.ivf_rabitq_search(fresh, q, 5, n_probes=8)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v0))


# -- serve --------------------------------------------------------------

def test_serve_batched_bit_identical_to_unbatched(blobs, index):
    from raft_tpu import serve

    q = blobs[:6]
    sp = ivf_rabitq.SearchParams(n_probes=16, rerank_mult=8)
    uv, ui = ivf_rabitq.search(sp, index, q, 5)
    server = serve.SearchServer(index, serve.ServerConfig(buckets=(8, 32)),
                                search_params=sp)
    assert isinstance(server.searcher, serve.IvfRabitqSearcher)
    futs = [server.submit(q[i:i + 1], 5) for i in range(6)]
    while any(not f.done() for f in futs):
        server.step()
    for i, f in enumerate(futs):
        reply = f.result(1.0)
        assert reply.coverage == 1.0
        np.testing.assert_array_equal(np.asarray(reply.ids)[0],
                                      np.asarray(ui)[i])
        np.testing.assert_array_equal(np.asarray(reply.values)[0],
                                      np.asarray(uv)[i])


def test_serve_mnmg_searcher_coverage(comms4, mblobs, rb8):
    from raft_tpu import serve

    searcher = serve.as_searcher(rb8, n_probes=8)
    assert isinstance(searcher, serve.MnmgSearcher)
    assert searcher.kind == "ivf_rabitq" and searcher.engine is None
    h = RankHealth.all_healthy(WORLD)
    h.mark_unhealthy(1)
    searcher.set_health(h)
    _, _, coverage = searcher.search(mblobs[:8], 5)
    assert coverage == 0.75
    # an explicit engine= is a config error for the single-engine index
    # — rejected loudly, never silently ignored
    with pytest.raises(ValueError, match="meaningless for ivf_rabitq"):
        serve.as_searcher(rb8, engine="recon8_list")

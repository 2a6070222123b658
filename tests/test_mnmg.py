"""MNMG algorithm tests on the virtual 8-device mesh (the reference tests
distributed algorithms with multi-process-on-one-node; survey §4)."""

import numpy as np
import pytest
from sklearn.metrics import adjusted_rand_score

from raft_tpu.comms import Comms, mnmg
from raft_tpu.cluster import kmeans
from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
from raft_tpu.random import make_blobs


@pytest.fixture(scope="module")
def comms():
    return Comms()


@pytest.fixture(scope="module")
def blobs():
    data, labels = make_blobs(4003, 16, n_clusters=6, cluster_std=0.4, seed=13)
    return np.asarray(data), np.asarray(labels)


# Shared full-data indexes (VERDICT r3 #8 test-cost discipline): many
# tests search the same geometry and never mutate the index — distributed
# indexes are immutable (extend returns a new object; the lazily derived
# per-rank stores are idempotent caches), so one build serves them all.
# Tests that extend, use other params/metrics, or slice the data still
# build their own.


@pytest.fixture(scope="module")
def flat16(comms, blobs):
    data, _ = blobs
    return mnmg.ivf_flat_build(
        comms, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=10), data)


@pytest.fixture(scope="module")
def pq16(comms, blobs):
    data, _ = blobs
    return mnmg.ivf_pq_build(
        comms, ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=8),
        data)


def test_distributed_kmeans_matches_quality(comms, blobs):
    data, true_labels = blobs
    centers, inertia, n_iter = mnmg.kmeans_fit(comms, data, 6, seed=0)
    assert centers.shape == (6, 16)
    pred = np.asarray(mnmg.kmeans_predict(comms, data, centers))
    assert pred.shape == (len(data),)
    assert adjusted_rand_score(true_labels, pred) > 0.95
    # single-device reference gets comparable inertia
    _, inertia_local, _ = kmeans.fit(data, n_clusters=6, seed=0)
    assert inertia <= inertia_local * 1.1


def test_distributed_knn_exact_match(comms, blobs):
    data, _ = blobs
    q = data[:17]
    dv, di = mnmg.knn(comms, data, q, 10)
    lv, li = brute_force.knn(data, q, 10)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(lv), rtol=1e-3, atol=1e-3)
    # distances of returned ids must match exact distances (ties may permute ids)
    got = np.sort(np.asarray(di), axis=1)
    # self must be among neighbors
    assert all(i in set(np.asarray(di)[i].tolist()) for i in range(17))


def test_distributed_knn_compute_dtype(comms, blobs):
    """compute_dtype threads through the sharded scan: near-exact vs the
    f32 merge result, same id space, merge semantics unchanged."""
    import jax.numpy as jnp

    data, _ = blobs
    q = data[:17]
    dv, di = mnmg.knn(comms, data, q, 10, compute_dtype=jnp.bfloat16)
    _, li = mnmg.knn(comms, data, q, 10)
    di, li = np.asarray(di), np.asarray(li)
    overlap = np.mean(
        [len(set(di[j]) & set(li[j])) / 10 for j in range(len(q))]
    )
    assert overlap >= 0.95, overlap
    assert all(j in set(di[j].tolist()) for j in range(17))  # self found
    assert np.isfinite(np.asarray(dv)).all()


def test_distributed_ivf_flat(comms, blobs, flat16):
    data, _ = blobs
    q = data[:29]
    dindex = flat16
    dv, di = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16)
    _, truth = brute_force.knn(data, q, 5)
    truth = np.asarray(truth)
    di = np.asarray(di)
    hits = sum(len(set(a.tolist()) & set(b.tolist())) for a, b in zip(di, truth))
    assert hits / truth.size >= 0.99  # all lists probed -> near exact


def test_distributed_ivf_flat_extend(comms, blobs):
    """Distributed IVF-Flat extend: second half appended SPMD; near-exact
    recall with all lists probed (exact-within-probed-lists contract)."""
    data, _ = blobs
    half = len(data) // 2
    q = data[:29]
    params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=10)
    dindex = mnmg.ivf_flat_build(comms, params, data[:half])
    dindex = mnmg.ivf_flat_extend(dindex, data[half:])
    assert dindex.n == len(data)
    assert int(dindex.list_sizes.sum()) == len(data)
    dv, di = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16)
    _, truth = brute_force.knn(data, q, 5)
    truth, di = np.asarray(truth), np.asarray(di)
    hits = sum(len(set(a.tolist()) & set(b.tolist())) for a, b in zip(di, truth))
    assert hits / truth.size >= 0.99, hits / truth.size


def test_distributed_build_balanced_lists(comms, blobs, pq16):
    """The balanced coarse trainer keeps every list populated (the
    adjust_centers re-seed; empty/starved lists inflate max_list padding
    and waste scan work in the list-major engines)."""
    from raft_tpu.neighbors import ivf_pq

    data, _ = blobs
    dindex = pq16
    global_sizes = dindex.list_sizes.sum(axis=0)  # (n_lists,)
    assert int(global_sizes.sum()) == len(data)
    assert int(global_sizes.min()) > 0, global_sizes.tolist()
    mean = len(data) / 16
    assert int(global_sizes.max()) <= 6 * mean, global_sizes.tolist()


def test_distributed_extend_tiny_batch(comms, blobs):
    """Regression: a batch smaller than the rank count leaves trailing
    ranks with empty shards — the host bookkeeping must not crash."""
    from raft_tpu.neighbors import ivf_pq

    data, _ = blobs
    params = ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4)
    dindex = mnmg.ivf_pq_build(comms, params, data[:500])
    dindex = mnmg.ivf_pq_extend(dindex, data[500:505])  # 5 rows on 8 ranks
    assert dindex.n == 505
    assert int(dindex.list_sizes.sum()) == 505
    fparams = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4)
    findex = mnmg.ivf_flat_build(comms, fparams, data[:500])
    findex = mnmg.ivf_flat_extend(findex, data[500:505])
    assert findex.n == 505 and int(findex.list_sizes.sum()) == 505
    # the 5 appended rows are findable as their own nearest neighbors
    _, di = mnmg.ivf_flat_search(findex, data[500:505], 1, n_probes=8)
    assert sorted(np.asarray(di).ravel().tolist()) == [500, 501, 502, 503, 504]


def test_distributed_ivf_pq(comms, blobs, pq16):
    data, _ = blobs
    q = data[:29]
    dindex = pq16
    dv, di = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16)
    _, truth = brute_force.knn(data, q, 5)
    truth = np.asarray(truth)
    di = np.asarray(di)
    assert di.shape == (29, 5)
    # every returned id is a valid global row
    assert di.min() >= 0 and di.max() < len(data)
    hits = sum(len(set(a.tolist()) & set(b.tolist())) for a, b in zip(di, truth))
    # PQ-quantized scoring over all lists: recall gated like the
    # single-device ivf_pq tests (quantization-bound, not sharding-bound)
    assert hits / truth.size >= 0.5, hits / truth.size
    # distances sorted best-first
    assert np.all(np.diff(np.asarray(dv), axis=1) >= -1e-4)


def test_distributed_ivf_pq_listmajor_engine(comms, blobs, pq16):
    """The recon8_list (list-major) engine — the single-chip flagship — is
    reachable from the MNMG path and agrees with the LUT engine."""
    data, _ = blobs
    q = data[:29]
    dindex = pq16
    lv, li = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16, engine="recon8_list")
    qv, qi = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16, engine="lut")
    li, qi = np.asarray(li), np.asarray(qi)
    assert li.shape == (29, 5)
    assert li.min() >= 0 and li.max() < len(data)
    # engines score the same candidates modulo int8-reconstruction noise:
    # overlap of returned ids should be high
    hits = sum(len(set(a.tolist()) & set(b.tolist())) for a, b in zip(li, qi))
    assert hits / qi.size >= 0.7, hits / qi.size
    # the auto heuristic routes this (nq*probes/lists = 29) to list-major
    av, ai = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16, engine="auto")
    np.testing.assert_array_equal(np.asarray(ai), li)


def test_distributed_ivf_pq_extend(comms, blobs):
    """Distributed extend: second half appended SPMD; recall matches a
    one-shot build of the full data."""
    from raft_tpu.neighbors import ivf_pq

    data, _ = blobs
    half = len(data) // 2
    q = data[:29]
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=8)
    dindex = mnmg.ivf_pq_build(comms, params, data[:half])
    dindex = mnmg.ivf_pq_extend(dindex, data[half:])
    assert dindex.n == len(data)
    dv, di = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16)
    di = np.asarray(di)
    assert di.min() >= 0 and di.max() < len(data)
    # extended ids exist in results when they are true neighbors
    _, truth = brute_force.knn(data, q, 5)
    truth = np.asarray(truth)
    hits = sum(len(set(a.tolist()) & set(b.tolist())) for a, b in zip(di, truth))
    assert hits / truth.size >= 0.5, hits / truth.size
    # per-rank fill counts track the appended rows
    assert int(dindex.list_sizes.sum()) == len(data)


def test_distributed_ivf_pq_recall_parity_with_single_device(comms, blobs,
                                                             pq16):
    """VERDICT round-1 gate: the 8-device mesh build reaches recall parity
    with the single-device index on the same data/config."""
    from raft_tpu.neighbors import ivf_pq

    data, _ = blobs
    q = data[:64]
    k = 10
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=8)
    _, truth = brute_force.knn(data, q, k)
    truth = np.asarray(truth)

    dindex = pq16
    _, di = mnmg.ivf_pq_search(dindex, q, k, n_probes=16)
    dist_recall = sum(
        len(set(a.tolist()) & set(b.tolist())) for a, b in zip(np.asarray(di), truth)
    ) / truth.size

    sindex = ivf_pq.build(params, data)
    _, si = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), sindex, q, k)
    single_recall = sum(
        len(set(a.tolist()) & set(b.tolist())) for a, b in zip(np.asarray(si), truth)
    ) / truth.size

    # same quantization budget => same recall regime (different RNG paths)
    assert dist_recall >= single_recall - 0.05, (dist_recall, single_recall)


def test_distributed_ivf_pq_inner_product(comms, blobs):
    """IP metric: coarse training assigns by dot against normalized centers
    (regression: the distributed EM used to ignore params.metric)."""
    from raft_tpu.distance.distance_types import DistanceType
    from raft_tpu.neighbors import ivf_pq

    data, _ = blobs
    data = data + 2.0  # keep dots discriminative
    q = data[:29]
    params = ivf_pq.IndexParams(
        n_lists=16, pq_dim=8, kmeans_n_iters=8, metric=DistanceType.InnerProduct
    )
    dindex = mnmg.ivf_pq_build(comms, params, data)
    dv, di = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16)
    _, truth = brute_force.knn(data, q, 5, metric="inner_product")
    truth, di = np.asarray(truth), np.asarray(di)
    hits = sum(len(set(a.tolist()) & set(b.tolist())) for a, b in zip(di, truth))
    assert hits / truth.size >= 0.5, hits / truth.size
    # IP scores come back best(largest)-first
    assert np.all(np.diff(np.asarray(dv), axis=1) <= 1e-3)


def test_distributed_ivf_pq_n_lists_guard(comms):
    from raft_tpu.neighbors import ivf_pq

    data = np.zeros((10, 8), np.float32)
    with pytest.raises(ValueError, match="n_lists"):
        mnmg.ivf_pq_build(comms, ivf_pq.IndexParams(n_lists=64, pq_dim=4), data)


def test_distributed_ivf_pq_save_load(comms, blobs, tmp_path):
    """Distributed index checkpoint: same-mesh round-trip preserves search
    results; a fold-merge load (stored ranks = 2x mesh) keeps recall and
    stays extendable."""
    from raft_tpu.neighbors import ivf_pq

    data, _ = blobs
    q = data[:29]
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=6)
    dindex = mnmg.ivf_pq_build(comms, params, data[:3500])
    dv, di = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16)

    path = str(tmp_path / "dist.idx")
    mnmg.ivf_pq_save(path, dindex)
    loaded = mnmg.ivf_pq_load(comms, path)
    lv, li = mnmg.ivf_pq_search(loaded, q, 5, n_probes=16)
    np.testing.assert_array_equal(np.asarray(li), np.asarray(di))
    np.testing.assert_allclose(np.asarray(lv), np.asarray(dv), rtol=1e-5)

    # fold-merge: fake a 16-rank save by splitting each rank's table in two
    r, n_lists, w, pq = np.asarray(dindex.codes).shape
    half = w // 2
    codes16 = np.asarray(dindex.codes).reshape(r, n_lists, 2, half, pq)
    codes16 = np.moveaxis(codes16, 2, 1).reshape(2 * r, n_lists, half, pq)
    gids16 = dindex.host_gids.reshape(r, n_lists, 2, half)
    gids16 = np.moveaxis(gids16, 2, 1).reshape(2 * r, n_lists, half)
    sizes16 = np.stack([(gids16[rr] >= 0).sum(axis=1) for rr in range(2 * r)])
    from raft_tpu.core.serialize import serialize_arrays

    path2 = str(tmp_path / "dist16.idx")
    serialize_arrays(path2, {
        "rotation": dindex.rotation, "centers": dindex.centers,
        "pq_centers": dindex.pq_centers, "codes": codes16,
        "host_gids": gids16, "list_sizes": sizes16.astype(np.int32),
    }, {
        "kind": "mnmg_ivf_pq", "version": 1, "n": dindex.n, "n_ranks": 2 * r,
        "metric": int(params.metric), "n_lists": 16, "pq_dim": 8,
        "pq_bits": 8, "per_cluster": False,
    })
    merged = mnmg.ivf_pq_load(comms, path2)
    assert int(merged.list_sizes.sum()) == 3500
    # valid slots form a prefix (extend contract)
    hg = merged.host_gids
    for rr in range(r):
        valid = hg[rr] >= 0
        assert np.all(valid[:, :-1] >= valid[:, 1:])  # monotone per row
    mv, mi = mnmg.ivf_pq_search(merged, q, 5, n_probes=16)
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(np.asarray(mi), np.asarray(di)))
    assert hits / np.asarray(di).size >= 0.9  # same index, re-partitioned
    merged = mnmg.ivf_pq_extend(merged, data[3500:4000])
    assert merged.n == 4000 and int(merged.list_sizes.sum()) == 4000


def test_distributed_ivf_flat_save_load(comms, blobs, tmp_path):
    data, _ = blobs
    q = data[:19]
    params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=6)
    dindex = mnmg.ivf_flat_build(comms, params, data[:3000])
    dv, di = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16)
    path = str(tmp_path / "flat.idx")
    mnmg.ivf_flat_save(path, dindex)
    loaded = mnmg.ivf_flat_load(comms, path)
    lv, li = mnmg.ivf_flat_search(loaded, q, 5, n_probes=16)
    np.testing.assert_array_equal(np.asarray(li), np.asarray(di))
    np.testing.assert_allclose(np.asarray(lv), np.asarray(dv), rtol=1e-5)
    loaded = mnmg.ivf_flat_extend(loaded, data[3000:3400])
    assert loaded.n == 3400 and int(loaded.list_sizes.sum()) == 3400

    # fold-merge: fake a 16-rank save by splitting each rank's table in
    # two, then load onto the 8-rank mesh (covers _fold_merge_tables for
    # the (d,)-trailed float32 store)
    from raft_tpu.core.serialize import serialize_arrays

    r, n_lists, w, d = np.asarray(dindex.list_data).shape
    half = w // 2
    ld16 = np.asarray(dindex.list_data).reshape(r, n_lists, 2, half, d)
    ld16 = np.moveaxis(ld16, 2, 1).reshape(2 * r, n_lists, half, d)
    gids16 = dindex.host_gids.reshape(r, n_lists, 2, half)
    gids16 = np.moveaxis(gids16, 2, 1).reshape(2 * r, n_lists, half)
    sizes16 = np.stack([(gids16[rr] >= 0).sum(axis=1) for rr in range(2 * r)])
    path2 = str(tmp_path / "flat16.idx")
    serialize_arrays(path2, {
        "centers": dindex.centers, "list_data": ld16,
        "host_gids": gids16, "list_sizes": sizes16.astype(np.int32),
    }, {
        "kind": "mnmg_ivf_flat", "version": 1, "n": dindex.n,
        "n_ranks": 2 * r, "metric": int(params.metric), "n_lists": 16,
    })
    merged = mnmg.ivf_flat_load(comms, path2)
    assert int(merged.list_sizes.sum()) == 3000
    hg = merged.host_gids
    valid = hg >= 0
    assert np.all(valid[:, :, :-1] >= valid[:, :, 1:])  # prefix-compacted
    mv, mi = mnmg.ivf_flat_search(merged, q, 5, n_probes=16)
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(di))


def test_distributed_ivf_pq_empty_shards(comms):
    """n < n_ranks leaves trailing ranks with empty shards — the build
    must still produce a searchable index (regression: div-by-zero in the
    per-shard encode)."""
    from raft_tpu.neighbors import ivf_pq

    data = np.random.default_rng(0).standard_normal((9, 8)).astype(np.float32)
    didx = mnmg.ivf_pq_build(
        comms, ivf_pq.IndexParams(n_lists=2, pq_dim=4, kmeans_n_iters=2), data
    )
    dv, di = mnmg.ivf_pq_search(didx, data[:3], 2, n_probes=2)
    di = np.asarray(di)
    assert di.shape == (3, 2)
    assert di.min() >= 0 and di.max() < len(data)


def test_distribute_index_bridge(comms, blobs):
    """Single-chip build -> mesh serving: distributed search over the
    block-split lists matches the single-chip search's recall, ids stay
    the caller's, and refine is refused (no contiguous rank ownership)."""
    from raft_tpu.neighbors import ivf_pq, brute_force

    data, _ = blobs
    q = data[:32]
    _, truth = brute_force.knn(data, q, 5, metric="sqeuclidean")
    t = np.asarray(truth)

    si = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4), data)
    _, s_ids = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), si, q, 5)
    di = mnmg.distribute_index(comms, si)
    _, d_ids = mnmg.ivf_pq_search(di, q, 5, n_probes=8)

    def rec(ids):
        g = np.asarray(ids)
        return float(np.mean([len(set(g[i]) & set(t[i])) / 5 for i in range(32)]))

    assert abs(rec(s_ids) - rec(d_ids)) < 0.1
    assert np.asarray(d_ids).min() >= -1 and np.asarray(d_ids).max() < data.shape[0]
    with pytest.raises(ValueError):
        mnmg.ivf_pq_search(di, q, 5, refine_dataset=data)


def test_distribute_index_flat_and_flag_persistence(comms, blobs, tmp_path):
    """The flat branch of the bridge, plus: bridged indexes refuse extend,
    and the flag survives save/load (a reloaded bridged index must still
    refuse refine/extend — silent wrong results otherwise)."""
    from raft_tpu.neighbors import ivf_flat as sc_flat, brute_force

    data, _ = blobs
    q = data[:32]
    _, truth = brute_force.knn(data, q, 5, metric="sqeuclidean")
    t = np.asarray(truth)

    si = sc_flat.build(sc_flat.IndexParams(n_lists=8, kmeans_n_iters=4), data)
    di = mnmg.distribute_index(comms, si)
    _, ids = mnmg.ivf_flat_search(di, q, 5, n_probes=8)
    g = np.asarray(ids)
    rec = float(np.mean([len(set(g[i]) & set(t[i])) / 5 for i in range(32)]))
    assert rec > 0.95, rec

    with pytest.raises(ValueError):
        mnmg.ivf_flat_extend(di, data[:8])

    path = str(tmp_path / "bridged.rtivf")
    mnmg.ivf_flat_save(path, di)
    loaded = mnmg.ivf_flat_load(comms, path)
    assert loaded.bridged
    with pytest.raises(ValueError):
        mnmg.ivf_flat_extend(loaded, data[:8])


def test_distributed_prefilter(comms, blobs, flat16, pq16):
    """prefilter excludes global ids on every rank in knn, ivf_flat, and
    ivf_pq distributed search — parity with the single-index prefilter."""
    from raft_tpu.core import Bitset

    data, _ = blobs
    q = data[:13]
    n = len(data)
    rng = np.random.default_rng(5)
    mask = rng.random(n) < 0.5

    # exact kNN vs filtered oracle
    d = ((q[:, None, :] - data[None, :, :]) ** 2).sum(-1)
    d = np.where(mask[None, :], d, np.inf)
    want = np.argsort(d, axis=1, kind="stable")[:, :6]
    dv, di = mnmg.knn(comms, data, q, 6, prefilter=mask)
    np.testing.assert_array_equal(np.asarray(di), want)

    # IVF-Flat, all lists probed: nothing filtered returns; near-exact
    dindex = flat16
    assert dindex.id_bound == n
    _, fi = mnmg.ivf_flat_search(dindex, q, 6, n_probes=16, prefilter=mask)
    got = np.asarray(fi)
    assert np.all((got == -1) | mask[np.maximum(got, 0)])
    hits = sum(len(set(a.tolist()) & set(b.tolist())) for a, b in zip(got, want))
    assert hits / want.size >= 0.99

    # IVF-PQ, both engines: filter invariant + unfiltered-identical check
    pindex = pq16
    for eng in ("lut", "recon8_list"):
        _, pi = mnmg.ivf_pq_search(pindex, q, 6, n_probes=16, engine=eng,
                                   prefilter=mask)
        gp = np.asarray(pi)
        assert np.all((gp == -1) | mask[np.maximum(gp, 0)]), eng
        base = np.asarray(mnmg.ivf_pq_search(pindex, q, 6, n_probes=16,
                                             engine=eng)[1])
        allow = np.asarray(mnmg.ivf_pq_search(
            pindex, q, 6, n_probes=16, engine=eng,
            prefilter=Bitset.full(n))[1])
        np.testing.assert_array_equal(allow, base)

    # refined pipeline composes: _refine_local drops gid=-1 candidates
    _, ri = mnmg.ivf_pq_search(pindex, q, 6, n_probes=16, engine="recon8_list",
                               refine_dataset=data, prefilter=mask)
    gr = np.asarray(ri)
    assert np.all((gr == -1) | mask[np.maximum(gr, 0)])

    # length validation
    with pytest.raises(ValueError, match="covers"):
        mnmg.ivf_flat_search(dindex, q, 3, prefilter=Bitset.full(n + 7))


def test_query_sharded_mode_matches_replicated(comms, blobs, flat16, pq16):
    """query_mode="sharded" (all_to_all merge, R× less traffic) returns
    the same values as the replicated allgather merge for knn, ivf_flat,
    and ivf_pq search — including nq not divisible by the comm size,
    refine, and prefilter composition."""
    data, _ = blobs
    n = len(data)
    q = data[:13]  # 13 % 8 != 0: exercises query padding + strip
    rng = np.random.default_rng(11)
    mask = rng.random(n) < 0.6

    rv, ri = mnmg.knn(comms, data, q, 5, query_mode="replicated")
    sv, si = mnmg.knn(comms, data, q, 5, query_mode="sharded")
    np.testing.assert_allclose(np.asarray(sv), np.asarray(rv), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))

    fidx = flat16
    rv, ri = mnmg.ivf_flat_search(fidx, q, 5, n_probes=16,
                                  query_mode="replicated")
    sv, si = mnmg.ivf_flat_search(fidx, q, 5, n_probes=16,
                                  query_mode="sharded")
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))

    pidx = pq16
    for kwargs in (
        dict(engine="lut"),
        dict(engine="recon8_list"),
        dict(engine="recon8_list", refine_dataset=data),
        dict(engine="lut", prefilter=mask),
    ):
        rv, ri = mnmg.ivf_pq_search(pidx, q, 5, n_probes=16,
                                    query_mode="replicated", **kwargs)
        sv, si = mnmg.ivf_pq_search(pidx, q, 5, n_probes=16,
                                    query_mode="sharded", **kwargs)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(ri),
                                      err_msg=str(kwargs))
        np.testing.assert_allclose(np.asarray(sv), np.asarray(rv),
                                   rtol=1e-5, atol=1e-5, err_msg=str(kwargs))

    with pytest.raises(ValueError, match="query_mode"):
        mnmg.knn(comms, data, q, 5, query_mode="bogus")


def test_extend_local(comms, blobs):
    """Collective multi-controller extend (single-process degenerate):
    new rows get ids continuing the build's id space; search over the
    extended index matches brute force over the concatenation."""
    data, _ = blobs
    n0 = 3000
    base, extra = data[:n0], data[n0:3600]
    q = data[:24]

    # IVF-Flat: build_local + extend_local, searched near-exactly
    params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=6)
    fidx = mnmg.ivf_flat_build_local(comms, params, base)
    assert fidx.local_gids is not None and fidx.local_sizes is not None
    fidx2 = mnmg.ivf_flat_extend_local(fidx, extra)
    assert fidx2.n == 3600 and fidx2.id_bound == 3600
    _, ti = brute_force.knn(data[:3600], q, 6, metric="sqeuclidean")
    _, fi = mnmg.ivf_flat_search(fidx2, q, 6, n_probes=16)
    ti, fi = np.asarray(ti), np.asarray(fi)
    rec = np.mean([len(set(fi[i]) & set(ti[i])) / 6 for i in range(len(q))])
    assert rec >= 0.99, rec
    # ids above n0 (the new rows) must be reachable
    probe = extra[:4]
    _, pi_ = mnmg.ivf_flat_search(fidx2, probe, 1, n_probes=16)
    assert np.all(np.asarray(pi_).ravel() >= n0)

    # empty batch is the identity
    assert mnmg.ivf_flat_extend_local(fidx, base[:0]) is fidx

    # IVF-PQ: extend_local + search; refined pipeline refuses extended
    pparams = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=6)
    pidx = mnmg.ivf_pq_build_local(comms, pparams, base)
    pidx2 = mnmg.ivf_pq_extend_local(pidx, extra)
    assert pidx2.n == 3600 and pidx2.extended
    _, gi = mnmg.ivf_pq_search(pidx2, q, 6, n_probes=16)
    gi = np.asarray(gi)
    rec_p = np.mean([len(set(gi[i]) & set(ti[i])) / 6 for i in range(len(q))])
    assert rec_p >= 0.5, rec_p
    assert gi.max() < 3600
    with pytest.raises(ValueError, match="extend"):
        mnmg.ivf_pq_search(pidx2, q, 6, n_probes=16, refine_dataset=data[:3600])

    # chained extend_local keeps growing the same id space
    fidx3 = mnmg.ivf_flat_extend_local(fidx2, data[3600:3700])
    assert fidx3.n == 3700
    _, ci = mnmg.ivf_flat_search(fidx3, data[3650:3654], 1, n_probes=16)
    assert np.all(np.asarray(ci).ravel() >= 3600)

    # loaded/bridged indexes refuse (no per-process mirrors)
    si = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8,
                                         kmeans_n_iters=4), np.asarray(base))
    bridged = mnmg.distribute_index(comms, si)
    with pytest.raises(ValueError, match="bridged"):
        mnmg.ivf_pq_extend_local(bridged, extra)


def test_ivf_pq_extend_local_nondividing_pq_dim(comms, blobs):
    """extend_local on a geometry where pq_dim does not divide dim
    (rot_dim = pq_dim*ceil(dim/pq_dim) > dim): row-width validation must
    accept (n, dim) batches — the rotation maps dim -> rot_dim, so the
    INPUT width is rotation.shape[1], not rot_dim (ADVICE r3)."""
    data, _ = blobs
    base, extra = data[:3000], data[3000:3200]
    pparams = ivf_pq.IndexParams(n_lists=16, pq_dim=5, kmeans_n_iters=6)
    pidx = mnmg.ivf_pq_build_local(comms, pparams, base)
    assert int(pidx.rotation.shape[0]) > int(pidx.rotation.shape[1])  # rot_dim > dim
    pidx2 = mnmg.ivf_pq_extend_local(pidx, extra)
    assert pidx2.n == 3200
    # appended rows are reachable under their continued ids
    _, pi_ = mnmg.ivf_pq_search(pidx2, extra[:4], 1, n_probes=16)
    assert np.all(np.asarray(pi_).ravel() >= 3000)
    # a genuinely wrong width still rejects, quoting the INPUT dim
    with pytest.raises(ValueError, match=r"\(n, 16\)"):
        mnmg.ivf_pq_extend_local(pidx2, np.zeros((4, 20), np.float32))


def test_extend_local_after_load(comms, blobs, tmp_path):
    """Checkpoint loads keep per-process mirror slices, so the collective
    extend_local works on a loaded index (the round-trip a serving
    cluster does: build once, load onto the mesh, keep ingesting)."""
    data, _ = blobs
    path = str(tmp_path / "loadext.rtivf")
    params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=6)
    built = mnmg.ivf_flat_build(comms, params, data[:3000])
    mnmg.ivf_flat_save(path, built)
    loaded = mnmg.ivf_flat_load(comms, path)
    assert loaded.local_gids is not None
    grown = mnmg.ivf_flat_extend_local(loaded, data[3000:3200])
    assert grown.n == 3200
    _, gi = mnmg.ivf_flat_search(grown, data[3100:3104], 1, n_probes=16)
    assert np.all(np.asarray(gi).ravel() == np.arange(3100, 3104))


def test_sharded_checkpoint_roundtrip(comms, blobs, tmp_path):
    """save_local (per-process part files + manifest) round-trips through
    the kind-dispatching load for both index types, preserves search
    results exactly, and supports extend_local after load."""
    data, _ = blobs
    q = data[:16]

    params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=6)
    fidx = mnmg.ivf_flat_build_local(comms, params, data[:3000])
    fpath = str(tmp_path / "sharded.rtivf")
    mnmg.ivf_flat_save_local(fpath, fidx)
    import os
    assert os.path.exists(fpath) and os.path.exists(fpath + ".part0")
    floaded = mnmg.ivf_flat_load(comms, fpath)
    assert floaded.n == 3000 and floaded.local_gids is not None
    _, i0 = mnmg.ivf_flat_search(fidx, q, 5, n_probes=16)
    _, i1 = mnmg.ivf_flat_search(floaded, q, 5, n_probes=16)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    grown = mnmg.ivf_flat_extend_local(floaded, data[3000:3100])
    assert grown.n == 3100

    pparams = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=6)
    pidx = mnmg.ivf_pq_build_local(comms, pparams, data[:3000])
    ppath = str(tmp_path / "sharded.rtpq")
    mnmg.ivf_pq_save_local(ppath, pidx)
    ploaded = mnmg.ivf_pq_load(comms, ppath)
    assert ploaded.n == 3000
    _, p0 = mnmg.ivf_pq_search(pidx, q, 5, n_probes=16, engine="lut")
    _, p1 = mnmg.ivf_pq_search(ploaded, q, 5, n_probes=16, engine="lut")
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p0))

    # fold-merge: 8 stored rank shards load onto a 4-device mesh
    small = Comms(n_devices=4)
    ffold = mnmg.ivf_flat_load(small, fpath)
    assert ffold.n == 3000 and ffold.list_data.shape[0] == 4
    _, i2 = mnmg.ivf_flat_search(ffold, q, 5, n_probes=16)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i0))

    # single-controller interop: a sharded load's assembly doubles as
    # the global host mirrors, so classic extend/save work on it...
    assert floaded.host_gids is not None
    classic_grown = mnmg.ivf_flat_extend(floaded, data[3000:3050])
    assert classic_grown.n == 3050
    reexport = str(tmp_path / "reexport.rtivf")
    mnmg.ivf_flat_save(reexport, floaded)
    assert mnmg.ivf_flat_load(comms, reexport).n == 3000
    # ...and a classic *_build index sharded-saves via its host mirrors
    params2 = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=6)
    built2 = mnmg.ivf_flat_build(comms, params2, data[:2000])
    spath2 = str(tmp_path / "classic_sharded.rtivf")
    mnmg.ivf_flat_save_local(spath2, built2)
    assert mnmg.ivf_flat_load(comms, spath2).n == 2000

    # classic single-file load still works (kind dispatch)
    spath = str(tmp_path / "classic.rtivf")
    built = mnmg.ivf_flat_build(comms, params, data[:2000])
    mnmg.ivf_flat_save(spath, built)
    assert mnmg.ivf_flat_load(comms, spath).n == 2000
    # wrong-kind error still clean
    with pytest.raises(ValueError, match="not a distributed ivf_pq"):
        mnmg.ivf_pq_load(comms, spath)


def test_refined_search_on_extended_index(comms, blobs):
    """The high-recall pipeline works on driver-built EXTENDED indexes
    via the post-merge refine topology: recall matches the unextended
    refined path and beats the unrefined extended search."""
    data, _ = blobs
    q = data[:24]
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=8)
    dindex = mnmg.ivf_pq_build(comms, params, data[:3000])
    dindex = mnmg.ivf_pq_extend(dindex, data[3000:])
    assert dindex.extended

    _, truth = brute_force.knn(data, q, 5)
    truth = np.asarray(truth)

    def rec(ids):
        ids = np.asarray(ids)
        return np.mean([len(set(ids[i]) & set(truth[i])) / 5
                        for i in range(len(q))])

    _, ui = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16)
    _, ri = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                               refine_dataset=data)
    r_unref, r_ref = rec(ui), rec(ri)
    assert r_ref >= r_unref, (r_ref, r_unref)
    assert r_ref >= 0.95, r_ref
    # extended-block rows must be reachable refined (their gids are in
    # the appended id range)
    probe = np.asarray(data[3200:3204])
    _, pi_ = mnmg.ivf_pq_search(dindex, probe, 1, n_probes=16,
                                refine_dataset=data)
    assert np.all(np.asarray(pi_).ravel() >= 3000)
    # an explicit sharded query_mode request degrades to replicated WITH
    # a warning (the caller asked for a layout it can't get; ADVICE r3),
    # and still returns correct results
    with pytest.warns(UserWarning, match="sharded.*REPLICATED"):
        _, si = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                                   refine_dataset=data, query_mode="sharded")
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
    # auto mode keeps the silent fallback
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                           refine_dataset=data, query_mode="auto")
    # wrong row count still validated
    with pytest.raises(ValueError, match="rows"):
        mnmg.ivf_pq_search(dindex, q, 5, refine_dataset=data[:3000])


def test_bad_query_mode_rejected_with_refine(comms, blobs):
    """query_mode validation runs even when the refined-extended path
    overrides the mode to replicated."""
    data, _ = blobs
    params = ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4)
    dindex = mnmg.ivf_pq_build(comms, params, data[:600])
    dindex = mnmg.ivf_pq_extend(dindex, data[600:700])
    with pytest.raises(ValueError, match="query_mode"):
        mnmg.ivf_pq_search(dindex, data[:4], 3, refine_dataset=data[:700],
                           query_mode="shraded")


def test_distributed_ivf_flat_engines_agree(comms, blobs, flat16):
    """The list-major engine is reachable from the distributed path and
    agrees with query-major (both exact within probed lists; all lists
    probed -> identical neighbor sets). Bad engine names reject."""
    data, _ = blobs
    q = data[:17]
    dindex = flat16
    _, qi = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16, engine="query")
    _, li = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16, engine="list")
    qi_, li_ = np.asarray(qi), np.asarray(li)
    # overlap gate, not exact equality: the list-major per-chunk trim is
    # approx top-k (0.99 target) on TPU — exact only on the CPU fallback
    # (same tolerance rationale as tests/test_ivf_flat.py)
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(li_, qi_))
    assert hits / qi_.size >= 0.95, hits / qi_.size
    # auto routes this duplication (17*16/16 = 17 >= 4) to list-major:
    # same code path, same inputs -> identical output
    _, ai = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16, engine="auto")
    np.testing.assert_array_equal(np.asarray(ai), li_)
    # prefilter composes with the list engine
    mask = np.ones(len(data), bool); mask[::2] = False
    _, fi = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16, engine="list",
                                 prefilter=mask)
    fi = np.asarray(fi)
    assert np.all((fi == -1) | mask[np.maximum(fi, 0)])
    # fused Pallas scan per rank (interpret on CPU): near-exact, high
    # overlap with the exact list-major engine + prefilter invariant
    _, zi = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16, engine="pallas")
    zi = np.asarray(zi)
    hits_z = sum(len(set(a.tolist()) & set(b.tolist()))
                 for a, b in zip(zi, qi_))
    assert hits_z / qi_.size >= 0.9, hits_z / qi_.size
    _, zf = mnmg.ivf_flat_search(dindex, q, 5, n_probes=16, engine="pallas",
                                 prefilter=mask)
    zf = np.asarray(zf)
    assert np.all((zf == -1) | mask[np.maximum(zf, 0)])
    with pytest.raises(ValueError, match="engine"):
        mnmg.ivf_flat_search(dindex, q, 5, engine="warpsort")


def test_distributed_int8_query_scoring(comms, blobs):
    """score_dtype='int8' (symmetric int8 query scoring, the int8 MXU
    path) is reachable distributed: high overlap with bf16 scoring on
    both trim engines; invalid combos reject."""
    data, _ = blobs
    q = data[:9]
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=6)
    dindex = mnmg.ivf_pq_build(comms, params, data[:2000])
    _, b16 = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                                engine="recon8_list")
    b16 = np.asarray(b16)
    for kwargs in (dict(), dict(trim_engine="fused")):
        _, i8 = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                                   engine="recon8_list",
                                   score_dtype="int8", **kwargs)
        i8 = np.asarray(i8)
        hits = sum(len(set(a.tolist()) & set(b.tolist()))
                   for a, b in zip(i8, b16))
        assert hits / b16.size >= 0.8, (kwargs, hits / b16.size)
    with pytest.raises(ValueError, match="score_dtype"):
        mnmg.ivf_pq_search(dindex, q, 5, engine="lut", score_dtype="int8")
    with pytest.raises(ValueError, match="score_dtype"):
        mnmg.ivf_pq_search(dindex, q, 5, score_dtype="fp8")
    # engine="auto" pins int8 / fused requests to recon8_list — a tiny
    # batch (heuristic would pick lut) must still be accepted
    _, a8 = mnmg.ivf_pq_search(dindex, q[:2], 5, n_probes=4,
                               score_dtype="int8")
    assert np.asarray(a8).shape == (2, 5)
    _, ap = mnmg.ivf_pq_search(dindex, q[:2], 5, n_probes=4,
                               trim_engine="fused")
    assert np.asarray(ap).shape == (2, 5)


def test_distributed_int8_fused_trim_engine(comms, blobs):
    """ISSUE 11: trim_engine='fused' + score_dtype='int8' per rank (the
    dispatch layer's fused_int8 strategy) — the XLA int8 trim's
    neighbors, with values to within the one re-associated f32 add
    (see test_fused_int_scan), prefilter invariant, envelope raises;
    a fused search pads the store in place and the XLA trim on the same
    index still returns what it did before."""
    data, _ = blobs
    q = data[:9]
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=6)
    dindex = mnmg.ivf_pq_build(comms, params, data[:2000])
    pv, pi_ = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                                 engine="recon8_list", score_dtype="int8")
    fv, fi = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                                trim_engine="fused", score_dtype="int8")
    np.testing.assert_array_max_ulp(np.asarray(pv), np.asarray(fv),
                                    maxulp=1)
    pi_, fi = np.asarray(pi_), np.asarray(fi)
    assert all(set(a.tolist()) == set(b.tolist()) for a, b in zip(pi_, fi))
    pv2, pi2 = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                                  engine="recon8_list", score_dtype="int8")
    np.testing.assert_array_equal(np.asarray(pv2), np.asarray(pv))
    np.testing.assert_array_equal(np.asarray(pi2), pi_)
    assert dindex.fused_kb == 128
    mask = np.ones(2000, bool); mask[::2] = False
    _, mi = mnmg.ivf_pq_search(dindex, q, 5, n_probes=16,
                               trim_engine="fused", score_dtype="int8",
                               prefilter=mask)
    mi = np.asarray(mi)
    assert np.all((mi == -1) | mask[np.maximum(mi, 0)])
    with pytest.raises(ValueError, match="recon8_list"):
        mnmg.ivf_pq_search(dindex, q, 5, engine="lut", trim_engine="fused")
    with pytest.raises(ValueError, match="trim_engine"):
        mnmg.ivf_pq_search(dindex, q, 5, trim_engine="pallas")
    with pytest.raises(ValueError, match="caps per-list"):
        # k past FUSED_MAX_K: explicit fused must refuse loudly
        mnmg.ivf_pq_search(dindex, q, 300, n_probes=16,
                           trim_engine="fused", score_dtype="int8")


def test_query_mode_auto_is_volume_aware(comms, monkeypatch, tmp_path):
    """The auto merge-topology policy consults BOTH thresholds: absolute
    batch size and queries-per-k (merge volume is nq*k*world; the round-3
    race surface flips winner with k at fixed nq)."""
    import json
    from raft_tpu.core import tuned

    p = str(tmp_path / "tuned_defaults.json")
    with open(p, "w") as f:
        json.dump({"mnmg_query_sharded_min_nq": 1024,
                   "mnmg_query_sharded_min_nq_per_k": 64}, f)
    monkeypatch.setattr(tuned, "_PATH", p)
    tuned.reload()
    try:
        rq = mnmg._resolve_query_mode
        assert rq("auto", comms, 2048, 10) == "sharded"     # both pass
        assert rq("auto", comms, 2048, 100) == "replicated" # nq < 64*k
        assert rq("auto", comms, 512, 5) == "replicated"    # nq < min_nq
        assert rq("auto", comms, 6400, 100) == "sharded"    # nq == 64*k
        # explicit requests are never overridden by the tuned surface
        assert rq("sharded", comms, 4, 100) == "sharded"
        assert rq("replicated", comms, 10**6, 1) == "replicated"
    finally:
        tuned.reload()


@pytest.mark.parametrize("world", [2, 4, 8])
def test_tournament_merge_matches_allgather_merge(world):
    """The butterfly tournament merge must return EXACTLY what the flat
    allgather merge returns — including on adversarial inputs: exact
    value ties across ranks (broken by rank-major position) and +inf
    padding rows — at every edge width: world=2 runs a single round (no
    interior position re-sort executes), world=4 exactly one, world=8
    two. Runs both implementations on the same per-rank candidates and
    compares bit-for-bit."""
    import jax
    from jax.sharding import PartitionSpec as P
    from raft_tpu.comms.mnmg import (
        _merge_local_topk_tournament, _merge_local_topk_allgather)

    sub = Comms(n_devices=world)
    ac = sub.comms
    rng = np.random.default_rng(3)
    nq, kk, k = 6, 5, 8
    # quantized values force many exact cross-rank ties; one rank all-inf
    v = rng.integers(0, 4, (world, nq, kk)).astype(np.float32)
    v[-1] = np.inf
    v = np.sort(v, axis=-1)
    ids = rng.integers(0, 10_000, (world, nq, kk)).astype(np.int32)

    def both(vv, ii):
        fv, fi = _merge_local_topk_allgather(ac, vv[0], ii[0], k, True)
        tv, ti = _merge_local_topk_tournament(ac, vv[0], ii[0], k, True)
        return fv, fi, tv, ti

    fv, fi, tv, ti = jax.shard_map(
        both, mesh=sub.mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data"), P("data")),
        check_vma=False,
    )(sub.shard(v, axis=0), sub.shard(ids, axis=0))
    np.testing.assert_array_equal(np.asarray(tv).reshape(world, nq, k),
                                  np.asarray(fv).reshape(world, nq, k))
    np.testing.assert_array_equal(np.asarray(ti).reshape(world, nq, k),
                                  np.asarray(fi).reshape(world, nq, k))
    # replicated contract: every rank holds the identical merged result
    t_all = np.asarray(tv).reshape(world, nq, k)
    assert all(np.array_equal(t_all[0], t_all[j]) for j in range(world))


def test_replicated_merge_schedule_gate(comms, monkeypatch, tmp_path):
    """The replicated-merge schedule is a backend-dependent engine choice:
    CPU defaults to allgather (tournament measured ~2x slower on the
    memcpy mesh), TPU to tournament, and the tuned key overrides both."""
    import json
    from raft_tpu.comms.mnmg import _replicated_merge_schedule
    from raft_tpu.core import tuned
    import raft_tpu.core.config as cfg

    # isolate from the COMMITTED tuned file up front: once the on-chip
    # queue writes the schedule key there, the default-behavior asserts
    # below would otherwise read it
    p = str(tmp_path / "tuned_defaults.json")
    monkeypatch.setattr(tuned, "_PATH", p)
    tuned.reload()
    try:
        assert _replicated_merge_schedule() == "allgather"  # CPU default
        monkeypatch.setattr(cfg, "is_tpu_backend", lambda: True)
        assert _replicated_merge_schedule() == "tournament"
        # tuned key measured on THIS backend wins
        with open(p, "w") as f:
            json.dump({"mnmg_replicated_merge_schedule": "allgather",
                       "hints": {"merge_schedule_measured_on": "cpu"}}, f)
        tuned.reload()
        assert _replicated_merge_schedule() == "allgather"
        # a key measured on a DIFFERENT backend is ignored (a chip-won
        # tournament must not flip the CPU mesh and vice versa)
        with open(p, "w") as f:
            json.dump({"mnmg_replicated_merge_schedule": "allgather",
                       "hints": {"merge_schedule_measured_on": "tpu"}}, f)
        tuned.reload()
        monkeypatch.setattr(cfg, "is_tpu_backend", lambda: False)
        assert _replicated_merge_schedule() == "allgather"  # CPU default anyway
        monkeypatch.setattr(cfg, "is_tpu_backend", lambda: True)
        # backend default (tournament) because the hint says tpu != cpu
        assert _replicated_merge_schedule() == "tournament"
    finally:
        tuned.reload()


def test_tournament_schedule_end_to_end(comms, blobs, monkeypatch, tmp_path):
    """Forcing the tournament schedule through the tuned key, the full
    distributed knn returns exactly what the allgather schedule returns
    (integration-level check of the dispatch; CPU defaults to allgather,
    so this is the virtual mesh's only end-to-end tournament exercise)."""
    import json
    import jax
    from raft_tpu.core import tuned

    data, _ = blobs
    q = data[:13]
    base_v, base_i = mnmg.knn(comms, data, q, 6)
    p = str(tmp_path / "tuned_defaults.json")
    with open(p, "w") as f:
        json.dump({"mnmg_replicated_merge_schedule": "tournament",
                   "hints": {"merge_schedule_measured_on": "cpu"}}, f)
    monkeypatch.setattr(tuned, "_PATH", p)
    tuned.reload()
    jax.clear_caches()  # the schedule is baked into traces at trace time
    try:
        tv, ti = mnmg.knn(comms, data, q, 6)
        np.testing.assert_array_equal(np.asarray(ti), np.asarray(base_i))
        np.testing.assert_allclose(np.asarray(tv), np.asarray(base_v),
                                   rtol=1e-6)
    finally:
        tuned.reload()
        jax.clear_caches()


def test_mnmg_lut_fence_and_auto_on_tpu(comms, blobs, pq16, monkeypatch):
    """VERDICT r4 #5 on the distributed path: with the backend reading
    'tpu', engine='auto' never resolves to the device-faulting lut engine
    and explicit engine='lut' raises the fence."""
    import jax

    from raft_tpu.core import tuned
    from raft_tpu.neighbors import ivf_pq as sc_pq

    data, _ = blobs
    q = data[:3]  # dup = 3*4/16 < 4: off the TPU this would be lut
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        _, i_auto = mnmg.ivf_pq_search(pq16, q, 5, n_probes=4, engine="auto")
        assert np.asarray(i_auto).shape == (3, 5)  # ran recon8_list, not lut
        with pytest.raises(ValueError, match="fenced on TPU"):
            mnmg.ivf_pq_search(pq16, q, 5, n_probes=4, engine="lut")
        # the sanctioned override lifts the distributed fence too
        monkeypatch.setenv(sc_pq._LUT_TPU_OVERRIDE, "1")
        _, i_lut = mnmg.ivf_pq_search(pq16, q, 5, n_probes=4, engine="lut")
        assert np.asarray(i_lut).shape == (3, 5)
    finally:
        tuned.reload()

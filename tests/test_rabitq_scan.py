"""IVF-RaBitQ's re-rank depth at quick-tier sizes: the depth rule, and
a re-rank deep enough to cover every probed row returning the exact
k-NN."""

import numpy as np
import pytest

from raft_tpu.neighbors import ivf_rabitq


@pytest.fixture(scope="module")
def small():
    rng = np.random.default_rng(11)
    data = rng.standard_normal((800, 64)).astype(np.float32)
    queries = rng.standard_normal((12, 64)).astype(np.float32)
    index = ivf_rabitq.build(ivf_rabitq.IndexParams(
        n_lists=8, kmeans_n_iters=3, store_dataset=False), data, seed=1)
    return data, queries, index


def _exact(data, queries, k):
    d64 = ((queries.astype(np.float64)[:, None, :]
            - data.astype(np.float64)[None, :, :]) ** 2).sum(-1)
    ids = np.argsort(d64, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d64, ids, axis=1)


@pytest.mark.parametrize("k,mult,depth", [
    (10, 4, 40), (10, 32, 320), (10, 200, 1024), (300, 1, 300)])
def test_rerank_depth(k, mult, depth):
    """rerank_mult * k rows, capped at 1,024, never fewer than k."""
    assert ivf_rabitq.rerank_depth(k, mult) == depth


@pytest.mark.parametrize("k,stored", [(10, False), (40, False),
                                      (10, True)])
def test_deep_rerank_is_exact(small, k, stored):
    """Every list probed and a 1,000-row re-rank over 800 rows: the
    shortlist holds every row, so the re-rank returns the exact k-NN
    and their exact distances, from `refine_dataset` or from the rows
    the index stored."""
    data, queries, index = small
    if stored:
        index = ivf_rabitq.build(ivf_rabitq.IndexParams(
            n_lists=8, kmeans_n_iters=3), data, seed=1)
    vals, ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=8, rerank_mult=1000 // k), index,
        queries, k, refine_dataset=None if stored else data)
    want_ids, want_d = _exact(data, queries, k)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(vals), want_d, rtol=1e-5,
                               atol=1e-5)

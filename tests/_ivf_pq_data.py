"""Shared IVF-PQ test data: a 20,000 x 64 blob corpus with 80 queries,
its exact top-10, and one n_lists=32 / pq_dim=16 index that tests
search read-only. Imported by the IVF-PQ test modules; each module
builds its own (module-scoped) copies."""

import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, ivf_pq
from raft_tpu.random import make_blobs


def recall(found, truth):
    found, truth = np.asarray(found), np.asarray(truth)
    hits = sum(len(set(f.tolist()) & set(t.tolist())) for f, t in zip(found, truth))
    return hits / truth.size


@pytest.fixture(scope="module")
def dataset():
    data, _ = make_blobs(20000, 64, n_clusters=40, cluster_std=1.5, seed=31)
    q, _ = make_blobs(80, 64, n_clusters=40, cluster_std=1.5, seed=32)
    return np.asarray(data), np.asarray(q)


@pytest.fixture(scope="module")
def truth10(dataset):
    data, queries = dataset
    _, t = brute_force.knn(data, queries, 10)
    return np.asarray(t)


@pytest.fixture(scope="module")
def index16(dataset):
    """Shared n_lists=32/pq_dim=16 index: a dozen engine/validation tests
    search it read-only (lazy recon/lane-pad caches are idempotent) —
    one build instead of twelve (VERDICT r3 #8)."""
    data, _ = dataset
    return ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=16), data)

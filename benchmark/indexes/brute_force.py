"""Exact k-NN over the float32 corpus (`brute_force.knn`, served by
`serve.BruteForceSearcher`) with the library's default engine. There is
no index to build.

variant "control" switches on the program's own lower-precision path,
`compute_dtype=bfloat16`.
"""

from types import SimpleNamespace

import jax.numpy as jnp

from raft_tpu import serve
from raft_tpu.neighbors import brute_force

KNN_KWARGS = {"program": {}, "control": {"compute_dtype": jnp.bfloat16}}


def build(cfg: dict, dataset, seed: int):
    return SimpleNamespace(dataset=dataset)


def search_fn(state, cfg: dict, variant: str):
    kw = KNN_KWARGS[variant]
    return lambda queries, k: brute_force.knn(state.dataset, queries, k, **kw)


def searcher(state, cfg: dict, variant: str, buckets) -> serve.Searcher:
    return serve.BruteForceSearcher(state.dataset, **KNN_KWARGS[variant])

"""IVF-PQ with an exact re-rank, composed as a user composes it:
`ivf_pq.search` for a shortlist of refine_mult x k, then
`neighbors.refine` over the float32 corpus. The library's own
`serve.IvfPqSearcher` has no refine step, so the served path uses the
`Refined` searcher below.

variant "program" lets refine pick its strategy ("auto"); "control"
switches on the program's own lower-precision path, the fused re-rank
over bf16-rounded rows (`strategy="fused"`).
"""

from types import SimpleNamespace

import jax

from raft_tpu import serve
from raft_tpu.neighbors import ivf_pq, refine

REFINE_STRATEGY = {"program": None, "control": "fused"}


def build(cfg: dict, dataset, seed: int):
    ix = cfg["index"]
    params = ivf_pq.IndexParams(n_lists=ix["n_lists"], pq_dim=ix["pq_dim"],
                                pq_bits=ix["pq_bits"],
                                kmeans_n_iters=ix["kmeans_n_iters"])
    index = ivf_pq.build(params, dataset, seed=int(seed) % (1 << 31))
    jax.block_until_ready([v for v in vars(index).values()
                           if isinstance(v, jax.Array)])
    return SimpleNamespace(index=index, dataset=dataset)


def _search(state, cfg: dict, variant: str, params):
    mult = cfg["search"]["refine_mult"]
    strategy = REFINE_STRATEGY[variant]

    def fn(queries, k):
        _, cand = ivf_pq.search(params, state.index, queries, mult * k)
        return refine(state.dataset, queries, cand, k, strategy=strategy)
    return fn


def search_fn(state, cfg: dict, variant: str):
    """One batch call; score_mode "auto" resolves by the call's shape."""
    return _search(state, cfg, variant,
                   ivf_pq.SearchParams(n_probes=cfg["search"]["n_probes"]))


class Refined(serve.Searcher):
    def __init__(self, fn, dim: int):
        self._fn = fn
        self.dim = dim

    def search(self, queries, k, probe_scale=1.0, recall_target=None):
        vals, ids = self._fn(queries, k)
        return vals, ids, 1.0


def searcher(state, cfg: dict, variant: str, buckets) -> serve.Searcher:
    """The served searcher, its score_mode pinned to what "auto"
    resolves to at the largest bucket (the program picks the engine; a
    pinned one keeps a request's numerics free of its batch-mates)."""
    n_probes = cfg["search"]["n_probes"]
    mode = ivf_pq._resolve_score_mode(ivf_pq.SearchParams(n_probes=n_probes),
                                      max(buckets), n_probes,
                                      state.index.n_lists)
    params = ivf_pq.SearchParams(n_probes=n_probes, score_mode=mode)
    return Refined(_search(state, cfg, variant, params), cfg["dim"])

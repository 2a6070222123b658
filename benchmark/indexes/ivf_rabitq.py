"""IVF-RaBitQ with its exact re-rank, composed as a user composes it:
`ivf_rabitq.build` with `store_dataset=False`, then `ivf_rabitq.search`
with `refine_dataset` set to the corpus the harness already holds, so
the float32 rows live on the device once. The scan engine is the
library's own choice ("auto").

variant "program" re-ranks the scan's rerank_mult x k shortlist
exactly; "control" switches on the program's own lower-precision path:
the same shortlist (`ivf_rabitq.search` with no rows, at the re-rank
depth) re-ranked by `neighbors.refine(strategy="fused")` over
bf16-rounded rows, as `indexes/ivf_pq.py`'s control is. The fused
re-rank gathers a whole call's candidate rows at once, so the control
hands it `CONTROL_BLOCK` queries at a time.
"""

import copy
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from raft_tpu import serve
from raft_tpu.neighbors import ivf_rabitq, refine

CONTROL_BLOCK = 1000


def build(cfg: dict, dataset, seed: int):
    depth = cfg["search"]["rerank_mult"] * cfg["k"]
    if ivf_rabitq.rerank_depth(cfg["k"], cfg["search"]["rerank_mult"]) != depth:
        raise SystemExit(f"benchmark: this library caps the re-rank below "
                         f"the configuration's {depth} rows")
    ix = cfg["index"]
    params = ivf_rabitq.IndexParams(n_lists=ix["n_lists"],
                                    kmeans_n_iters=ix["kmeans_n_iters"],
                                    store_dataset=False)
    index = ivf_rabitq.build(params, dataset, seed=int(seed) % (1 << 31))
    jax.block_until_ready([v for v in vars(index).values()
                           if isinstance(v, jax.Array)])
    return SimpleNamespace(index=index, dataset=dataset)


def _params(cfg: dict):
    se = cfg["search"]
    return ivf_rabitq.SearchParams(n_probes=se["n_probes"],
                                   rerank_mult=se["rerank_mult"],
                                   query_bits=se["query_bits"])


def _control(state, params):
    depth = ivf_rabitq.rerank_depth
    mult = params.rerank_mult

    def fn(queries, k):
        _, cand = ivf_rabitq.search(params, state.index, queries,
                                    depth(k, mult))
        out = [refine(state.dataset, queries[lo:lo + CONTROL_BLOCK],
                      cand[lo:lo + CONTROL_BLOCK], k, strategy="fused")
               for lo in range(0, queries.shape[0], CONTROL_BLOCK)]
        return (jnp.concatenate([v for v, _ in out]),
                jnp.concatenate([i for _, i in out]))
    return fn


def search_fn(state, cfg: dict, variant: str):
    params = _params(cfg)
    if variant == "control":
        return _control(state, params)
    return lambda queries, k: ivf_rabitq.search(
        params, state.index, queries, k, refine_dataset=state.dataset)


def searcher(state, cfg: dict, variant: str, buckets) -> serve.Searcher:
    """The library's `serve.IvfRabitqSearcher` over a shallow copy of
    the index whose re-rank rows are the harness's corpus (the same
    device buffer, not a copy), so a served reply is re-ranked as a
    batch call is. It has no control variant."""
    if variant == "control":
        raise ValueError("the served IVF-RaBitQ searcher has no control")
    index = copy.copy(state.index)
    index.dataset = state.dataset
    return serve.IvfRabitqSearcher(index, _params(cfg))

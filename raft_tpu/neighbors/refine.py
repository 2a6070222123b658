"""Candidate refinement: exact re-ranking of ANN results.

Reference parity: `raft::neighbors::refine` (neighbors/refine.cuh:71,93,
detail/refine.cuh) — given candidate neighbor ids from a lossy index
(typically IVF-PQ), recompute exact distances against the original dataset
and keep the best k. pylibraft `neighbors.refine`.

TPU design: a gather of candidate rows + one batched matmul per query block
+ select_k — the same streamed pattern as IVF-Flat's fine stage. The
select is dispatched through `matrix.select_k`: the "fused" strategy
(tuned `select_k_strategy`, or explicit `strategy="fused"`) re-ranks each
query's gathered candidate block with the fused distance+select-k kernel
(ops/fused_scan.fused_list_topk, one "list" of candidates per query), so
the (nq, n_cand) score matrix never materializes — the fused
exact-distance rerank that backs IVF-PQ/IVF-RaBitQ recall recovery.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.distance.distance_types import DistanceType, resolve_metric
from raft_tpu.matrix.select_k import _select_k_impl
from raft_tpu.core.config import auto_convert_output
from raft_tpu.core.tracing import scoped

_LANES = 128


@functools.partial(jax.jit, static_argnames=("k", "metric"))
@scoped("refine")
def _refine_impl(dataset, queries, candidates, k: int, metric: DistanceType):
    nq, nc = candidates.shape
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf

    qb = max(1, (1 << 22) // max(1, nc * dataset.shape[1]))
    qb = min(qb, nq)
    nblocks = -(-nq // qb)
    pad = nblocks * qb - nq
    qp = jnp.pad(queries, ((0, pad), (0, 0))) if pad else queries
    cp = jnp.pad(candidates, ((0, pad), (0, 0)), constant_values=-1) if pad else candidates

    from raft_tpu.distance.pairwise import _MATMUL_PRECISION

    def block(inp):
        qs, cand = inp
        cdata = dataset[jnp.maximum(cand, 0)].astype(jnp.float32)  # (qb, nc, dim)
        dots = jnp.einsum("qd,qcd->qc", qs.astype(jnp.float32), cdata,
                          precision=_MATMUL_PRECISION)
        if metric == DistanceType.InnerProduct:
            score = dots
        else:
            qn = jnp.sum(qs.astype(jnp.float32) ** 2, axis=1)[:, None]
            cn = jnp.sum(cdata**2, axis=2)
            score = jnp.maximum(qn + cn - 2.0 * dots, 0.0)
        score = jnp.where(cand >= 0, score, worst)
        v, pos = _select_k_impl(score, k, select_min)
        return v, jnp.take_along_axis(cand, pos, axis=1)

    vals, ids = lax.map(
        block, (qp.reshape(nblocks, qb, -1), cp.reshape(nblocks, qb, nc))
    )
    vals = vals.reshape(-1, k)[:nq]
    ids = ids.reshape(-1, k)[:nq]
    if metric == DistanceType.L2SqrtExpanded:
        vals = jnp.sqrt(vals)
    return vals, ids


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "interpret", "fault_key")
)
@scoped("refine")
def _refine_fused_impl(dataset, queries, candidates, k: int,
                       metric: DistanceType, interpret: bool = False,
                       fault_key=None):
    """Fused exact rerank: gather each query's candidate rows and hand
    the block to the fused scan+select kernel as one "list" per query
    (chunk=1), so scoring and selection stay in VMEM and only the
    (nq, k) result reaches HBM. Exact over the bf16-rounded candidate
    rows, ties to the smaller candidate slot (== the smaller position
    in the candidate list — the lax.top_k stable order)."""
    cdata = dataset[jnp.maximum(candidates, 0)]
    return _fused_rerank_gathered(
        cdata, queries, candidates, k, metric, interpret, fault_key
    )


def _fused_rerank_gathered(cdata, queries, candidates, k: int,
                           metric: DistanceType, interpret: bool,
                           fault_key):
    """Shared fused rerank over already-gathered candidate rows
    (cdata (nq, nc, dim) aligned with candidates (nq, nc)); traced
    inside the callers' jits."""
    from raft_tpu.ops.fused_scan import fused_list_topk

    ip = metric == DistanceType.InnerProduct
    nq, nc = candidates.shape
    ncp = -(-nc // _LANES) * _LANES
    # the kernel dots bf16 operands: ship the store AS bf16 (halving the
    # dominant candidate stream, like every other fused caller) and —
    # critically — derive |v|^2 and |q|^2 from the SAME rounded rows.
    # Mixing unrounded f32 norms with bf16 dots cancels wrong on data
    # with a large common offset (|v|^2 - 2<q,v> is a difference of two
    # huge near-equal terms; the flat _scan_fused_impl pins the same
    # invariant).
    cb = cdata.astype(jnp.bfloat16)
    if ncp > nc:
        cb = jnp.pad(cb, ((0, 0), (0, ncp - nc), (0, 0)))
        candidates = jnp.pad(
            candidates, ((0, 0), (0, ncp - nc)), constant_values=-1
        )
    cf = cb.astype(jnp.float32)
    valid = candidates >= 0
    if ip:
        base = jnp.where(valid, 0.0, jnp.inf)[:, None, :]
    else:
        base = jnp.where(valid, jnp.sum(cf * cf, axis=2), jnp.inf)[:, None, :]
    qf = queries.astype(jnp.float32)
    vals, slots = fused_list_topk(
        jnp.arange(nq, dtype=jnp.int32), qf[:, None, :], cb, base, k,
        inner_product=ip, interpret=interpret, fault_key=fault_key,
    )  # (nq, 1, kbuf) exact best-first, minimizing
    vals = vals[:, 0, :k]
    slots = slots[:, 0, :k]
    invalid = ~jnp.isfinite(vals)
    slots = jnp.where(invalid, 0, slots)  # sentinel -> safe gather
    ids = jnp.take_along_axis(candidates, slots, axis=1)
    ids = jnp.where(invalid, -1, ids)
    if ip:
        return jnp.where(invalid, -jnp.inf, -vals), ids
    qb = qf.astype(jnp.bfloat16).astype(jnp.float32)
    qn = jnp.sum(qb * qb, axis=1, keepdims=True)
    v = jnp.maximum(vals + qn, 0.0)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(v)
    return v, ids


def _resolve_refine_strategy(strategy, metric: DistanceType, nc: int,
                             dim: int, k: int) -> str:
    """Refine's select dispatch: the one tuned `select_k_strategy`
    policy (matrix.select_k), gated on the fused LIST kernel covering
    this metric/geometry — refine's fused path is one lane-padded
    candidate "list" per query, so the fit check is fits_fused_list
    (bf16 store), not the flat-scan envelope."""
    from raft_tpu.matrix.select_k import (
        _fused_metric_kind, resolve_scan_strategy,
    )
    from raft_tpu.ops.fused_scan import fits_fused_list

    ncp = -(-nc // _LANES) * _LANES
    fits = 0 < k <= ncp and fits_fused_list(1, ncp, dim, k,
                                            store_itemsize=2)
    if strategy == "fused":
        if _fused_metric_kind(metric) is None:
            raise ValueError(
                f"strategy='fused' supports L2/inner_product metrics, "
                f"got {metric}"
            )
        if not fits:
            raise ValueError(
                f"strategy='fused': candidate block ({ncp} x dim {dim}, "
                f"k={k}) exceeds the fused kernel's envelope; use "
                "strategy='two_phase'"
            )
        return "fused"
    return resolve_scan_strategy(
        nc, dim, k, strategy,
        fused_ok=_fused_metric_kind(metric) is not None and fits,
    )


def _charge_refine_cost(nq: int, nc: int, dim: int, k: int, fused: bool):
    if obs.enabled():
        obs.span_cost(**obs.perf.cost_for(
            "neighbors.refine", nq=nq, n_cand=nc, dim=dim, k=k,
            dtype="bf16" if fused else "f32", fused=fused))


@obs.spanned("neighbors.refine")
@auto_convert_output
def refine(
    dataset,
    queries,
    candidates,
    k: int,
    metric="sqeuclidean",
    resources=None,
    strategy: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank `candidates` (nq, n_cand) with exact distances; return the
    best (distances, indices) of shape (nq, k). Ids of -1 are skipped.

    `strategy`: None/"auto" resolves the select through the tuned
    `select_k_strategy` dispatch (matrix.select_k); "fused" forces the
    fused rerank kernel (exact over bf16-rounded rows, score matrix
    never in HBM); "two_phase" forces the materializing reference path.
    """
    from raft_tpu.core.validation import check_matrix

    ds = check_matrix(dataset, name="dataset")
    q = check_matrix(queries, name="queries")
    cand = jnp.asarray(candidates)
    if cand.ndim != 2 or cand.shape[0] != q.shape[0]:
        raise ValueError("candidates must be (n_queries, n_candidates)")
    m = resolve_metric(metric)
    if k > cand.shape[1]:
        raise ValueError(f"k={k} > n_candidates={cand.shape[1]}")
    strat = _resolve_refine_strategy(
        strategy, m, int(cand.shape[1]), int(ds.shape[1]), int(k)
    )
    _charge_refine_cost(int(q.shape[0]), int(cand.shape[1]),
                        int(ds.shape[1]), int(k), strat == "fused")
    if strat == "fused":
        from raft_tpu.core import faults

        vals, ids = _refine_fused_impl(
            ds, q, cand.astype(jnp.int32), int(k), m,
            interpret=jax.default_backend() == "cpu",  # Mosaic needs TPU
            fault_key=faults.trace_key(),
        )
    else:
        vals, ids = _refine_impl(ds, q, cand.astype(jnp.int32), int(k), m)
    if resources is not None:
        resources.track(vals, ids)
    return vals, ids


@obs.spanned("neighbors.refine")
@auto_convert_output
def refine_host(
    dataset,
    queries,
    candidates,
    k: int,
    metric="sqeuclidean",
    resources=None,
    strategy: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Host-dataset refine (the reference's host-side overload,
    detail/refine.cuh host impl; neighbors/refine.cuh:93): the full
    dataset stays in host RAM (numpy/memmap) — only the candidate rows
    (nq x n_cand x dim, a few MB) are gathered on host and shipped to the
    device for the exact re-rank. This is the 10M+/100M-row pipeline where
    uploading the whole dataset to HBM is not an option. `strategy`
    dispatches the device-side select like `refine`."""
    import numpy as np

    from raft_tpu.core.validation import check_matrix

    q = check_matrix(queries, name="queries")
    cand = np.asarray(candidates)
    if cand.ndim != 2 or cand.shape[0] != q.shape[0]:
        raise ValueError("candidates must be (n_queries, n_candidates)")
    m = resolve_metric(metric)
    if k > cand.shape[1]:
        raise ValueError(f"k={k} > n_candidates={cand.shape[1]}")
    host = np.asarray(dataset)
    strat = _resolve_refine_strategy(
        strategy, m, int(cand.shape[1]), int(host.shape[1]), int(k)
    )
    _charge_refine_cost(int(q.shape[0]), int(cand.shape[1]),
                        int(host.shape[1]), int(k), strat == "fused")
    cdata = host[np.clip(cand, 0, host.shape[0] - 1)].astype(np.float32)
    if strat == "fused":
        from raft_tpu.core import faults

        vals, ids = _refine_fused_gathered_impl(
            jnp.asarray(cdata), q, jnp.asarray(cand.astype(np.int32)),
            int(k), m, interpret=jax.default_backend() == "cpu",
            fault_key=faults.trace_key(),
        )
    else:
        vals, ids = _refine_gathered_impl(
            jnp.asarray(cdata), q, jnp.asarray(cand.astype(np.int32)),
            int(k), m
        )
    if resources is not None:
        resources.track(vals, ids)
    return vals, ids


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "interpret", "fault_key")
)
@scoped("refine")
def _refine_fused_gathered_impl(cdata, queries, candidates, k: int,
                                metric: DistanceType,
                                interpret: bool = False, fault_key=None):
    """Fused twin of `_refine_gathered_impl` (candidate rows already
    gathered on host)."""
    return _fused_rerank_gathered(
        cdata, queries, candidates, k, metric, interpret, fault_key
    )


@functools.partial(jax.jit, static_argnames=("k", "metric"))
@scoped("refine")
def _refine_gathered_impl(cdata, queries, candidates, k: int, metric: DistanceType):
    """Exact re-rank when candidate rows are already gathered:
    cdata (nq, nc, dim) aligned with candidates (nq, nc)."""
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf
    qs = queries.astype(jnp.float32)

    from raft_tpu.distance.pairwise import _MATMUL_PRECISION

    dots = jnp.einsum("qd,qcd->qc", qs, cdata.astype(jnp.float32),
                      precision=_MATMUL_PRECISION)
    if metric == DistanceType.InnerProduct:
        score = dots
    else:
        qn = jnp.sum(qs**2, axis=1)[:, None]
        cn = jnp.sum(cdata.astype(jnp.float32) ** 2, axis=2)
        score = jnp.maximum(qn + cn - 2.0 * dots, 0.0)
    score = jnp.where(candidates >= 0, score, worst)
    v, pos = _select_k_impl(score, k, select_min)
    ids = jnp.take_along_axis(candidates, pos, axis=1)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(v)
    return v, ids

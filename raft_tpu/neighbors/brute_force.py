"""Brute-force (exact) k-nearest neighbors.

Reference parity: `raft::neighbors::brute_force::knn` (neighbors/brute_force.cuh:148),
the tiled engine `tiled_brute_force_knn` (detail/knn_brute_force.cuh:51) and
`knn_merge_parts` (neighbors/brute_force.cuh:80, detail/knn_merge_parts.cuh);
pylibraft `neighbors.brute_force.knn`.

TPU design: stream the database through in column tiles. Each tile computes a
(q, tile) distance block (MXU matmul for expanded metrics) and immediately
reduces it to a running top-k carried through a `lax.scan` — distance
materialization is bounded by the tile size, exactly the role of the
reference's tiling + warpsort queue merging, but expressed functionally so
XLA can overlap the matmul of tile t+1 with the top-k of tile t.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.distance.distance_types import DistanceType, resolve_metric, SIMILARITY_METRICS
from raft_tpu.distance.pairwise import _pairwise_impl
from raft_tpu.matrix.select_k import _select_k_impl
from raft_tpu import obs
from raft_tpu.core.config import auto_convert_output

# database rows per tile in the scanned path
_TILE = 1 << 15
#: stage scopes of `_bf_knn_impl`, in its ops' metadata
DISTANCE, SELECT = "brute_force.distance", "brute_force.select"


@functools.partial(
    jax.jit, static_argnums=(2, 3), static_argnames=("k", "metric", "metric_arg", "tile")
)
def _bf_knn_impl(
    dataset: jax.Array,
    queries: jax.Array,
    k: int,
    metric: DistanceType,
    *,
    metric_arg: float = 2.0,
    tile: int = _TILE,
    n_valid=None,
    prefilter=None,
) -> Tuple[jax.Array, jax.Array]:
    """`n_valid` (may be a traced scalar): rows at or past it are masked
    to the worst value BEFORE selection — masking after a top-k lets pad
    rows displace true neighbors out of the selection entirely (zero pads
    sit closer to many queries than real far-away rows). `prefilter`
    (core.bitset.Bitset over dataset row ids, a pytree arg) masks
    filtered-out rows the same way, also before selection.

    Stage scopes in the program's op metadata: `brute_force.distance`
    (tiling, distances, masks) and `brute_force.select` (the per-tile
    select-k and the running merge)."""
    n = dataset.shape[0]
    select_min = metric not in SIMILARITY_METRICS
    worst = jnp.inf if select_min else -jnp.inf

    if n <= max(2 * tile, 4 * k):
        with jax.named_scope(DISTANCE):
            d = _pairwise_impl(queries, dataset, metric, metric_arg=metric_arg)
            if n_valid is not None:
                d = jnp.where(jnp.arange(n)[None, :] < n_valid, d, worst)
            if prefilter is not None:
                d = jnp.where(prefilter.test(jnp.arange(n))[None, :], d, worst)
        with jax.named_scope(SELECT):
            vals, idx = _select_k_impl(d, k, select_min)
            return vals, idx.astype(jnp.int32)

    ntiles = -(-n // tile)
    pad = ntiles * tile - n
    with jax.named_scope(DISTANCE):
        if pad:
            padval = jnp.full((pad, dataset.shape[1]), 0, dataset.dtype)
            dataset = jnp.concatenate([dataset, padval], axis=0)
        tiles = dataset.reshape(ntiles, tile, dataset.shape[1])
    q = queries.shape[0]
    limit = n if n_valid is None else jnp.minimum(n_valid, n)

    def step(carry, inp):
        best_v, best_i = carry
        t, dtile = inp
        with jax.named_scope(DISTANCE):
            d = _pairwise_impl(queries, dtile, metric, metric_arg=metric_arg)
            base = t * tile
            if pad or n_valid is not None or prefilter is not None:
                col = jnp.arange(tile) + base
                keep = col[None, :] < limit
                if prefilter is not None:
                    keep = keep & prefilter.test(col)[None, :]
                d = jnp.where(keep, d, worst)
        with jax.named_scope(SELECT):
            v, i = _select_k_impl(d, min(k, tile), select_min)
            i = i.astype(jnp.int32) + base
            # merge running queue with tile candidates (knn_merge_parts)
            cat_v = jnp.concatenate([best_v, v], axis=1)
            cat_i = jnp.concatenate([best_i, i], axis=1)
            mv, mi = _select_k_impl(cat_v, k, select_min)
            return (mv, jnp.take_along_axis(cat_i, mi, axis=1)), None

    with jax.named_scope(SELECT):
        init = (
            jnp.full((q, k), worst, jnp.float32),
            jnp.full((q, k), -1, jnp.int32),
        )
    # the loop's own work (slicing each tile out of the corpus) is the
    # distance stage's; an op takes the innermost scope on its path
    with jax.named_scope(DISTANCE):
        (vals, idx), _ = lax.scan(step, init, (jnp.arange(ntiles), tiles))
    return vals, idx

@obs.spanned("neighbors.brute_force.knn")
@auto_convert_output
def knn(
    dataset,
    queries,
    k: int,
    metric="sqeuclidean",
    metric_arg: float = 2.0,
    resources=None,
    engine: str = "tiled",
    prefilter=None,
    compute_dtype=None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact k-NN: returns (distances, indices), each (n_queries, k),
    sorted best-first. pylibraft-compatible (neighbors/brute_force.pyx).

    `engine`: "tiled" (default — XLA pairwise tiles + select_k),
    "pallas"/"fused" — the fused distance+select-k scan (the
    fused_l2_knn analogue, spatial/knn/detail/fused_l2_knn.cuh), a thin
    wrapper over `matrix.scan_select_k(strategy="fused")`
    (ops/fused_scan.py): one Pallas kernel scores bf16 tiles on the MXU
    and keeps the per-query candidate buffer in VMEM, so the
    (nq, n) score matrix never touches HBM. EXACT over the
    bf16-rounded operands (ties to the smaller row id) — the same
    rounding trade as compute_dtype=bfloat16;
    L2/sqeuclidean/inner_product only, k <= 256 — or "auto", which
    resolves through the tuned `select_k_strategy` dispatch policy.

    `compute_dtype`: optional dtype the operands are cast to before the
    distance computation (accumulation stays f32). `jnp.bfloat16` takes
    ONE MXU pass where f32 inputs need the six-pass HIGHEST mode —
    several times faster — at the cost of ranking the bf16-rounded
    points: neighbors whose true distance gap is below bf16 noise may
    swap (measured recall@10 ~0.99 on 1M x 96 gaussian blobs). The
    reference's half-precision instantiations make the same trade
    (detail/knn_brute_force.cuh's half specializations).

    `prefilter`: optional `core.bitset.Bitset` (or 1-D boolean mask)
    over dataset row ids — rows whose bit is clear are excluded BEFORE
    selection (sample-filtering parity with later RAFT's
    `search_with_filtering`). When fewer than k rows pass, the tail
    holds the worst distance with index -1.

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import brute_force
    >>> data = np.array([[0.0], [1.0], [10.0]])
    >>> d, i = brute_force.knn(data, np.array([[0.9]]), k=2)
    >>> np.asarray(i).tolist()
    [[1, 0]]
    """
    from raft_tpu.core.validation import check_matrix, check_same_cols

    ds = check_matrix(dataset, name="dataset")
    q = check_matrix(queries, name="queries")
    check_same_cols(ds, q, "dataset", "queries")
    if engine == "fused":
        engine = "pallas"  # one fused engine, two spellings
    if compute_dtype is not None:
        if engine == "pallas":
            # the fused kernel already computes in bf16; pre-rounding
            # the operands would only degrade recall with no speed gain
            raise ValueError(
                "compute_dtype applies to engine='tiled' only "
                "(engine='pallas' already computes in bf16)"
            )
        ds = ds.astype(compute_dtype)
        q = q.astype(compute_dtype)
    if not (0 < k <= ds.shape[0]):
        raise ValueError(f"k={k} out of range for dataset with {ds.shape[0]} rows")
    m = resolve_metric(metric)
    if engine == "auto":
        # route the engine decision through the one dispatch policy
        # (matrix.select_k): the tuned `select_k_strategy` winner picks
        # the fused scan when the kernel fits this geometry
        from raft_tpu.matrix.select_k import (
            _fused_metric_kind, resolve_scan_strategy,
        )

        strat = resolve_scan_strategy(
            int(ds.shape[0]), int(ds.shape[1]), int(k), None,
            fused_ok=_fused_metric_kind(m) is not None
            and compute_dtype is None,
        )
        engine = "pallas" if strat == "fused" else "tiled"
    if engine not in ("tiled", "pallas"):
        raise ValueError(f"unknown engine {engine!r}")
    if obs.enabled():
        # the fused engine never materializes the score matrix: charge
        # the fused geometry so banked MFU reflects the fusion
        obs.span_cost(**obs.perf.cost_for(
            "neighbors.brute_force.knn", n=int(ds.shape[0]),
            nq=int(q.shape[0]), d=int(ds.shape[1]), k=int(k),
            dtype=jnp.bfloat16 if engine == "pallas" else ds.dtype,
            fused=engine == "pallas"))
    pf = None
    if prefilter is not None:
        from raft_tpu.core.bitset import as_bitset

        pf = as_bitset(prefilter, ds.shape[0])
    if engine == "pallas":
        vals, idx = _bf_fused_pallas(ds, q, int(k), m, prefilter=pf)
    else:
        vals, idx = _bf_knn_impl(
            ds, q, int(k), m, metric_arg=float(metric_arg), prefilter=pf
        )
    if pf is not None:
        # fewer than k rows may pass the filter: a worst-scored slot can
        # still carry a masked row's id out of the tie — re-test returned
        # ids against the bitset (score-based detection would also clobber
        # a surviving row whose true distance overflows to inf)
        idx = jnp.where(pf.test(idx), idx, -1)
    if resources is not None:
        resources.track(vals, idx)
    return vals, idx


def _bf_fused_pallas(
    dataset: jax.Array,
    queries: jax.Array,
    k: int,
    metric: DistanceType,
    prefilter=None,
) -> Tuple[jax.Array, jax.Array]:
    """Thin wrapper over the one dispatch door: the fused scan IS
    `matrix.scan_select_k(strategy="fused")` (ops/fused_scan.py). The
    old residual-chunked reuse of the IVF list-scan engine is gone —
    the flat fused kernel streams the dataset directly, is exact over
    the bf16-rounded operands, and returns (values, ids) without the
    score matrix ever touching HBM."""
    from raft_tpu.matrix.select_k import _fused_metric_kind, scan_select_k

    if _fused_metric_kind(metric) is None:
        raise ValueError(
            f"engine='pallas' supports L2/inner_product metrics, got {metric}"
        )
    valid = None
    if prefilter is not None:
        # a (n,) mask IS the whole filtering mechanism: masked rows
        # score +inf before the in-kernel selection
        valid = prefilter.test(jnp.arange(dataset.shape[0]))
    vals, idx = scan_select_k(
        queries, dataset, int(k), metric=metric, strategy="fused",
        valid=valid,
    )
    return vals, idx.astype(jnp.int32)


@obs.spanned("neighbors.brute_force.knn_merge_parts")
def knn_merge_parts(
    distances,
    indices,
    k: Optional[int] = None,
    select_min: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-part top-k results into a global top-k.

    Parity with `knn_merge_parts` (neighbors/brute_force.cuh:80): inputs are
    (n_parts, n_queries, k_part) stacks or (n_queries, n_parts*k_part)
    concatenations of per-shard results whose indices are already global.
    """
    d = jnp.asarray(distances)
    i = jnp.asarray(indices)
    if d.ndim == 3:
        n_parts, n_q, kp = d.shape
        d = jnp.moveaxis(d, 0, 1).reshape(n_q, n_parts * kp)
        i = jnp.moveaxis(i, 0, 1).reshape(n_q, n_parts * kp)
    k = d.shape[1] if k is None else k
    v, sel = _select_k_impl(d, int(k), bool(select_min))
    return v, jnp.take_along_axis(i, sel, axis=1)

"""IVF-Flat: inverted-file index over raw vectors.

Reference parity: `raft::neighbors::ivf_flat` — index type & params
(ivf_flat_types.hpp:41-161), build (detail/ivf_flat_build.cuh: balanced
k-means on a trainset fraction + assign + per-list interleaved storage),
search (detail/ivf_flat_search.cuh:1086: coarse GEMM+select over centers,
then fused interleaved scan+top-k per probed list), `adaptive_centers`
(ivf_flat_types.hpp:63); pylibraft `neighbors.ivf_flat`.

TPU design (not a port): XLA needs static shapes, so the CUDA growable
interleaved lists become a **padded dense list-major store**:

  - `list_data` (n_lists, max_list, dim) — each vector stored inside its
    list's slots, the direct analogue of the reference's interleaved list
    chunks (data lives IN the lists, not behind an indirection). A probed
    list is one contiguous (max_list, dim) block, so search gathers whole
    lists with large DMAs instead of per-row random access.
  - `slot_rows` (n_lists, max_list) int32 — slot -> position in
    `source_ids`, -1 for padding (kIndexGroupSize-style group-of-32
    padding, ivf_list_types.hpp:42); balanced k-means keeps max/mean small.

Search = coarse top-n_probes over centers (one MXU matmul + select_k), then
per query block: gather probed lists, one batched matmul for the fine
distances, mask padding, select_k. Both stages ride the MXU; the list
gather is the HBM-bandwidth term the reference pays in its interleaved scan.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.core.config import auto_convert_output
from raft_tpu.distance.distance_types import DistanceType, resolve_metric
from raft_tpu.matrix.select_k import _select_k_impl
from raft_tpu.cluster import kmeans_balanced


@dataclasses.dataclass
class IndexParams:
    """Mirrors ivf_flat::index_params (ivf_flat_types.hpp:44-70)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    metric_arg: float = 2.0
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False
    add_data_on_build: bool = True

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)


@dataclasses.dataclass
class SearchParams:
    """Mirrors ivf_flat::search_params (ivf_flat_types.hpp:125).

    engine (TPU design choice, no reference analogue):
      "query" — query-major: gather each query's probed lists and score
                with one batched matmul per query block.
      "list"  — list-major: probe pairs inverted into per-list chunks so
                each list's vectors stream from HBM once per batch
                (~nq*n_probes/n_lists x less gather traffic; best for
                large query batches). Each (query, list)'s candidates are
                trimmed to their exact top-k (the select-k dispatch; the
                TPU's partial-select TopK); the final per-query merge is
                exact.
      "auto"  — "list" when the batch re-reads each list >= 4x, else
                "query".

    Both engines keep IVF-Flat's contract, exact-within-probed-lists
    (recall loss comes only from probing). The default stays "query"; opt
    into "list"/"auto" for batch-throughput workloads.

    Adaptive probing (neighbors/probe_budget, ROADMAP item 2): with
    `adaptive=True` (or a `recall_target` / explicit `budget_tau`) each
    query gets its own probe budget from the normalized gap profile of
    its sorted coarse scores, clamped to [`min_probes`, n_probes], and
    — when the index carries build-time list radii and the metric is
    L2 — `early_term` additionally skips probed lists whose distance
    lower bound provably cannot reach the query's top-k. All engines
    honor the resulting keep mask; `recall_target=1.0` (the saturated
    plan) is bit-identical to the fixed-n_probes reference, which also
    remains the fallback whenever radii are absent (old checkpoints)
    or centers move under `adaptive_centers`.

    "pallas" (alias "fused"; experimental until validated on-chip) runs
    the list-major scheme with the fused distance+select-k Pallas
    kernel (ops/fused_scan.fused_list_topk, the analogue of the
    reference's fused interleaved scan, ivf_flat_search.cuh:670):
    scoring + an EXACT in-kernel partial top-k stay fused, so the
    (chunk, L) score tile never touches HBM and — unlike the older
    bin-trim kernel — the engine is exact-within-probed-lists, the same
    contract as "query"/"list" modulo bf16 rounding of the residual
    store. It pads the index's list store to lane multiples IN PLACE on
    first use (monotone; other engines then recompile once for the
    wider shape and scan the masked pad slots), records the compiled
    candidate-buffer width (`Index.fused_kb`, grown monotonically when
    a later search asks for a larger k — a k past the recorded width
    must rebuild, never silently truncate candidates), and caps k at
    256. Scoring streams a derived bf16 RESIDUAL store (v - center,
    built lazily like IVF-PQ's recon8; +0.5x dataset HBM): residual
    magnitudes keep the bf16 matmul precise and halve the scan's
    dominant HBM stream.
    """

    n_probes: int = 20
    engine: str = "query"  # "query" | "list" | "auto" | "pallas"
    # -- adaptive probing (neighbors/probe_budget) --
    adaptive: bool = False
    recall_target: Optional[float] = None  # implies adaptive; >=1 saturates
    budget_tau: Optional[float] = None     # explicit profile cutoff
    min_probes: int = 1
    early_term: bool = True                # bound-based list skipping


class Index:
    """IVF-Flat index (ivf_flat_types.hpp:126 `struct index`).

    Attributes (all jax.Arrays):
      centers    (n_lists, dim) f32 coarse centroids
      list_data  (n_lists, max_list, dim) vectors in list-major slots
      slot_rows  (n_lists, max_list) int32 slot -> source_ids position (-1 pad)
      list_sizes (n_lists,) int32
      source_ids (n_rows,) int32 caller row ids
    """

    def __init__(self, params: IndexParams, centers, list_data, slot_rows, list_sizes, source_ids):
        self.params = params
        self.centers = centers
        self.list_data = list_data
        self.slot_rows = slot_rows
        self.list_sizes = list_sizes
        self.source_ids = source_ids
        # derived store for the fused Pallas engine (built lazily, like
        # IVF-PQ's recon8): bf16 per-slot residuals v - center and their
        # f32 norms |v - center|^2, plus the candidate-buffer width the
        # fused kernel was compiled for (k past it triggers a monotone
        # rebuild in _pad_store_to_lanes — never a silent truncation)
        self.resid_bf16 = None
        self.resid_norm = None
        self.fused_kb = None
        # per-list radii (max member distance to its centroid), the
        # early-termination bounds of adaptive probing: computed in one
        # pass at build, max-folded by extend, serialized alongside the
        # store. None = bounds absent (old checkpoints, or centers moved
        # under adaptive_centers) -> budgets-only fallback.
        self.list_radii = None
        # live-mutation state (neighbors/mutation): `tombstones` is an
        # optional (n_lists, max_list) dead-row mask (nonzero = dead;
        # None = all live, the zero-cost fast path — searches on an
        # unmutated index trace the identical program). `mut_cursor`
        # counts applied mutation-log entries at the last checkpoint
        # commit; `append_slack` records the per-list tail-slot reserve
        # the mutator maintains so upserts land without re-padding.
        self.tombstones = None
        self.mut_cursor = 0
        self.append_slack = 0
        # integrity sidecar (raft_tpu/integrity): per-list / per-table
        # CRC-32C digests; None = no sidecar (legacy), the scrubber
        # attaches one on first contact
        self.list_digests = None
        self.table_digests = None
        self._id_bound = None

    @property
    def n_tombstones(self) -> int:
        """Dead-slot count (0 when all-live) — the truthful-accounting
        input: cost-model charges bill live rows only."""
        if self.tombstones is None:
            return 0
        return int(jnp.sum(jnp.asarray(self.tombstones).astype(jnp.int32)))

    @property
    def id_bound(self) -> int:
        """One past the largest source id — the id space a search
        `prefilter` must cover. Equals `size` for default arange ids;
        larger when extend() was given custom new_indices (a size-bound
        filter would silently exclude those rows). Cached per Index
        instance (extend returns a new Index, so mutation invalidates)."""
        if self._id_bound is None:
            self._id_bound = (
                int(jnp.max(self.source_ids)) + 1 if self.size else 0
            )
        return self._id_bound

    @property
    def metric(self) -> DistanceType:
        return self.params.metric

    @property
    def n_lists(self) -> int:
        return int(self.centers.shape[0])

    @property
    def dim(self) -> int:
        return int(self.centers.shape[1])

    @property
    def size(self) -> int:
        return int(self.source_ids.shape[0])

    @property
    def adaptive_centers(self) -> bool:
        return self.params.adaptive_centers

    @property
    def dataset(self) -> jax.Array:
        """Flat (n, dim) view of the stored vectors in insertion order
        (decoded from the list-major store; build-time helper, not a hot
        path)."""
        return _unpack_flat(self.list_data, self.slot_rows, self.size)

    def __repr__(self):
        return (
            f"ivf_flat.Index(n_lists={self.n_lists}, dim={self.dim}, size={self.size}, "
            f"metric={self.metric.name})"
        )


# ---------------------------------------------------------------------------
# build / extend
# ---------------------------------------------------------------------------


def _pack_lists(labels: np.ndarray, n_lists: int, group: int = 32):
    """Build the padded slot table from assignment labels.

    Rounds max list size up to a multiple of `group`, mirroring the
    reference's kIndexGroupSize=32 interleaving (ivf_list_types.hpp:42) —
    keeps gathered tiles lane-aligned on the VPU. Uses the native C++
    packer (raft_tpu.native) when available; numpy fallback below.
    """
    from raft_tpu import native

    packed = native.pack_lists(np.asarray(labels), n_lists, group)
    if packed is not None:
        return packed
    sizes = np.bincount(labels, minlength=n_lists)
    max_sz = max(int(sizes.max()) if len(labels) else 0, 1)
    max_sz = -(-max_sz // group) * group
    row_ids = np.full((n_lists, max_sz), -1, np.int32)
    order = np.argsort(labels, kind="stable")
    starts = np.zeros(n_lists + 1, np.int64)
    np.cumsum(sizes, out=starts[1:])
    for l in range(n_lists):
        members = order[starts[l] : starts[l + 1]]
        row_ids[l, : len(members)] = members
    return row_ids, sizes.astype(np.int32)


def _unpack_flat(list_data: jax.Array, slot_rows: jax.Array, n: int) -> jax.Array:
    """Recover the flat (n, d) row store from the list-major slots."""
    d = list_data.shape[-1]
    valid = slot_rows >= 0
    rows = jnp.where(valid, slot_rows, n)  # dump padding into a scratch row
    flat = jnp.zeros((n + 1, d), list_data.dtype).at[rows.reshape(-1)].set(
        list_data.reshape(-1, d)
    )
    return flat[:n]


@obs.spanned("neighbors.ivf_flat.build")
def build(params: IndexParams, dataset, resources=None, seed: int = 0) -> Index:
    """Train coarse centers (balanced k-means on a trainset fraction) and
    populate lists (detail/ivf_flat_build.cuh `build`)."""
    from raft_tpu.core.validation import check_matrix

    x = check_matrix(dataset, name="dataset")
    n = x.shape[0]
    if params.n_lists > n:
        raise ValueError(f"n_lists={params.n_lists} > dataset rows {n}")
    frac = min(max(params.kmeans_trainset_fraction, 0.0), 1.0)
    n_train = max(params.n_lists, int(n * frac)) if frac < 1.0 else n
    n_train = min(n_train, n)
    # random trainset subsample (parity with ivf_flat_build.cuh's build
    # path, which subsamples its trainset; IVF-PQ here already does):
    # a first-n slice is biased on sorted/clustered datasets
    if n_train < n:
        from raft_tpu.random.rng import sample_without_replacement

        sel = sample_without_replacement(jax.random.PRNGKey(seed), n, n_train)
        x_train = x[sel]
    else:
        x_train = x
    metric_name = "inner_product" if params.metric == DistanceType.InnerProduct else "sqeuclidean"
    if params.n_lists > 1024:
        centers = kmeans_balanced.fit_hierarchical(
            x_train, params.n_lists, n_iters=params.kmeans_n_iters, metric=metric_name,
            seed=seed,
        )
    else:
        centers = kmeans_balanced.fit(
            x_train, params.n_lists, n_iters=params.kmeans_n_iters, metric=metric_name,
            seed=seed,
        )
    index = Index(
        params,
        centers,
        jnp.zeros((params.n_lists, 1, x.shape[1]), x.dtype),
        jnp.full((params.n_lists, 1), -1, jnp.int32),
        jnp.zeros((params.n_lists,), jnp.int32),
        jnp.zeros((0,), jnp.int32),
    )
    # empty index: every list radius is 0 — extend max-folds each batch
    # in, so streamed builds carry exact bounds at no extra pass
    index.list_radii = jnp.zeros((params.n_lists,), jnp.float32)
    if params.add_data_on_build:
        index = extend(index, x, jnp.arange(n, dtype=jnp.int32))
    # build-time integrity sidecar: one full digest pass here, then
    # every mutation keeps it incrementally fresh (integrity/digest)
    from raft_tpu.integrity.digest import attach as _attach_digests

    _attach_digests(index, "ivf_flat")
    return index


def _append_slots(labels_new: np.ndarray, old_sizes: np.ndarray, n_lists: int,
                  group: int = 32):
    """Compute per-new-row (list, slot) placements appended after the
    existing list contents, and the grown table geometry.

    Returns (slot_abs (n_new,), new_sizes (n_lists,), new_max_list int) —
    O(n_new) host work, independent of the rows already stored (this is what
    makes batched `extend` linear overall)."""
    counts_new = np.bincount(labels_new, minlength=n_lists)
    new_sizes = old_sizes + counts_new
    new_max = max(int(new_sizes.max()) if n_lists else 1, 1)
    new_max = -(-new_max // group) * group
    # stable within-list order of the new rows
    order = np.argsort(labels_new, kind="stable")
    rank = np.empty_like(order)
    starts = np.zeros(n_lists, np.int64)
    starts[1:] = np.cumsum(counts_new)[:-1]
    rank[order] = np.arange(len(labels_new)) - starts[labels_new[order]]
    slot_abs = old_sizes[labels_new] + rank
    return slot_abs.astype(np.int32), new_sizes.astype(np.int32), new_max


@functools.partial(jax.jit, static_argnames=("new_max",))
def _grow_and_scatter_multi(tables, slot_rows, new_rows, labels, slots,
                            positions, new_max: int):
    """Grow N parallel list tables to new_max slots and place the new
    batch into its (label, slot) cells with ONE shared placement. The
    placement is a sort + searchsorted + gather — NOT an XLA scatter,
    which TPU lowers to a serialized per-index loop (a 1M-row extend
    would crawl): sort the new rows by destination cell, then every
    table cell binary-searches whether a new row landed on it and
    selects between the old value and that row. Multi-payload indexes
    (IVF-RaBitQ's codes + corrections) pay the sort once."""
    old_max = tables[0].shape[1]
    if new_max > old_max:
        tables = tuple(
            jnp.pad(t, ((0, 0), (0, new_max - old_max), (0, 0)))
            for t in tables
        )
        slot_rows = jnp.pad(
            slot_rows, ((0, 0), (0, new_max - old_max)), constant_values=-1
        )
    n_lists = tables[0].shape[0]
    n_new = new_rows[0].shape[0]
    if n_new == 0:
        return tables, slot_rows
    fl = labels.astype(jnp.int32) * new_max + slots.astype(jnp.int32)  # unique cells
    order = jnp.argsort(fl)
    sorted_fl = fl[order]
    cells = jnp.arange(n_lists * new_max, dtype=jnp.int32)
    pos = jnp.minimum(
        jnp.searchsorted(sorted_fl, cells).astype(jnp.int32), n_new - 1
    )
    hit = sorted_fl[pos] == cells
    row = order[pos]
    out = []
    for t, nv in zip(tables, new_rows):
        d = t.shape[-1]
        flat = t.reshape(n_lists * new_max, d)
        flat = jnp.where(hit[:, None], nv[row].astype(flat.dtype), flat)
        out.append(flat.reshape(n_lists, new_max, d))
    flat_rows = slot_rows.reshape(n_lists * new_max)
    flat_rows = jnp.where(hit, positions[row], flat_rows)
    return tuple(out), flat_rows.reshape(n_lists, new_max)


def _grow_and_scatter(list_data, slot_rows, nv, labels, slots, positions,
                      new_max: int):
    """Single-payload wrapper over `_grow_and_scatter_multi` (IVF-Flat's
    vectors, IVF-PQ's codes)."""
    (out,), rows = _grow_and_scatter_multi(
        (list_data,), slot_rows, (nv,), labels, slots, positions, new_max
    )
    return out, rows


@obs.spanned("neighbors.ivf_flat.extend")
def extend(index: Index, new_vectors, new_indices=None) -> Index:
    """Append vectors to the index (ivf_flat build.cuh `extend`): label ONLY
    the new rows, grow the list tables, scatter the batch into its slots.
    Cost is O(n_new + table copy) — no re-clustering or re-packing of the
    rows already stored, so streamed builds stay linear."""
    from raft_tpu.core.validation import check_matrix

    nv = check_matrix(new_vectors, name="new_vectors")
    old_n = index.size
    if new_indices is None:
        new_indices = jnp.arange(old_n, old_n + nv.shape[0], dtype=jnp.int32)
    else:
        new_indices = jnp.asarray(new_indices, jnp.int32)

    metric_name = (
        "inner_product" if index.metric == DistanceType.InnerProduct else "sqeuclidean"
    )
    labels = np.asarray(kmeans_balanced.predict(nv, index.centers, metric=metric_name))
    old_sizes = np.asarray(index.list_sizes, np.int64)
    slot_abs, new_sizes, new_max = _append_slots(labels, old_sizes, index.n_lists)
    # a store padded for the Pallas engine may be wider than the sizes
    # imply — never shrink it (slots stay where they are)
    new_max = max(new_max, int(index.list_data.shape[1]))
    positions = jnp.arange(old_n, old_n + nv.shape[0], dtype=jnp.int32)
    list_data, slot_rows = _grow_and_scatter(
        index.list_data,
        index.slot_rows,
        jnp.asarray(nv).astype(index.list_data.dtype),
        jnp.asarray(labels),
        jnp.asarray(slot_abs),
        positions,
        new_max,
    )
    all_ids = jnp.concatenate([index.source_ids, new_indices]) if old_n else new_indices

    centers = index.centers
    if index.adaptive_centers:
        # running-mean center update from the new batch only
        # (ivf_flat_types.hpp:63 semantics, applied incrementally)
        from raft_tpu.cluster.kmeans_common import assign_and_reduce

        _, sums, counts, _ = assign_and_reduce(jnp.asarray(nv), centers)
        old_w = jnp.asarray(old_sizes, jnp.float32)[:, None]
        total = old_w + counts[:, None]
        upd = (centers * old_w + sums) / jnp.maximum(total, 1.0)
        centers = jnp.where(counts[:, None] > 0, upd, centers)

    out = Index(
        index.params, centers, list_data, slot_rows, jnp.asarray(new_sizes), all_ids
    )
    if index.adaptive_centers:
        # moved centers invalidate the stored bounds (radii were taken
        # against the OLD centers); adaptive probing falls back to
        # budgets-only, the documented bounds-absent semantics
        out.list_radii = None
    else:
        from raft_tpu.neighbors.probe_budget import updated_radii

        dists = np.asarray(jnp.sqrt(jnp.maximum(jnp.sum(
            (jnp.asarray(nv, jnp.float32) - index.centers[jnp.asarray(labels)]
             ) ** 2, axis=1), 0.0)))
        out.list_radii = updated_radii(
            index.list_radii, labels, dists, index.n_lists)
    # mutation state survives extend: the mask pads with live columns
    # when the store grew (new tail slots are live appends by
    # construction), cursor/slack carry verbatim
    from raft_tpu.core.bitset import carry_tombstones

    out.tombstones = carry_tombstones(index.tombstones, new_max)
    out.mut_cursor = index.mut_cursor
    out.append_slack = index.append_slack
    # integrity sidecar: only the lists this batch touched re-digest
    from raft_tpu.integrity.digest import refresh as _refresh_digests

    _refresh_digests(out, index, "ivf_flat")
    return out


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _coarse_scores(queries: jax.Array, centers: jax.Array, metric: DistanceType):
    from raft_tpu.distance.pairwise import _dot

    if metric == DistanceType.InnerProduct:
        return _dot(queries, centers), False  # larger better
    d = _dot(queries, centers)
    qn = jnp.sum(queries.astype(jnp.float32) ** 2, axis=1)[:, None]
    cn = jnp.sum(centers.astype(jnp.float32) ** 2, axis=1)[None, :]
    return jnp.maximum(qn + cn - 2.0 * d, 0.0), True  # smaller better


def resolve_auto_engine(nq: int, n_probes: int, n_lists: int,
                        pallas_ok=None) -> str:
    """The ONE "auto" engine policy, shared by the single-chip and
    distributed searches: a tuned winner (`flat_auto_engine`) first,
    else the duplication heuristic (list-major streams each probed list
    once, paying off when nq*n_probes/n_lists >= 4). A tuned "fused"
    winner names the fused scan+select kernel — the same engine the
    "pallas" spelling always named, so both resolve identically.
    `pallas_ok` (callable or None) gates that winner: None means the
    caller has no pallas engine (distributed) and the winner maps to
    "list", its closest list-major analogue."""
    from raft_tpu.core import tuned

    t = tuned.get("flat_auto_engine")
    if t == "fused":
        t = "pallas"  # one fused engine, two spellings
    if t == "pallas":
        if pallas_ok is None:
            t = "list"
        elif not pallas_ok():
            t = None  # tuned winner doesn't fit this index/k; fall through
    if t in ("query", "list", "pallas"):
        return t
    dup = nq * n_probes / max(1, n_lists)
    return "list" if dup >= 4.0 else "query"


@functools.partial(
    jax.jit, static_argnames=("k", "n_probes", "metric", "query_block")
)
def _search_impl(
    queries: jax.Array,
    centers: jax.Array,
    list_data: jax.Array,
    slot_rows: jax.Array,
    k: int,
    n_probes: int,
    metric: DistanceType,
    query_block: int = 8,
    pvalid: jax.Array = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (distances, slot-table values): the second output carries
    whatever `slot_rows` holds per slot (source positions locally; global
    row ids in the distributed path). `pvalid` ((nq, n_probes) bool,
    optional): the adaptive probe keep mask — masked probes' slots read
    as -1, exactly like padding, before any selection."""
    nq = queries.shape[0]
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf

    cs, coarse_min = _coarse_scores(queries, centers, metric)
    _, probes = _select_k_impl(cs, n_probes, coarse_min)  # (nq, n_probes)

    qb = min(query_block, nq)
    nblocks = -(-nq // qb)
    pad = nblocks * qb - nq
    qp = jnp.pad(queries, ((0, pad), (0, 0))) if pad else queries
    pp = jnp.pad(probes, ((0, pad), (0, 0))) if pad else probes
    qblocks = qp.reshape(nblocks, qb, -1)
    pblocks = pp.reshape(nblocks, qb, n_probes)
    if pvalid is not None:
        pvp = jnp.pad(pvalid, ((0, pad), (0, 0))) if pad else pvalid
        pvblocks = pvp.reshape(nblocks, qb, n_probes)

    from raft_tpu.distance.pairwise import _MATMUL_PRECISION

    def block(inp):
        if pvalid is not None:
            qs, pr, pvb = inp  # (qb, dim), (qb, n_probes), (qb, n_probes)
        else:
            qs, pr = inp  # (qb, dim), (qb, n_probes)
        cand = slot_rows[pr]  # (qb, n_probes, max_list), -1 pad
        if pvalid is not None:
            cand = jnp.where(pvb[:, :, None], cand, -1)
        cand = cand.reshape(qb, -1)  # (qb, C) table values, -1 pad
        cdata = list_data[pr].reshape(qb, cand.shape[1], -1)  # (qb, C, dim)
        dots = jnp.einsum(
            "qd,qcd->qc", qs, cdata.astype(jnp.float32), precision=_MATMUL_PRECISION
        )
        if metric == DistanceType.InnerProduct:
            score = dots
        else:
            qn = jnp.sum(qs.astype(jnp.float32) ** 2, axis=1)[:, None]
            cn = jnp.sum(cdata.astype(jnp.float32) ** 2, axis=2)
            score = jnp.maximum(qn + cn - 2.0 * dots, 0.0)
        score = jnp.where(cand >= 0, score, worst)
        v, pos = _select_k_impl(score, k, select_min)
        ids = jnp.take_along_axis(cand, pos, axis=1)
        return v, ids

    vals, ids = lax.map(
        block,
        (qblocks, pblocks, pvblocks) if pvalid is not None
        else (qblocks, pblocks))
    vals = vals.reshape(-1, k)[:nq]
    ids = ids.reshape(-1, k)[:nq]
    if metric == DistanceType.L2SqrtExpanded:
        vals = jnp.sqrt(vals)
    return vals, ids


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "metric", "chunk",
    ),
)
def _search_impl_listmajor(
    queries: jax.Array,
    centers: jax.Array,
    list_data: jax.Array,
    slot_rows: jax.Array,
    k: int,
    n_probes: int,
    metric: DistanceType,
    chunk: int = 128,
    pvalid: jax.Array = None,
) -> Tuple[jax.Array, jax.Array]:
    """List-major search: each list's vectors stream from HBM once per
    ~chunk probing queries and score with one MXU matmul — vs the
    query-major engine re-reading every probed list per query block
    (~nq*n_probes/n_lists x more gather traffic). Same candidate math; the
    per-chunk trim keeps each (query, list)'s exact top-k and the final
    per-query merge is exact. See neighbors/probe_invert.py for the
    pair-inversion scheme.
    `pvalid` (adaptive probe budgets): masked pairs are dropped before
    inversion and masked again at the regroup."""
    from raft_tpu.distance.pairwise import _MATMUL_PRECISION
    from raft_tpu.neighbors.probe_invert import invert_probes, score_and_select

    nq, dim = queries.shape
    n_lists, max_list, _ = list_data.shape
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf

    cs, coarse_min = _coarse_scores(queries, centers, metric)
    _, probes = _select_k_impl(cs, n_probes, coarse_min)
    tables = invert_probes(probes, n_lists, chunk, pvalid)

    qf = queries.astype(jnp.float32)
    q_pad = jnp.concatenate([qf, jnp.zeros((1, dim), jnp.float32)])

    def block(inp):
        lofb, qids = inp  # (CB,), (CB, chunk)
        v = list_data[lofb].astype(jnp.float32)  # only read of these vectors
        srows = slot_rows[lofb]
        qs = q_pad[qids]  # (CB, chunk, dim)
        dots = jnp.einsum("lqd,lsd->lqs", qs, v, precision=_MATMUL_PRECISION)
        if metric == DistanceType.InnerProduct:
            score = dots
        else:
            qn = jnp.sum(qs**2, axis=2)[:, :, None]
            vn = jnp.sum(v**2, axis=2)[:, None, :]
            score = jnp.maximum(qn + vn - 2.0 * dots, 0.0)
        return jnp.where(srows[:, None, :] >= 0, score, worst)

    v, ids = score_and_select(
        tables, block, slot_rows, _select_k_impl, nq, n_probes, k, select_min,
        chunk, max_list, stage="ivf_flat",
    )
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(v)
    return v, ids


def _pad_store_to_lanes(index: Index, k: int) -> None:
    """Monotone in-place pad of the list store to the fused Pallas scan's
    lane contract (ops/fused_scan.lane_padded). Pad slots carry
    slot_rows=-1 and zero vectors, which every engine already masks; once
    padded the store stays padded (other engines recompile once for the
    wider shape and scan the masked pad slots).

    Also (re)builds the fused engine's derived store (the IVF-Flat
    analogue of IVF-PQ's build_reconstruction): per-slot RESIDUALS
    v - center_l in bf16 plus their f32 norms. Residuals are small, so
    the kernel's bf16 matmul keeps relative precision (scoring raw
    vectors loses ~1e-2 on near-ties from the large common component),
    and bf16 halves the dominant HBM stream of the scan. Costs 0.5x the
    dataset in extra HBM, rebuilt lazily after extend.

    `k` sizes the compiled candidate-buffer width (`Index.fused_kb`,
    ops/fused_scan.fused_kbuf): searches with k <= fused_kb reuse the
    store geometry as compiled; a LARGER k must grow the width here
    (monotone, like the lane pad) — before this check existed, only a
    store-shape change triggered the rebuild and a k past the compiled
    width silently truncated the per-list candidates."""
    from raft_tpu.ops.fused_scan import fused_kbuf, lane_padded

    max_list = index.list_data.shape[1]
    extra = lane_padded(max_list) - max_list
    if extra:
        index.list_data = jnp.pad(index.list_data, ((0, 0), (0, extra), (0, 0)))
        index.slot_rows = jnp.pad(
            index.slot_rows, ((0, 0), (0, extra)), constant_values=-1
        )
    if (
        getattr(index, "resid_bf16", None) is None
        or index.resid_bf16.shape != index.list_data.shape
    ):
        resid = index.list_data.astype(jnp.float32) - index.centers[:, None, :]
        valid = (index.slot_rows >= 0)[:, :, None]
        resid = jnp.where(valid, resid, 0.0)  # pad slots: exact zeros
        index.resid_bf16 = resid.astype(jnp.bfloat16)
        index.resid_norm = jnp.sum(resid**2, axis=2)
    kb = fused_kbuf(int(k))
    if getattr(index, "fused_kb", None) is None or kb > index.fused_kb:
        index.fused_kb = kb


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "kb", "n_probes", "metric", "chunk", "interpret", "fault_key",
    ),
)
def _search_impl_listmajor_pallas(
    queries: jax.Array,
    centers: jax.Array,
    resid_bf16: jax.Array,
    resid_norm: jax.Array,
    slot_rows: jax.Array,
    k: int,
    n_probes: int,
    metric: DistanceType,
    chunk: int = 128,
    kb: int = None,
    interpret: bool = False,
    fault_key=None,
    pvalid: jax.Array = None,
) -> Tuple[jax.Array, jax.Array]:
    """List-major IVF-Flat search with the fused distance+select-k scan
    (ops/fused_scan.fused_list_topk — the kernel is store-dtype
    generic: here it streams bf16 per-slot RESIDUALS v - center;
    |q - v|^2 = |q'|^2 - 2 q'.res + |res|^2 with q' = q - center, so
    the bf16 matmul sees only small residual magnitudes and the store
    stream is half the bytes of raw f32). Scoring + an EXACT in-kernel
    partial top-k happen fused, so the (chunk, L) score tile never
    round-trips HBM — the TPU analogue of the reference's fused
    interleaved scan (detail/ivf_flat_search.cuh:670). Probe inversion
    and the exact final merge are shared with the XLA trim engine.
    `kb` is the index's recorded candidate-buffer width (fused_kb);
    `fault_key` = faults.trace_key() so chaos plans retrace."""
    from raft_tpu.matrix.select_k import list_scan_select_k
    from raft_tpu.neighbors.probe_invert import (
        chunk_validity,
        invert_probes,
        regroup_merge,
    )

    nq, dim = queries.shape
    n_lists, lpad, _ = resid_bf16.shape
    select_min = metric != DistanceType.InnerProduct
    ip = metric == DistanceType.InnerProduct

    cs, coarse_min = _coarse_scores(queries, centers, metric)
    _, probes = _select_k_impl(cs, n_probes, coarse_min)
    tables = invert_probes(probes, n_lists, chunk, pvalid)
    lof, qid_tbl = tables.lof, tables.qid_tbl
    ncb = lof.shape[0]
    # empty chunks (trailing fragmentation + everything adaptive budgets
    # emptied) skip their MXU work inside the kernel
    cvalid = chunk_validity(qid_tbl, nq)

    qf = queries.astype(jnp.float32)
    q_pad = jnp.concatenate([qf, jnp.zeros((1, dim), jnp.float32)])
    qs = q_pad[qid_tbl]  # (ncb, chunk, dim)
    cent = centers[lof]  # (ncb, dim)
    qres = qs if ip else qs - cent[:, None, :]

    valid = slot_rows >= 0
    if ip:
        base = jnp.where(valid, 0.0, jnp.inf)[:, None, :]
    else:
        base = jnp.where(valid, resid_norm, jnp.inf)[:, None, :]

    vals, slot_idx = list_scan_select_k(
        lof, qres, resid_bf16, base, k, strategy="fused", kbuf=kb,
        inner_product=ip, interpret=interpret, fault_key=fault_key,
        chunk_valid=cvalid,
    )  # (ncb, chunk, kb) exact best-first, minimizing
    # the buffer is sorted: the first k slots ARE the per-(query, list)
    # top-k, so the old post-kernel trim select is gone entirely
    vals = vals[:, :, :k]
    slot_idx = slot_idx[:, :, :k]

    invalid = ~jnp.isfinite(vals)
    slots = jnp.where(invalid, -1, slot_idx)

    if ip:
        # IP score = q.res + q.center; kernel returned -(q.res) on valid
        qdotc = jnp.einsum("cqd,cd->cq", qs, cent)
        vals = jnp.where(invalid, -jnp.inf, -vals + qdotc[:, :, None])
    else:
        qn = jnp.sum(qres**2, axis=2)  # |q - center|^2 per (chunk row)
        vals = jnp.maximum(vals + qn[:, :, None], 0.0)

    v, rows_out = regroup_merge(
        tables, vals, slots, slot_rows, _select_k_impl, nq, n_probes, int(k),
        select_min
    )
    v = v.astype(jnp.float32)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, rows_out


def _pallas_fits(index, k: int) -> bool:
    """engine='pallas' envelope: the fused kernel's k cap and the VMEM
    budget for one grid step (the scanned store is the bf16 residual
    copy, itemsize 2) — ONE definition shared by the auto-dispatch gate
    and the explicit-engine validation. Checked at the buffer width the
    kernel will RUN with: the recorded fused_kb when it is already
    wider than this k needs (a k=10 search on a store grown to kb=256
    compiles the 256-wide buffer). raftlint's `dispatch-envelope-guard`
    machine-checks that every route into the fused kernel stays under
    this validation (docs/linting.md, kernelcheck catalog)."""
    from raft_tpu.ops.fused_scan import (
        FUSED_MAX_K, fits_fused_list, fused_kbuf, lane_padded,
    )

    if not 0 < k <= FUSED_MAX_K:
        return False
    kb = max(fused_kbuf(int(k)), getattr(index, "fused_kb", None) or 0)
    return fits_fused_list(
        128, lane_padded(int(index.list_data.shape[1])), index.dim, int(k),
        store_itemsize=2, kbuf=kb,
    )


@obs.spanned("neighbors.ivf_flat.search")
@auto_convert_output
def search(
    params: SearchParams,
    index: Index,
    queries,
    k: int,
    resources=None,
    prefilter=None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (distances, neighbor source ids), (nq, k), best-first
    (pylibraft ivf_flat.search signature).

    `prefilter`: optional `core.bitset.Bitset` (or 1-D boolean mask) over
    the index's id space (`index.id_bound` ids — == size unless extend() used custom new_indices) — samples whose bit is clear
    are excluded before any trim/selection in EVERY engine, including the
    fused Pallas scan (sample-filtering parity with later RAFT's
    `search_with_filtering`). When fewer than k samples pass, the tail
    holds the worst distance with id -1."""
    from raft_tpu.core.validation import check_matrix

    q = check_matrix(queries, name="queries")
    if q.shape[1] != index.dim:
        raise ValueError(f"query dim {q.shape[1]} != index dim {index.dim}")
    if index.size == 0:
        raise ValueError("index is empty")
    k = int(k)
    if not (0 < k):
        raise ValueError("k must be positive")
    n_probes = int(min(max(1, params.n_probes), index.n_lists))
    # every engine masks scores to the worst value wherever the slot
    # table reads -1 (before trim/selection), so a filtered view is the
    # entire filtering mechanism; applied per branch because the pallas
    # branch pads the table first
    from raft_tpu.core.bitset import make_slot_filter

    maybe_filter = make_slot_filter(prefilter, index.id_bound,
                                    index.source_ids,
                                    tombstones=index.tombstones)
    engine = params.engine
    if engine == "fused":
        engine = "pallas"  # one fused engine, two spellings
    if engine == "auto":
        engine = resolve_auto_engine(
            q.shape[0], n_probes, index.n_lists,
            pallas_ok=lambda: _pallas_fits(index, k),
        )
    # adaptive probing: one (nq, n_probes) keep mask from the coarse
    # geometry (budgets + optional radius bounds), applied by every
    # engine; None = the fixed-n_probes reference path, verbatim
    from raft_tpu.neighbors import probe_budget

    ap = probe_budget.resolve_params(params, n_probes)
    pvalid = None
    scanned_mean = None
    if ap is not None:
        # bounds stay OFF under a prefilter: list_sizes counts
        # filtered-out members, so a bound's k-covering prefix could be
        # entirely filtered and a list holding true ELIGIBLE neighbors
        # would be skipped — budgets-only is the sound fallback. Same
        # soundness argument for tombstones (sizes count dead rows).
        radii = (index.list_radii
                 if ap.early_term and prefilter is None
                 and index.tombstones is None else None)
        pvalid, scanned = probe_budget.probe_plan(
            jnp.asarray(q, jnp.float32), index.centers,
            n_probes=n_probes, min_probes=ap.min_probes, k=k,
            metric=index.metric, tau=ap.tau,
            radii=radii, sizes=index.list_sizes)
        scanned_mean = probe_budget.account(
            "ivf_flat", scanned, int(q.shape[0]), n_probes)
    if obs.enabled():
        # list-major streams every padded list; query-major touches the
        # probed ones — the model must charge what the engine scans
        # (the ACTUAL adaptive mean, not worst-case n_probes, on the
        # engines that skip masked work), and the fused engine never
        # materializes the score tile
        # truthful accounting under mutation: dead (tombstoned) slots
        # contribute no candidates, so the model bills live rows only
        obs.span_cost(**obs.perf.cost_for(
            "neighbors.ivf_flat.search", nq=int(q.shape[0]),
            n_probes=n_probes, n_lists=int(index.n_lists),
            n_rows=int(index.list_data.shape[0] * index.list_data.shape[1])
            - index.n_tombstones,
            dim=int(index.dim), k=k,
            scanned_lists=(int(index.n_lists) if engine == "list"
                           else (scanned_mean if scanned_mean is not None
                                 else n_probes)),
            fused=engine == "pallas"))
    if engine == "pallas":
        from raft_tpu.neighbors.probe_invert import macro_batched
        from raft_tpu.ops.fused_scan import FUSED_MAX_K

        if k > FUSED_MAX_K:
            raise ValueError(
                f"engine='pallas' caps per-list candidates at "
                f"{FUSED_MAX_K}; k={k}"
            )
        # check the VMEM envelope BEFORE padding the store: a rejected
        # request must not leave the index mutated
        if not _pallas_fits(index, k):
            raise ValueError(
                f"engine='pallas': padded list length x dim {index.dim} "
                "exceeds the kernel's VMEM envelope; use engine='list'"
            )
        _pad_store_to_lanes(index, k)
        srows = maybe_filter(index.slot_rows)
        from raft_tpu.core import faults

        vals, rows = macro_batched(
            lambda sl, pv=None: _search_impl_listmajor_pallas(
                sl, index.centers, index.resid_bf16, index.resid_norm,
                srows, k, n_probes, index.metric, kb=index.fused_kb,
                interpret=jax.default_backend() == "cpu",
                fault_key=faults.trace_key(), pvalid=pv,
            ),
            jnp.asarray(q),
            int(k),
            extra=pvalid,
        )
    elif engine == "list":
        from raft_tpu.neighbors.probe_invert import macro_batched

        srows = maybe_filter(index.slot_rows)
        vals, rows = macro_batched(
            lambda sl, pv=None: _search_impl_listmajor(
                sl, index.centers, index.list_data, srows, k, n_probes,
                index.metric, pvalid=pv,
            ),
            jnp.asarray(q),
            int(k),
            extra=pvalid,
        )
    elif engine == "query":
        vals, rows = _search_impl(
            q, index.centers, index.list_data, maybe_filter(index.slot_rows),
            k, n_probes, index.metric, pvalid=pvalid
        )
    else:
        raise ValueError(f"unknown engine {engine!r}")
    ids = jnp.where(rows >= 0, index.source_ids[jnp.maximum(rows, 0)], -1)
    if resources is not None:
        resources.track(vals, ids)
    return vals, ids


# ---------------------------------------------------------------------------
# serialization (detail/ivf_flat_serialize.cuh parity)
# ---------------------------------------------------------------------------

_SERIAL_VERSION = 4  # v2: list-major; v3: mutation; v4: digest sidecar


def save(filename: str, index: Index) -> None:
    from raft_tpu.core.serialize import serialize_arrays

    arrays = {
        "centers": index.centers,
        "list_data": index.list_data,
        "slot_rows": index.slot_rows,
        "list_sizes": index.list_sizes,
        "source_ids": index.source_ids,
    }
    if index.list_radii is not None:
        # early-termination bounds ride the checkpoint; old files
        # simply lack the key and load with bounds absent (fallback)
        arrays["list_radii"] = index.list_radii
    if index.tombstones is not None:
        # dead-row mask (u8: serialized compactly); absent = all-live,
        # the pre-mutation era's implicit contract
        arrays["tombstones"] = jnp.asarray(index.tombstones).astype(jnp.uint8)
    meta = {
        "kind": "ivf_flat",
        "version": _SERIAL_VERSION,
        "metric": int(index.metric),
        "metric_arg": index.params.metric_arg,
        "n_lists": index.n_lists,
        "adaptive_centers": index.params.adaptive_centers,
        # mutation protocol state: applied-log-entry count at this
        # commit + the mutator's reserved per-list tail slack
        "mut_cursor": int(index.mut_cursor),
        "append_slack": int(index.append_slack),
    }
    from raft_tpu.integrity.digest import pack_lists

    packed = pack_lists(index, "ivf_flat")
    if packed is not None:
        # per-list CRC-32C sidecar (v4) rides first-class so the
        # scrubber resumes with build-time coverage after a load
        arrays["list_digests"] = packed
        meta["table_digests"] = {
            k: int(v) for k, v in (index.table_digests or {}).items()}
    serialize_arrays(filename, arrays, meta)


def load(filename: str) -> Index:
    # schema-checked read (core.serialize.CKPT_SCHEMA): kind + version
    # gates, required-field presence, and corrupt registered-optional
    # fields (list_radii) dropped so the load degrades to budgets-only
    # instead of crashing
    from raft_tpu.core.serialize import read_ckpt

    arrays, meta = read_ckpt(filename, "ivf_flat")
    if meta.get("version", 1) < 2:
        raise ValueError("ivf_flat index file version too old (pre-list-major)")
    params = IndexParams(
        n_lists=meta["n_lists"],
        metric=DistanceType(meta["metric"]),
        metric_arg=meta.get("metric_arg", 2.0),
        adaptive_centers=meta.get("adaptive_centers", False),
    )
    index = Index(
        params,
        arrays["centers"],
        arrays["list_data"],
        arrays["slot_rows"],
        arrays["list_sizes"],
        arrays["source_ids"],
    )
    index.list_radii = arrays.get("list_radii")
    # mutation-era fields (v3): absent in old checkpoints -> all-live,
    # cursor 0, no reserved slack — exactly the pre-mutation semantics
    index.tombstones = arrays.get("tombstones")
    index.mut_cursor = int(meta.get("mut_cursor", 0))
    index.append_slack = int(meta.get("append_slack", 0))
    # integrity sidecar (v4): absent/corrupt -> no sidecar, the
    # scrubber attaches a fresh one on first contact
    from raft_tpu.integrity.digest import unpack_lists

    unpack_lists(index, "ivf_flat", arrays.get("list_digests"),
                 meta.get("table_digests"))
    return index

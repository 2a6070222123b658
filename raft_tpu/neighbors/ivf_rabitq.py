"""IVF-RaBitQ: inverted-file ANN index over 1-bit RaBitQ codes.

Reference: the IVF-RaBitQ paper (arXiv 2602.23999) — binary codes
scanned with popcount-style integer ops, an exact-ish UNBIASED distance
estimator, and a cheap exact rerank; and the TPU-KNN paper (arXiv
2206.14286) for the scan shape: never materialize full fp32 score
matrices — the candidate stream here is 1 bit/dim plus two f32
correction scalars per row.

Why it exists next to IVF-PQ (ROADMAP open item 2): *build speed*.
IVF-PQ's build is dominated by codebook EM + codebook-assignment encode;
RaBitQ has NO codebooks — encode is a sign() and two reductions — so
the index builds in roughly the coarse-kmeans time alone, which is what
extrapolates to 100M-row production indexes. Search trades that for
1-bit codes: the estimator ranks candidates well enough that a
`rerank_mult * k` exact re-rank through the shared refine stage
(neighbors/refine.py) recovers recall >= 0.95 at bench geometry.

Layout (all per-IVF-list, the ivf_flat/ivf_pq slot-table scheme):

    rotation  (rot_dim, dim) f32   random orthogonal (always random —
                                   sign binarization needs isotropy);
                                   rot_dim = dim rounded up to 32
    centers   (n_lists, rot_dim)   coarse centroids in rotated space
    codes     (n_lists, max_list, rot_dim/32) uint32 packed sign bits
    aux       (n_lists, max_list, 2) f32  [|r|, <o, x_bar>] corrections
    slot_rows / list_sizes / source_ids   as in ivf_flat

Search: coarse top-n_probes (shared `_coarse_select`), then per query
block the packed codes of the probed lists are scanned with AND+popcount
over the query's quantized bit planes (quantizer.binary_dot), the
unbiased estimator (quantizer.estimate_dot) turns bit overlaps into
distance estimates, and the top rerank_mult*k candidates re-rank exactly
against the original rows (stored on the index by default, or passed as
`refine_dataset`).

Quantizer math lives in neighbors/quantizer.py (`RabitqQuantizer`); the
engine here is the blocked/jitted application of the same traceable
helpers, so the property-tested reference and the hot path cannot
disagree.
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
from raft_tpu.core import faults
from raft_tpu.core.config import auto_convert_output
from raft_tpu.distance.distance_types import DistanceType, resolve_metric
from raft_tpu.matrix.select_k import _select_k_impl
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.neighbors.ivf_pq import _coarse_select, _make_rotation
from raft_tpu.neighbors.ivf_flat import _append_slots, _grow_and_scatter_multi
from raft_tpu.neighbors.quantizer import (
    DEFAULT_QUERY_BITS,
    RabitqQuantizer,
    binary_dot,
    estimate_dot,
    packed_words,
    quantize_queries,
)

#: host-side chaos site: the encode stage of build/extend (the stage
#: whose cheapness IS the fast-build claim — drills prove a slow or
#: flaky encode pass degrades latency, never results)
ENCODE_SITE = "ivf_rabitq.build.encode"

#: exact-rerank depth cap, shared by the single-chip and distributed
#: searches (`rerank_depth`). Refine gathers the shortlist a block of
#: queries at a time, so the cap bounds work, not memory: 1-bit codes of
#: 768-d rows need a few hundred re-ranked rows where near neighbours
#: lie within a few percent of each other's distances
_MAX_RERANK = 1024


@dataclasses.dataclass
class IndexParams:
    """Build parameters (coarse stage mirrors ivf_pq.IndexParams; there
    is deliberately no codebook knob — RaBitQ has none to tune)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    metric_arg: float = 2.0
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    add_data_on_build: bool = True
    # keep the raw rows on the index for the exact rerank stage (the
    # single-chip convenience; costs dataset-sized HBM like IVF-Flat).
    # False = quantized-only index; pass refine_dataset to search, or
    # accept estimator-ranked results.
    store_dataset: bool = True

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)


@dataclasses.dataclass
class SearchParams:
    """Search parameters.

    query_bits   scalar-quantization bits of the query bit planes
                 (1..8); 0 = auto — the measured tuned key
                 ("rabitq_query_bits") when a chip profile wrote one,
                 else 8.
    rerank_mult  exact-rerank depth multiplier: the scan keeps
                 rerank_mult * k candidates (capped at 1,024) for the
                 refine stage; 0 = auto — tuned key
                 ("rabitq_rerank_mult"), else 4. Rerank engages whenever
                 original rows are available (index.dataset or
                 refine_dataset); without them the estimator ranking is
                 returned directly.
    """

    n_probes: int = 20
    query_bits: int = 0
    rerank_mult: int = 0
    # -- adaptive probing (neighbors/probe_budget, ROADMAP item 2) --
    # per-query probe budgets from the rotated coarse gap profile;
    # early-termination bounds come FREE here — the aux table already
    # stores every member's residual norm |r|, so list radii derive
    # lazily with no build-time pass or serialization change. Bounds
    # are exact-space; the estimator ranking's recall is covered by
    # the banked frontier (the PQ caveat). recall_target >= 1.0
    # saturates, bit-identical to the fixed-n_probes reference.
    adaptive: bool = False
    recall_target: Optional[float] = None
    budget_tau: Optional[float] = None
    min_probes: int = 1
    early_term: bool = True


def resolve_query_bits(query_bits: int) -> int:
    """The ONE auto-resolution of the query quantization depth (tuned
    key "rabitq_query_bits"), shared by the single-chip and distributed
    searches."""
    if query_bits:
        if not (1 <= int(query_bits) <= 8):
            raise ValueError(f"query_bits must be in [1, 8], got {query_bits}")
        return int(query_bits)
    from raft_tpu.core import tuned

    t = tuned.get("rabitq_query_bits")
    return int(t) if t in (1, 2, 3, 4, 5, 6, 7, 8) else DEFAULT_QUERY_BITS


def resolve_rerank_mult(rerank_mult: int) -> int:
    """Auto-resolution of the rerank depth multiplier (tuned key
    "rabitq_rerank_mult")."""
    if rerank_mult:
        if rerank_mult < 1:
            raise ValueError(f"rerank_mult must be >= 1, got {rerank_mult}")
        return int(rerank_mult)
    from raft_tpu.core import tuned

    t = tuned.get("rabitq_rerank_mult")
    return int(t) if isinstance(t, int) and 1 <= t <= 64 else 4


class Index:
    """IVF-RaBitQ index (see module docstring for the table layout)."""

    def __init__(self, params: IndexParams, rotation, centers, codes, aux,
                 slot_rows, list_sizes, source_ids, dataset=None):
        self.params = params
        self.rotation = rotation
        self.centers = centers
        self.codes = codes
        self.aux = aux
        self.slot_rows = slot_rows
        self.list_sizes = list_sizes
        self.source_ids = source_ids
        # raw rows in insertion order (store_dataset=True) — the rerank
        # stage's gather source; None on loaded / quantized-only indexes
        self.dataset = dataset
        # adaptive probing's per-list radii, derived lazily from the
        # aux table's stored |r| column (extend returns a new Index,
        # so the cache can never go stale)
        self._list_radii = None
        # live-mutation state (neighbors/mutation): optional dead-row
        # mask (n_lists, max_list; nonzero = dead, None = all-live),
        # applied-log cursor at the last checkpoint commit, reserved
        # per-list append slack. Masked into slot_rows by
        # `core.bitset.make_slot_filter` (pad-aware).
        self.tombstones = None
        self.mut_cursor = 0
        self.append_slack = 0
        # integrity sidecar (raft_tpu/integrity): per-list / per-table
        # CRC-32C digests; None = no sidecar (legacy)
        self.list_digests = None
        self.table_digests = None
        self._id_bound = None

    @property
    def n_tombstones(self) -> int:
        """Dead-slot count (0 when all-live) — truthful accounting:
        cost-model charges bill live rows only."""
        if self.tombstones is None:
            return 0
        return int(jnp.sum(jnp.asarray(self.tombstones).astype(jnp.int32)))

    @property
    def list_radii(self):
        """(n_lists,) f32 max member residual norm per list — the
        early-termination bounds of adaptive probing, a free per-list
        max over the aux table's |r| column."""
        if self._list_radii is None and self.size:
            from raft_tpu.neighbors.probe_budget import list_radii_from_aux

            self._list_radii = list_radii_from_aux(self.aux, self.slot_rows)
        return self._list_radii

    @property
    def id_bound(self) -> int:
        """One past the largest source id — the id space a search
        `prefilter` must cover (== size unless extend() used custom
        new_indices). Cached per instance (extend returns a new Index)."""
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
        return int(self.rotation.shape[1])

    @property
    def rot_dim(self) -> int:
        return int(self.rotation.shape[0])

    @property
    def words(self) -> int:
        return int(self.codes.shape[2])

    @property
    def size(self) -> int:
        return int(self.source_ids.shape[0])

    def __repr__(self):
        return (
            f"ivf_rabitq.Index(n_lists={self.n_lists}, dim={self.dim}, "
            f"rot_dim={self.rot_dim}, size={self.size}, "
            f"metric={self.metric.name})"
        )


# ---------------------------------------------------------------------------
# build / extend
# ---------------------------------------------------------------------------


def rabitq_rot_dim(dim: int) -> int:
    """Packing geometry: dim rounded up to whole 32-bit words."""
    return -(-dim // 32) * 32


@jax.jit
def _encode_rotated(v_rot, labels, centers):
    """Rotated rows -> (codes (n, W) uint32, aux (n, 2) f32) RaBitQ
    payload — the quantizer's encode applied to per-list residuals, as
    one jitted program (shared by extend and the distributed build,
    which calls it inside shard_map)."""
    residuals = v_rot - centers[labels]
    quant = RabitqQuantizer(int(v_rot.shape[-1]))
    payload = quant.encode(residuals)
    return payload["codes"], payload["aux"]


def label_and_encode(vectors, rotation, centers, metric: DistanceType):
    """Rotate, assign to coarse lists, and RaBitQ-encode the residuals —
    the shared encode sequence of `extend` and the distributed build
    (which traces this under shard_map — keep it host-effect-free; the
    "ivf_rabitq.build.encode" chaos hook fires in the HOST callers,
    `extend` and `mnmg.ivf_rabitq_build`, so injection is per-call on
    both paths, never swallowed by a trace cache).
    Returns (labels (n,), codes (n, W) uint32, aux (n, 2) f32)."""
    metric_name = (
        "inner_product" if metric == DistanceType.InnerProduct else "sqeuclidean"
    )
    from raft_tpu.distance.pairwise import _dot

    v_rot = _dot(jnp.asarray(vectors, jnp.float32), rotation)
    labels = kmeans_balanced.predict(v_rot, centers, metric=metric_name)
    codes, aux = _encode_rotated(v_rot, labels, centers)
    return labels, codes, aux


def build(params: IndexParams, dataset, resources=None, seed: int = 0) -> Index:
    """Train rotation + coarse centers, then encode + pack lists. No
    codebook stage — the build is coarse-kmeans-bound, the fast-build
    half of the RaBitQ paper (measured vs IVF-PQ in
    bench/bench_ivf_rabitq.py).

    Spans, with obs enabled: `neighbors.ivf_rabitq.build` around it
    all, `ivf_rabitq.build.coarse` (trainset, balanced k-means) and,
    inside `neighbors.ivf_rabitq.extend`, `ivf_rabitq.build.encode`
    (rotate, label, sign-encode). Each blocks on its outputs as it
    closes, so its time is device time; disabled, the build adds no
    sync."""
    with obs.span("neighbors.ivf_rabitq.build") as sp:
        from raft_tpu.core.validation import check_matrix

        x = check_matrix(dataset, name="dataset").astype(jnp.float32)
        n, dim = x.shape
        if params.n_lists > n:
            raise ValueError(f"n_lists={params.n_lists} > dataset rows {n}")
        rot_dim = rabitq_rot_dim(dim)
        key = jax.random.PRNGKey(seed)
        key, rk = jax.random.split(key)
        # always a random rotation: sign binarization is only unbiased
        # under an isotropic basis (identity would bias toward
        # axis-aligned data)
        rotation = _make_rotation(rk, rot_dim, dim, True)

        # the ONE single-chip coarse-fit scaffolding shared with
        # ivf_pq.build — and the whole training: no codebook stage follows
        from raft_tpu.neighbors.ivf_pq import _coarse_fit

        with obs.span("ivf_rabitq.build.coarse") as st:
            centers, _, key = st.settle(
                _coarse_fit(params, x, rotation, key, seed))

        W = packed_words(rot_dim)
        index = Index(
            params,
            rotation,
            centers,
            jnp.zeros((params.n_lists, 1, W), jnp.uint32),
            jnp.zeros((params.n_lists, 1, 2), jnp.float32),
            jnp.full((params.n_lists, 1), -1, jnp.int32),
            jnp.zeros((params.n_lists,), jnp.int32),
            jnp.zeros((0,), jnp.int32),
        )
        if params.add_data_on_build:
            index = extend(index, x, jnp.arange(n, dtype=jnp.int32))
        # build-time integrity sidecar (kept fresh incrementally after)
        from raft_tpu.integrity.digest import attach as _attach_digests

        _attach_digests(index, "ivf_rabitq")
        if resources is not None:
            resources.track(index.codes)
        sp.settle([v for v in vars(index).values()
                   if isinstance(v, jax.Array)])
    return index


@obs.spanned("neighbors.ivf_rabitq.extend")
def extend(index: Index, new_vectors, new_indices=None) -> Index:
    """Label, encode and append new vectors — O(n_new + table copy),
    sharing ivf_flat's slot placement + gather-scatter so streamed
    builds stay linear."""
    from raft_tpu.core.validation import check_matrix

    nv = check_matrix(new_vectors, name="new_vectors").astype(jnp.float32)
    old_n = index.size
    if new_indices is None:
        new_indices = jnp.arange(old_n, old_n + nv.shape[0], dtype=jnp.int32)
    else:
        new_indices = jnp.asarray(new_indices, jnp.int32)

    # chaos site (host-side, per call): slow_rank models a slow encode
    # pass — latency only, results untouched; flaky_bootstrap a
    # transient dispatch failure retried by callers
    with obs.span("ivf_rabitq.build.encode") as st:
        faults.fault_point(ENCODE_SITE)
        labels, new_codes, new_aux = st.settle(label_and_encode(
            nv, index.rotation, index.centers, index.metric
        ))

    labels_np = np.asarray(labels, np.int64)
    old_sizes = np.asarray(index.list_sizes, np.int64)
    slot_abs, new_sizes, new_max = _append_slots(labels_np, old_sizes,
                                                 index.n_lists)
    # a store padded wider than the sizes imply (mutation append slack)
    # must never shrink — slots stay where they are
    new_max = max(new_max, int(index.slot_rows.shape[1]))
    positions = jnp.arange(old_n, old_n + nv.shape[0], dtype=jnp.int32)
    # one shared placement sort grows BOTH payload tables
    (codes_tbl, aux_tbl), slot_rows = _grow_and_scatter_multi(
        (index.codes, index.aux), index.slot_rows, (new_codes, new_aux),
        jnp.asarray(labels_np), jnp.asarray(slot_abs), positions, new_max,
    )
    all_ids = (jnp.concatenate([index.source_ids, new_indices])
               if old_n else new_indices)

    ds = None
    if index.params.store_dataset:
        ds = nv if index.dataset is None else jnp.concatenate(
            [index.dataset, nv])

    out = Index(
        index.params,
        index.rotation,
        index.centers,
        codes_tbl,
        aux_tbl,
        slot_rows,
        jnp.asarray(new_sizes),
        all_ids,
        dataset=ds,
    )
    # mutation state survives extend (new tail slots are live appends)
    from raft_tpu.core.bitset import carry_tombstones

    out.tombstones = carry_tombstones(index.tombstones,
                                      int(slot_rows.shape[1]))
    out.mut_cursor = index.mut_cursor
    out.append_slack = index.append_slack
    # integrity sidecar: only the lists this batch touched re-digest
    from raft_tpu.integrity.digest import refresh as _refresh_digests

    _refresh_digests(out, index, "ivf_rabitq")
    return out


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _rabitq_query_block(n_probes: int, max_list: int, query_bits: int,
                        words: int) -> int:
    # the (qb, np, max_list, bits, W) popcount intersection tensor, kept
    # ~<= 2^26 int32 elements; XLA fuses it into the per-slot sums, so a
    # block's temp does not grow with qb (0.97 GB at qb 1 and at qb 16
    # for 768-d, 16 probes of 2,016 slots, compiled for a v5e), while
    # each step of the block loop runs a whole top-k: at those widths
    # 2^22 would give one query a step, 10,000 steps a call
    qb = max(1, (1 << 26) // max(1, n_probes * max_list * query_bits * words))
    return int(min(qb, 16))


@functools.partial(
    jax.jit, static_argnames=("k", "n_probes", "metric", "query_bits")
)
def _search_impl_rabitq(
    queries,
    rotation,
    centers,
    codes,
    aux,
    slot_rows,
    k: int,
    n_probes: int,
    metric: DistanceType,
    query_bits: int = DEFAULT_QUERY_BITS,
    pvalid: jax.Array = None,
):
    """Binary-code scan: per (query, probe) the packed sign codes stream
    once and score via AND+popcount against the query's quantized bit
    planes (quantizer.binary_dot), then the unbiased RaBitQ estimator
    (quantizer.estimate_dot) maps bit overlaps to distances. Integer ops
    end to end on the candidate side — no fp32 score matrix of the
    probed rows ever materializes (TPU-KNN's memory-shape argument).
    Returns (estimated distances, slot-table values) of shape (nq, k);
    the second output carries whatever `slot_rows` holds (positions
    locally, global ids distributed)."""
    nq = queries.shape[0]
    n_lists, max_list, W = codes.shape
    rot_dim = rotation.shape[0]
    select_min = metric != DistanceType.InnerProduct
    ip = metric == DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf

    with jax.named_scope("ivf_rabitq.coarse"):
        q_rot, probes = _coarse_select(queries, rotation, centers, n_probes,
                                       metric)
    rnorm = aux[..., 0]
    o_dot = aux[..., 1]
    # a candidate depth beyond the probed width selects everything there
    # is; the tail pads to k below (worst score, row -1) so the output
    # width contract holds for ANY k
    k_sel = int(min(k, n_probes * max_list))

    qb = _rabitq_query_block(n_probes, max_list, query_bits, W)
    nblocks = -(-nq // qb)
    pad = nblocks * qb - nq
    qp = jnp.pad(q_rot, ((0, pad), (0, 0))) if pad else q_rot
    pp = jnp.pad(probes, ((0, pad), (0, 0))) if pad else probes
    qblocks = qp.reshape(nblocks, qb, rot_dim)
    pblocks = pp.reshape(nblocks, qb, n_probes)
    if pvalid is not None:
        pvp = jnp.pad(pvalid, ((0, pad), (0, 0))) if pad else pvalid
        pvblocks = pvp.reshape(nblocks, qb, n_probes)

    def block(inp):
        if pvalid is not None:
            qs, pr, pvb = inp  # + (qb, n_probes) adaptive keep mask
        else:
            qs, pr = inp  # (qb, rot_dim), (qb, n_probes)
        pc = centers[pr]  # (qb, np, rot)
        if ip:
            qres = jnp.broadcast_to(qs[:, None, :], pc.shape)
        else:
            qres = qs[:, None, :] - pc
        planes, lo, delta = quantize_queries(qres, query_bits)
        qsum = jnp.sum(qres, axis=-1)  # (qb, np)

        cand = codes[pr]  # (qb, np, max_list, W) uint32
        # per-slot set-bit counts of the PROBED lists only (popcounting
        # the whole table would make every query O(index size))
        pop = jnp.sum(
            lax.population_count(cand).astype(jnp.int32), axis=-1
        ).astype(jnp.float32)  # (qb, np, max_list)
        # S_u[q,n,s] = sum of quantized query levels over the code's set
        # bits — AND+popcount over the bit planes (the fast-scan core)
        s_u = binary_dot(cand, planes[:, :, None, :, :])  # (qb,np,S)
        s = lo * pop + delta * s_u  # (qb, np, S); lo/delta (qb,np,1)
        est = estimate_dot(s, pop, qsum[:, :, None], o_dot[pr], rot_dim)
        rn = rnorm[pr]
        if ip:
            qdotc = jnp.sum(qs[:, None, :] * pc, axis=2)
            scores = qdotc[:, :, None] + rn * est
        else:
            qcn = jnp.sum(qres**2, axis=2)
            scores = qcn[:, :, None] + rn**2 - 2.0 * rn * est
        rows = slot_rows[pr]  # (qb, np, max_list)
        if pvalid is not None:
            rows = jnp.where(pvb[:, :, None], rows, -1)
        rows = rows.reshape(qb, -1)
        scores = scores.reshape(qb, -1)
        scores = jnp.where(rows >= 0, scores, worst)
        with jax.named_scope("ivf_rabitq.merge"):
            v, pos = _select_k_impl(scores, k_sel, select_min)
            r = jnp.take_along_axis(rows, pos, axis=1)
            if k_sel < k:  # pad the tail: worst score, row -1 (static)
                v = jnp.pad(v, ((0, 0), (0, k - k_sel)),
                            constant_values=worst)
                r = jnp.pad(r, ((0, 0), (0, k - k_sel)), constant_values=-1)
        return v, r

    with jax.named_scope("ivf_rabitq.scan"):
        vals, rows = lax.map(
            block,
            (qblocks, pblocks, pvblocks) if pvalid is not None
            else (qblocks, pblocks))
    with jax.named_scope("ivf_rabitq.merge"):
        vals = vals.reshape(-1, k)[:nq]
        rows = rows.reshape(-1, k)[:nq]
        if metric == DistanceType.L2SqrtExpanded:
            vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    return vals, rows


def rerank_depth(k: int, rerank_mult: int) -> int:
    """Candidate depth the scan keeps for the exact rerank: never below
    k, capped at `_MAX_RERANK`."""
    return max(int(k), min(int(rerank_mult) * int(k), _MAX_RERANK))


@obs.spanned("neighbors.ivf_rabitq.search")
@auto_convert_output
def search(
    params: SearchParams, index: Index, queries, k: int, resources=None,
    prefilter=None, refine_dataset=None,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search; returns (distances, neighbor source ids) (nq, k).

    The scan ranks candidates by the unbiased RaBitQ estimator; when
    original rows are available (the index stored them, or
    `refine_dataset` — rows in insertion order — is passed) the top
    `rerank_mult * k` candidates re-rank EXACTLY through the shared
    refine stage and the returned distances are exact. Without rows the
    estimator ranking (and its estimated distances) is returned.

    `prefilter`: optional `core.bitset.Bitset` (or 1-D boolean mask)
    over the index's id space (`index.id_bound` ids) — filtered samples
    are excluded before trim/selection, same contract as ivf_flat/
    ivf_pq. When fewer than k samples pass, the tail holds the worst
    distance with id -1."""
    from raft_tpu.core.validation import check_matrix

    q = check_matrix(queries, name="queries")
    if q.shape[1] != index.dim:
        raise ValueError(f"query dim {q.shape[1]} != index dim {index.dim}")
    if index.size == 0:
        raise ValueError("index is empty")
    k = int(k)
    if k <= 0:
        raise ValueError("k must be positive")
    from raft_tpu.core.bitset import make_slot_filter

    maybe_filter = make_slot_filter(prefilter, index.id_bound,
                                    index.source_ids,
                                    tombstones=index.tombstones)
    n_probes = int(min(max(1, params.n_probes), index.n_lists))
    query_bits = resolve_query_bits(params.query_bits)
    rerank_mult = resolve_rerank_mult(params.rerank_mult)
    ds = refine_dataset if refine_dataset is not None else index.dataset
    kk = rerank_depth(k, rerank_mult) if ds is not None else k

    # adaptive probing: one (nq, n_probes) keep mask from the rotated
    # coarse geometry; radii come free from the aux |r| column. Plan
    # depth = kk (the rerank shortlist must survive early termination)
    from raft_tpu.neighbors import probe_budget

    ap = probe_budget.resolve_params(params, n_probes)
    pvalid = None
    scanned_mean = None
    if ap is not None:
        # bounds OFF under a prefilter (see ivf_flat.search: the
        # k-covering prefix counts filtered members) — budgets only;
        # same soundness argument under tombstones (sizes count dead)
        radii = (index.list_radii
                 if ap.early_term and prefilter is None
                 and index.tombstones is None else None)
        pvalid, scanned = probe_budget.probe_plan(
            jnp.asarray(q, jnp.float32), index.centers,
            n_probes=n_probes, min_probes=ap.min_probes, k=int(kk),
            metric=index.metric, tau=ap.tau, rotation=index.rotation,
            radii=radii, sizes=index.list_sizes)
        scanned_mean = probe_budget.account(
            "ivf_rabitq", scanned, int(q.shape[0]), n_probes)
    if obs.enabled():
        # n_rows = padded slot count (n_lists * max_list) — the scan
        # streams pad slots of each probed list too. Adaptive budgets
        # charge the ACTUAL per-query scanned mean, not worst-case
        # n_probes.
        obs.span_cost(**obs.perf.cost_for(
            "neighbors.ivf_rabitq.search", nq=int(q.shape[0]),
            n_probes=(scanned_mean if scanned_mean is not None
                      else n_probes),
            n_lists=int(index.n_lists),
            n_rows=int(index.codes.shape[0] * index.codes.shape[1])
            - index.n_tombstones,
            dim=int(index.dim), k=k,
            query_bits=int(query_bits),
            rerank_mult=int(rerank_mult) if ds is not None else 0))
        # the scan's rows from static shapes, no device sync: the live
        # (query, list) pairs, and the (query, list) rows the scan
        # program scores for them, query-block padding included
        nq = int(q.shape[0])
        qb = _rabitq_query_block(n_probes, int(index.codes.shape[1]),
                                 int(query_bits), index.words)
        scanned_rows = -(-nq // qb) * qb * n_probes
        obs.counter("ivf_rabitq.scan.pairs").inc(nq * n_probes)
        obs.counter("ivf_rabitq.scan.rows").inc(scanned_rows)

    vals, rows = _search_impl_rabitq(
        jnp.asarray(q), index.rotation, index.centers, index.codes,
        index.aux, maybe_filter(index.slot_rows), kk, n_probes,
        index.metric, query_bits=query_bits, pvalid=pvalid,
    )
    if ds is not None:
        # exact rerank through the shared refine stage: candidates are
        # dataset POSITIONS (insertion order; -1 pads skipped), the id
        # map applies after
        quant = RabitqQuantizer(index.rot_dim, query_bits)
        vals, rows = quant.rerank_candidates(
            ds, q, rows, k, metric=index.metric)
    ids = jnp.where(rows >= 0, index.source_ids[jnp.maximum(rows, 0)], -1)
    if resources is not None:
        resources.track(vals, ids)
    return vals, ids


# ---------------------------------------------------------------------------
# serialization (quantizer serialize hooks + the shared CRC container)
# ---------------------------------------------------------------------------

_SERIAL_VERSION = 3  # v2: mutation fields; v3: digest sidecar


def save(filename: str, index: Index) -> None:
    """Serialize the quantized index (checksummed container,
    core/serialize.py). The raw-row store is NOT serialized — a loaded
    index reranks via `refine_dataset`, or serves estimator-ranked."""
    from raft_tpu.core.serialize import serialize_arrays

    quant = RabitqQuantizer(index.rot_dim)
    arrays = {
        "rotation": index.rotation,
        "centers": index.centers,
        "codes": index.codes,
        "aux": index.aux,
        "slot_rows": index.slot_rows,
        "list_sizes": index.list_sizes,
        "source_ids": index.source_ids,
        **quant.state_arrays(),
    }
    if index.tombstones is not None:
        # dead-row mask (u8); absent = all-live (pre-mutation files)
        arrays["tombstones"] = jnp.asarray(index.tombstones).astype(jnp.uint8)
    meta = {
        "kind": "ivf_rabitq",
        "version": _SERIAL_VERSION,
        "metric": int(index.metric),
        "n_lists": index.n_lists,
        "mut_cursor": int(index.mut_cursor),
        "append_slack": int(index.append_slack),
        **quant.state_meta(),
    }
    from raft_tpu.integrity.digest import pack_lists

    packed = pack_lists(index, "ivf_rabitq")
    if packed is not None:
        # per-list CRC-32C sidecar (v3, raft_tpu/integrity)
        arrays["list_digests"] = packed
        meta["table_digests"] = {
            k: int(v) for k, v in (index.table_digests or {}).items()}
    serialize_arrays(filename, arrays, meta)


def load(filename: str) -> Index:
    # schema-checked read (core.serialize.CKPT_SCHEMA): kind + version
    # gates, required-field presence checked before construction
    from raft_tpu.core.serialize import read_ckpt

    arrays, meta = read_ckpt(filename, "ivf_rabitq")
    params = IndexParams(
        n_lists=meta["n_lists"],
        metric=DistanceType(meta["metric"]),
        store_dataset=False,
    )
    index = Index(
        params,
        arrays["rotation"],
        arrays["centers"],
        arrays["codes"],
        arrays["aux"],
        arrays["slot_rows"],
        arrays["list_sizes"],
        arrays["source_ids"],
    )
    # mutation-era fields (v2): absent in old checkpoints -> all-live
    index.tombstones = arrays.get("tombstones")
    index.mut_cursor = int(meta.get("mut_cursor", 0))
    index.append_slack = int(meta.get("append_slack", 0))
    # integrity sidecar (v3): absent/corrupt -> no sidecar
    from raft_tpu.integrity.digest import unpack_lists

    unpack_lists(index, "ivf_rabitq", arrays.get("list_digests"),
                 meta.get("table_digests"))
    return index

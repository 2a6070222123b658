"""IVF-PQ: product-quantized inverted-file ANN index (the north star).

Reference parity: `raft::neighbors::ivf_pq` — params & index
(ivf_pq_types.hpp:43-110, list layout :153-215), build
(detail/ivf_pq_build.cuh:1074: subsample → random-orthogonal rotation via QR
:177 → balanced k-means :1189 → per-subspace :393 / per-cluster :473
codebook training → encode :578,:629), search (detail/ivf_pq_search.cuh:1550:
batch → rotate → select_clusters :133 → LUT scoring kernel :611 →
postprocess :373,:401); pylibraft `neighbors.ivf_pq` (ivf_pq.pyx:91-271).

TPU design (not a port):
  - Codebook training is ONE jit: `vmap` of the balanced-EM trainer over
    subspaces — pq_dim independent k-means problems become a single batched
    XLA program (vs the reference's sequential per-subspace kernel launches).
  - Codes are stored one-byte-per-code in a padded (n_lists, max_list,
    pq_dim) uint8 slot table (4..8 bit codes all fit; bit-packing on TPU
    costs more in unpack VPU ops than it saves in HBM for pq_bits=8, and
    pq_bits<8 simply uses a smaller codebook).
  - Search scoring: per (query, probe) the LUT (pq_dim, 2^bits) is built by
    one batched MXU matmul; scores are pq_dim embedding-style gathers from
    the LUT summed on the VPU — the XLA-native equivalent of the
    reference's shared-memory LUT kernel (compute_similarity_kernel).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.distance.distance_types import DistanceType, resolve_metric
from raft_tpu.distance.pairwise import _dot
from raft_tpu.matrix.select_k import _select_k_impl
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.neighbors.ivf_flat import _pack_lists
# codebook training + encode live in the shared quantizer layer now
# (neighbors/quantizer.py, PR 6); the underscore names stay importable
# from here because comms/ and bench/ call them by these paths — and the
# jitted functions are the very same objects, so the refactor is
# bit-identical (pinned by tests/goldens/ivf_pq_prerefactor.json)
from raft_tpu.neighbors.quantizer import (
    PER_CLUSTER,
    PER_SUBSPACE,
    PqQuantizer,
    _block_rows_for_encode,  # noqa: F401  (re-export: bench/profilers)
    _encode,
    _train_codebooks_per_cluster,  # noqa: F401  (re-export: comms)
    _train_codebooks_per_subspace,  # noqa: F401  (re-export: comms)
)
from raft_tpu import obs
from raft_tpu.core.config import auto_convert_output


@dataclasses.dataclass
class IndexParams:
    """Mirrors ivf_pq::index_params (ivf_pq_types.hpp:43-110)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    metric_arg: float = 2.0
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8
    pq_dim: int = 0  # 0 = auto (dim/4 rounded to multiple of 8, ref heuristic)
    codebook_kind: str = PER_SUBSPACE
    force_random_rotation: bool = False
    add_data_on_build: bool = True

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)
        if not (4 <= self.pq_bits <= 8):
            raise ValueError("pq_bits must be in [4, 8]")
        if self.pq_dim < 0:
            raise ValueError(f"pq_dim must be >= 0 (0 = auto), got {self.pq_dim}")
        if self.codebook_kind not in (PER_SUBSPACE, PER_CLUSTER):
            raise ValueError(f"bad codebook_kind {self.codebook_kind}")


@dataclasses.dataclass
class SearchParams:
    """Mirrors ivf_pq::search_params (ivf_pq_types.hpp:112-150).

    `internal_distance_dtype`/`lut_dtype` map to the score dtype used in
    scoring (fp32 default; bf16 reduces HBM traffic like the reference's
    half/fp8 LUTs).
    """

    n_probes: int = 20
    lut_dtype: str = "float32"  # "float32" | "bfloat16"
    # API parity with ivf_pq_types.hpp:112-150: the reference lets scores
    # accumulate in half precision. On TPU the MXU accumulates f32 natively
    # (bf16 inputs, f32 accumulation), so "float16"/"bfloat16" instead
    # control the stored score dtype in the list-major engine: bf16 trim
    # scores, halving that engine's dominant HBM stream (~1e-3 relative
    # ranking noise). Other engines keep f32 scores (the lut engine's LUT
    # dtype is `lut_dtype`). "float32" = exact f32 everywhere. "auto"
    # (default) is "bfloat16" when jax.default_backend() is "tpu" (bf16
    # trim won the 2026-08-01 chip ladder by 11% at equal recall) and
    # "float32" on every other backend.
    internal_distance_dtype: str = "auto"
    # Scoring engine (TPU design choice, no reference analogue):
    #   "lut"    — classic PQ LUT scoring (embedding-style gathers from the
    #              per-probe LUT; minimal HBM traffic: pq_dim bytes/vector).
    #   "recon8" — ScaNN-style int8 reconstruction scoring: codes are
    #              decoded once (build side) into per-dim-quantized int8
    #              vectors and scored with one MXU matmul per query block
    #              (rot_dim bytes/vector of traffic, zero gathers). Fastest
    #              on TPU, where the MXU beats per-element gathers.
    #   "recon8_list" — list-major recon8: probe pairs inverted to per-list
    #              query buckets so each list's codes are streamed from HBM
    #              exactly once per batch (vs ~nq*n_probes/n_lists times in
    #              the query-major engines). Best for large query batches.
    #   "auto"   — recon8_list when score_dtype="int8" or trim_engine is
    #              "exact"/"fused" (the only engine honoring them), or
    #              when the batch re-reads lists >=4x (nq * n_probes /
    #              n_lists >= 4); below that recon8 on TPU (lut's big
    #              flattened gather kernel-faults TPU devices —
    #              docs/perf.md device-fault section), lut elsewhere.
    # Default "auto" (VERDICT r4 #5): a default-constructed SearchParams
    # must land on the measured winner, never the faulting lut engine.
    score_mode: str = "auto"  # "lut" | "recon8" | "recon8_list" | "auto"
    # recon8_list matmul operand dtype (TPU design choice): "bf16" upcasts
    # the int8 codes to bfloat16; "int8" additionally quantizes each
    # query's residual row to int8 (ScaNN-style symmetric scoring) so the
    # chunk matmul runs int8 x int8 -> int32 at the MXU's double int8
    # rate with half the query-side operand bytes. Adds one more
    # quantization to the query side only; candidate ordering shifts are
    # absorbed by refine/probe margins.
    score_dtype: str = "bf16"  # "bf16" | "int8"
    # recon8_list per-chunk trim implementation:
    #   "approx", "exact" — one trim: XLA scoring matmul, then each
    #              (query, list) row's exact top-k through the select-k
    #              dispatch on a rank-2 view, which the TPU lowers to its
    #              partial-select TopK (no sort). Zero candidate loss,
    #              ties to the lower slot; an exact answer meets the
    #              "approx" recall floor, so both names run it.
    #   "fused"  — fused distance + exact partial select-k
    #              (matrix/select_k.list_scan_select_k, the select_k
    #              dispatch layer's fused list kernel): the score tile
    #              never reaches HBM and codes are read from the int8
    #              store by scalar-prefetch indexing, with the same
    #              per-(query, list) candidates as the XLA trim up to
    #              bf16 operand rounding. Caps per-list candidates at
    #              256 (k <= 256). With score_dtype="int8" the scoring
    #              matmul runs on the MXU's int8 datapath (dispatch
    #              strategy "fused_int8": int8 dot, int32 accumulate,
    #              per-row dequant on the VPU).
    #   "auto"   — "approx" unless the measured integer tuned key
    #              (matrix/select_k.INT8_SCAN_KEY, written by
    #              bench_select_k_strategies --apply on chip data)
    #              promotes the fused int8 trim for an int8-scored
    #              list-major search whose geometry fits the kernel.
    trim_engine: str = "auto"  # "auto" | "approx" | "exact" | "fused"
    # -- adaptive probing (neighbors/probe_budget, ROADMAP item 2) --
    # per-query probe budgets from the coarse gap profile (+ radius
    # bounds for L2 when the index carries them); recall_target >= 1.0
    # saturates, bit-identical to the fixed-n_probes reference. Note
    # the PQ caveat: bounds are exact-space (rotation is orthonormal),
    # while PQ scores are quantized estimates — early termination's
    # no-dropped-neighbor guarantee is exact-geometry, the quantized
    # ranking's recall is covered by the banked frontier instead.
    adaptive: bool = False
    recall_target: Optional[float] = None
    budget_tau: Optional[float] = None
    min_probes: int = 1
    early_term: bool = True


class Index:
    """IVF-PQ index.

    rotation  (rot_dim, dim) f32 — orthogonal input transform
    centers   (n_lists, rot_dim) f32 — coarse centroids (rotated space)
    pq_centers:
        per_subspace: (pq_dim, 2^bits, pq_len)
        per_cluster:  (n_lists, 2^bits, pq_len)
    codes     (n_lists, max_list, pq_dim) uint8 slot table
    slot_valid(n_lists, max_list) bool
    source_ids(n_rows,) int32; slot_rows (n_lists, max_list) int32 -> row id
    """

    def __init__(self, params, rotation, centers, pq_centers, codes, slot_rows,
                 list_sizes, source_ids):
        self.params = params
        self.rotation = rotation
        self.centers = centers
        self.pq_centers = pq_centers
        self.codes = codes
        self.slot_rows = slot_rows
        self.list_sizes = list_sizes
        self.source_ids = source_ids
        # int8 reconstruction store, built lazily for score_mode="recon8":
        # recon8 (n_lists, lpad, rot_dim) int8, recon_scale (rot_dim,) f32,
        # recon_norm (n_lists, lpad) f32, slot_rows_pad (n_lists, lpad)
        # int32 — lpad = max_list rounded up to 128 (see build_reconstruction)
        self.recon8 = None
        self.recon_scale = None
        self.recon_norm = None
        self.slot_rows_pad = None
        # fused-trim candidate-buffer width (ops/fused_scan.fused_kbuf),
        # grown monotonically when a later search's k outruns it — the
        # ivf_flat lazy-store invalidation contract, applied to the
        # fused/fused_int8 trims (a narrower compiled buffer would
        # silently truncate the per-list candidates)
        self.fused_kb = None
        # per-list radii in ROTATED space (max member residual norm) —
        # the early-termination bounds of adaptive probing, computed
        # incrementally by extend from the exact pre-quantization rows
        # and serialized with the index. None = bounds absent (old
        # checkpoints) -> budgets-only fallback.
        self.list_radii = None
        # live-mutation state (neighbors/mutation): optional dead-row
        # mask (n_lists, max_list; nonzero = dead, None = all-live),
        # the applied-log cursor at the last checkpoint commit, and the
        # mutator's reserved per-list append slack. The mask is masked
        # into the slot tables (slot_rows AND the lane-padded
        # slot_rows_pad — pad-aware) by `core.bitset.make_slot_filter`.
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
    def metric(self):
        return self.params.metric

    @property
    def n_lists(self):
        return int(self.centers.shape[0])

    @property
    def dim(self):
        return int(self.rotation.shape[1])

    @property
    def rot_dim(self):
        return int(self.rotation.shape[0])

    @property
    def pq_dim(self):
        return int(self.codes.shape[2])

    @property
    def pq_len(self):
        return self.rot_dim // self.pq_dim

    @property
    def pq_bits(self):
        return int(self.params.pq_bits)

    @property
    def size(self):
        return int(self.source_ids.shape[0])

    def __repr__(self):
        return (
            f"ivf_pq.Index(n_lists={self.n_lists}, dim={self.dim}, pq_dim={self.pq_dim}, "
            f"pq_bits={self.pq_bits}, size={self.size}, metric={self.metric.name})"
        )


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def _auto_pq_dim(dim: int) -> int:
    # ivf_pq_types.hpp pq_dim==0 heuristic: dim/4 rounded down to mult of 8
    d = max(1, dim // 4)
    if d > 8:
        d = d // 8 * 8
    return d


def _make_rotation(key, rot_dim: int, dim: int, force_random: bool) -> jax.Array:
    """Random orthogonal rotation via QR of a gaussian
    (ivf_pq_build.cuh:177 make_rotation_matrix)."""
    if not force_random and rot_dim == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    g = jax.random.normal(key, (max(rot_dim, dim), max(rot_dim, dim)), dtype=jnp.float32)
    q, r = jnp.linalg.qr(g)
    # sign-fix for a uniform (Haar) rotation
    q = q * jnp.sign(jnp.diagonal(r))[None, :]
    return q[:rot_dim, :dim]


def _coarse_fit(params, x, rotation, key, seed: int):
    """Single-chip coarse stage shared by the PQ and RaBitQ builds:
    trainset-fraction subsample (key-top-k sampler — no n-length
    permutation at 10M+ scale, rng.py:128), rotate, balanced k-means
    (hierarchical past 1024 lists). ONE implementation so trainset
    sizing/seeding/EM choices cannot diverge per quantizer (the
    single-chip mirror of mnmg_ivf_build._coarse_fit_rotated). Splits
    the caller's `key` exactly once, so downstream draws (PQ's codebook
    key) see the same stream as before the extraction. Returns
    (centers, rotated trainset, key)."""
    n = x.shape[0]
    frac = min(max(params.kmeans_trainset_fraction, 0.0), 1.0)
    n_train = min(n, max(params.n_lists * 4, int(n * frac)))
    key, sk = jax.random.split(key)
    if n_train < n:
        from raft_tpu.random.rng import sample_without_replacement

        train_sel = sample_without_replacement(sk, n, n_train)
        x_train_rot = _dot(x[train_sel], rotation)
    else:
        x_train_rot = _dot(x, rotation)

    metric_name = (
        "inner_product" if params.metric == DistanceType.InnerProduct
        else "sqeuclidean"
    )
    fit = (kmeans_balanced.fit_hierarchical if params.n_lists > 1024
           else kmeans_balanced.fit)
    centers = fit(x_train_rot, params.n_lists, n_iters=params.kmeans_n_iters,
                  metric=metric_name, seed=seed)
    return centers, x_train_rot, key


def build(params: IndexParams, dataset, resources=None, seed: int = 0) -> Index:
    """Train rotation, coarse centers, codebooks; encode + pack lists
    (detail/ivf_pq_build.cuh:1074).

    Spans, with obs enabled: `neighbors.ivf_pq.build` around it all,
    `ivf_pq.build.coarse` (trainset, balanced k-means) and
    `ivf_pq.build.codebook` (labels, residuals, codebook training);
    `neighbors.ivf_pq.extend` holds the encode. Each of the three blocks
    on its outputs as it closes, so its time is device time; disabled,
    the build adds no sync."""
    with obs.span("neighbors.ivf_pq.build") as sp:
        from raft_tpu.core.validation import check_matrix

        x = check_matrix(dataset, name="dataset").astype(jnp.float32)
        n, dim = x.shape
        if params.n_lists > n:
            raise ValueError(f"n_lists={params.n_lists} > dataset rows {n}")
        pq_dim = params.pq_dim or _auto_pq_dim(dim)
        pq_len = -(-dim // pq_dim)
        rot_dim = pq_dim * pq_len
        key = jax.random.PRNGKey(seed)
        key, rk = jax.random.split(key)
        rotation = _make_rotation(rk, rot_dim, dim, params.force_random_rotation or rot_dim != dim)

        with obs.span("ivf_pq.build.coarse") as st:
            centers, x_train_rot, key = st.settle(
                _coarse_fit(params, x, rotation, key, seed))
        n_train = int(x_train_rot.shape[0])
        metric_name = "inner_product" if params.metric == DistanceType.InnerProduct else "sqeuclidean"

        # codebooks from trainset residuals. Codebook EM only needs enough
        # samples to fit 2^pq_bits centroids per subspace (the reference trains
        # codebooks on the same subsampled trainset, ivf_pq_build.cuh:393);
        # capping the residual set keeps the vmapped-EM stage O(1) in dataset
        # size without measurable recall cost. PER_CLUSTER partitions the
        # sampled rows across n_lists before training, so its cap must scale
        # with n_lists to keep every cluster's sample set populated.
        nb = 1 << params.pq_bits
        max_cb_rows = max(65536, 64 * nb)
        if params.codebook_kind == PER_CLUSTER:
            max_cb_rows = max(max_cb_rows, 256 * params.n_lists)
        if n_train > max_cb_rows:
            key, rk2 = jax.random.split(key)
            cb_sel = jax.random.choice(rk2, n_train, (max_cb_rows,), replace=False)
            x_cb = x_train_rot[cb_sel]
        else:
            x_cb = x_train_rot
        with obs.span("ivf_pq.build.codebook") as st:
            train_labels = kmeans_balanced.predict(x_cb, centers,
                                                   metric=metric_name)
            residuals = x_cb - centers[train_labels]
            key, ck = jax.random.split(key)
            # codebook training through the shared quantizer layer (the jitted
            # trainers are the pre-refactor functions — bit-identical)
            quant = PqQuantizer(
                codebook_kind=params.codebook_kind, pq_bits=params.pq_bits,
                pq_dim=pq_dim, pq_len=pq_len, n_lists=params.n_lists,
            )
            pq_centers = st.settle(
                quant.train(ck, residuals, train_labels).pq_centers)

        index = Index(
            params,
            rotation,
            centers,
            pq_centers,
            jnp.zeros((params.n_lists, 1, pq_dim), jnp.uint8),
            jnp.full((params.n_lists, 1), -1, jnp.int32),
            jnp.zeros((params.n_lists,), jnp.int32),
            jnp.zeros((0,), jnp.int32),
        )
        # empty index: zero radii — extend max-folds each batch's exact
        # (rotated-space) residual norms in, one pass over assignments
        index.list_radii = jnp.zeros((params.n_lists,), jnp.float32)
        if params.add_data_on_build:
            index = extend(index, x, jnp.arange(n, dtype=jnp.int32))
        # build-time integrity sidecar (kept fresh incrementally after)
        from raft_tpu.integrity.digest import attach as _attach_digests

        _attach_digests(index, "ivf_pq")
        if resources is not None:
            resources.track(index.codes)
        sp.settle([v for v in vars(index).values()
                   if isinstance(v, jax.Array)])
    return index


def label_and_encode(
    vectors, rotation, centers, pq_centers, metric: DistanceType,
    per_cluster: bool, with_dists: bool = False,
):
    """Rotate, assign to coarse lists, and PQ-encode the residuals — the
    shared encode sequence used by `extend` and the distributed build
    (comms.mnmg.ivf_pq_build). Returns (labels (n,), codes (n, pq_dim));
    with `with_dists` additionally the exact rotated-space residual
    norms (adaptive probing's list-radius update rides the residuals
    this pass already computed — no second rotation matmul)."""
    metric_name = (
        "inner_product" if metric == DistanceType.InnerProduct else "sqeuclidean"
    )
    v_rot = _dot(jnp.asarray(vectors, jnp.float32), rotation)
    labels = kmeans_balanced.predict(v_rot, centers, metric=metric_name)
    residuals = v_rot - centers[labels]
    quant = PqQuantizer.from_centers(pq_centers, per_cluster)
    codes = quant.encode(residuals, labels)["codes"]
    if with_dists:
        dists = jnp.sqrt(jnp.maximum(
            jnp.sum(residuals ** 2, axis=1), 0.0))
        return labels, codes, dists
    return labels, codes


@obs.spanned("neighbors.ivf_pq.extend")
def extend(index: Index, new_vectors, new_indices=None) -> Index:
    """Label, encode and append new vectors (ivf_pq_build.cuh:1061 extend +
    process_and_fill_codes :724). Incremental: only the new batch is
    labeled/encoded and scattered into grown code tables — O(n_new + table
    copy), so streamed 100M-row builds stay linear."""
    from raft_tpu.core.validation import check_matrix
    from raft_tpu.neighbors.ivf_flat import _append_slots, _grow_and_scatter

    nv = check_matrix(new_vectors, name="new_vectors").astype(jnp.float32)
    old_n = index.size
    if new_indices is None:
        new_indices = jnp.arange(old_n, old_n + nv.shape[0], dtype=jnp.int32)
    else:
        new_indices = jnp.asarray(new_indices, jnp.int32)

    per_cluster = index.params.codebook_kind == PER_CLUSTER
    labels, new_codes, resid_dists = label_and_encode(
        nv, index.rotation, index.centers, index.pq_centers, index.metric,
        per_cluster, with_dists=True,
    )

    labels_np = np.asarray(labels, np.int64)
    old_sizes = np.asarray(index.list_sizes, np.int64)
    slot_abs, new_sizes, new_max = _append_slots(labels_np, old_sizes, index.n_lists)
    # a store padded wider than the sizes imply (fused-engine lanes,
    # mutation append slack) must never shrink — slots stay where they are
    new_max = max(new_max, int(index.codes.shape[1]))
    positions = jnp.arange(old_n, old_n + nv.shape[0], dtype=jnp.int32)
    codes_tbl, slot_rows = _grow_and_scatter(
        index.codes,
        index.slot_rows,
        new_codes,
        jnp.asarray(labels_np),
        jnp.asarray(slot_abs),
        positions,
        new_max,
    )
    all_ids = jnp.concatenate([index.source_ids, new_indices]) if old_n else new_indices

    out = Index(
        index.params,
        index.rotation,
        index.centers,
        index.pq_centers,
        codes_tbl,
        slot_rows,
        jnp.asarray(new_sizes),
        all_ids,
    )
    from raft_tpu.neighbors.probe_budget import updated_radii

    # exact rotated-space residual norms of the new batch (the bounds
    # must hold for the TRUE geometry, not the quantized codes) — the
    # encode pass above already computed the residuals
    out.list_radii = updated_radii(
        index.list_radii, labels_np, np.asarray(resid_dists), index.n_lists)
    # mutation state survives extend (new tail slots are live appends)
    from raft_tpu.core.bitset import carry_tombstones

    out.tombstones = carry_tombstones(index.tombstones,
                                      int(codes_tbl.shape[1]))
    out.mut_cursor = index.mut_cursor
    out.append_slack = index.append_slack
    # integrity sidecar: only the lists this batch touched re-digest
    from raft_tpu.integrity.digest import refresh as _refresh_digests

    _refresh_digests(out, index, "ivf_pq")
    return out


# ---------------------------------------------------------------------------
# int8 reconstruction store (TPU scoring engine for score_mode="recon8")
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("per_cluster", "list_block"))
def _decode_quantize(codes, pq_centers, per_cluster: bool, list_block: int = 64):
    """Decode PQ codes to per-dim symmetric int8 + the decoded norms.

    Returns (recon8 (L, S, rot) int8, scale (rot,) f32, rnorm (L, S) f32).
    Decoding is the inverse of `_encode` (per-subspace codebook lookup);
    scale is a per-dimension max-abs over the codebooks themselves, so it
    needs no pass over the decoded data."""
    n_lists, max_list, pq_dim = codes.shape
    pq_len = pq_centers.shape[-1]
    rot_dim = pq_dim * pq_len
    # per-dim scale from codebook entries (bounds every reconstruction)
    if per_cluster:
        # entries shared by all subspaces of a list -> same per-pq_len scale
        amax = jnp.max(jnp.abs(pq_centers), axis=(0, 1))  # (pq_len,)
        scale = jnp.tile(amax, pq_dim) / 127.0
    else:
        amax = jnp.max(jnp.abs(pq_centers), axis=1)  # (pq_dim, pq_len)
        scale = amax.reshape(rot_dim) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    inv = (1.0 / scale).reshape(pq_dim, pq_len)

    nblocks = -(-n_lists // list_block)
    pad = nblocks * list_block - n_lists
    cp = jnp.pad(codes, ((0, pad), (0, 0), (0, 0))) if pad else codes
    cblocks = cp.reshape(nblocks, list_block, max_list, pq_dim)
    lids = jnp.arange(nblocks * list_block).reshape(nblocks, list_block)

    def dec(inp):
        cb, lid = inp  # (lb, S, P) uint8, (lb,)
        idx = cb.astype(jnp.int32)
        # codebook lookups as flat axis-0 gathers (the broadcasted 5-D
        # take_along_axis form kernel-faults on TPU at large index counts,
        # same class as the search-path gather fixed alongside)
        nb = pq_centers.shape[-2]
        if per_cluster:
            books = pq_centers[jnp.minimum(lid, pq_centers.shape[0] - 1)]  # (lb,B,pl)
            flat = books.reshape(-1, pq_len)
            lb = idx.shape[0]
            rows = jnp.arange(lb, dtype=jnp.int32)[:, None, None] * nb + idx
            rec = flat[rows]  # (lb, S, P, pl)
        else:
            flat = pq_centers.reshape(-1, pq_len)  # (P*B, pl)
            rows = jnp.arange(pq_dim, dtype=jnp.int32)[None, None, :] * nb + idx
            rec = flat[rows]  # (lb, S, P, pl)
        q = jnp.clip(jnp.round(rec * inv[None, None, :, :]), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale.reshape(pq_dim, pq_len)[None, None]
        rnorm = jnp.sum(deq.reshape(*q.shape[:2], -1) ** 2, axis=-1)
        return q.reshape(*q.shape[:2], rot_dim), rnorm

    recon8, rnorm = lax.map(dec, (cblocks, lids))
    recon8 = recon8.reshape(-1, max_list, rot_dim)[:n_lists]
    rnorm = rnorm.reshape(-1, max_list)[:n_lists]
    return recon8, scale, rnorm


def build_reconstruction(index: Index, pad_to_lanes: bool = False) -> Index:
    """Populate the int8 reconstruction store used by score_mode="recon8"
    (idempotent; called lazily from `search`).

    With `pad_to_lanes` the store's slot axis is padded to the fused
    list scan's lane contract (ops/fused_scan.lane_padded) — with
    `slot_rows_pad` marking pad slots invalid and `recon_norm` +inf
    there, so every recon8 engine masks them exactly like in-list
    padding. Only the fused trim asks for the padding (the default
    engines keep the tight store); once padded, the store stays padded
    (monotone, still idempotent)."""
    if index.recon8 is None:
        r8, scale, rnorm = _decode_quantize(
            index.codes, index.pq_centers, index.params.codebook_kind == PER_CLUSTER
        )
        index.recon8, index.recon_scale, index.recon_norm = r8, scale, rnorm
        index.slot_rows_pad = index.slot_rows
    if pad_to_lanes:
        from raft_tpu.ops.fused_scan import lane_padded

        max_list = index.recon8.shape[1]
        extra = lane_padded(max_list) - max_list
        if extra:
            index.recon8 = jnp.pad(index.recon8, ((0, 0), (0, extra), (0, 0)))
            index.recon_norm = jnp.pad(
                index.recon_norm, ((0, 0), (0, extra)), constant_values=jnp.inf
            )
            index.slot_rows_pad = jnp.pad(
                index.slot_rows_pad, ((0, 0), (0, extra)), constant_values=-1
            )
    return index


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _resolve_score_mode(params: SearchParams, nq: int, n_probes: int, n_lists: int) -> str:
    """Resolve score_mode="auto" to a concrete engine.

    Order: an explicit int8/exact/fused request pins recon8_list (the
    only engine honoring it); else the duplication heuristic. On TPU
    the resolution NEVER lands on lut: its flattened gather
    kernel-faulted the device and a fault poisons the process backend —
    small batches get the gather-free recon8 engine instead."""
    mode = params.score_mode
    if mode != "auto":
        return mode
    if params.score_dtype == "int8" or params.trim_engine in ("exact", "fused"):
        return "recon8_list"
    dup = nq * n_probes / max(1, n_lists)
    if dup >= 4.0:
        return "recon8_list"
    return "recon8" if jax.default_backend() == "tpu" else "lut"


_LUT_TPU_OVERRIDE = "RAFT_TPU_ALLOW_LUT_TPU"


def _check_lut_allowed() -> None:
    """Permanent fence (VERDICT r4 #5): explicit score_mode='lut' on TPU
    raises with the fault context instead of risking a device fault; the
    env override exists for fault-repro/profiling sessions only."""
    import os

    if jax.default_backend() == "tpu" and os.environ.get(_LUT_TPU_OVERRIDE) != "1":
        raise ValueError(
            "score_mode='lut' is fenced on TPU: its flattened LUT gather "
            "kernel-faulted the device at bench index counts (2026-08-01, "
            "docs/perf.md device-fault section) and a fault poisons the "
            "process's backend. Use score_mode='auto' (the measured "
            "engine), 'recon8', or 'recon8_list'; set "
            f"{_LUT_TPU_OVERRIDE}=1 only to reproduce/profile the fault."
        )


def _quantize_query_rows(u):
    """Symmetric per-row int8 quantization for ScaNN-style scoring:
    returns (q8, row_scale) with u ~= q8 * row_scale. Shared by the XLA
    and fused list-major trims — their parity depends on identical
    quantization."""
    ua = jnp.max(jnp.abs(u), axis=-1, keepdims=True) + 1e-12
    q8 = jnp.clip(jnp.round(u / ua * 127.0), -127, 127).astype(jnp.int8)
    return q8, ua / 127.0


def _query_block_size(n_probes: int, max_list: int, pq_dim: int) -> int:
    # keep the gathered codes block (qb, n_probes*max_list, pq_dim) ~<= 2^24 elems
    qb = max(1, (1 << 24) // max(1, n_probes * max_list * pq_dim))
    return int(min(qb, 16))


def _coarse_select(queries, rotation, centers, n_probes: int, metric: DistanceType):
    """Coarse stage shared by all engines (traced inside each engine's jit):
    rotate queries and pick the n_probes closest coarse centers
    (select_clusters, ivf_pq_search.cuh:133). Returns (q_rot, probes)."""
    select_min = metric != DistanceType.InnerProduct
    # at the library's matmul precision: under the TPU's default
    # single-pass bf16 a 1-row batch and a padded batch rotated to
    # different bits (a served row must not depend on its batch-mates)
    q_rot = _dot(queries.astype(jnp.float32), rotation)
    cd = _dot(q_rot, centers)
    if metric == DistanceType.InnerProduct:
        coarse = cd
    else:
        # query norm is constant per row; the argmin is unaffected
        coarse = jnp.sum(centers**2, axis=1)[None, :] - 2.0 * cd
    _, probes = _select_k_impl(coarse, n_probes, select_min)
    return q_rot, probes


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "per_cluster", "lut_bf16"),
)
def _search_impl(
    queries,
    rotation,
    centers,
    pq_centers,
    codes,
    slot_rows,
    k: int,
    n_probes: int,
    metric: DistanceType,
    per_cluster: bool,
    lut_bf16: bool = False,
    pvalid: jax.Array = None,
):
    nq, _ = queries.shape
    n_lists, max_list, pq_dim = codes.shape
    nb = pq_centers.shape[-2] if per_cluster else pq_centers.shape[1]
    pq_len = pq_centers.shape[-1]
    rot_dim = pq_dim * pq_len
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf

    with jax.named_scope("ivf_pq.coarse"):
        q_rot, probes = _coarse_select(queries, rotation, centers, n_probes,
                                       metric)

    qb = _query_block_size(n_probes, max_list, pq_dim)
    nblocks = -(-nq // qb)
    pad = nblocks * qb - nq
    qp = jnp.pad(q_rot, ((0, pad), (0, 0))) if pad else q_rot
    pp = jnp.pad(probes, ((0, pad), (0, 0))) if pad else probes
    qblocks = qp.reshape(nblocks, qb, rot_dim)
    pblocks = pp.reshape(nblocks, qb, n_probes)
    if pvalid is not None:
        pvp = jnp.pad(pvalid, ((0, pad), (0, 0))) if pad else pvalid
        pvblocks = pvp.reshape(nblocks, qb, n_probes)

    sub_dim = (pq_dim, pq_len)

    def block(inp):
        if pvalid is not None:
            qs, pr, pvb = inp  # + (qb, n_probes) adaptive keep mask
        else:
            qs, pr = inp  # (qb, rot_dim), (qb, n_probes)
        # residual of query vs each probed center: (qb, n_probes, rot_dim)
        pc = centers[pr]
        if metric == DistanceType.InnerProduct:
            qres = jnp.broadcast_to(qs[:, None, :], (qb, n_probes, rot_dim))
        else:
            qres = qs[:, None, :] - pc
        qsub = qres.reshape(qb, n_probes, *sub_dim)  # (qb,np,pq_dim,pq_len)

        # ---- LUT build: one batched matmul (compute_similarity LUT :726) ----
        if per_cluster:
            books = pq_centers[pr]  # (qb, np, nb, pq_len)
            dots = jnp.einsum("qnpl,qnbl->qnpb", qsub, books)
            bn = jnp.sum(books**2, axis=3)[:, :, None, :]
        else:
            dots = jnp.einsum("qnpl,pbl->qnpb", qsub, pq_centers)
            bn = jnp.sum(pq_centers**2, axis=2)[None, None, :, :]
        if metric == DistanceType.InnerProduct:
            lut = dots  # score contribution q·c_b (plus q·center handled below)
        else:
            lut = bn - 2.0 * dots  # ||q_sub - c_b||² minus const ||q_sub||²
        if lut_bf16:
            lut = lut.astype(jnp.bfloat16)

        # ---- gather codes & score (compute_similarity_kernel :611) ----
        cand_codes = codes[pr]  # (qb, np, max_list, pq_dim) uint8
        idx = cand_codes.astype(jnp.int32)
        # embedding-style gather: scores[q,n,s] = sum_p lut[q,n,p, idx[q,n,s,p]],
        # flattened to one 2-D take_along_axis (per-subspace offsets fold the
        # pq_dim axis into the LUT row) — the broadcasted 5-D gather form
        # kernel-faulted on TPU at 1M-index shapes
        lut2 = lut.reshape(qb * n_probes, pq_dim * nb)
        idx2 = (idx + jnp.arange(pq_dim, dtype=jnp.int32) * nb).reshape(
            qb * n_probes, max_list * pq_dim
        )
        gathered = jnp.take_along_axis(lut2, idx2, axis=1).reshape(
            qb, n_probes, max_list, pq_dim
        )
        scores = jnp.sum(gathered.astype(jnp.float32), axis=3)  # (qb,np,max_list)
        if metric == DistanceType.InnerProduct:
            # add query·center term per probe
            qdotc = jnp.einsum("qnd,qnd->qn", jnp.broadcast_to(qs[:, None, :], pc.shape), pc)
            scores = scores + qdotc[:, :, None]
        else:
            # add residual-norm const: ||q - center||² per probe
            qcn = jnp.sum(qres**2, axis=2)
            scores = scores + qcn[:, :, None]

        rows = slot_rows[pr]  # (qb, np, max_list)
        if pvalid is not None:
            rows = jnp.where(pvb[:, :, None], rows, -1)
        rows = rows.reshape(qb, -1)  # (qb, np*max_list)
        scores = scores.reshape(qb, -1)
        scores = jnp.where(rows >= 0, scores, worst)
        with jax.named_scope("ivf_pq.merge"):
            v, pos = _select_k_impl(scores, k, select_min)
            return v, jnp.take_along_axis(rows, pos, axis=1)

    # stage scopes: an op takes the innermost one on its path, so the
    # select inside `block` is merge and the rest of the loop is scan
    with jax.named_scope("ivf_pq.scan"):
        vals, rows = lax.map(
            block,
            (qblocks, pblocks, pvblocks) if pvalid is not None
            else (qblocks, pblocks))
    with jax.named_scope("ivf_pq.merge"):
        vals = vals.reshape(-1, k)[:nq]
        rows = rows.reshape(-1, k)[:nq]
        if metric == DistanceType.L2SqrtExpanded:
            vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    return vals, rows


@functools.partial(
    jax.jit, static_argnames=("k", "n_probes", "metric")
)
def _search_impl_recon8(
    queries,
    rotation,
    centers,
    recon8,
    recon_scale,
    recon_norm,
    slot_rows,
    k: int,
    n_probes: int,
    metric: DistanceType,
    pvalid: jax.Array = None,
):
    """int8 reconstruction scoring: one bf16 MXU matmul per query block
    against dequantized decoded vectors — the TPU-native replacement for
    the reference's shared-memory LUT kernel (compute_similarity_kernel,
    ivf_pq_search.cuh:611). Residual math matches the LUT path:
    score = ||q - center||^2 shifted by the reconstruction terms."""
    nq, _ = queries.shape
    n_lists, max_list, rot_dim = recon8.shape
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf

    with jax.named_scope("ivf_pq.coarse"):
        q_rot, probes = _coarse_select(queries, rotation, centers, n_probes,
                                       metric)

    qb = _query_block_size(n_probes, max_list, rot_dim)
    nblocks = -(-nq // qb)
    pad = nblocks * qb - nq
    qp = jnp.pad(q_rot, ((0, pad), (0, 0))) if pad else q_rot
    pp = jnp.pad(probes, ((0, pad), (0, 0))) if pad else probes
    qblocks = qp.reshape(nblocks, qb, rot_dim)
    pblocks = pp.reshape(nblocks, qb, n_probes)
    if pvalid is not None:
        pvp = jnp.pad(pvalid, ((0, pad), (0, 0))) if pad else pvalid
        pvblocks = pvp.reshape(nblocks, qb, n_probes)
    scale_bf = recon_scale.astype(jnp.bfloat16)

    def block(inp):
        if pvalid is not None:
            qs, pr, pvb = inp  # + (qb, n_probes) adaptive keep mask
        else:
            qs, pr = inp  # (qb, rot_dim), (qb, n_probes)
        pc = centers[pr]  # (qb, np, rot)
        if metric == DistanceType.InnerProduct:
            qres = jnp.broadcast_to(qs[:, None, :], pc.shape)
        else:
            qres = qs[:, None, :] - pc
        r8 = recon8[pr]  # (qb, np, max_list, rot) int8
        deq = r8.astype(jnp.bfloat16) * scale_bf[None, None, None, :]
        dots = jnp.einsum(
            "qnd,qnsd->qns",
            qres.astype(jnp.bfloat16),
            deq,
            preferred_element_type=jnp.float32,
        )
        if metric == DistanceType.InnerProduct:
            qdotc = jnp.sum(qs[:, None, :] * pc, axis=2)
            scores = dots + qdotc[:, :, None]
        else:
            qcn = jnp.sum(qres**2, axis=2)
            scores = qcn[:, :, None] - 2.0 * dots + recon_norm[pr]
        rows = slot_rows[pr]  # (qb, np, max_list)
        if pvalid is not None:
            rows = jnp.where(pvb[:, :, None], rows, -1)
        rows = rows.reshape(qb, -1)
        scores = scores.reshape(qb, -1)
        scores = jnp.where(rows >= 0, scores, worst)
        with jax.named_scope("ivf_pq.merge"):
            v, pos = _select_k_impl(scores, k, select_min)
            return v, jnp.take_along_axis(rows, pos, axis=1)

    # stage scopes: an op takes the innermost one on its path, so the
    # select inside `block` is merge and the rest of the loop is scan
    with jax.named_scope("ivf_pq.scan"):
        vals, rows = lax.map(
            block,
            (qblocks, pblocks, pvblocks) if pvalid is not None
            else (qblocks, pblocks))
    with jax.named_scope("ivf_pq.merge"):
        vals = vals.reshape(-1, k)[:nq]
        rows = rows.reshape(-1, k)[:nq]
        if metric == DistanceType.L2SqrtExpanded:
            vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    return vals, rows


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "metric", "chunk", "int8_queries", "trim_bf16",
    ),
)
def _search_impl_recon8_listmajor(
    queries,
    rotation,
    centers,
    recon8,
    recon_scale,
    recon_norm,
    slot_rows,
    k: int,
    n_probes: int,
    metric: DistanceType,
    chunk: int = None,
    int8_queries: bool = False,
    trim_bf16: bool = False,
    pvalid: jax.Array = None,
):
    """List-major scoring: each list's codes are streamed from HBM once per
    ~chunk queries probing it and scored with one bf16 MXU matmul.

    The query-major engines gather `codes[probes]` per query, so each list
    is re-read dup = nq*n_probes/n_lists times; at the batch cell's slice
    shape (nq=4096, n_probes=8, n_lists=1024) that is a 32x duplication of
    the dominant HBM stream. Here the (query, list) probe pairs are sorted
    by list and split into chunks of `chunk` pairs ("virtual lists" — hot
    lists get several chunks, so query skew costs padding only inside one
    chunk, never globally). `chunk` defaults to
    `probe_invert.list_chunk`'s rule, the largest power of two at most
    dup in [32, 128], fixed at trace time from the static shapes: each
    list then pads at most one chunk of that width, and where dup >= 32
    the trim's `TopK` works on at most about 2P rows for P pairs. Each
    chunk does one (chunk, rot) x (rot, max_list) matmul plus a per-row
    top-k, and the per-pair candidates are regrouped to query-major by an
    inverse-permutation *gather* for the final select_k.

    TPU notes: the whole pipeline is sorts + searchsorted + gathers — no
    XLA scatters (TPU lowers scatters to a serialized per-index loop, which
    measured ~100x slower here). The chunk-table bound P//chunk + n_lists
    is static, so batches of the same shape never recompile. The reference
    has no analogue of this engine: its SM-resident LUT makes query-major
    cheap on GPU (compute_similarity_kernel, ivf_pq_search.cuh:611), while
    on TPU the MXU/HBM economics invert the loop instead.

    The coarse probe selection runs inside this same jit (one program,
    one dispatch)."""
    from raft_tpu.neighbors.probe_invert import (
        invert_probes,
        list_chunk,
        score_and_select,
    )

    nq = queries.shape[0]
    n_lists, max_list, rot_dim = recon8.shape
    if chunk is None:
        chunk = list_chunk(nq, n_probes, n_lists)
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf
    with jax.named_scope("ivf_pq.coarse"):
        q_rot, probes = _coarse_select(queries, rotation, centers, n_probes,
                                       metric)
    with jax.named_scope("ivf_pq.invert"):
        tables = invert_probes(probes, n_lists, chunk, pvalid)

    with jax.named_scope("ivf_pq.scan"):
        q_pad = jnp.concatenate([q_rot, jnp.zeros((1, rot_dim), q_rot.dtype)])
        scale_bf = recon_scale.astype(jnp.bfloat16)
        # gathered per chunk as 1-byte flags, not the 4-byte slot table
        live = slot_rows >= 0

    def block(inp):
        lofb, qids = inp  # (CB,), (CB, chunk)
        r8 = recon8[lofb]  # (CB, max_list, rot) — the only read of these codes
        rn = recon_norm[lofb]
        lv = live[lofb]
        cent = centers[lofb]
        qs = q_pad[qids]  # (CB, chunk, rot)
        if metric == DistanceType.InnerProduct:
            qres = qs
        else:
            qres = qs - cent[:, None, :]
        if int8_queries:
            # symmetric int8 scoring: fold the per-dim code scale into the
            # query residual, quantize each residual row to int8, and run
            # the chunk matmul as int8 x int8 -> int32 on the MXU
            u = qres * recon_scale[None, None, :]
            u8, row_scale = _quantize_query_rows(u)
            idots = jnp.einsum(
                "lqd,lsd->lqs", u8, r8, preferred_element_type=jnp.int32
            )
            dots = idots.astype(jnp.float32) * row_scale
        else:
            deq = r8.astype(jnp.bfloat16) * scale_bf[None, None, :]
            dots = jnp.einsum(
                "lqd,lsd->lqs",
                qres.astype(jnp.bfloat16),
                deq,
                preferred_element_type=jnp.float32,
            )
        if metric == DistanceType.InnerProduct:
            qdotc = jnp.einsum("lqd,ld->lq", qs, cent)
            scores = dots + qdotc[:, :, None]
        else:
            qcn = jnp.sum(qres**2, axis=2)
            scores = qcn[:, :, None] - 2.0 * dots + rn[:, None, :]
        scores = jnp.where(lv[:, None, :], scores, worst)
        if trim_bf16:
            # bf16 trim (internal_distance_dtype parity with the
            # reference's half-precision internal distances,
            # ivf_pq_types.hpp:112-150): the score tensor is the dominant
            # HBM stream of this engine (~chunk*max_list*4B per chunk vs
            # max_list*rot_dim*1B of codes); storing it bf16 halves that
            # round-trip into the trim. The final merge then
            # ranks on bf16 scores (~1e-3 relative noise on near-ties).
            scores = scores.astype(jnp.bfloat16)
        return scores

    v, rows_out = score_and_select(
        tables, block, slot_rows, _select_k_impl, nq, n_probes, k, select_min,
        chunk, max_list, stage="ivf_pq",
    )
    with jax.named_scope("ivf_pq.merge"):
        v = v.astype(jnp.float32)
        if metric == DistanceType.L2SqrtExpanded:
            v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, rows_out


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "metric", "chunk", "interpret", "int8_queries",
        "kb", "fault_key",
    ),
)
def _search_impl_recon8_listmajor_fused(
    queries,
    rotation,
    centers,
    recon8,
    recon_scale,
    recon_norm,
    slot_rows_pad,
    k: int,
    n_probes: int,
    metric: DistanceType,
    chunk: int = 128,
    interpret: bool = False,
    int8_queries: bool = False,
    kb: int = None,
    fault_key=None,
    pvalid: jax.Array = None,
):
    """List-major search with the fused distance + EXACT select-k trim
    (matrix/select_k.list_scan_select_k — the select_k dispatch layer's
    fused list kernel): one kernel per chunk scores the whole list
    straight out of the int8 store, so the (chunk, L) score tile never
    round-trips HBM, and the in-kernel partial top-k is exact with ties
    to the smaller slot — the per-(query, list) candidates are what
    trim_engine='exact' computes, without materializing the scores.
    With `int8_queries` the scoring matmul runs int8 x int8 -> int32 on
    the MXU's doubled int8 rate (dispatch strategy "fused_int8"): rows
    quantize through the SAME `_quantize_query_rows` as the XLA int8
    trim, so the two trims return the same candidates, their scores
    apart only by the order of one f32 add. `kb` is the index's
    recorded monotonic candidate-buffer width (`fused_kb`); `fault_key`
    = faults.trace_key() so chaos plans retrace."""
    from raft_tpu.matrix.select_k import list_scan_select_k
    from raft_tpu.neighbors.probe_invert import (
        chunk_validity,
        invert_probes,
        regroup_merge,
    )

    nq = queries.shape[0]
    n_lists, lpad, rot_dim = recon8.shape
    select_min = metric != DistanceType.InnerProduct
    ip = metric == DistanceType.InnerProduct

    with jax.named_scope("ivf_pq.coarse"):
        q_rot, probes = _coarse_select(queries, rotation, centers, n_probes,
                                       metric)
    with jax.named_scope("ivf_pq.invert"):
        tables = invert_probes(probes, n_lists, chunk, pvalid)
    lof, qid_tbl = tables.lof, tables.qid_tbl
    with jax.named_scope("ivf_pq.scan"):
        cvalid = chunk_validity(qid_tbl, nq)  # empty chunks skip in-kernel

        q_pad = jnp.concatenate([q_rot, jnp.zeros((1, rot_dim), q_rot.dtype)])
        qs = q_pad[qid_tbl]  # (ncb, chunk, rot)
        cent = centers[lof]
        qres = qs if ip else qs - cent[:, None, :]
        qres_s = qres * recon_scale[None, None, :]

        valid = slot_rows_pad >= 0
        if ip:
            base = jnp.where(valid, 0.0, jnp.inf)[:, None, :]
        else:
            base = jnp.where(valid, recon_norm, jnp.inf)[:, None, :]

        if int8_queries:
            # symmetric int8 scoring fused end to end: quantize the
            # scale-folded residual rows and hand the int8 operands to
            # the dispatch layer's int8 kernel
            q8, row_scale = _quantize_query_rows(qres_s)
            vals, slot_idx = list_scan_select_k(
                lof, q8, recon8, base, k, strategy="fused_int8",
                q_scale=row_scale, kbuf=kb, inner_product=ip,
                interpret=interpret, fault_key=fault_key, chunk_valid=cvalid,
            )
        else:
            vals, slot_idx = list_scan_select_k(
                lof, qres_s, recon8, base, k, strategy="fused", kbuf=kb,
                inner_product=ip, interpret=interpret, fault_key=fault_key,
                chunk_valid=cvalid,
            )  # (ncb, chunk, kbuf) exact best-first, minimizing
        vals = vals[:, :, :k]
        slot_idx = slot_idx[:, :, :k]

        invalid = ~jnp.isfinite(vals)
        slots = jnp.where(invalid, -1, slot_idx)

        if ip:
            qdotc = jnp.einsum("cqd,cd->cq", qs, cent)
            vals = jnp.where(invalid, -jnp.inf, -vals + qdotc[:, :, None])
        else:
            qcn = jnp.sum(qres**2, axis=2)  # (ncb, chunk)
            vals = vals + qcn[:, :, None]

    with jax.named_scope("ivf_pq.merge"):
        v, rows_out = regroup_merge(
            tables, vals, slots, slot_rows_pad, _select_k_impl, nq, n_probes,
            int(k), select_min
        )
        v = v.astype(jnp.float32)
        if metric == DistanceType.L2SqrtExpanded:
            v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, rows_out


@obs.spanned("neighbors.ivf_pq.search")
@auto_convert_output
def search(
    params: SearchParams, index: Index, queries, k: int, resources=None,
    prefilter=None,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search; returns (distances, neighbor source ids) (nq, k).

    `prefilter`: optional `core.bitset.Bitset` (or 1-D boolean mask) over
    the index's id space (`index.id_bound` ids — == size unless extend() used custom new_indices) — samples whose bit is clear
    are excluded before any trim/selection in EVERY engine, including the
    fused Pallas scan (sample-filtering parity with later RAFT's
    `search_with_filtering`). When fewer than k samples pass, the tail
    holds the worst distance with id -1.

    Note: trim_engine='fused' pads the index's reconstruction store to
    lane multiples IN PLACE on first use; later searches on the same
    index with other engines recompile for the padded shape and scan the
    (masked) pad slots."""
    from raft_tpu.core.validation import check_matrix

    q = check_matrix(queries, name="queries")
    if q.shape[1] != index.dim:
        raise ValueError(f"query dim {q.shape[1]} != index dim {index.dim}")
    if index.size == 0:
        raise ValueError("index is empty")
    # every engine masks candidate scores to the worst value wherever its
    # slot table reads -1 (before trim/selection), so a filtered view is
    # the entire filtering mechanism; applied per branch because the
    # recon8 engines use the padded table from build_reconstruction
    from raft_tpu.core.bitset import make_slot_filter

    maybe_filter = make_slot_filter(prefilter, index.id_bound,
                                    index.source_ids,
                                    tombstones=index.tombstones)
    n_probes = int(min(max(1, params.n_probes), index.n_lists))
    mode = params.score_mode
    if params.score_dtype not in ("bf16", "int8"):
        raise ValueError(f"unknown score_dtype {params.score_dtype!r}")
    idd = params.internal_distance_dtype
    if idd == "auto":
        # bf16 trim scores on the chip, where they won the 2026-08-01
        # ladder; exact f32 trim numerics everywhere else
        idd = "bfloat16" if jax.default_backend() == "tpu" else "float32"
    if idd not in ("float32", "float16", "bfloat16"):
        raise ValueError(
            f"unknown internal_distance_dtype {params.internal_distance_dtype!r}"
        )
    if mode == "auto":
        mode = _resolve_score_mode(params, q.shape[0], n_probes, index.n_lists)
    elif params.score_dtype == "int8" and mode != "recon8_list":
        raise ValueError(
            f"score_dtype='int8' requires score_mode 'recon8_list' or 'auto', got {mode!r}"
        )
    # trim resolution: explicit values pin; "auto" = "approx" unless the
    # dispatch layer's measured integer key promotes the fused int8 trim
    # for this geometry (chip-measured, envelope-gated — the single
    # chooser of ISSUE 11)
    trim = params.trim_engine
    if trim not in ("auto", "approx", "exact", "fused"):
        raise ValueError(f"unknown trim_engine {params.trim_engine!r}")
    if trim == "auto":
        trim = "approx"
        if mode == "recon8_list" and params.score_dtype == "int8":
            from raft_tpu.matrix.select_k import resolve_int8_trim_strategy
            from raft_tpu.ops.fused_scan import (
                FUSED_MAX_K, fused_kbuf, lane_padded,
            )

            if 0 < int(k) <= FUSED_MAX_K:
                kb_probe = max(fused_kbuf(int(k)), index.fused_kb or 0)
                promoted = resolve_int8_trim_strategy(
                    lane_padded(int(index.codes.shape[1])), index.rot_dim,
                    int(k), kbuf=kb_probe,
                )
                if promoted == "fused_int8":
                    trim = "fused"
    # adaptive probing: one (nq, n_probes) keep mask from the rotated
    # coarse geometry (budgets + optional radius bounds), shared by
    # every score mode; None = the fixed-n_probes reference, verbatim
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
            n_probes=n_probes, min_probes=ap.min_probes, k=int(k),
            metric=index.metric, tau=ap.tau, rotation=index.rotation,
            radii=radii, sizes=index.list_sizes)
        scanned_mean = probe_budget.account(
            "ivf_pq", scanned, int(q.shape[0]), n_probes)
    if obs.enabled():
        # list-major modes stream every padded list per query batch;
        # query-major modes touch the probed lists only (the ACTUAL
        # adaptive mean when budgets are on); the fused trim
        # never materialize the score tile
        obs.span_cost(**obs.perf.cost_for(
            "neighbors.ivf_pq.search", nq=int(q.shape[0]),
            n_probes=n_probes, n_lists=int(index.n_lists),
            n_rows=int(index.codes.shape[0] * index.codes.shape[1])
            - index.n_tombstones,
            dim=int(index.dim), pq_dim=int(index.pq_dim), k=int(k),
            dtype=params.score_dtype,
            scanned_lists=(int(index.n_lists)
                           if (mode.endswith("_list") and trim != "fused")
                           else (scanned_mean if scanned_mean is not None
                                 else n_probes)),
            fused=mode == "recon8_list" and trim == "fused"))
    for eng in ("exact", "fused"):
        if trim == eng and mode != "recon8_list":
            raise ValueError(
                f"trim_engine='{eng}' requires score_mode 'recon8_list'"
            )
    if mode == "recon8_list" and trim == "fused":
        from raft_tpu.matrix.select_k import check_fused_list_request
        from raft_tpu.neighbors.probe_invert import macro_batched
        from raft_tpu.ops.fused_scan import lane_padded

        # caps/envelope checked BEFORE padding the index's store (a
        # rejected request must not leave the index mutated), at the
        # buffer width the kernel will RUN with: the recorded fused_kb
        # when it is already wider than this k needs
        kb = check_fused_list_request(
            "trim_engine='fused'", lane_padded(int(index.codes.shape[1])),
            index.rot_dim, int(k), 1, index.fused_kb,
            "the default trim_engine='approx'",
        )
        build_reconstruction(index, pad_to_lanes=True)
        index.fused_kb = kb  # monotonic: kb >= the recorded width
        srows_pad = maybe_filter(index.slot_rows_pad)
        from raft_tpu.core import faults

        vals, rows = macro_batched(
            lambda sl, pv=None: _search_impl_recon8_listmajor_fused(
                sl,
                index.rotation,
                index.centers,
                index.recon8,
                index.recon_scale,
                index.recon_norm,
                srows_pad,
                int(k),
                n_probes,
                index.metric,
                interpret=jax.default_backend() == "cpu",
                int8_queries=params.score_dtype == "int8",
                kb=kb,
                fault_key=faults.trace_key(),
                pvalid=pv,
            ),
            jnp.asarray(q),
            int(k),
            extra=pvalid,
        )
    elif mode == "recon8_list":
        from raft_tpu.neighbors.probe_invert import macro_batched

        build_reconstruction(index)
        srows_pad = maybe_filter(index.slot_rows_pad)
        vals, rows = macro_batched(
            lambda sl, pv=None: _search_impl_recon8_listmajor(
                sl,
                index.rotation,
                index.centers,
                index.recon8,
                index.recon_scale,
                index.recon_norm,
                srows_pad,
                int(k),
                n_probes,
                index.metric,
                int8_queries=params.score_dtype == "int8",
                trim_bf16=idd in ("bfloat16", "float16"),
                pvalid=pv,
            ),
            jnp.asarray(q),
            int(k),
            extra=pvalid,
        )
    elif mode == "recon8":
        build_reconstruction(index)
        vals, rows = _search_impl_recon8(
            q,
            index.rotation,
            index.centers,
            index.recon8,
            index.recon_scale,
            index.recon_norm,
            maybe_filter(index.slot_rows_pad),
            int(k),
            n_probes,
            index.metric,
            pvalid=pvalid,
        )
    elif mode == "lut":
        _check_lut_allowed()
        vals, rows = _search_impl(
            q,
            index.rotation,
            index.centers,
            index.pq_centers,
            index.codes,
            maybe_filter(index.slot_rows),
            int(k),
            n_probes,
            index.metric,
            index.params.codebook_kind == PER_CLUSTER,
            params.lut_dtype == "bfloat16",
            pvalid=pvalid,
        )
    else:
        raise ValueError(f"unknown score_mode {mode!r}")
    if obs.enabled() and mode == "recon8_list":
        # the list-major merge looks up one row id per winner of every
        # padded slice row (probe_invert.regroup_merge); the XLA trim
        # selects over its chunk rows, superblock padding included. Both
        # from shapes alone
        from raft_tpu.neighbors.probe_invert import (
            list_chunk,
            macro_batch_slices,
            trim_rows,
        )

        slices = macro_batch_slices(int(q.shape[0]))
        obs.counter("ivf_pq.merge.id_lookups").inc(sum(slices) * int(k))
        if trim != "fused":
            n_lists, max_list = (int(d) for d in index.recon8.shape[:2])
            obs.counter("ivf_pq.trim.rows").inc(sum(
                trim_rows(s, n_probes, n_lists, max_list,
                          list_chunk(s, n_probes, n_lists))
                for s in slices))
    ids = jnp.where(rows >= 0, index.source_ids[jnp.maximum(rows, 0)], -1)
    if resources is not None:
        resources.track(vals, ids)
    return vals, ids


# ---------------------------------------------------------------------------
# serialization (detail/ivf_pq_serialize.cuh:36, version-tagged container)
# ---------------------------------------------------------------------------

_SERIAL_VERSION = 3  # v2: mutation fields; v3: digest sidecar


def save(filename: str, index: Index) -> None:
    from raft_tpu.core.serialize import serialize_arrays

    arrays = {
        "rotation": index.rotation,
        "centers": index.centers,
        "pq_centers": index.pq_centers,
        "codes": index.codes,
        "slot_rows": index.slot_rows,
        "list_sizes": index.list_sizes,
        "source_ids": index.source_ids,
    }
    if index.list_radii is not None:
        # adaptive probing's early-termination bounds; absent in old
        # files, which load with bounds off (budgets-only fallback)
        arrays["list_radii"] = index.list_radii
    if index.tombstones is not None:
        # dead-row mask (u8); absent = all-live (pre-mutation files)
        arrays["tombstones"] = jnp.asarray(index.tombstones).astype(jnp.uint8)
    meta = {
        "kind": "ivf_pq",
        "version": _SERIAL_VERSION,
        "metric": int(index.metric),
        "n_lists": index.n_lists,
        "pq_bits": index.pq_bits,
        "codebook_kind": index.params.codebook_kind,
        "mut_cursor": int(index.mut_cursor),
        "append_slack": int(index.append_slack),
    }
    from raft_tpu.integrity.digest import pack_lists

    packed = pack_lists(index, "ivf_pq")
    if packed is not None:
        # per-list CRC-32C sidecar (v3, raft_tpu/integrity)
        arrays["list_digests"] = packed
        meta["table_digests"] = {
            k: int(v) for k, v in (index.table_digests or {}).items()}
    serialize_arrays(filename, arrays, meta)


def load(filename: str) -> Index:
    # schema-checked read (core.serialize.CKPT_SCHEMA): kind + version
    # gates, required-field presence, corrupt optional fields dropped
    from raft_tpu.core.serialize import read_ckpt

    arrays, meta = read_ckpt(filename, "ivf_pq")
    params = IndexParams(
        n_lists=meta["n_lists"],
        metric=DistanceType(meta["metric"]),
        pq_bits=meta["pq_bits"],
        codebook_kind=meta["codebook_kind"],
    )
    index = Index(
        params,
        arrays["rotation"],
        arrays["centers"],
        arrays["pq_centers"],
        arrays["codes"],
        arrays["slot_rows"],
        arrays["list_sizes"],
        arrays["source_ids"],
    )
    index.list_radii = arrays.get("list_radii")
    # mutation-era fields (v2): absent in old checkpoints -> all-live
    index.tombstones = arrays.get("tombstones")
    index.mut_cursor = int(meta.get("mut_cursor", 0))
    index.append_slack = int(meta.get("append_slack", 0))
    # integrity sidecar (v3): absent/corrupt -> no sidecar
    from raft_tpu.integrity.digest import unpack_lists

    unpack_lists(index, "ivf_pq", arrays.get("list_digests"),
                 meta.get("table_digests"))
    return index

"""select_k: batched top-k selection — the ANN performance spine.

Reference parity: `raft::matrix::select_k` (matrix/select_k.cuh:78) selects
the k smallest (or largest) elements per row with their indices. The CUDA
implementation dispatches between warp-level bitonic queues
(detail/select_warpsort.cuh) and multi-pass radix select
(detail/select_radix.cuh) based on k/len/batch (detail/select_k.cuh:67-88).

TPU design: `jax.lax.top_k` lowers to an XLA sort-based TopK that is already
heavily tuned for TPU for moderate len. For very large rows we use a
two-phase selection mirroring the reference's strategy split: partition each
row into chunks, take a per-chunk top-k on-chip (phase 1, bandwidth-bound
streaming pass), then merge the per-chunk candidates with a final top-k
(phase 2) — the same shape as warpsort's per-warp queues + block merge.
Selecting the smallest is implemented by negation (top_k selects largest).

This module is the ONE dispatch layer for every top-k decision:

  - `select_k(values, ...)` — matrix input, strategies "topk" /
    "two_phase" / "counting" (explicit or promoted by the tuned
    `select_k_strategy` key measured by bench_select_k_strategies).
  - `scan_select_k(queries, dataset, ...)` — OPERAND input: the scores
    are a derived quantity, so the "fused" strategy can hand the whole
    scan+select to the fused Pallas kernel (ops/fused_scan.py) and the
    (n_queries, n_rows) score matrix never materializes in HBM — the
    TPU-KNN fusion (arxiv 2206.14286) behind ROADMAP item 1. The
    "two_phase" strategy is the materializing reference path the fused
    kernel must bit-agree with (tests/test_fused_scan.py).

Engines (brute_force, ivf_flat, ivf_pq, refine) ask this layer for
top-k and never pick kernels; `select_k_strategy` is resolved via
`core/tuned.py` exactly like `flat_auto_engine`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Rows longer than this go through the two-phase chunked path.
_CHUNK_THRESHOLD = 1 << 16
_CHUNK = 1 << 14

# dtypes whose values embed exactly in f32 — the one list both the
# explicit strategy="counting" validation and the tuned auto-promotion
# gate consult (int32+ and f64 would silently lose precision)
_COUNTING_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16,
                    jnp.int8, jnp.int16, jnp.uint8, jnp.uint16)


def _two_phase_largest(vals: jax.Array, k: int,
                       chunk: int = _CHUNK) -> Tuple[jax.Array, jax.Array]:
    """Two-phase chunked top-k (warpsort-queues + block-merge shape):
    per-chunk top-k (streaming pass), then a merge top-k over candidates.
    Exposed separately so the strategy bench can race it against plain
    lax.top_k / approx_max_k at any shape."""
    batch = vals.shape[:-1]
    n = vals.shape[-1]
    nchunks = -(-n // chunk)
    pad = nchunks * chunk - n
    if pad:
        vals = jnp.pad(vals, [(0, 0)] * len(batch) + [(0, pad)], constant_values=-jnp.inf)
    chunked = vals.reshape(*batch, nchunks, chunk)
    cvals, cidx = lax.top_k(chunked, min(k, chunk))  # (..., nchunks, kc)
    base = (jnp.arange(nchunks, dtype=cidx.dtype) * chunk)[:, None]
    cidx = cidx + base  # chunk-local -> row-global indices
    # phase 2: merge candidates
    cand_vals = cvals.reshape(*batch, -1)
    cand_idx = cidx.reshape(*batch, -1)
    mvals, midx = lax.top_k(cand_vals, k)
    out_idx = jnp.take_along_axis(cand_idx, midx, axis=-1)
    return mvals, out_idx


#: matrix-input strategies the tuned `select_k_strategy` key may name
#: ("fused" is operand-level only — a materialized matrix can't fuse)
_MATRIX_STRATEGIES = ("topk", "two_phase", "counting")


def _tuned_strategy():
    """The measured `select_k_strategy` winner (bench --apply writes it),
    or None. The ONE tuned policy every top-k call site consults; an
    out-of-set value degrades to None (heuristics), never crashes."""
    from raft_tpu.core import tuned

    t = tuned.get("select_k_strategy")
    return t if t in _MATRIX_STRATEGIES + ("fused",) else None


def _tuned_chunk_threshold():
    """Validated on-chip-measured chunk threshold, or None. A hand-merged
    or corrupt tuned value must degrade to the built-in heuristic, not
    crash the ANN spine (ivf_pq/ivf_flat guard their tuned keys the same
    way)."""
    from raft_tpu.core import tuned

    t = tuned.get("select_k_chunk_threshold")
    if not isinstance(t, (int, float)) or isinstance(t, bool) or t <= 0:
        return None
    return int(t)


def _top_k_largest(vals: jax.Array, k: int,
                   chunk_threshold: int = None,
                   forced: str = None) -> Tuple[jax.Array, jax.Array]:
    """top-k largest per row; two-phase for long rows. The length
    threshold is measured on-chip (bench_select_k_strategies --apply
    writes it into the tuned defaults). Public select_k reads it OUTSIDE
    jit and threads it through as a static argument (reload-aware); the
    internal ANN-spine callers reach here inside their own traces with
    chunk_threshold=None, so the tuned value is read at trace time — a
    later tuned.reload() only affects newly-traced shapes, which is fine:
    the --apply writers run in fresh processes per on-chip queue step."""
    n = vals.shape[-1]
    # an explicit caller strategy, else the measured tuned winner,
    # overrides the length heuristic (but the two-phase guards stay: a
    # row that fits one chunk, or a k too large for the per-chunk
    # phase, degenerates to plain top_k anyway)
    if forced is None:
        forced = _tuned_strategy()
    if forced == "topk":
        return lax.top_k(vals, k)
    if forced == "two_phase":
        if n > 2 * _CHUNK and k <= _CHUNK // 4:
            return _two_phase_largest(vals, k)
        return lax.top_k(vals, k)
    if chunk_threshold is None:
        chunk_threshold = _tuned_chunk_threshold()
    thresh = _CHUNK_THRESHOLD if chunk_threshold is None else int(chunk_threshold)
    if n <= thresh or n <= 2 * _CHUNK or k > _CHUNK // 4:
        return lax.top_k(vals, k)
    return _two_phase_largest(vals, k)


def _counting_promoted(vals, k: int) -> bool:
    """Trace-time gate for the measured counting-engine promotion,
    shared by the public API and `_select_k_impl` so internal hot paths
    (the brute-force per-tile select, IVF merges) also benefit from an
    on-chip strategy win. Exact engine — the flip is purely perf."""
    from raft_tpu.core import tuned
    from raft_tpu.core.config import is_tpu_backend

    promoted = (tuned.get("select_k_auto_strategy") == "counting"
                or _tuned_strategy() == "counting")
    if (
        not promoted
        or not is_tpu_backend()  # Mosaic kernel, chip-measured: CPU would
        # interpret (orders slower), GPU would fail to lower
        or vals.ndim != 2
        or vals.dtype not in _COUNTING_DTYPES
    ):
        return False
    from raft_tpu.ops.select_counting import fits_counting

    padded = vals.shape[-1] + (-vals.shape[-1]) % 128
    return bool(fits_counting(vals.shape[0], padded, int(k)))


@functools.partial(
    jax.jit, static_argnames=("k", "select_min", "chunk_threshold", "forced")
)
def _select_k_impl(vals: jax.Array, k: int, select_min: bool,
                   chunk_threshold: int = None, forced: str = None):
    if forced is None and _counting_promoted(vals, k):
        return _select_k_counting(vals, k, select_min)
    if select_min:
        # negate; NaNs/infs: -inf stays worst under negation of +inf
        v, i = _top_k_largest(-vals, k, chunk_threshold, forced)
        return -v, i
    return _top_k_largest(vals, k, chunk_threshold, forced)


@functools.partial(jax.jit, static_argnames=("k", "select_min", "interpret"))
def _select_k_counting(vals: jax.Array, k: int, select_min: bool,
                       interpret: bool = False):
    """Pallas counting-select engine (ops/select_counting.py): exact
    threshold via in-VMEM bit-fixing, then a tiny (B, k) sort for the
    best-first output contract. Opt-in (strategy="counting") until the
    on-chip strategy bench decides where it wins."""
    from raft_tpu.ops.select_counting import counting_select_min

    n = vals.shape[-1]
    pad = (-n) % 128
    # cast BEFORE negating: integer negation wraps (int8 -128 -> -128,
    # unsigned mod 2^n), f32 negation is exact for every admitted dtype
    v = vals.astype(jnp.float32)
    if not select_min:
        v = -v
    if pad:
        v = jnp.pad(v, ((0, 0), (0, pad)), constant_values=jnp.inf)
    cv, ci = counting_select_min(v, k, interpret=interpret)
    # finish: best-first order over the k survivors (tiny)
    sv, order = lax.top_k(-cv, k)
    iv = jnp.take_along_axis(ci, order, axis=-1)
    out = -sv if select_min else sv
    # match every other strategy's contract: values keep the input dtype
    # (exact: all admitted dtypes embed in f32)
    return out.astype(vals.dtype), iv


def select_k(
    values,
    k: int,
    select_min: bool = True,
    indices: Optional[jax.Array] = None,
    resources=None,
    strategy: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Select the k smallest (default) or largest values per row.

    Returns (values, indices), both shaped (batch, k), sorted best-first —
    matching matrix/select_k.cuh semantics. `indices`, when given, maps
    row-local positions to caller ids (the reference's `in_idx` optional
    input used by tile merging).

    `strategy`: None/"auto" picks the measured default (the tuned
    `select_k_strategy` winner when set, else lax.top_k or the
    two-phase chunked path by shape); "topk" forces plain top_k;
    "two_phase" forces the chunked warpsort-shaped path; "counting"
    opts into the Pallas counting-select engine
    (ops/select_counting.py), the radix-select analogue aimed at large
    rows — all exact, raced by bench/bench_select_k_strategies.py.
    For top-k over operands (queries x dataset) use `scan_select_k`,
    which adds the "fused" strategy.

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.matrix import select_k
    >>> v, i = select_k(np.array([[3.0, 1.0, 2.0], [0.5, 4.0, 0.25]]), 2)
    >>> np.asarray(i).tolist()
    [[1, 2], [2, 0]]
    >>> np.asarray(v).tolist()
    [[1.0, 2.0], [0.25, 0.5]]
    """
    from raft_tpu.core.validation import as_array

    vals = as_array(values)
    squeeze = vals.ndim == 1
    if squeeze:
        vals = vals[None, :]
    if not (0 < k <= vals.shape[-1]):
        raise ValueError(f"k={k} out of range for row length {vals.shape[-1]}")
    if strategy not in (None, "auto", "topk", "two_phase", "counting"):
        raise ValueError(f"unknown select_k strategy {strategy!r}")
    if strategy in (None, "auto"):
        # a measured on-chip winner can promote the counting engine for
        # the shapes it fits (shared gate with _select_k_impl, so
        # internal hot paths get the same flip). The kernel is strictly
        # 2-D; higher-rank batches keep the ndim-agnostic default path.
        if _counting_promoted(vals, k):
            strategy = "counting"
    if strategy == "counting":
        # the engine works on the f32 order image; only dtypes that embed
        # exactly in f32 keep the documented exact-selection contract
        if vals.dtype not in _COUNTING_DTYPES:
            raise ValueError(
                f"strategy='counting' requires an f32-embeddable dtype, got {vals.dtype}"
            )
        interp = jax.default_backend() == "cpu"  # Mosaic needs TPU
        v, i = _select_k_counting(vals, int(k), bool(select_min), interp)
    else:
        v, i = _select_k_impl(
            vals, int(k), bool(select_min), _tuned_chunk_threshold(),
            forced=strategy if strategy in ("topk", "two_phase") else None,
        )
    if indices is not None:
        idx = as_array(indices)
        if idx.ndim == 1:
            idx = idx[None, :]
        i = jnp.take_along_axis(idx, i, axis=-1)
    if squeeze:
        v, i = v[0], i[0]
    if resources is not None:
        resources.track(v, i)
    return v, i


# ---------------------------------------------------------------------------
# operand-level dispatch: scan + select in one decision
# ---------------------------------------------------------------------------

#: strategies scan_select_k accepts (None/"auto" resolves via the tuned
#: `select_k_strategy` key, like ivf_flat's `flat_auto_engine`)
SCAN_STRATEGIES = ("fused", "two_phase")


def _fused_metric_kind(metric):
    """("l2"|"ip", want_sqrt) when the fused kernel covers `metric`,
    else None — the one gate both the auto-resolution and the explicit
    validation consult."""
    from raft_tpu.distance.distance_types import DistanceType as D

    if metric == D.InnerProduct:
        return "ip", False
    if metric in (D.L2Expanded, D.L2Unexpanded):
        return "l2", False
    if metric in (D.L2SqrtExpanded, D.L2SqrtUnexpanded):
        return "l2", True
    return None


def resolve_scan_strategy(n_rows: int, dim: int, k: int,
                          strategy=None, fused_ok: bool = True) -> str:
    """Resolve a scan_select_k strategy: explicit wins; else the tuned
    `select_k_strategy` winner promotes "fused" when the kernel fits
    (TPU backend, supported metric, k/VMEM envelope); else the
    materializing two-phase reference path."""
    if strategy in SCAN_STRATEGIES:
        return strategy
    if strategy not in (None, "auto"):
        raise ValueError(f"unknown scan_select_k strategy {strategy!r}")
    if fused_ok and _tuned_strategy() == "fused":
        from raft_tpu.core.config import is_tpu_backend
        from raft_tpu.ops.fused_scan import fits_fused

        if is_tpu_backend() and fits_fused(1, n_rows, dim, k):
            return "fused"
    return "two_phase"


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "interpret", "fault_key")
)
def _scan_fused_impl(queries, dataset, k: int, metric, valid=None,
                     interpret: bool = False, fault_key=None):
    """Fused scan+select: distances and selection in one Pallas kernel,
    score matrix never in HBM (ops/fused_scan.py)."""
    from raft_tpu.ops.fused_scan import fused_topk

    kind, want_sqrt = _fused_metric_kind(metric)
    ip = kind == "ip"
    vc, ids = fused_topk(
        jnp.asarray(queries, jnp.float32), jnp.asarray(dataset, jnp.float32),
        k, inner_product=ip, valid=valid, interpret=interpret,
        fault_key=fault_key,
    )
    vc, ids = vc[:, :k], ids[:, :k]
    ids = jnp.where(jnp.isfinite(vc), ids, -1)
    if ip:
        return -vc, ids  # exhausted slots: -inf, the IP worst
    # the kernel scores the bf16-rounded geometry; |q|^2 must be the
    # SAME rounded rows or near-tie ranks and values drift apart
    qb = jnp.asarray(queries, jnp.float32).astype(jnp.bfloat16).astype(
        jnp.float32
    )
    qn = jnp.sum(qb * qb, axis=1, keepdims=True)
    v = jnp.maximum(vc + qn, 0.0)
    return (jnp.sqrt(v) if want_sqrt else v), ids


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _scan_two_phase_impl(queries, dataset, k: int, metric, valid=None):
    """The materializing reference: full pairwise distances + the
    matrix-input select (exactly the path the fused kernel must agree
    with — and the fallback wherever fused doesn't fit)."""
    from raft_tpu.distance.distance_types import SIMILARITY_METRICS
    from raft_tpu.distance.pairwise import _pairwise_impl

    select_min = metric not in SIMILARITY_METRICS
    worst = jnp.inf if select_min else -jnp.inf
    d = _pairwise_impl(queries, dataset, metric)
    if valid is not None:
        d = jnp.where(valid[None, :], d, worst)
    # forced: THIS strategy is the named reference — a tuned counting/
    # topk promotion must not silently swap the kernel under the
    # "two_phase" label (the bench race and the agreement tests both
    # compare against what this path actually runs)
    v, i = _select_k_impl(d, k, select_min, forced="two_phase")
    # one public contract across strategies: a slot holding the worst
    # value (sub-k survivors under a valid mask) reports id -1, exactly
    # like the fused path — not the masked row's id top_k happens to
    # surface
    i = jnp.where(jnp.isfinite(v), i, -1)
    return v, i.astype(jnp.int32)


def scan_select_k(
    queries,
    dataset,
    k: int,
    metric="sqeuclidean",
    strategy: Optional[str] = None,
    valid=None,
    resources=None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k nearest dataset rows per query, dispatched over OPERANDS:
    the caller never materializes (or even sees) the score matrix.

    Returns (values, indices), each (n_queries, k), best-first.
    `strategy`: None/"auto" resolves via the tuned `select_k_strategy`
    key; "fused" = the fused Pallas distance+select-k kernel
    (L2/inner-product, k <= ops.fused_scan.FUSED_MAX_K; exact, with
    ties broken to the smaller row id, over the bf16-rounded operands);
    "two_phase" = materialize pairwise distances and run the matrix
    select (any metric, f32). `valid`: optional (n_rows,) bool mask —
    False rows are excluded before selection; when fewer than k rows
    survive, the tail holds the worst value with index -1 on BOTH
    strategies (the prefilter contract).
    """
    from raft_tpu.core.validation import check_matrix, check_same_cols
    from raft_tpu.distance.distance_types import resolve_metric

    q = check_matrix(queries, name="queries")
    ds = check_matrix(dataset, name="dataset")
    check_same_cols(ds, q, "dataset", "queries")
    if not (0 < k <= ds.shape[0]):
        raise ValueError(f"k={k} out of range for dataset with {ds.shape[0]} rows")
    m = resolve_metric(metric)
    fused_ok = _fused_metric_kind(m) is not None
    strat = resolve_scan_strategy(
        ds.shape[0], ds.shape[1], int(k), strategy, fused_ok=fused_ok
    )
    if strat == "fused":
        from raft_tpu.ops.fused_scan import FUSED_MAX_K, fits_fused

        if not fused_ok:
            raise ValueError(
                f"strategy='fused' supports L2/inner_product metrics, got {m}"
            )
        if not fits_fused(q.shape[0], ds.shape[0], ds.shape[1], int(k)):
            raise ValueError(
                f"strategy='fused' caps k at {FUSED_MAX_K} and the tile at "
                "the kernel's VMEM envelope; use strategy='two_phase'"
            )
        from raft_tpu.core import faults

        v, i = _scan_fused_impl(
            q, ds, int(k), m,
            valid=None if valid is None else jnp.asarray(valid, bool),
            interpret=jax.default_backend() == "cpu",  # Mosaic needs TPU
            fault_key=faults.trace_key(),
        )
    else:
        v, i = _scan_two_phase_impl(
            q, ds, int(k), m,
            valid=None if valid is None else jnp.asarray(valid, bool),
        )
    if resources is not None:
        resources.track(v, i)
    return v, i


# ---------------------------------------------------------------------------
# list-scan dispatch: the IVF engines' fused kernels, one chooser
# ---------------------------------------------------------------------------
#
# The IVF list-major engines (ivf_flat / ivf_pq and their
# MNMG drivers) never import a kernel from ops directly — they ask THIS
# layer for a per-list fused scan+select. Strategy names extend the
# scan_select_k family onto the integer datapath (ISSUE 11):
#
#   "fused"          bf16 MXU scoring (the PR-10 family)
#   "fused_int8"     int8 x int8 -> int32 MXU scoring, per-row dequant
#                    (v5e: 394 int8 TOPS vs 197 bf16 TFLOP/s)
#
# Tuned promotion mirrors `select_k_strategy` for the int8 family: its
# own measured key (flipped by bench_select_k_strategies --apply on chip
# data only), consulted ONLY by auto resolution. Explicit strategies
# always win, and an explicit request past the kernel's envelope raises
# instead of silently falling back.

#: strategies the list-scan dispatch accepts
LIST_SCAN_STRATEGIES = ("fused", "fused_int8")

#: tuned key promoting the int8 fused scan — re-exported from the ONE
#: registry spelling (core.tuned.TUNED_KEYS; raftlint's
#: `tuned-key-registry` pins every `*_KEY` constant to it)
from raft_tpu.core.tuned import INT8_SCAN_KEY  # noqa: E402


def resolve_int8_trim_strategy(L: int, rot: int, k: int,
                               kbuf: Optional[int] = None,
                               strategy: Optional[str] = None):
    """Resolve the IVF-PQ int8 recon trim: explicit "fused_int8" wins
    (envelope-checked at the call site, which raises); None/"auto"
    promotes the fused int8 kernel only when the measured tuned key
    names it, the backend is TPU, and the geometry fits — else None
    (the caller keeps its reference trim)."""
    if strategy == "fused_int8":
        return strategy
    if strategy not in (None, "auto"):
        raise ValueError(f"unknown int8 trim strategy {strategy!r}")
    from raft_tpu.core import tuned

    if tuned.get(INT8_SCAN_KEY) != "fused_int8":
        return None
    from raft_tpu.core.config import is_tpu_backend
    from raft_tpu.ops.fused_scan import fits_fused_list

    if is_tpu_backend() and fits_fused_list(128, L, rot, int(k),
                                            store_itemsize=1, kbuf=kbuf):
        return "fused_int8"
    return None


def check_fused_list_request(label: str, L: int, rot: int, k: int,
                             store_itemsize: int, kbuf: Optional[int],
                             fallback: str) -> int:
    """Validate an EXPLICIT fused list-scan request against the kernel
    caps/envelope — the ONE copy of the 'explicit requests raise past
    the envelope' rule every engine call site (single-chip and MNMG)
    shares. Returns the candidate-buffer width the kernel must run
    with (>= the caller's recorded monotone `kbuf`)."""
    from raft_tpu.ops.fused_scan import (
        FUSED_MAX_K, fits_fused_list, fused_kbuf,
    )

    if int(k) > FUSED_MAX_K:
        raise ValueError(
            f"{label} caps per-list candidates at {FUSED_MAX_K}; k={k}"
        )
    kb = max(fused_kbuf(int(k)), kbuf or 0)
    if not fits_fused_list(128, L, rot, int(k),
                           store_itemsize=store_itemsize, kbuf=kb):
        raise ValueError(
            f"{label}: list length {L} exceeds the kernel's VMEM "
            f"envelope; use {fallback}"
        )
    return kb


def list_scan_select_k(
    lof, qres, store, base, k: int,
    strategy: str = "fused",
    q_scale=None,
    kbuf: Optional[int] = None,
    inner_product: bool = False,
    interpret: bool = False,
    fault_key=None,
    chunk_valid=None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-list fused scan+select over a slot-table store — the list
    geometry's `scan_select_k`. Returns ((ncb, chunk, kbuf) minimizing
    scores, in-list slots), best-first, exactly the `ops.fused_scan`
    list contract. `strategy`: "fused" casts the store to bf16 for the
    MXU matmul; "fused_int8" requires int8 `qres` + `store` and the
    (ncb, chunk, 1) `q_scale` per-row dequant operand, and scores on
    the int8 MXU path. Engines pass their recorded monotonic `kbuf`.
    `chunk_valid` ((ncb,) int32, probe_invert.chunk_validity): empty
    chunks — trailing fragmentation, or chunks adaptive probe budgets
    emptied — skip their MXU work in-kernel."""
    if strategy not in LIST_SCAN_STRATEGIES:
        raise ValueError(f"unknown list-scan strategy {strategy!r}")
    if strategy == "fused_int8":
        if q_scale is None:
            raise ValueError("strategy='fused_int8' requires q_scale")
        from raft_tpu.ops.fused_scan import fused_list_topk_int8

        return fused_list_topk_int8(
            lof, qres, store, base, q_scale, int(k), kbuf=kbuf,
            inner_product=inner_product, interpret=interpret,
            fault_key=fault_key, chunk_valid=chunk_valid,
        )
    if q_scale is not None:
        raise ValueError("q_scale requires strategy='fused_int8'")
    from raft_tpu.ops.fused_scan import fused_list_topk

    return fused_list_topk(
        lof, qres, store, base, int(k), kbuf=kbuf,
        inner_product=inner_product, interpret=interpret,
        fault_key=fault_key, chunk_valid=chunk_valid,
    )

"""Fused pairwise-distance + partial select-k Pallas kernel family.

Reference parity: the TPU-KNN paper (arxiv 2206.14286) runs brute-force
and IVF scans at near-peak FLOP/s by fusing the distance matmul with an
in-register partial top-k, so the (n_queries, n_rows) score matrix never
touches HBM. The CUDA analogue is `fused_l2_knn.cuh` (distance tile +
warp-level select queue in one kernel). This module is that kernel
family for TPU, and `matrix.select_k.scan_select_k` is its one dispatch
door — engines ask for top-k over operands and never pick kernels.

Four geometries share one epilogue:

  `fused_topk`      — flat scan: grid (m/bq, n/bn) with n innermost;
                      each step scores a (bq, bn) tile on the MXU (bf16
                      operands, f32 accumulate) and merges it into a
                      revisited (bq, kbuf) VMEM candidate buffer, the
                      analogue of the paper's per-core partial top-k
                      state. Only (m, kbuf) values+ids reach HBM.
  `fused_list_topk` — list scan: grid (ncb,) with scalar-prefetched
                      chunk->list ids indexing the store directly (no
                      gather copy of the scanned lists); per step the
                      (chunk, L) scores fold to an exact (chunk, kbuf)
                      top-k in-kernel. Stores follow the lane contract
                      of `lane_padded`. Backs the IVF-Flat/IVF-PQ fused
                      trims and the per-query fused rerank (chunk=1,
                      one "list" of gathered candidates per query).
  `fused_list_topk_int8`
                    — the list scan on the INTEGER datapath (ISSUE 11):
                      symmetric int8 queries x the int8 reconstruction
                      store -> int32 accumulate on the MXU's doubled
                      int8 rate (v5e: 394 int8 TOPS vs 197 bf16
                      TFLOP/s), per-row dequant scale applied on the
                      VPU, then the same exact epilogue — only the
                      (chunk, kbuf) survivors are ever dequantized to
                      f32 in HBM. Scores are `base - coef * dots`, dots
                      the int32 dot times the per-row scale, over the
                      same quantized rows as the XLA list-major
                      engine's int8 path.

The epilogue is an EXACT partial selection: `k` extraction passes over
the merged candidate window (the running kbuf buffer + the fresh tile —
the "2k candidates" the merge sorts), each pass taking the
lexicographic (score, id) minimum so ties break deterministically to the
smaller id — the same stable-tie order `lax.top_k` produces, which is
what makes the fused path bit-agree with the two-phase reference
select-k. Exhausted slots carry (+inf, _ID_SENTINEL); callers map
non-finite winners to id -1.

Scores are canonical-minimizing: `base - 2<q,v>` for L2 (the per-query
|q|^2 constant cannot change any ranking, so it is added OUTSIDE the
kernel) and `base - <q,v>` for inner product (base 0 on valid slots,
+inf on masked/padded ones — the mask IS the base operand). Operands
are cast to bf16 for the one-pass MXU matmul with f32 accumulation;
like `knn(compute_dtype=bfloat16)`, the fused path ranks the
bf16-rounded geometry (exact whenever the inputs embed in bf16, which
is what the agreement tests pin).

Compiled-path status: validated in interpret mode (CPU tests); first
on-chip Mosaic compile may need block-shape adjustment (the lane-axis
concatenate and the fori_loop extraction are the highest-risk shapes).
The dispatch layer can always fall back to strategy="two_phase".
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: hard cap on k for every fused engine: the extraction epilogue costs
#: one VPU pass over the candidate window per selected element, and the
#: (bq, kbuf) buffer must stay a small fraction of the score tile
FUSED_MAX_K = 256
_ID_SENTINEL = 2**31 - 1  # python int: kernels close over no arrays

#: injection site for the chaos drill: corrupt_in_trace on the kernel's
#: candidate buffer (the values half), before callers merge/finalize
FUSED_SCORES_SITE = "fused.scan.scores"

#: Machine-readable kernel -> envelope pairing (the FAULT_SITES
#: pattern), read BY AST by raftlint's kernelcheck engine
#: (tools/raftlint/kernels.py): each pallas_call wrapper below is
#: cross-checked against its `fits_*` formula — per-grid-step block
#: bytes compared monomial by monomial over the SHARED parameter names,
#: so an envelope term drifting from the kernel geometry fires at lint
#: time instead of as a chip OOM. The binding dict pins envelope
#: parameters the kernel fixes (the int8 kernel shares the bf16 list
#: envelope at store itemsize 1). Keep it a literal dict.
KERNEL_ENVELOPES = {
    "fused_topk": ("fits_fused", {}),
    "fused_list_topk": ("fits_fused_list", {}),
    "fused_list_topk_int8": ("fits_fused_list", {"store_itemsize": 1}),
}


def lane_padded(width: int) -> int:
    """The slot-axis width the list scans' lane contract requires: a
    multiple of the 128-lane register width, at least 256 slots. Shared
    by every caller that pads a store for them."""
    return max(2 * _LANES, -(-width // _LANES) * _LANES)


def fused_kbuf(k: int) -> int:
    """Candidate-buffer width compiled for a requested k: the 128-lane
    multiple that holds it. ONE definition shared by the kernels, the
    dispatch fit checks, and ivf_flat's lazy-store invalidation (a store
    built for kbuf=128 must rebuild when k grows past it, or the
    per-list candidate slice silently truncates)."""
    if not 0 < k <= FUSED_MAX_K:
        raise ValueError(f"fused select-k caps k at {FUSED_MAX_K}; k={k}")
    return max(_LANES, -(-int(k) // _LANES) * _LANES)


def _maybe_corrupt(vals):
    """Chaos hook on the candidate buffer. Inert (same jaxpr) without an
    installed plan; callers key their jits on `faults.trace_key()` so a
    plan install retraces instead of serving the clean program."""
    from raft_tpu.core.faults import corrupt_in_trace

    return corrupt_in_trace(FUSED_SCORES_SITE, vals, jnp.int32(0))


def _extract_topk(wv, wi, out_shape, k: int):
    """The shared exact epilogue: `k` lexicographic-min extraction
    passes over the candidate window (wv, wi), writing a sorted
    best-first (rows, kbuf) buffer. Ties break to the smaller id
    (stable order — the lax.top_k contract the reference paths use);
    selected entries are retired to (+inf, sentinel) so the next pass
    sees the remainder."""
    rows, kbuf = out_shape
    slot = lax.broadcasted_iota(jnp.int32, (rows, kbuf), 1)

    def extract(t, carry):
        wv_, wi_, ov, oi = carry
        m = jnp.min(wv_, axis=1, keepdims=True)  # (rows, 1)
        tie = wv_ == m
        mi = jnp.min(jnp.where(tie, wi_, _ID_SENTINEL), axis=1, keepdims=True)
        sel = tie & (wi_ == mi)
        hot = slot == t
        ov = jnp.where(hot, m, ov)
        oi = jnp.where(hot, mi, oi)
        wv_ = jnp.where(sel, jnp.float32(jnp.inf), wv_)
        wi_ = jnp.where(sel, _ID_SENTINEL, wi_)
        return wv_, wi_, ov, oi

    ov0 = jnp.full((rows, kbuf), jnp.inf, jnp.float32)
    oi0 = jnp.full((rows, kbuf), _ID_SENTINEL, jnp.int32)
    _, _, ov, oi = lax.fori_loop(0, k, extract, (wv, wi, ov0, oi0))
    return ov, oi


# ---------------------------------------------------------------------------
# flat scan: fused_topk
# ---------------------------------------------------------------------------


def _make_flat_kernel(bn: int, kbuf: int, k: int, inner_product: bool):
    coef = 1.0 if inner_product else 2.0

    def kernel(x_ref, y_ref, base_ref, vals_ref, idx_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            vals_ref[:] = jnp.full(vals_ref.shape, jnp.inf, jnp.float32)
            idx_ref[:] = jnp.full(idx_ref.shape, _ID_SENTINEL, jnp.int32)

        dots = lax.dot_general(
            x_ref[:], y_ref[:],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bn)
        score = base_ref[:] - coef * dots  # masked/padded slots: base=+inf
        col = j * bn + lax.broadcasted_iota(jnp.int32, score.shape, 1)
        wv = jnp.concatenate([vals_ref[:], score], axis=1)
        wi = jnp.concatenate([idx_ref[:], col], axis=1)
        ov, oi = _extract_topk(wv, wi, vals_ref.shape, k)
        vals_ref[:] = ov
        idx_ref[:] = oi

    return kernel


def fits_fused(m: int, n: int, d: int, k: int,
               bq: int = 128, bn: int = 512) -> bool:
    """VMEM envelope for one flat-scan grid step: the score tile, the
    merged candidate window (values + ids), and the bf16 operand
    blocks. `m`/`n` only gate trivial emptiness; the grid streams any
    row count."""
    if not (0 < k <= FUSED_MAX_K and m >= 1 and n >= 1 and d >= 1):
        return False
    kbuf = fused_kbuf(k)
    d_pad = -(-d // _LANES) * _LANES
    step_bytes = (
        4 * bq * bn            # score tile
        + 8 * bq * (kbuf + bn)  # extraction window (f32 + int32)
        + 8 * bq * kbuf        # output buffers
        + 2 * (bq + bn) * d_pad  # bf16 operand blocks
        + 4 * bn               # base row
    )
    return step_bytes <= 10 * 1024 * 1024


@functools.partial(
    jax.jit,
    static_argnames=("k", "inner_product", "bq", "bn", "interpret",
                     "fault_key"),
)
def fused_topk(
    x: jax.Array,            # (m, d) queries
    y: jax.Array,            # (n, d) database rows
    k: int,
    *,
    inner_product: bool = False,
    valid: Optional[jax.Array] = None,  # (n,) bool: False rows excluded
    bq: int = 128,
    bn: int = 512,
    interpret: bool = False,
    fault_key=None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact fused scan+select over the full (m, n) pair space.

    Returns ((m, kbuf) canonical-minimizing scores, (m, kbuf) int32 row
    ids), best-first, kbuf = fused_kbuf(k); slots past k (and exhausted
    slots) carry (+inf, sentinel). L2 scores are |y|^2 - 2<x,y> — add
    the per-query |x|^2 and clamp at the call site; inner-product
    scores are -<x,y>. `fault_key` must be `faults.trace_key()` so an
    installed chaos plan retraces this jit.
    """
    del fault_key  # participates in the jit cache key only
    m, d = x.shape
    n = y.shape[0]
    kbuf = fused_kbuf(k)

    xb = x.astype(jnp.bfloat16)
    yb = y.astype(jnp.bfloat16)
    # base row: L2 -> |y|^2 of the bf16-rounded rows (the geometry the
    # matmul scores); IP -> 0. Padding and the valid mask fold in as
    # +inf, so the kernel needs no separate mask operand.
    yf = yb.astype(jnp.float32)
    base = jnp.zeros((n,), jnp.float32) if inner_product else jnp.sum(
        yf * yf, axis=1
    )
    if valid is not None:
        base = jnp.where(valid, base, jnp.inf)

    d_pad = -(-d // _LANES) * _LANES
    m_pad = -(-m // bq) * bq
    n_pad = -(-n // bn) * bn
    xb = jnp.pad(xb, ((0, m_pad - m), (0, d_pad - d)))
    yb = jnp.pad(yb, ((0, n_pad - n), (0, d_pad - d)))
    base = jnp.pad(base, (0, n_pad - n), constant_values=jnp.inf)[None, :]

    vals, idx = pl.pallas_call(
        _make_flat_kernel(bn, kbuf, int(k), bool(inner_product)),
        grid=(m_pad // bq, n_pad // bn),
        in_specs=[
            pl.BlockSpec((bq, d_pad), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, d_pad), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((bq, kbuf), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, kbuf), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m_pad, kbuf), jnp.float32),
            jax.ShapeDtypeStruct((m_pad, kbuf), jnp.int32),
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(xb, yb, base)
    return _maybe_corrupt(vals[:m]), idx[:m]


# ---------------------------------------------------------------------------
# list scan: fused_list_topk
# ---------------------------------------------------------------------------


def _make_list_kernel(kbuf: int, k: int, inner_product: bool,
                      with_valid: bool = False):
    coef = 1.0 if inner_product else 2.0

    def kernel(lof_ref, *refs):
        del lof_ref  # consumed by the index maps
        if with_valid:
            cva_ref, qres_ref, store_ref, base_ref, vals_ref, idx_ref = refs
        else:
            qres_ref, store_ref, base_ref, vals_ref, idx_ref = refs

        def compute():
            q = qres_ref[0]  # (chunk, rot) f32
            dots = lax.dot_general(
                q.astype(jnp.bfloat16),
                store_ref[0].astype(jnp.bfloat16),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (chunk, L)
            score = base_ref[0] - coef * dots
            slot = lax.broadcasted_iota(jnp.int32, score.shape, 1)
            ov, oi = _extract_topk(score, slot, (score.shape[0], kbuf), k)
            vals_ref[0] = ov
            idx_ref[0] = oi

        if not with_valid:
            compute()
            return
        # sentinel/valid-chunk path (adaptive probe budgets): a chunk
        # with no live pairs skips the MXU matmul and the extraction
        # loop entirely — its output slots are never addressed by a
        # live pair's regroup gather, so (+inf, sentinel) is exact
        i = pl.program_id(0)

        @pl.when(cva_ref[i] != 0)
        def _():
            compute()

        @pl.when(cva_ref[i] == 0)
        def _():
            vals_ref[0] = jnp.full(vals_ref.shape[1:], jnp.inf, jnp.float32)
            idx_ref[0] = jnp.full(idx_ref.shape[1:], _ID_SENTINEL, jnp.int32)

    return kernel


def fits_fused_list(chunk: int, L: int, rot: int, k: int,
                    store_itemsize: int = 2,
                    kbuf: Optional[int] = None) -> bool:
    """VMEM envelope for one list-scan grid step: the score tile, the
    slot-id plane, the scanned list block, the query rows, the output
    buffers, the base row, and the int8 kernel's dequant-scale column
    — machine-checked against the kernels' actual block bytes by
    raftlint's `kernel-vmem-envelope` via KERNEL_ENVELOPES. `kbuf`:
    the buffer width the kernel will ACTUALLY run with — callers that
    cache a monotonically-grown width (ivf_flat's `fused_kb`) must pass
    it, or a small-k search on a grown store is gated against a
    narrower buffer than it compiles."""
    if not (0 < k <= FUSED_MAX_K):
        return False
    kbuf = fused_kbuf(k) if kbuf is None else int(kbuf)
    step_bytes = (
        4 * chunk * L                    # score tile (f32)
        + 4 * chunk * L                  # slot-id plane (int32)
        + store_itemsize * L * rot       # the scanned list block
        + 4 * chunk * rot                # query residuals
        + 8 * chunk * kbuf               # output buffers
        + 4 * L                          # base row (f32)
        + 4 * chunk                      # per-row dequant scale (int8 kernel)
    )
    return L % _LANES == 0 and step_bytes <= 10 * 1024 * 1024


@functools.partial(
    jax.jit,
    static_argnames=("k", "kbuf", "inner_product", "interpret", "fault_key"),
)
def fused_list_topk(
    lof: jax.Array,     # (ncb,) int32 chunk -> list id (scalar prefetch)
    qres: jax.Array,    # (ncb, chunk, rot) f32 query rows/residuals
    store: jax.Array,   # (n_lists, L, rot) slot table (bf16/f32/int8)
    base: jax.Array,    # (n_lists, 1, L) f32 additive base, +inf invalid
    k: int,
    *,
    kbuf: Optional[int] = None,
    inner_product: bool = False,
    interpret: bool = False,
    fault_key=None,
    chunk_valid: Optional[jax.Array] = None,  # (ncb,) int32 0 = skip
) -> Tuple[jax.Array, jax.Array]:
    """Exact fused scan+select of each chunk's probed list.

    Returns ((ncb, chunk, kbuf) minimizing scores, (ncb, chunk, kbuf)
    int32 in-list slots), best-first per row; slots past k carry
    (+inf, sentinel). `kbuf` defaults to fused_kbuf(k); callers that
    cache a compiled width (ivf_flat's lazy store) pass their recorded
    one — it must be >= fused_kbuf(k) or the top-k truncates, which is
    exactly the invalidation `_pad_store_to_lanes` enforces. Scores are
    `base - 2<q,v>` (L2; add |q|^2 outside) or `base - <q,v>` (IP).

    `chunk_valid` (probe_invert.chunk_validity): chunks flagged 0 hold
    no live pairs — the kernel skips their MXU matmul and extraction
    loop and writes (+inf, sentinel), the exact values a pad slot
    carries anyway. This is the ragged-work path adaptive probe budgets
    ride: shrunken budgets empty out whole chunks, and emptied chunks
    cost no compute.
    """
    del fault_key  # participates in the jit cache key only
    ncb, chunk, rot = qres.shape
    n_lists, L, _ = store.shape
    if qres.dtype != jnp.float32 or base.dtype != jnp.float32:
        # the documented operand contract (f32 rows/base) — also what
        # the envelope charges; trace-time only, so the guard is free
        raise ValueError(
            f"fused_list_topk requires float32 qres and base, got "
            f"{qres.dtype}/{base.dtype}"
        )
    if L % _LANES:
        raise ValueError(f"list length {L} must be a multiple of {_LANES}")
    kb = fused_kbuf(k) if kbuf is None else int(kbuf)
    if kb < fused_kbuf(k):
        raise ValueError(
            f"candidate buffer width {kb} cannot hold k={k} "
            f"(needs {fused_kbuf(k)})"
        )

    with_valid = chunk_valid is not None
    nsp = 2 if with_valid else 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(ncb,),
        in_specs=[
            pl.BlockSpec((1, chunk, rot), lambda i, *s: (i, 0, 0)),
            pl.BlockSpec((1, L, rot), lambda i, *s: (s[0][i], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i, *s: (s[0][i], 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, kb), lambda i, *s: (i, 0, 0)),
            pl.BlockSpec((1, chunk, kb), lambda i, *s: (i, 0, 0)),
        ),
    )
    scalars = (lof, chunk_valid.astype(jnp.int32)) if with_valid else (lof,)
    vals, idx = pl.pallas_call(
        _make_list_kernel(kb, int(k), bool(inner_product), with_valid),
        out_shape=(
            jax.ShapeDtypeStruct((ncb, chunk, kb), jnp.float32),
            jax.ShapeDtypeStruct((ncb, chunk, kb), jnp.int32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(*scalars, qres, store, base)
    return _maybe_corrupt(vals), idx


# ---------------------------------------------------------------------------
# integer list scan: fused_list_topk_int8
# ---------------------------------------------------------------------------


def _make_list_kernel_int8(kbuf: int, k: int, inner_product: bool,
                           with_valid: bool = False):
    coef = 1.0 if inner_product else 2.0

    def kernel(lof_ref, *refs):
        del lof_ref  # consumed by the index maps
        if with_valid:
            (cva_ref, q8_ref, store_ref, base_ref, rs_ref,
             vals_ref, idx_ref) = refs
        else:
            q8_ref, store_ref, base_ref, rs_ref, vals_ref, idx_ref = refs

        def compute():
            # int8 x int8 -> int32 at the MXU's doubled int8 rate; the
            # per-row dequant scale is the ONLY float multiply before the
            # epilogue
            idots = lax.dot_general(
                q8_ref[0], store_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # (chunk, L)
            dots = idots.astype(jnp.float32) * rs_ref[0]  # (chunk, 1) scale
            score = base_ref[0] - coef * dots
            slot = lax.broadcasted_iota(jnp.int32, score.shape, 1)
            ov, oi = _extract_topk(score, slot, (score.shape[0], kbuf), k)
            vals_ref[0] = ov
            idx_ref[0] = oi

        if not with_valid:
            compute()
            return
        i = pl.program_id(0)  # empty chunk: skip (see _make_list_kernel)

        @pl.when(cva_ref[i] != 0)
        def _():
            compute()

        @pl.when(cva_ref[i] == 0)
        def _():
            vals_ref[0] = jnp.full(vals_ref.shape[1:], jnp.inf, jnp.float32)
            idx_ref[0] = jnp.full(idx_ref.shape[1:], _ID_SENTINEL, jnp.int32)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("k", "kbuf", "inner_product", "interpret", "fault_key"),
)
def fused_list_topk_int8(
    lof: jax.Array,      # (ncb,) int32 chunk -> list id (scalar prefetch)
    q8: jax.Array,       # (ncb, chunk, rot) int8 symmetric query rows
    store: jax.Array,    # (n_lists, L, rot) int8 reconstruction store
    base: jax.Array,     # (n_lists, 1, L) f32 additive base, +inf invalid
    q_scale: jax.Array,  # (ncb, chunk, 1) f32 per-row dequant scale
    k: int,
    *,
    kbuf: Optional[int] = None,
    inner_product: bool = False,
    interpret: bool = False,
    fault_key=None,
    chunk_valid: Optional[jax.Array] = None,  # (ncb,) int32 0 = skip
) -> Tuple[jax.Array, jax.Array]:
    """Exact fused int8 scan+select of each chunk's probed list: the
    `fused_list_topk` contract (same outputs, same deterministic
    smaller-slot ties) with the scoring matmul on the int8 MXU path —
    int8 dot, int32 accumulate, per-row f32 dequant on the VPU. Callers
    quantize rows exactly like the pallas trim (`ivf_pq.
    _quantize_query_rows` on scale-folded residuals), so the two
    engines' scores are bit-identical f32 values. `fault_key` =
    faults.trace_key() so chaos plans retrace. `chunk_valid`: the
    empty-chunk skip path (see `fused_list_topk`)."""
    del fault_key  # participates in the jit cache key only
    ncb, chunk, rot = q8.shape
    n_lists, L, _ = store.shape
    if q8.dtype != jnp.int8 or store.dtype != jnp.int8:
        raise ValueError(
            f"fused_list_topk_int8 requires int8 queries and store, got "
            f"{q8.dtype}/{store.dtype}"
        )
    if base.dtype != jnp.float32 or q_scale.dtype != jnp.float32:
        # f32 base/dequant-scale operands: the contract the envelope
        # charges (trace-time only)
        raise ValueError(
            f"fused_list_topk_int8 requires float32 base and q_scale, "
            f"got {base.dtype}/{q_scale.dtype}"
        )
    if L % _LANES:
        raise ValueError(f"list length {L} must be a multiple of {_LANES}")
    kb = fused_kbuf(k) if kbuf is None else int(kbuf)
    if kb < fused_kbuf(k):
        raise ValueError(
            f"candidate buffer width {kb} cannot hold k={k} "
            f"(needs {fused_kbuf(k)})"
        )

    with_valid = chunk_valid is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if with_valid else 1,
        grid=(ncb,),
        in_specs=[
            pl.BlockSpec((1, chunk, rot), lambda i, *s: (i, 0, 0)),
            pl.BlockSpec((1, L, rot), lambda i, *s: (s[0][i], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i, *s: (s[0][i], 0, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, *s: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, kb), lambda i, *s: (i, 0, 0)),
            pl.BlockSpec((1, chunk, kb), lambda i, *s: (i, 0, 0)),
        ),
    )
    scalars = (lof, chunk_valid.astype(jnp.int32)) if with_valid else (lof,)
    vals, idx = pl.pallas_call(
        _make_list_kernel_int8(kb, int(k), bool(inner_product), with_valid),
        out_shape=(
            jax.ShapeDtypeStruct((ncb, chunk, kb), jnp.float32),
            jax.ShapeDtypeStruct((ncb, chunk, kb), jnp.int32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(*scalars, q8, store, base, q_scale)
    return _maybe_corrupt(vals), idx

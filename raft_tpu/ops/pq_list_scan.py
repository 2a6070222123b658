"""Fused PQ list-scan Pallas kernel: score one list chunk + bin-reduce.

Reference parity: the IVF-PQ scoring kernel (`compute_similarity_kernel`,
detail/ivf_pq_search.cuh:611) fuses LUT scoring with an optional in-kernel
warpsort top-k queue so per-candidate scores never leave the SM. The XLA
list-major engine (neighbors/ivf_pq.py `_search_impl_recon8_listmajor`)
must instead materialize each (chunk, max_list) score tile in HBM for
its exact TopK trim — at bench shape that round-trip is ~10x the byte
volume of the code stream it scores. This kernel is the TPU analogue of
the reference's fused queue:

  grid = (n_chunks,); per step, scalar-prefetched chunk->list ids index
  the int8 reconstruction store DIRECTLY (no gather copy of codes), one
  MXU matmul scores the chunk's queries against the whole list, and the
  (chunk, L) scores fold on the VPU into 256 per-lane bins keeping the
  best AND second-best each (the PartialReduce/approx_min_k bin trick,
  or the reference's `warp_sort_filtered` in spirit) — only
  (chunk, 512) candidates reach HBM (~5-10x fewer bytes than the score
  tile at typical L).

Scale handling: the caller folds the int8 store's per-dim scale into the
query residuals, so the kernel consumes raw int8 codes with no dequant
multiply. Invalid/padded slots arrive pre-masked to +inf in the `base`
row operand. The selected candidates are the exact two minima of each
lane-column class; a (chunk, 512) -> top-k pass outside the kernel
(tiny) finishes the per-chunk trim. Only 3+ true top-k members landing
in one bin can drop a candidate, where the XLA trim drops none, and
the engine's exact final merge bounds the effect.

Compiled-path status: validated in interpret mode (CPU tests); first
on-chip Mosaic compile may need block-shape adjustments — the engine
flag (`SearchParams.trim_engine`) defaults to the XLA trim. The known
highest-risk shape property is the non-lane-aligned contracting dim
(rot=96 at bench geometry): if Mosaic rejects it,
RAFT_TPU_PALLAS_ROT_PAD=1 (or tuned `pallas_rot_pad`) zero-pads rot to
128 lanes, bit-identically (tests/test_pallas_ops.py), so the rescue is
one flag, not a rewrite.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BINS = 2 * _LANES  # two interleaved lane banks; also the kernel's k cap
_CANDS = 2 * _BINS  # best + second-best per (lane, bank) -> 512 candidates


def rot_pad_enabled() -> bool:
    """Opt-in lane padding of the contracting (rot) dim — the one-flag
    fallback if the first Mosaic compile rejects a non-128-multiple rot.
    Env wins in BOTH directions (an explicit 0/false overrides a
    committed tuned key, so A/B debugging stays possible); otherwise the
    tuned key decides. Read at trace time (flip + jax.clear_caches() to
    retrace)."""
    import os

    env = os.environ.get("RAFT_TPU_PALLAS_ROT_PAD", "").lower()
    if env in ("1", "true", "yes"):
        return True
    if env in ("0", "false", "no"):
        return False
    from raft_tpu.core import tuned

    return bool(tuned.get("pallas_rot_pad", False))


def _pack_scores(scores, fold_ids):
    """Monotone f32 -> int32 packing: high 16 bits carry the bf16-coarse
    order-preserving image of the score, low 16 the fold id, xor'd so
    SIGNED min == unsigned packed order. Collapses score ties at ~2^-8
    relative precision — the same noise class as the measured-winning
    bf16 trim (internal_distance_dtype hint, 2026-08-01 ladder)."""
    i = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # order-preserving uint32 image (select_counting's sign-flip trick)
    u = jnp.where(i < 0, ~i, i | jnp.int32(-2147483648))
    hi = u & jnp.int32(-65536)  # keep high 16 bits (order coarsened)
    return (hi | fold_ids) ^ jnp.int32(-2147483648)


def fold_variant() -> str:
    """The fold implementation the engines should use: the measured
    tuned key (`pallas_fold`, written by bench/bench_pallas_scan.py
    --apply on chip) when it names a known variant, else "exact". The
    one whitelist shared by every engine call site."""
    from raft_tpu.core import tuned

    v = tuned.get("pallas_fold", "exact")
    return v if v in ("exact", "packed") else "exact"


def _unpack_scores(packed):
    """Inverse of _pack_scores: (f32 LOWER bound of the score's bf16
    band — truncation rounds toward -inf in both sign branches, so the
    decoded value is always <= the true score — and the fold id)."""
    p = packed ^ jnp.int32(-2147483648)
    fold = p & jnp.int32(0xFFFF)
    u = p & jnp.int32(-65536)
    i = jnp.where(u < 0, u & jnp.int32(2147483647), ~u)
    # NB: u<0 in SIGNED int32 means the uint32 high bit is set = the
    # original f32 was non-negative (the flip set it); recover exactly.
    return jax.lax.bitcast_convert_type(i, jnp.float32), fold


def _make_kernel_packed(L: int, inner_product: bool, q_int8: bool = False):
    """Packed-fold variant: ~3 VPU ops per fold (streaming two-min on the
    int32-packed scores) instead of the exact fold's ~11, at bf16-coarse
    trim precision. Same output contract as the exact kernel; candidate
    VALUES are the bf16-band lower bounds (<= the true score), exact
    re-ranking happens in the engine's final merge as before."""
    n_folds = L // _LANES

    def kernel(lof_ref, qres_ref, r8_ref, base_ref, *rest):
        if q_int8:
            rs_ref, vals_ref, idx_ref = rest
        else:
            vals_ref, idx_ref = rest
        q = qres_ref[0]
        base = base_ref[0]
        if q_int8:
            dots = jax.lax.dot_general(
                q,
                r8_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.float32) * rs_ref[0]
        else:
            dots = jax.lax.dot_general(
                q.astype(jnp.bfloat16),
                r8_ref[0].astype(jnp.bfloat16),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        scores = base - dots if inner_product else base - 2.0 * dots

        chunk = scores.shape[0]
        fold_row = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1) // _LANES
        packed = _pack_scores(scores, jnp.broadcast_to(fold_row, scores.shape))
        top = jnp.int32(2147483647)
        banks = []
        for b in range(2):
            m1 = jnp.full((chunk, _LANES), top, jnp.int32)
            m2 = jnp.full((chunk, _LANES), top, jnp.int32)
            for c in range(b, n_folds, 2):
                xcol = packed[:, c * _LANES : (c + 1) * _LANES]
                m2 = jnp.minimum(m2, jnp.maximum(m1, xcol))
                m1 = jnp.minimum(m1, xcol)
            banks.append((m1, m2))
        (a1, a2), (c1, c2) = banks
        allp = jnp.concatenate([a1, c1, a2, c2], axis=1)  # (chunk, _CANDS)
        v, fold = _unpack_scores(allp)
        lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, _CANDS), 1) % _LANES
        idx = fold * _LANES + lane
        # never-filled slots carry fold id 0xFFFF -> out-of-range idx;
        # mask value to +inf so the engine's merge drops them (matches
        # the exact kernel's +inf padding semantics)
        invalid = fold >= n_folds
        vals_ref[0] = jnp.where(invalid, jnp.float32(jnp.inf), v)
        idx_ref[0] = jnp.where(invalid, 0, idx)

    return kernel


def _make_kernel(L: int, inner_product: bool, q_int8: bool = False):
    n_folds = L // _LANES

    def kernel(lof_ref, qres_ref, r8_ref, base_ref, *rest):
        # lof_ref: scalar-prefetch (ncb,) int32 — consumed by index_maps
        if q_int8:
            rs_ref, vals_ref, idx_ref = rest
        else:
            vals_ref, idx_ref = rest
        q = qres_ref[0]  # (chunk, rot): f32 scale-folded, or int8 symmetric
        base = base_ref[0]  # (1, L) f32: rnorm (+inf on invalid slots)
        if q_int8:
            # symmetric int8 x int8 -> int32 at the MXU's doubled int8
            # rate; per-row dequant scale applied on the VPU
            dots = jax.lax.dot_general(
                q,
                r8_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.float32) * rs_ref[0]  # (chunk, L) * (chunk, 1)
        else:
            dots = jax.lax.dot_general(
                q.astype(jnp.bfloat16),
                r8_ref[0].astype(jnp.bfloat16),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (chunk, L)
        if inner_product:
            scores = base - dots  # base=0 valid; minimize -dot
        else:
            scores = base - 2.0 * dots  # + |q-c|^2 const added outside

        chunk = scores.shape[0]
        inf = jnp.float32(jnp.inf)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
        # two interleaved banks, each keeping the best AND second-best per
        # lane: candidates lost to bin collisions need 3+ of a list's true
        # top-k in one (lane, bank) class instead of 2 — the dominant
        # recall-loss term of the trim drops from ~C(k,2)/256 to
        # ~C(k,3)/256^2 for a handful of extra VPU selects per fold.
        banks = []
        for b in range(2):
            bv1 = jnp.full((chunk, _LANES), inf, jnp.float32)
            bi1 = jnp.zeros((chunk, _LANES), jnp.int32)
            bv2 = jnp.full((chunk, _LANES), inf, jnp.float32)
            bi2 = jnp.zeros((chunk, _LANES), jnp.int32)
            for c in range(b, n_folds, 2):
                sc = scores[:, c * _LANES : (c + 1) * _LANES]
                ic = col + c * _LANES
                best = sc < bv1
                second = (~best) & (sc < bv2)
                # demote the old best where a new best arrives
                bv2 = jnp.where(best, bv1, jnp.where(second, sc, bv2))
                bi2 = jnp.where(best, bi1, jnp.where(second, ic, bi2))
                bv1 = jnp.where(best, sc, bv1)
                bi1 = jnp.where(best, ic, bi1)
            banks.append((bv1, bi1, bv2, bi2))
        (a1v, a1i, a2v, a2i), (c1v, c1i, c2v, c2i) = banks
        vals_ref[0] = jnp.concatenate([a1v, c1v, a2v, c2v], axis=1)
        idx_ref[0] = jnp.concatenate([a1i, c1i, a2i, c2i], axis=1)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("inner_product", "interpret", "fold")
)
def pq_list_scan(
    lof: jax.Array,      # (ncb,) int32 chunk -> list id
    qres_s: jax.Array,   # (ncb, chunk, rot) f32 query residuals * scale,
                         #   or int8 symmetric rows when q_scale is given
    recon8: jax.Array,   # (n_lists, L, rot) int8 codes or f32/bf16 raw
                         #   vectors (IVF-Flat), L % 128 == 0
    base: jax.Array,     # (n_lists, 1, L) f32 per-slot additive base
                         #   L2: rnorm, +inf for invalid; IP: 0 / +inf
    inner_product: bool = False,
    interpret: bool = False,
    q_scale: Optional[jax.Array] = None,  # (ncb, chunk, 1) f32 per-row
                         #   dequant scale -> int8 x int8 MXU scoring
    fold: str = "exact",  # "exact" (f32 fold) | "packed" (bf16-coarse,
                         #   ~3x fewer VPU ops/fold; bench_pallas_scan
                         #   races the two on chip)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (vals, idx): (ncb, chunk, 512) best+second-best-per-bin
    scores and the in-list slot of each, minimizing. Callers add per-query
    constants and finish with an exact top-k over the candidates. Works
    for any store the kernel can cast to bf16 — int8 PQ reconstructions
    or raw IVF-Flat vectors. With `q_scale`, `qres_s` must be int8 and
    the store int8: the matmul runs int8 x int8 -> int32 (the MXU's
    doubled int8 rate) with the per-row scale applied in-kernel."""
    ncb, chunk, rot = qres_s.shape
    n_lists, L, _ = recon8.shape
    if L % _LANES or L < _BINS:
        raise ValueError(f"list length {L} must be a multiple of {_LANES} and >= {_BINS}")
    q_int8 = q_scale is not None
    if q_int8 and (qres_s.dtype != jnp.int8 or recon8.dtype != jnp.int8):
        raise ValueError("q_scale requires int8 queries and an int8 store")
    if rot % _LANES and rot_pad_enabled():
        # First-compile rescue (VERDICT r3 #2 risk): the contracting dim
        # (rot) is not lane-aligned at the bench geometry (96). Mosaic is
        # expected to mask the ragged lane tile, but if the first on-chip
        # compile rejects it, RAFT_TPU_PALLAS_ROT_PAD=1 (or tuned key
        # pallas_rot_pad) zero-pads rot to the 128-lane width instead of
        # a kernel rewrite. Zero lanes contribute 0 to every dot, so
        # results are bit-identical; the pad materializes a store copy
        # per call, so if a chip session ends up needing this, move the
        # padding to store-build time before benching.
        pad = _LANES - rot % _LANES
        qres_s = jnp.pad(qres_s, ((0, 0), (0, 0), (0, pad)))
        recon8 = jnp.pad(recon8, ((0, 0), (0, 0), (0, pad)))
        rot += pad

    in_specs = [
        pl.BlockSpec((1, chunk, rot), lambda i, lof: (i, 0, 0)),
        pl.BlockSpec((1, L, rot), lambda i, lof: (lof[i], 0, 0)),
        pl.BlockSpec((1, 1, L), lambda i, lof: (lof[i], 0, 0)),
    ]
    operands = [lof, qres_s, recon8, base]
    if q_int8:
        in_specs.append(pl.BlockSpec((1, chunk, 1), lambda i, lof: (i, 0, 0)))
        operands.append(q_scale)
    if fold not in ("exact", "packed"):
        raise ValueError(f"unknown fold variant {fold!r}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ncb,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, chunk, _CANDS), lambda i, lof: (i, 0, 0)),
            pl.BlockSpec((1, chunk, _CANDS), lambda i, lof: (i, 0, 0)),
        ),
    )
    make = _make_kernel_packed if fold == "packed" else _make_kernel
    return pl.pallas_call(
        make(L, inner_product, q_int8),
        out_shape=(
            jax.ShapeDtypeStruct((ncb, chunk, _CANDS), jnp.float32),
            jax.ShapeDtypeStruct((ncb, chunk, _CANDS), jnp.int32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(*operands)


def lane_padded(width: int) -> int:
    """The slot-axis width the kernel's lane contract requires: a multiple
    of the 128-lane register width, with at least _BINS slots (so the two
    candidate banks fill). Shared by every caller that pads a store."""
    return max(_BINS, -(-width // _LANES) * _LANES)


def fits_pallas(chunk: int, L: int, rot: int, store_itemsize: int = 1) -> bool:
    """VMEM envelope for one grid step (f32 scores dominate).
    `store_itemsize` is the per-element width of the scanned store (1 for
    int8 PQ reconstructions, 2 for IVF-Flat's bf16 residual store).
    Sized against the rot the kernel will ACTUALLY run with: when the
    rot-pad rescue is on, the padded width counts, so dispatch can't
    admit a geometry the padded kernel then OOMs. (The fused family's
    envelopes in ops/fused_scan.py are machine-checked against their
    kernels by raftlint's kernelcheck; this legacy trim's envelope is
    not registered — the rot-pad rescue makes its block geometry
    runtime-conditional.)"""
    if rot % _LANES and rot_pad_enabled():
        rot = -(-rot // _LANES) * _LANES
    step_bytes = (
        4 * chunk * L + store_itemsize * L * rot + 4 * chunk * rot + 8 * chunk * _CANDS
    )
    return L % _LANES == 0 and L >= _BINS and step_bytes <= 10 * 1024 * 1024

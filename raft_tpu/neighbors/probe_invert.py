"""Probe-pair inversion for list-major IVF search engines.

Query-major IVF search (the reference's layout: one CUDA block per (query,
probe) — ivf_pq_search.cuh:611, ivf_flat_search.cuh:670) gathers each
probed list's storage once per query, so a batch re-reads every list
~nq*n_probes/n_lists times from HBM. The list-major engines instead invert
the (query, list) probe pairs into per-list buckets and stream each list
once. This module holds the shared inversion: sort pairs by list, split
each list's bucket into chunks of `chunk` pairs ("virtual lists", so
hot-list skew costs padding only inside one chunk), and emit
  - per-chunk tables (which list, which queries) for the scoring loop, and
  - a per-pair (chunk, slot) address for regrouping candidates back to
    query-major order with a pure gather.

Everything is sorts + searchsorted + gathers — no XLA scatters (TPU lowers
scatter to a serialized per-index loop) — and every shape is static: the
chunk budget uses the bound sum(ceil(c_i/chunk)) <= P//chunk + n_lists, so
equal-shaped batches never recompile and no host sync is needed.

The bound's n_lists term is one chunk of padding per list, so a chunk
much wider than the batch's duplication (P / n_lists pairs a list) pads
far more rows than it fills. `list_chunk` sizes the chunk to that
duplication from the batch's static shapes, so IVF-PQ's XLA trim works
on chunk tables of at most 2P rows where dup >= 32.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class ChunkTables(NamedTuple):
    """Static-shape chunk tables for one query batch.

    lof      (ncb,)        list id scored by each chunk
    qid_tbl  (ncb, chunk)  query ids in each chunk; `nq` marks padding
                           (callers append a zero sentinel query row)
    g0       (nq*n_probes,) chunk id holding each original probe pair
    s0       (nq*n_probes,) slot of that pair within its chunk
    pair_valid (nq*n_probes,) bool, or None — adaptive probe budgets
                           (neighbors/probe_budget): False pairs were
                           dropped before inversion (they occupy no
                           chunk slot; their g0/s0 are clamped to 0 and
                           `regroup_merge` masks their candidates to
                           the worst value / row -1). None = every
                           pair live (the fixed-n_probes reference).
    """

    lof: jax.Array
    qid_tbl: jax.Array
    g0: jax.Array
    s0: jax.Array
    pair_valid: Optional[jax.Array] = None


def chunk_count(nq: int, n_probes: int, n_lists: int, chunk: int) -> int:
    """Static upper bound on the number of chunks for a batch."""
    return (nq * n_probes) // chunk + n_lists


def list_chunk(nq: int, n_probes: int, n_lists: int) -> int:
    """Chunk width from a batch's static shapes: the largest power of two
    at most its duplication dup = nq * n_probes / n_lists, clamped to
    [32, 128]. The chunk tables then hold P + chunk * n_lists <= 2P rows
    (P = nq * n_probes pairs) where dup >= 32, against P + 128 * n_lists
    at a fixed 128. Below 32 the TPU compiler gives the trim's `TopK`
    the slots-on-lanes layout, about 3x slower a row on a v5e: there a
    2,048-query slice at 8 probes over 1,024 lists (dup 16) runs its
    search program in 37 ms at chunk 32 and 90 ms at 16."""
    dup = (nq * n_probes) // max(1, n_lists)
    return min(128, max(32, 1 << max(0, dup.bit_length() - 1)))


#: elements of one superblock's score buffer (sb * chunk * max_list), at
#: most, unless one chunk alone holds more
SUPERBLOCK_ELEMS = 1 << 27


def superblocks(ncb: int, chunk: int, max_list: int) -> tuple:
    """(nsuper, sb): `score_and_select` splits `ncb` chunks into the
    fewest superblocks within SUPERBLOCK_ELEMS, nsuper equal ones of sb
    chunks, so the split pads at most nsuper - 1 chunks."""
    sb_max = max(1, SUPERBLOCK_ELEMS // max(1, chunk * max_list))
    nsuper = -(-ncb // sb_max)
    return nsuper, -(-ncb // nsuper)


def trim_rows(nq: int, n_probes: int, n_lists: int, max_list: int,
              chunk: int) -> int:
    """(query, list) rows the trim selects over for an nq-query batch,
    superblock padding included, from shapes alone."""
    nsuper, sb = superblocks(chunk_count(nq, n_probes, n_lists, chunk),
                             chunk, max_list)
    return nsuper * sb * chunk


def invert_probes(probes: jax.Array, n_lists: int, chunk: int,
                  pvalid: Optional[jax.Array] = None) -> ChunkTables:
    """Build chunk tables from a (nq, n_probes) probe matrix (traced):
    two stable argsorts over the P=nq*n_probes pair array (the second
    computes the inverse permutation for the regroup addresses).

    `pvalid` (nq, n_probes) bool, optional: adaptive probe budgets —
    False pairs move to the sentinel bucket `n_lists`: they sort past
    every real list, count toward no chunk (so shrunken budgets shrink
    the populated chunk count and the fused kernels' `chunk_valid` path
    can skip the empties), and their regroup addresses clamp to (0, 0)
    behind the tables' `pair_valid` mask."""
    nq, n_probes = probes.shape
    p_total = nq * n_probes
    flat = probes.reshape(-1).astype(jnp.int32)
    pv = None
    if pvalid is not None:
        pv = pvalid.reshape(-1)
        flat = jnp.where(pv, flat, jnp.int32(n_lists))
    order = jnp.argsort(flat, stable=True)
    sorted_lists = flat[order]
    sorted_q = (order // n_probes).astype(jnp.int32)
    lids = jnp.arange(n_lists, dtype=jnp.int32)
    starts = jnp.searchsorted(sorted_lists, lids, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(sorted_lists, lids, side="right").astype(jnp.int32)
    counts = ends - starts
    cpl = (counts + chunk - 1) // chunk  # chunks per list
    cb = jnp.cumsum(cpl)  # inclusive
    base = (cb - cpl).astype(jnp.int32)  # first chunk id of each list

    g = jnp.arange(chunk_count(nq, n_probes, n_lists, chunk), dtype=jnp.int32)
    lof = jnp.minimum(jnp.searchsorted(cb, g, side="right"), n_lists - 1).astype(
        jnp.int32
    )
    cl = g - base[lof]  # chunk index within its list
    pos = cl[:, None] * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    valid = pos < counts[lof][:, None]
    pair = jnp.clip(starts[lof][:, None] + pos, 0, p_total - 1)
    qid_tbl = jnp.where(valid, sorted_q[pair], nq)

    inv = jnp.argsort(order).astype(jnp.int32)  # original pair -> sorted position
    pos0 = inv - starts[jnp.minimum(flat, n_lists - 1)]
    g0 = base[jnp.minimum(flat, n_lists - 1)] + pos0 // chunk
    s0 = pos0 % chunk
    if pv is not None:
        g0 = jnp.where(pv, g0, 0)
        s0 = jnp.where(pv, s0, 0)
    return ChunkTables(lof, qid_tbl, g0, s0, pv)


def score_and_select(
    tables: ChunkTables,
    block_fn,
    slot_rows: jax.Array,
    select_k_fn,
    nq: int,
    n_probes: int,
    k: int,
    select_min: bool,
    chunk: int,
    max_list: int,
    *,
    stage: str,
):
    """Shared back half of a list-major search (traced inside the engine's
    jit): superblock scoring, per-superblock exact trim to
    in-list slots, gather-based regroup to query-major, exact final
    merge, and the row-id lookup of the merged top-k — the stage
    scopes `<stage>.scan`, `<stage>.trim` and `<stage>.merge` in the
    program's op metadata (an op takes the innermost scope on its path).

    `block_fn((lof_block, qid_block)) -> (sb, chunk, max_list)` computes the
    engine-specific candidate scores (IVF-Flat: raw-vector distances;
    IVF-PQ: int8-reconstruction distances) with invalid slots already
    masked to the worst value. `select_k_fn(scores, k, select_min)` is the
    exact 2-D top-k (the select-k dispatch) used for the trim and the
    final merge.

    `chunk` is the engine's chunk width (`list_chunk` for IVF-PQ's XLA
    trim, 128 for the other engines). The `ncb` chunks split evenly into
    the fewest superblocks whose score buffer stays within 2^27 elements
    regardless of max_list skew (`superblocks`), so the split pads at
    most nsuper - 1 chunks. Each superblock's
    (chunk, max_list) rows are trimmed to their exact top-kk, ties to the
    lower slot, through `select_k_fn` on a rank-2 (sb * chunk, max_list)
    view: on the TPU that lowers to the partial-select `TopK` custom call,
    where a rank-3 top-k (and `approx_min_k` whenever its reduction size
    exceeds the row) lowers to a full sort of every row. The superblocks
    are unrolled into the program rather than looped over, which keeps
    each TopK in its fast layout; the score buffers are still bounded,
    since XLA schedules the independent superblocks one after another,
    and the engines' query macro-batches keep their count small. Each
    superblock is scored by one batched `block_fn` call (one einsum).
    """
    lof, qid_tbl = tables.lof, tables.qid_tbl
    ncb = lof.shape[0]
    kk = min(k, max_list)

    nsuper, sb = superblocks(ncb, chunk, max_list)
    bpad = nsuper * sb - ncb

    def super_block(lofs, qids):  # (sb,), (sb, chunk)
        scores = block_fn((lofs, qids))
        with jax.named_scope(f"{stage}.trim"):
            # rank 2: the TPU lowers a rank-3 top-k to a full sort
            v, si = select_k_fn(scores.reshape(sb * chunk, max_list), kk,
                                select_min)
            return v.reshape(sb, chunk, kk), si.reshape(sb, chunk, kk)

    with jax.named_scope(f"{stage}.scan"):
        lof_b = (jnp.pad(lof, (0, bpad)) if bpad else lof).reshape(nsuper, sb)
        qid_b = (
            jnp.pad(qid_tbl, ((0, bpad), (0, 0)), constant_values=nq)
            if bpad else qid_tbl
        ).reshape(nsuper, sb, chunk)
        # unrolled, not lax.map: inside a loop body the TPU's TopK takes
        # the row-major layout (slots on lanes), about 4x slower on the
        # chip than the rows-on-lanes layout it gets outside one
        outs = [super_block(lof_b[i], qid_b[i]) for i in range(nsuper)]
    with jax.named_scope(f"{stage}.merge"):
        vals = jnp.concatenate([v for v, _ in outs])[:ncb]
        slots = jnp.concatenate([s for _, s in outs])[:ncb]
        return regroup_merge(tables, vals, slots, slot_rows, select_k_fn, nq,
                             n_probes, k, select_min)


def regroup_merge(
    tables: ChunkTables,
    vals: jax.Array,   # (ncb, chunk, kk) per-chunk trimmed candidate scores
    slots: jax.Array,  # (ncb, chunk, kk) their in-list slots (-1 invalid)
    slot_rows: jax.Array,  # (n_lists, max_list) slot -> row id (-1 empty)
    select_k_fn,
    nq: int,
    n_probes: int,
    k: int,
    select_min: bool,
):
    """Regroup per-chunk candidates to query-major (pure gather through
    the (g0, s0) pair addresses — no scatter), merge exactly, then turn
    only the nq x k winners' (list, slot) into row ids: the trim keeps
    kk slots for every chunk row, padding included, so looking ids up
    before the merge costs ncb*chunk*kk one-element gathers for the
    nq*k the merge keeps. A winner's list is its probe pair's chunk's
    list, found from its merge position. Budget-masked pairs
    (tables.pair_valid False) contribute the worst value / row -1,
    exactly like a sub-k prefilter tail."""
    g0, s0 = tables.g0, tables.s0
    kk = vals.shape[-1]
    cand_v = vals[g0, s0]
    cand_s = slots[g0, s0]
    if tables.pair_valid is not None:
        worst = jnp.asarray(
            jnp.inf if select_min else -jnp.inf, cand_v.dtype)
        m = tables.pair_valid[:, None]
        cand_v = jnp.where(m, cand_v, worst)
        cand_s = jnp.where(m, cand_s, -1)
    cand_v = cand_v.reshape(nq, n_probes * kk)
    cand_s = cand_s.reshape(nq, n_probes * kk)
    v, pos2 = select_k_fn(cand_v, k, select_min)
    slot = jnp.take_along_axis(cand_s, pos2, axis=1)
    lists = jnp.take_along_axis(tables.lof[g0].reshape(nq, n_probes),
                                pos2 // kk, axis=1)
    ids = slot_rows[lists, jnp.maximum(slot, 0)]
    return v, jnp.where(slot >= 0, ids, -1)


def chunk_validity(qid_tbl: jax.Array, nq: int) -> jax.Array:
    """(ncb,) int32 flag per chunk: 1 when the chunk holds at least one
    live pair, 0 when every slot is padding (`nq`). The fused list
    kernels take it as a scalar-prefetch operand and skip the MXU/VPU
    work of empty chunks — the trailing fragmentation chunks of any
    batch, and every chunk adaptive budgets empty out."""
    return jnp.any(qid_tbl != nq, axis=1).astype(jnp.int32)


def macro_batched(search_slice_fn, queries: jax.Array, k: int, mb: int = 4096,
                  extra: Optional[jax.Array] = None):
    """Run a list-major search over macro-batches of queries, bounding the
    chunk tables and score buffers per call.

    Every slice is padded up a power-of-two ladder (256, 512, ..., mb), so
    arbitrary batch sizes compile at most ~5 shapes per index (a varying-
    batch serving workload never retraces), and a 4097-query batch pays one
    4096-batch plus one 256-batch of work — not two full batches.
    `search_slice_fn(padded_slice)` must return (vals, rows) for the padded
    slice.

    `extra`: optional (nq, ...) per-query side array (the adaptive probe
    keep mask) sliced and padded in LOCKSTEP with the queries — pad rows
    get all-False, so padding scans nothing — and passed as the slice
    fn's second argument."""
    nq_total = queries.shape[0]
    if nq_total == 0:
        return (
            jnp.zeros((0, k), jnp.float32),
            jnp.full((0, k), -1, jnp.int32),
        )
    outs = []
    for s in range(0, nq_total, mb):
        sl = queries[s : s + mb]
        ex = extra[s : s + mb] if extra is not None else None
        target = _ladder(sl.shape[0], mb)
        pad = target - sl.shape[0]
        if pad:
            sl = jnp.pad(sl, ((0, pad), (0, 0)))
            if ex is not None:
                ex = jnp.pad(ex, ((0, pad), (0, 0)),
                             constant_values=False)
        v, r = (search_slice_fn(sl) if extra is None
                else search_slice_fn(sl, ex))
        outs.append((v[: target - pad], r[: target - pad]))
    if len(outs) == 1:
        return outs[0]
    return (
        jnp.concatenate([v for v, _ in outs]),
        jnp.concatenate([r for _, r in outs]),
    )


def macro_batch_slices(nq: int, mb: int = 4096) -> list:
    """The padded slice sizes `macro_batched` runs for an nq-row call,
    each slice padded up its ladder. From shapes alone, no device work."""
    return [_ladder(min(mb, nq - s), mb) for s in range(0, nq, mb)]


def _ladder(n: int, cap: int) -> int:
    t = 256
    while t < n and t < cap:
        t *= 2
    return min(t, cap)

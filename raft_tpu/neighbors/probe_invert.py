"""Probe-pair inversion for list-major IVF search engines.

Query-major IVF search (the reference's layout: one CUDA block per (query,
probe) — ivf_pq_search.cuh:611, ivf_flat_search.cuh:670) gathers each
probed list's storage once per query, so a batch re-reads every list
~nq*n_probes/n_lists times from HBM. The list-major engines instead invert
the (query, list) probe pairs into per-list buckets and stream each list
once. This module holds the shared inversion: sort pairs by list, split
each list's bucket into fixed-size chunks of `chunk` pairs ("virtual
lists", so hot-list skew costs padding only inside one chunk), and emit
  - per-chunk tables (which list, which queries) for the scoring loop, and
  - a per-pair (chunk, slot) address for regrouping candidates back to
    query-major order with a pure gather.

Everything is sorts + searchsorted + gathers — no XLA scatters (TPU lowers
scatter to a serialized per-index loop) — and every shape is static: the
chunk budget uses the bound sum(ceil(c_i/chunk)) <= P//chunk + n_lists, so
equal-shaped batches never recompile and no host sync is needed.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


# listmajor_chunk_block tuned values every list-major engine honors
# (0 = single-einsum superblocks; positive = inner lax.map granularity)
CHUNK_BLOCKS = (0, 8, 16, 32, 64)


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


def invert_probes(probes: jax.Array, n_lists: int, chunk: int,
                  pvalid: Optional[jax.Array] = None) -> ChunkTables:
    """Build chunk tables from a (nq, n_probes) probe matrix (traced).

    Dispatches between the sort-based (`invert_probes_sort`) and
    counting-based (`invert_probes_count`) constructions via the
    `invert_impl` tuned key; both produce bit-identical tables (raced and
    equality-checked by `bench/bench_invert_race.py`). Engines should
    prefer resolving the impl OUTSIDE their jit via
    `resolve_setup_impls` and calling the chosen construction directly,
    so a tuned flip retraces instead of serving the stale program.

    `pvalid` (nq, n_probes) bool, optional: adaptive probe budgets —
    False pairs are dropped from the tables entirely (they enter the
    sentinel bucket `n_lists`, which owns no chunks), so shrunken
    budgets shrink the populated chunk count and the fused kernels'
    `chunk_valid` path can skip the empties."""
    if resolve_invert_impl(n_lists) == "count":
        return invert_probes_count(probes, n_lists, chunk, pvalid)
    return invert_probes_sort(probes, n_lists, chunk, pvalid)


INVERT_IMPLS = ("sort", "count")

# the counting construction's blocked one-hot planes cost O(P * n_lists)
# compare/cumsum work and its block floor stops bounding memory past this
# many lists — above it the sort-based construction wins regardless of
# what the (1024-list) chip race measured, so the tuned choice is gated
_COUNT_MAX_LISTS = 8192


def resolve_invert_impl(n_lists: int = 0) -> str:
    """The tuned chunk-table construction for list-major engines."""
    from raft_tpu.core import tuned

    impl = tuned.get_choice("invert_impl", INVERT_IMPLS, "sort")
    if impl == "count" and n_lists > _COUNT_MAX_LISTS:
        return "sort"
    return impl


def resolve_setup_impls(n_lists: int, engine: str = "pq") -> tuple:
    """(invert_impl, qs_impl) for a list-major search, resolved at the
    call site OUTSIDE the engine's jit so they participate in the jit
    cache key — a tuned flip mid-process (bench --apply + reload) must
    retrace the engine, not keep serving the stale wrapper (the same
    hazard the distributed wrapper cache keys guard against). `engine`
    ("pq" | "flat") keys the qs-impl resolution: see `resolve_qs_impl`
    for the flat-engine bf16 gate."""
    return resolve_invert_impl(n_lists), resolve_qs_impl(engine)


def _chunk_geometry(counts, nq: int, n_probes: int, n_lists: int, chunk: int):
    """Chunk-table geometry shared by both constructions: per-list chunk
    spans and the per-chunk (list, in-list position, validity) tables,
    derived purely from per-list pair counts. Returns
    (base, lof, cl, pos, valid) — both impls MUST share this (the
    `invert_impl` tuned key's bit-identity contract rides on it)."""
    cpl = (counts + chunk - 1) // chunk  # chunks per list
    cb = jnp.cumsum(cpl)  # inclusive
    base = (cb - cpl).astype(jnp.int32)  # first chunk id of each list

    ncb = chunk_count(nq, n_probes, n_lists, chunk)
    g = jnp.arange(ncb, dtype=jnp.int32)
    lof = jnp.minimum(jnp.searchsorted(cb, g, side="right"), n_lists - 1).astype(
        jnp.int32
    )
    cl = g - base[lof]  # chunk index within its list
    pos = cl[:, None] * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    valid = pos < counts[lof][:, None]
    return base, lof, cl, pos, valid


def invert_probes_sort(probes: jax.Array, n_lists: int, chunk: int,
                       pvalid: Optional[jax.Array] = None) -> ChunkTables:
    """Sort-based construction: two stable argsorts over the P=nq*n_probes
    pair array (the second computes the inverse permutation for the
    regroup addresses). Budget-masked pairs (`pvalid` False) move to the
    sentinel bucket `n_lists` — they sort past every real list, count
    toward no chunk, and their regroup addresses clamp to (0, 0) behind
    the tables' `pair_valid` mask."""
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
    base, lof, _, pos, valid = _chunk_geometry(counts, nq, n_probes, n_lists, chunk)
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


def _blocked_bucket_ranks(flat: jax.Array, n_lists: int) -> tuple:
    """Stable per-pair rank within its list bucket + per-list counts,
    without sorting: a lax.scan over fixed-size blocks builds each
    block's one-hot list membership, cumsums it down the block for
    in-block stable ranks, and carries per-list totals across blocks.
    All work is compares/cumsums/reduces on (block, n_lists+1) planes —
    VPU-shaped, no XLA sort or scatter. Returns (rank[P], counts)."""
    (p_total,) = flat.shape
    # bound the per-iteration plane to ~64MB of int32
    block = min(8192, max(256, (1 << 24) // (n_lists + 1)))
    nb = -(-p_total // block)
    pad = nb * block - p_total
    fpad = jnp.pad(flat, (0, pad), constant_values=n_lists) if pad else flat
    cols = jnp.arange(n_lists + 1, dtype=jnp.int32)

    def step(carry, l):
        oh = l[:, None] == cols[None, :]
        cs = jnp.cumsum(oh.astype(jnp.int32), axis=0)
        rank = jnp.sum(jnp.where(oh, cs - 1 + carry[None, :], 0), axis=1)
        return carry + cs[-1], rank

    carry0 = jnp.zeros(n_lists + 1, jnp.int32)
    totals, ranks = jax.lax.scan(step, carry0, fpad.reshape(nb, block))
    return ranks.reshape(-1)[:p_total], totals[:n_lists]


def invert_probes_count(probes: jax.Array, n_lists: int, chunk: int,
                        pvalid: Optional[jax.Array] = None) -> ChunkTables:
    """Counting-based construction (TPU-native): ONE variadic stable sort
    replaces the sort-heavy parts of `invert_probes_sort` (which pays two
    chained argsorts plus two searchsorted passes over the P-sized array),
    and the inverse-permutation addresses come from a blocked one-hot
    cumsum instead of a second sort.

      - per-pair in-bucket ranks + per-list counts: `_blocked_bucket_ranks`
        (no sort) — this alone replaces argsort(order) and both
        P-sized searchsorted calls (starts = exclusive-cumsum of counts);
      - g0/s0: base[flat] + rank arithmetic (pure elementwise + one
        small-table gather);
      - qid_tbl: one stable `lax.sort((flat, qid))` for the list-grouped
        query ids, then per-chunk CONTIGUOUS rows via vmapped
        dynamic_slice (each chunk's pairs are adjacent in sorted order —
        a windowed load, not a 262k-element random gather).

    Bit-identical to `invert_probes_sort` (stability makes ranks equal to
    inv - starts[flat]); raced + equality-gated on chip by
    `bench/bench_invert_race.py --apply`, which flips the `invert_impl`
    tuned key. Budget-masked pairs (`pvalid` False) land in the sentinel
    bucket `n_lists` — the blocked rank pass already treats it as its
    padding column, so counts/chunks shrink exactly like the sort
    construction's."""
    nq, n_probes = probes.shape
    p_total = nq * n_probes
    flat = probes.reshape(-1).astype(jnp.int32)
    pv = None
    if pvalid is not None:
        pv = pvalid.reshape(-1)
        flat = jnp.where(pv, flat, jnp.int32(n_lists))

    rank, counts = _blocked_bucket_ranks(flat, n_lists)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    base, lof, cl, _, valid = _chunk_geometry(counts, nq, n_probes, n_lists, chunk)

    # list-grouped query ids: one stable variadic sort (same permutation
    # as invert_probes_sort's stable argsort, so payload order matches)
    qid = (jnp.arange(p_total, dtype=jnp.int32) // n_probes).astype(jnp.int32)
    _, sorted_q = jax.lax.sort((flat, qid), num_keys=1)
    # each chunk reads a contiguous window [starts[lof]+cl*chunk, +chunk);
    # pad by one chunk of sentinels so trailing empty chunks stay in range
    sq_pad = jnp.concatenate(
        [sorted_q, jnp.full((chunk,), nq, jnp.int32)]
    )
    off = jnp.clip(starts[lof] + cl * chunk, 0, p_total)
    rows = jax.vmap(
        lambda o: jax.lax.dynamic_slice(sq_pad, (o,), (chunk,))
    )(off)
    qid_tbl = jnp.where(valid, rows, nq)

    g0 = base[jnp.minimum(flat, n_lists - 1)] + rank // chunk
    s0 = rank % chunk
    if pv is not None:
        g0 = jnp.where(pv, g0, 0)
        s0 = jnp.where(pv, s0, 0)
    return ChunkTables(lof, qid_tbl, g0, s0, pv)


# listmajor_qs_impl tuned values (query-row materialization inside the
# scoring blocks): "gather" = XLA fancy-index; "onehot_bf16" = one-hot
# matmul in bf16 (MXU-shaped; rows bf16-rounded — acceptable for the PQ
# engines, whose int8-reconstruction scoring already quantizes harder
# than bf16 rounding, but NOT precision-neutral for the IVF-Flat
# list-major engine, which scores qs at f32 Precision.HIGHEST —
# distance/pairwise.py — so a shared bf16 winner is gated off the flat
# engines in `resolve_qs_impl`); "onehot_f32h" = one-hot matmul at
# precision=highest (bit-exact vs the gather, ~6x the MXU passes). The
# first on-chip diag measured the isolated gather at ~1 GB/s (106.7 ms
# for a ~100 MB stream at bench shape) — the one-hot forms trade that
# for ~0.2 TFLOP of MXU work. Raced by bench/bench_invert_race.py.
QS_IMPLS = ("gather", "onehot_bf16", "onehot_f32h")


def gather_query_rows(q_pad: jax.Array, qids: jax.Array, impl: str) -> jax.Array:
    """Materialize (..., chunk, dim) query rows from a (..., chunk) id
    table over the sentinel-padded (nq+1, dim) query matrix.

    The one-hot impls bound their materialized (rows, nq+1) plane to
    ~32 MB by looping sub-blocks of leading rows through `lax.map` —
    the SAME formulation at any caller granularity, so a chip race of
    this function measures exactly what the engines execute."""
    if impl == "gather":
        return q_pad[qids]
    if impl == "onehot_bf16":
        dt, prec = jnp.bfloat16, jax.lax.Precision.DEFAULT
    elif impl == "onehot_f32h":
        dt, prec = jnp.float32, jax.lax.Precision.HIGHEST
    else:
        raise ValueError(f"unknown query-row impl {impl!r}")
    nq1 = q_pad.shape[0]
    qp = q_pad.astype(dt)

    def onehot_rows(ids):
        oh = (ids[..., None] == jnp.arange(nq1, dtype=jnp.int32)).astype(dt)
        return jnp.einsum(
            "...cn,nd->...cd", oh, qp, precision=prec,
            preferred_element_type=jnp.float32,
        )

    lead = qids.shape[:-1]
    chunk = qids.shape[-1]
    rows_total = 1
    for s in lead:
        rows_total *= s
    # sub-block size bounding the one-hot plane to ~32 MB
    qb = max(1, (1 << 25) // max(1, chunk * nq1 * jnp.dtype(dt).itemsize))
    if not lead or rows_total <= qb:
        return onehot_rows(qids).astype(q_pad.dtype)
    flat_ids = qids.reshape(rows_total, chunk)
    bpad = (-rows_total) % qb
    if bpad:
        flat_ids = jnp.pad(flat_ids, ((0, bpad), (0, 0)))
    out = jax.lax.map(onehot_rows, flat_ids.reshape(-1, qb, chunk))
    out = out.reshape(-1, chunk, q_pad.shape[1])[:rows_total]
    return out.reshape(*lead, chunk, q_pad.shape[1]).astype(q_pad.dtype)


def resolve_qs_impl(engine: str = "pq") -> str:
    """The tuned query-row materialization for list-major engines.

    The shared `listmajor_qs_impl` key was raced on the PQ engine, where
    bf16-rounded query rows are lossless relative to the int8 scoring
    that follows. The IVF-Flat list-major engine scores at f32
    Precision.HIGHEST, so a shared "onehot_bf16" winner would silently
    degrade flat-engine precision — for engine="flat" it is gated back
    to "gather" unless the flat-specific key `listmajor_qs_impl_flat`
    (written by a flat-engine race) explicitly opts in."""
    from raft_tpu.core import tuned

    if engine == "flat":
        own = tuned.get_choice("listmajor_qs_impl_flat", QS_IMPLS, None)
        if own is not None:
            return own
        shared = tuned.get_choice("listmajor_qs_impl", QS_IMPLS, "gather")
        return "gather" if shared == "onehot_bf16" else shared
    return tuned.get_choice("listmajor_qs_impl", QS_IMPLS, "gather")


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
    chunk_block: int,
    max_list: int,
    *,
    stage: str,
):
    """Shared back half of a list-major search (traced inside the engine's
    jit): two-level blocked scoring, per-superblock exact trim to
    in-list slots, gather-based regroup to query-major, exact final
    merge, and the row-id lookup of the merged top-k — the stage
    scopes `<stage>.scan`, `<stage>.trim` and `<stage>.merge` in the
    program's op metadata (an op takes the innermost scope on its path).

    `block_fn(lof_block, qid_block) -> (CB, chunk, max_list)` computes the
    engine-specific candidate scores (IVF-Flat: raw-vector distances;
    IVF-PQ: int8-reconstruction distances) with invalid slots already
    masked to the worst value. `select_k_fn(scores, k, select_min)` is the
    exact 2-D top-k (the select-k dispatch) used for the trim and the
    final merge.

    Superblocks of `sb` chunks bound the materialized score buffer to
    ~2^27 elements regardless of max_list skew. Each superblock's
    (chunk, max_list) rows are trimmed to their exact top-kk, ties to the
    lower slot, through `select_k_fn` on a rank-2 (sb * chunk, max_list)
    view: on the TPU that lowers to the partial-select `TopK` custom call,
    where a rank-3 top-k (and `approx_min_k` whenever its reduction size
    exceeds the row) lowers to a full sort of every row. The superblocks
    are unrolled into the program rather than looped over, which keeps
    each TopK in its fast layout; the score buffers are still bounded,
    since XLA schedules the independent superblocks one after another,
    and the engines' query macro-batches keep their count small.

    `chunk_block` controls the scoring granularity WITHIN a superblock:
    0 (the default dispatch) scores the whole superblock with one
    batched `block_fn` call — one large einsum, ~nsuper scan iterations
    per batch. A positive value runs an inner `lax.map` over blocks of
    that many chunks; at bench shape (ncb≈2048, chunk_block=8) that is
    ~256 serialized scan iterations whose per-iteration overhead, not
    FLOPs or bytes, dominated the round-4 measured 570 ms/batch (~60×
    off the HBM roofline, docs/perf.md). Kept raceable via the
    `listmajor_chunk_block` tuned key so the chip profiler can flip it
    with data.
    """
    from jax import lax

    lof, qid_tbl = tables.lof, tables.qid_tbl
    ncb = lof.shape[0]
    kk = min(k, max_list)

    budget = 1 << 27
    step = chunk_block if chunk_block else 1
    sb = max(step, budget // max(1, chunk * max_list))
    sb = min(-(-sb // step) * step, -(-ncb // step) * step)
    nsuper = -(-ncb // sb)
    bpad = nsuper * sb - ncb

    def super_block(inp):
        lofs, qids = inp  # (sb,), (sb, chunk)
        if chunk_block:
            nb_in = sb // chunk_block
            scores = lax.map(
                block_fn,
                (
                    lofs.reshape(nb_in, chunk_block),
                    qids.reshape(nb_in, chunk_block, chunk),
                ),
            )
            scores = scores.reshape(sb, chunk, max_list)
        else:
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
        outs = [super_block((lof_b[i], qid_b[i])) for i in range(nsuper)]
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


def macro_batch_rows(nq: int, mb: int = 4096) -> int:
    """Query rows `macro_batched` runs for an nq-row call: every slice
    padded up its ladder. From shapes alone, no device work."""
    return sum(_ladder(min(mb, nq - s), mb) for s in range(0, nq, mb))


def _ladder(n: int, cap: int) -> int:
    t = 256
    while t < n and t < cap:
        t *= 2
    return min(t, cap)

"""Chunk-table inversion invariants (raft_tpu.neighbors.probe_invert).

The list-major engines rest on invert_probes' static-shape chunk tables;
these tests pin its invariants directly (the engine-level overlap tests in
test_ivf_pq/test_ivf_flat check end-to-end agreement, but a silent
slot-addressing bug can hide behind top-k ties there). Skewed probe
distributions exercise multi-chunk ("virtual list") splitting.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from raft_tpu.matrix.select_k import _select_k_impl
from raft_tpu.neighbors.probe_invert import (
    chunk_count,
    invert_probes,
    list_chunk,
    macro_batch_slices,
    macro_batched,
    regroup_merge,
    superblocks,
    trim_rows,
)


@pytest.mark.parametrize(
    "nq,n_probes,n_lists,chunk,skew",
    [
        (64, 8, 16, 16, False),
        (128, 4, 8, 32, True),   # hot lists get several chunks
        (33, 7, 64, 8, True),    # non-divisible everything
        (16, 3, 4, 64, False),   # chunk larger than any bucket
        (512, 8, 64, None, True),   # the rule's chunk: dup 64
        (40, 3, 64, None, False),   # the rule's floor of 32 at dup 1
    ],
)
def test_invert_probes_invariants(nq, n_probes, n_lists, chunk, skew, rng):
    if chunk is None:
        chunk = list_chunk(nq, n_probes, n_lists)
    if skew:
        # zipf-ish skew: low-id lists drawn far more often
        raw = rng.zipf(1.5, size=(nq, n_probes)) % n_lists
    else:
        raw = rng.integers(0, n_lists, size=(nq, n_probes))
    probes = jnp.asarray(raw.astype(np.int32))
    t = invert_probes(probes, n_lists, chunk)
    assert t.pair_valid is None  # fixed path: every pair live
    lof, qid_tbl, g0, s0 = (np.asarray(t.lof), np.asarray(t.qid_tbl),
                            np.asarray(t.g0), np.asarray(t.s0))

    ncb = chunk_count(nq, n_probes, n_lists, chunk)
    assert lof.shape == (ncb,)
    assert qid_tbl.shape == (ncb, chunk)
    assert g0.shape == s0.shape == (nq * n_probes,)

    # every original (query, list) pair must be recoverable through its
    # (chunk, slot) address, and the chunk must score that pair's list
    flat = raw.reshape(-1)
    qidx = np.arange(nq * n_probes) // n_probes
    assert np.all((g0 >= 0) & (g0 < ncb))
    assert np.all((s0 >= 0) & (s0 < chunk))
    assert np.array_equal(lof[g0], flat)
    assert np.array_equal(qid_tbl[g0, s0], qidx)

    # no two pairs share a slot
    addr = g0.astype(np.int64) * chunk + s0
    assert len(np.unique(addr)) == len(addr)

    # padding sentinel: every table entry is either a valid query id or nq
    assert qid_tbl.min() >= 0
    assert qid_tbl.max() <= nq
    # valid entries per list match the probe counts
    for l in range(n_lists):
        want = int((flat == l).sum())
        got = int((qid_tbl[lof == l] < nq).sum())
        assert got == want, f"list {l}: {got} != {want}"

def test_invert_probes_masked_pairs(rng):
    """Adaptive probe budgets: masked pairs occupy NO chunk slot (the
    populated-chunk count shrinks), and live pairs keep exactly the
    addresses/invariants of the unmasked construction restricted to
    them."""
    nq, n_probes, n_lists, chunk = 48, 8, 16, 16
    raw = rng.integers(0, n_lists, size=(nq, n_probes)).astype(np.int32)
    pv = rng.random((nq, n_probes)) < 0.5
    pv[:, 0] = True  # budget floor: first probe always live
    t = invert_probes(jnp.asarray(raw), n_lists, chunk, jnp.asarray(pv))
    lof, qid_tbl, g0, s0, pvalid = map(np.asarray, t)
    flat = raw.reshape(-1)
    qidx = np.arange(nq * n_probes) // n_probes
    live = pv.reshape(-1)
    assert np.array_equal(pvalid, live)
    # live pairs recoverable through their addresses
    assert np.array_equal(lof[g0[live]], flat[live])
    assert np.array_equal(qid_tbl[g0[live], s0[live]], qidx[live])
    # no two live pairs share a slot; masked pairs are clamped to (0,0)
    addr = g0.astype(np.int64) * chunk + s0
    assert len(np.unique(addr[live])) == live.sum()
    assert np.all(g0[~live] == 0) and np.all(s0[~live] == 0)
    # populated entries == live pair count (masked pairs dropped)
    assert int((qid_tbl < nq).sum()) == int(live.sum())


def _regroup_ids_first(tables, vals, slots, slot_rows, nq, n_probes, k,
                       select_min):
    """The gather-first regroup: every trimmed slot of every chunk row
    turned into a row id, then the ids moved with the candidates."""
    lof, g0, s0 = tables.lof, tables.g0, tables.s0
    kk = vals.shape[-1]
    rows = jnp.take_along_axis(slot_rows[lof][:, None, :],
                               jnp.maximum(slots, 0), axis=2)
    rows = jnp.where(slots >= 0, rows, -1)
    cand_v, cand_r = vals[g0, s0], rows[g0, s0]
    if tables.pair_valid is not None:
        m = tables.pair_valid[:, None]
        cand_v = jnp.where(m, cand_v, jnp.inf if select_min else -jnp.inf)
        cand_r = jnp.where(m, cand_r, -1)
    v, pos = _select_k_impl(cand_v.reshape(nq, n_probes * kk), k, select_min)
    return v, jnp.take_along_axis(cand_r.reshape(nq, n_probes * kk), pos,
                                  axis=1)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("select_min", [True, False])
def test_regroup_merge_ids_after_merge(masked, select_min, rng):
    """Looking ids up for the merged top-k only gives the values and ids
    of the gather-first regroup bit for bit: tied scores, slots -1 (a
    kernel's invalid candidate) and slots past a short list's end (row
    -1 in the slot table), and budget-masked pairs."""
    nq, n_probes, n_lists, chunk, max_list, kk, k = 40, 5, 12, 8, 20, 6, 24
    raw = rng.integers(0, n_lists, size=(nq, n_probes)).astype(np.int32)
    pv = None
    if masked:
        pv = rng.random((nq, n_probes)) < 0.6
        pv[:, 0] = True
        pv = jnp.asarray(pv)
    tables = invert_probes(jnp.asarray(raw), n_lists, chunk, pv)
    ncb = int(tables.lof.shape[0])

    # lists of 2..max_list rows; a few live slots filtered out (-1)
    lens = rng.integers(2, max_list + 1, size=n_lists)
    lens[-1] = 3  # shorter than kk
    slot_rows = np.where(np.arange(max_list) < lens[:, None],
                         rng.permutation(n_lists * max_list).reshape(
                             n_lists, max_list), -1)
    slot_rows[rng.random(slot_rows.shape) < 0.1] = -1
    slots = np.argsort(rng.random((ncb, chunk, max_list)), axis=2)[..., :kk]
    slots[rng.random(slots.shape) < 0.3] = -1
    worst = np.inf if select_min else -np.inf
    vals = rng.integers(0, 4, size=slots.shape).astype(np.float32)  # ties
    vals = np.where(slots >= 0, vals, worst)
    args = (tables, jnp.asarray(vals), jnp.asarray(slots.astype(np.int32)),
            jnp.asarray(slot_rows.astype(np.int32)))

    v, ids = regroup_merge(*args, _select_k_impl, nq, n_probes, k, select_min)
    rv, rids = _regroup_ids_first(*args, nq, n_probes, k, select_min)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    ids = np.asarray(ids)
    assert (ids == -1).any() and (ids >= 0).any()


@pytest.mark.parametrize("nq", [0, 1, 256, 300, 4096, 4097, 10_000])
def test_macro_batch_rows_counts_padded_slices(nq):
    """`macro_batch_slices` are the padded slices `macro_batched` runs."""
    seen = []

    def fn(sl):
        seen.append(sl.shape[0])
        return jnp.zeros((sl.shape[0], 1)), jnp.zeros((sl.shape[0], 1),
                                                       jnp.int32)

    macro_batched(fn, jnp.zeros((nq, 2)), 1)
    assert macro_batch_slices(nq) == seen


@pytest.mark.parametrize("nq,n_probes,n_lists,chunk", [
    (4096, 8, 1024, 32),   # the batch cell's full slices: dup 32
    (2048, 8, 1024, 32),   # its last slice: dup 16, the floor
    (8192, 8, 1024, 64),   # dup 64
    (6000, 8, 1024, 32),   # dup 46: the power of two below it
    (256, 1, 1024, 32),    # dup 0: the floor
    (4096, 32, 64, 128),   # dup 2,048: the ceiling
    (0, 8, 1024, 32),
])
def test_list_chunk_rule(nq, n_probes, n_lists, chunk):
    """The largest power of two at most the duplication, in [32, 128]."""
    assert list_chunk(nq, n_probes, n_lists) == chunk


def test_trim_rows_of_the_batch_cell():
    """Rows the trim selects over in one 10,000-query `batch-10k` call
    (slices 4,096, 4,096 and 2,048; 8 probes, 1,024 lists, 2,944 slots),
    from shapes alone: 180,224 at the rule's chunk of 32, 475,136 at a
    fixed 128 with even superblocks (546,816 with 356-chunk ones)."""
    slices = macro_batch_slices(10_000)
    assert slices == [4096, 4096, 2048]

    def rows(chunk_of):
        return sum(trim_rows(s, 8, 1024, 2944, chunk_of(s)) for s in slices)

    assert [trim_rows(s, 8, 1024, 2944, list_chunk(s, 8, 1024))
            for s in slices] == [65_536, 65_536, 49_152]
    assert rows(lambda s: list_chunk(s, 8, 1024)) == 180_224
    assert rows(lambda s: 128) == 475_136


@pytest.mark.parametrize("chunk,max_list", [
    (128, 2944), (32, 2944), (16, 2944), (8, 2944), (128, 47), (8, 1 << 24),
    (128, 1 << 22)])  # a chunk row alone past 2^27 elements: sb = 1
def test_superblocks_split_evenly(chunk, max_list):
    """The fewest superblocks whose score buffer stays within 2^27
    elements, split evenly: at most nsuper - 1 chunks of padding."""
    cap = max(1, (1 << 27) // (chunk * max_list))
    for ncb in [1, 2, 7, 355, 356, 357, 1152, 1280, 2048, 3072, 5000]:
        nsuper, sb = superblocks(ncb, chunk, max_list)
        assert nsuper == -(-ncb // cap)
        assert ncb <= nsuper * sb <= ncb + nsuper - 1
        assert sb <= cap
        if cap > 1:
            assert sb * chunk * max_list <= 1 << 27
    # the cell's slices at the parent's chunk: 4 x 320, not 4 x 356
    assert superblocks(1280, 128, 2944) == (4, 320)
    assert superblocks(2048, 32, 2944) == (2, 1024)
    assert superblocks(1536, 32, 2944) == (2, 768)


def test_score_and_select_scores_even_superblocks(rng, monkeypatch):
    """`score_and_select` scores `superblocks`' split: every block_fn call
    sees sb chunks, the padding chunks are dropped, and the answers equal
    those of one superblock."""
    import jax

    from raft_tpu.neighbors import probe_invert

    nq, n_probes, n_lists, chunk, max_list, k = 64, 4, 8, 8, 32, 5
    raw = rng.integers(0, n_lists, size=(nq, n_probes)).astype(np.int32)
    tables = invert_probes(jnp.asarray(raw), n_lists, chunk)
    ncb = int(tables.lof.shape[0])
    assert ncb == 40
    slot_rows = jnp.arange(n_lists * max_list, dtype=jnp.int32).reshape(
        n_lists, max_list)
    table = jax.random.normal(jax.random.PRNGKey(0),
                              (n_lists, nq + 1, max_list))

    def run():
        seen = []

        def block(inp):
            lofs, qids = inp
            seen.append(lofs.shape[0])
            return table[lofs[:, None], qids]

        out = probe_invert.score_and_select(
            tables, block, slot_rows, _select_k_impl, nq, n_probes, k, True,
            chunk, max_list, stage="t")
        return [np.asarray(x) for x in out], seen

    (v1, i1), seen = run()
    assert seen == [ncb]
    # room for 14 chunks: 3 superblocks of 14 (2 chunks of padding), not
    # 14 + 14 + 12
    monkeypatch.setattr(probe_invert, "SUPERBLOCK_ELEMS",
                        14 * chunk * max_list)
    assert superblocks(ncb, chunk, max_list) == (3, 14)
    (v3, i3), seen = run()
    assert seen == [14] * 3
    np.testing.assert_array_equal(v3, v1)
    np.testing.assert_array_equal(i3, i1)

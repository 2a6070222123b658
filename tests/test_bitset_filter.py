"""Bitset + pre-filtered search (forward-parity with RAFT's
core/bitset + `search_with_filtering`; the ~23.02 reference snapshot
predates the feature, so the oracle here is a numpy filtered brute
force, mirroring how cpp/test/neighbors/ann_utils.cuh:121 builds naive
ground truth)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.core.bitset import Bitset, as_bitset, filter_slot_table


def _naive_filtered_knn(data, queries, k, mask):
    """Filtered brute-force oracle: ids where mask holds, -1 tail."""
    d = ((queries[:, None, :] - data[None, :, :]) ** 2).sum(-1)
    d = np.where(mask[None, :], d, np.inf)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(d, idx, axis=1)
    idx = np.where(np.isfinite(vals), idx, -1)
    return vals, idx


class TestBitset:
    @pytest.mark.parametrize("n", [1, 31, 32, 33, 257, 4096])
    def test_mask_roundtrip_count(self, n):
        rng = np.random.default_rng(n)
        mask = rng.random(n) < 0.3
        b = Bitset.from_mask(mask)
        np.testing.assert_array_equal(np.asarray(b.to_mask()), mask)
        assert int(b.count()) == int(mask.sum())
        assert len(b) == n

    def test_full_and_excluding(self):
        f = Bitset.full(100)
        assert int(f.count()) == 100
        e = Bitset.excluding(100, np.array([3, 3, 99, 200, -1]))
        assert int(e.count()) == 98
        got = np.asarray(e.test(np.array([3, 99, 4, -1, 200])))
        np.testing.assert_array_equal(got, [False, False, True, False, False])

    def test_set_and_flip(self):
        b = Bitset.full(70, value=False).set(np.array([0, 69, 69, 33]))
        assert int(b.count()) == 3
        assert int(b.flip().count()) == 67
        b2 = b.set(np.array([0]), False)
        assert int(b2.count()) == 2

    def test_and_or_length_check(self):
        a = Bitset.from_mask(np.array([1, 0, 1, 0], bool))
        b = Bitset.from_mask(np.array([1, 1, 0, 0], bool))
        np.testing.assert_array_equal(np.asarray((a & b).to_mask()),
                                      [True, False, False, False])
        np.testing.assert_array_equal(np.asarray((a | b).to_mask()),
                                      [True, True, True, False])
        with pytest.raises(ValueError, match="length mismatch"):
            a & Bitset.full(5)

    def test_jit_pytree_arg(self):
        # bit values can change without retracing (bits is a leaf)
        calls = []

        @jax.jit
        def probe(bs, ids):
            calls.append(1)
            return bs.test(ids)

        ids = jnp.arange(4)
        m1 = probe(Bitset.from_mask(np.array([1, 0, 1, 0], bool)), ids)
        m2 = probe(Bitset.from_mask(np.array([0, 1, 0, 1], bool)), ids)
        np.testing.assert_array_equal(np.asarray(m1), [True, False, True, False])
        np.testing.assert_array_equal(np.asarray(m2), [False, True, False, True])
        assert len(calls) == 1  # one trace

    def test_empty_bitset(self):
        # n==0 is reachable (empty bridged index); test/set must not
        # gather from the zero-word bits array
        b = Bitset.full(0)
        got = np.asarray(b.test(np.array([0, -1, 5])))
        np.testing.assert_array_equal(got, [False, False, False])
        assert int(b.set(np.array([0, 3])).count()) == 0
        assert int(b.count()) == 0

    def test_inf_score_survivor_keeps_id(self):
        # rows passing the filter whose true distance overflows to +inf:
        # masked-slot detection is by id re-test, not score, so a
        # returned id is ALWAYS a survivor (never the masked row 0) even
        # though every candidate ties at +inf. Which inf-tied survivors
        # fill the slots is unspecified (a masked row may consume a slot
        # as -1), but a survivor id must never be clobbered when one is
        # selected.
        from raft_tpu.neighbors import brute_force

        data = np.array([[0.0], [1e25], [2e25], [3e25]], np.float32)
        q = np.array([[-3e25]], np.float32)  # d^2 to rows 1-3 overflows
        mask = np.array([False, True, True, True])
        d, i = brute_force.knn(data, q, k=3, prefilter=mask)
        got = np.asarray(i).ravel()
        assert set(got.tolist()) <= {-1, 1, 2, 3}
        assert len(set(got.tolist()) & {1, 2, 3}) >= 2
        assert np.all(np.isinf(np.asarray(d)))

    def test_as_bitset_validation(self):
        with pytest.raises(ValueError, match="covers 4 ids"):
            as_bitset(Bitset.full(4), 5)
        with pytest.raises(ValueError, match="boolean mask"):
            as_bitset(np.array([1.0, 0.0]), 2)
        with pytest.raises(ValueError, match="has 3 entries"):
            as_bitset(np.array([1, 0, 1], bool), 4)

    def test_filter_slot_table(self):
        slot_rows = jnp.array([[0, 2, -1], [1, 3, -1]], jnp.int32)
        source_ids = jnp.array([10, 11, 12, 13], jnp.int32)
        bs = Bitset.excluding(14, np.array([12, 13]))
        out = np.asarray(filter_slot_table(slot_rows, source_ids, bs))
        np.testing.assert_array_equal(out, [[0, -1, -1], [1, -1, -1]])
        # direct-id table (source_ids=None)
        bs2 = Bitset.excluding(4, np.array([0]))
        out2 = np.asarray(filter_slot_table(slot_rows, None, bs2))
        np.testing.assert_array_equal(out2, [[-1, 2, -1], [1, 3, -1]])


class TestFilteredSearch:
    @pytest.fixture(scope="class")
    def blobs(self):
        rng = np.random.default_rng(7)
        centers = rng.uniform(-5, 5, (16, 24)).astype(np.float32)
        assign = rng.integers(0, 16, 3000)
        data = centers[assign] + rng.standard_normal((3000, 24)).astype(np.float32)
        queries = centers[rng.integers(0, 16, 40)] + rng.standard_normal(
            (40, 24)
        ).astype(np.float32)
        mask = rng.random(3000) < 0.5
        return data, queries, mask

    def test_brute_force_exact(self, blobs):
        from raft_tpu.neighbors import brute_force

        data, queries, mask = blobs
        want_v, want_i = _naive_filtered_knn(data, queries, 8, mask)
        d, i = brute_force.knn(data, queries, 8, prefilter=mask)
        np.testing.assert_array_equal(np.asarray(i), want_i)
        np.testing.assert_allclose(np.asarray(d), want_v, rtol=1e-4)
        # Bitset input path agrees with the mask path
        d2, i2 = brute_force.knn(data, queries, 8,
                                 prefilter=Bitset.from_mask(mask))
        np.testing.assert_array_equal(np.asarray(i2), want_i)

    def test_brute_force_tiled_path(self, blobs):
        from raft_tpu.neighbors import brute_force

        data, queries, mask = blobs
        want_v, want_i = _naive_filtered_knn(data, queries, 5, mask)
        # tile smaller than n forces the scan/merge path
        d, i = brute_force._bf_knn_impl(
            jnp.asarray(data), jnp.asarray(queries), 5,
            brute_force.resolve_metric("sqeuclidean"), tile=512,
            prefilter=Bitset.from_mask(mask),
        )
        np.testing.assert_array_equal(np.asarray(i), want_i)

    def test_brute_force_fused_respects_filter(self, blobs):
        from raft_tpu.neighbors import brute_force

        data, queries, mask = blobs
        d, i = brute_force.knn(data, queries, 8, prefilter=mask,
                               engine="pallas")
        got = np.asarray(i)
        bad = got[(got >= 0) & ~mask[np.maximum(got, 0)]]
        assert bad.size == 0, f"filtered ids returned: {bad[:5]}"

    def test_brute_force_filter_everything_but_k(self, blobs):
        from raft_tpu.neighbors import brute_force

        data, queries, _ = blobs
        only = np.zeros(len(data), bool)
        only[:3] = True  # fewer than k survivors
        d, i = brute_force.knn(data, queries, 8, prefilter=only)
        got = np.asarray(i)
        assert set(got[:, :3].ravel()) <= {0, 1, 2}
        np.testing.assert_array_equal(got[:, 3:], -1)
        assert np.all(np.isinf(np.asarray(d)[:, 3:]))

    @pytest.fixture(scope="class")
    def pq_index(self, blobs):
        from raft_tpu.neighbors import ivf_pq

        data, _, _ = blobs
        return ivf_pq.build(
            ivf_pq.IndexParams(n_lists=8, pq_dim=12, kmeans_n_iters=4), data
        )

    @pytest.mark.parametrize(
        "mode,trim", [
            # the recon8 engines (the list-major one is every batch
            # cell's) smoke the filter invariant in the quick tier; the
            # rest of the matrix (compile-heavy on 1 core) is slow-tier
            pytest.param("lut", "approx", marks=pytest.mark.slow),
            ("recon8", "approx"),
            ("recon8_list", "approx"),
            pytest.param("recon8_list", "fused", marks=pytest.mark.slow),
        ]
    )
    def test_ivf_pq_engines(self, blobs, pq_index, mode, trim):
        from raft_tpu.neighbors import ivf_pq

        data, queries, mask = blobs
        index = pq_index
        p = ivf_pq.SearchParams(n_probes=8, score_mode=mode, trim_engine=trim)
        _, want = _naive_filtered_knn(data, queries, 10, mask)
        d, i = ivf_pq.search(p, index, queries, 10, prefilter=mask)
        got = np.asarray(i)
        # invariant: nothing filtered comes back
        bad = got[(got >= 0) & ~mask[np.maximum(got, 0)]]
        assert bad.size == 0, f"filtered ids returned: {bad[:5]}"
        # recall vs the FILTERED oracle (all lists probed, PQ loss only)
        rec = np.mean([
            len(set(got[j]) & set(want[j][want[j] >= 0])) / max(1, (want[j] >= 0).sum())
            for j in range(len(queries))
        ])
        assert rec >= 0.55, rec

    def test_ivf_pq_unfiltered_unchanged(self, blobs, pq_index):
        from raft_tpu.neighbors import ivf_pq

        data, queries, mask = blobs
        index = pq_index
        p = ivf_pq.SearchParams(n_probes=8)
        d0, i0 = ivf_pq.search(p, index, queries, 10)
        d1, i1 = ivf_pq.search(p, index, queries, 10,
                               prefilter=np.ones(len(data), bool))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    @pytest.mark.parametrize("engine", ["query", "list", "pallas"])
    def test_ivf_flat_engines(self, blobs, engine):
        from raft_tpu.neighbors import ivf_flat

        data, queries, mask = blobs
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), data
        )
        p = ivf_flat.SearchParams(n_probes=8, engine=engine)
        _, want = _naive_filtered_knn(data, queries, 10, mask)
        d, i = ivf_flat.search(p, index, queries, 10, prefilter=mask)
        got = np.asarray(i)
        bad = got[(got >= 0) & ~mask[np.maximum(got, 0)]]
        assert bad.size == 0, f"filtered ids returned: {bad[:5]}"
        if engine != "pallas":  # exact scan: full-probe recall ~1
            rec = np.mean([
                len(set(got[j]) & set(want[j][want[j] >= 0]))
                / max(1, (want[j] >= 0).sum())
                for j in range(len(queries))
            ])
            assert rec >= 0.99, rec

    @pytest.mark.slow
    def test_custom_extend_ids(self, blobs):
        """extend(new_indices=...) ids live beyond index.size; the filter
        covers index.id_bound and those rows stay reachable."""
        from raft_tpu.neighbors import ivf_flat

        data, queries, _ = blobs
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4,
                                 add_data_on_build=False), data[:2000]
        )
        index = ivf_flat.extend(index, data[:2000])
        index = ivf_flat.extend(
            index, data[2000:], np.arange(50_000, 50_000 + 1000, dtype=np.int32)
        )
        assert index.size == 3000 and index.id_bound == 51_000
        with pytest.raises(ValueError, match="covers 3000 ids"):
            ivf_flat.search(ivf_flat.SearchParams(n_probes=8), index,
                            queries, 5, prefilter=Bitset.full(3000))
        # keep ONLY the custom-id rows: they must come back, not vanish
        keep = Bitset.full(51_000, value=False).set(
            np.arange(50_000, 51_000))
        _, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), index,
                               queries, 5, prefilter=keep)
        got = np.asarray(i)
        assert np.all((got >= 50_000) | (got == -1))
        assert np.any(got >= 50_000)

    def test_ivf_flat_filter_to_one_list(self, blobs):
        """Filter keeps only one list's members; every engine must still
        find them through other probes' masking (no cross-list leak)."""
        from raft_tpu.neighbors import ivf_flat

        data, queries, _ = blobs
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), data
        )
        # keep exactly the members of list 0
        sr = np.asarray(index.slot_rows)
        keep_rows = np.asarray(index.source_ids)[sr[0][sr[0] >= 0]]
        mask = np.zeros(index.size, bool)
        mask[keep_rows] = True
        p = ivf_flat.SearchParams(n_probes=8, engine="query")
        _, i = ivf_flat.search(p, index, queries, 5, prefilter=mask)
        got = np.asarray(i)
        assert set(got[got >= 0].ravel()) <= set(keep_rows.tolist())

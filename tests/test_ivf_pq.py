"""IVF-PQ tests: recall-gated against brute force (mirrors
cpp/test/neighbors/ann_ivf_pq.cuh:164-265 semantics: recall floor with
tolerance, serialization roundtrip inside fixtures)."""

import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, ivf_pq
from raft_tpu.random import make_blobs


def recall(found, truth):
    found, truth = np.asarray(found), np.asarray(truth)
    hits = sum(len(set(f.tolist()) & set(t.tolist())) for f, t in zip(found, truth))
    return hits / truth.size


@pytest.fixture(scope="module")
def dataset():
    data, _ = make_blobs(20000, 64, n_clusters=40, cluster_std=1.5, seed=31)
    q, _ = make_blobs(80, 64, n_clusters=40, cluster_std=1.5, seed=32)
    return np.asarray(data), np.asarray(q)


@pytest.fixture(scope="module")
def truth10(dataset):
    data, queries = dataset
    _, t = brute_force.knn(data, queries, 10)
    return np.asarray(t)


@pytest.fixture(scope="module")
def index32(dataset):
    """Shared pq_dim=32 index (the middle-quantization config): three
    tests read it, none mutates it — one build instead of three
    (full-suite cost discipline, VERDICT r3 #8)."""
    data, _ = dataset
    return ivf_pq.build(ivf_pq.IndexParams(n_lists=50, pq_dim=32), data)


@pytest.fixture(scope="module")
def index16(dataset):
    """Shared n_lists=32/pq_dim=16 index: a dozen engine/validation tests
    search it read-only (lazy recon/lane-pad caches are idempotent) —
    one build instead of twelve (VERDICT r3 #8)."""
    data, _ = dataset
    return ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=16), data)


def test_build_search_recall(dataset, truth10):
    # Floor calibrated against an oracle: sklearn-trained codebooks on this
    # dataset reach 0.6525 recall@10 (quantization-resolution-bound, 2 bits/
    # dim); the reference pairs IVF-PQ with `refine` for high recall, tested
    # below in test_search_plus_refine.
    data, queries = dataset
    params = ivf_pq.IndexParams(n_lists=50, pq_dim=16, pq_bits=8)
    index = ivf_pq.build(params, data)
    assert index.size == len(data)
    assert index.pq_dim == 16 and index.rot_dim == 64
    d, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=20), index, queries, 10)
    r = recall(i, truth10)
    assert r >= 0.6, f"recall {r}"
    d = np.asarray(d)
    assert np.all(np.diff(d, axis=1) >= -1e-4)


def test_search_plus_refine(dataset, truth10):
    """IVF-PQ shortlist + exact refinement: the reference's high-recall
    pipeline (neighbors/refine.cuh)."""
    from raft_tpu.neighbors.refine import refine

    data, queries = dataset
    index = ivf_pq.build(ivf_pq.IndexParams(n_lists=50, pq_dim=16), data)
    _, cand = ivf_pq.search(ivf_pq.SearchParams(n_probes=20), index, queries, 40)
    d, i = refine(data, queries, cand, 10)
    r = recall(i, truth10)
    assert r >= 0.9, f"refined recall {r}"
    assert np.all(np.diff(np.asarray(d), axis=1) >= -1e-5)


def test_reference_grade_recall95(dataset, truth10, index32):
    """Pins a reference-grade >= 0.95 recall@10 configuration end-to-end
    (ann_ivf_pq.cuh:257-265 gates 0.85-0.99 per config; BASELINE.md's
    north star counts QPS only at recall@10 >= 0.95): finer quantization
    (pq_dim=32 on 64 dims), wide probing, and exact refine over a 10x
    shortlist — the same pipeline the headline bench ladder runs."""
    from raft_tpu.neighbors.refine import refine

    data, queries = dataset
    _, cand = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), index32,
                            queries, 100)
    d, i = refine(data, queries, cand, 10)
    r = recall(i, truth10)
    assert r >= 0.95, f"reference-grade recall {r}"


def test_unrefined_middle_recall85(dataset, truth10, index32):
    """The MIDDLE quantization config (pq_dim = dim/2: 4 rotated bits per
    input dim) must clear a reference-grade unrefined gate
    (ann_ivf_pq.cuh:257-265 gates 0.85-0.99): measured 0.894 recall@10 at
    this geometry, gated at 0.85 — the headline ladder's second unrefined
    rung (bench.py 'mid' variant), so the bench gate does not depend
    solely on the refine pipeline or the full-fidelity index."""
    data, queries = dataset
    _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=25), index32,
                         queries, 10)
    r = recall(i, truth10)
    assert r >= 0.85, f"unrefined middle recall {r}"


def test_unrefined_high_fidelity_recall90(dataset, truth10):
    """An UNREFINED config must clear a reference-grade gate too
    (ann_ivf_pq.cuh:257-265 gates 0.85-0.99 without refine): pq_dim ==
    dim keeps 8 rotated bits per input dim, so raw PQ scores alone reach
    high recall — measured 0.976 on this geometry; gated at 0.9. The
    bench ladder's fine-index variant (bench.py) is the 1Mx96 analogue."""
    data, queries = dataset
    index = ivf_pq.build(ivf_pq.IndexParams(n_lists=50, pq_dim=64), data)
    _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=25), index, queries, 10)
    r = recall(i, truth10)
    assert r >= 0.9, f"unrefined high-fidelity recall {r}"


def test_probe_scaling(dataset, truth10, index32):
    data, queries = dataset
    index = index32
    r1 = recall(ivf_pq.search(ivf_pq.SearchParams(n_probes=2), index, queries, 10)[1], truth10)
    r2 = recall(ivf_pq.search(ivf_pq.SearchParams(n_probes=50), index, queries, 10)[1], truth10)
    assert r2 >= r1
    assert r2 >= 0.85, f"all-probe recall {r2}"


def test_pq_dim_quality_tradeoff(dataset, truth10, index16):
    """More subspaces -> better recall (finer quantization), asserted as
    a monotone chain over the full 8 -> 16 -> 32 span (the 16 point rides
    the shared fixture; the endpoints build here/below)."""
    data, queries = dataset
    def rec_at(index):
        return recall(
            ivf_pq.search(ivf_pq.SearchParams(n_probes=32), index,
                          queries, 10)[1], truth10)
    r8 = rec_at(ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=8), data))
    r16 = rec_at(index16)
    r32 = rec_at(ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=32), data))
    assert r16 >= r8 - 0.02, (r8, r16)
    assert r32 >= r16 - 0.02, (r16, r32)


def test_pq_bits_4(dataset, truth10):
    data, queries = dataset
    index = ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=32, pq_bits=4), data)
    assert np.asarray(index.codes).max() < 16
    r = recall(ivf_pq.search(ivf_pq.SearchParams(n_probes=32), index, queries, 10)[1], truth10)
    # 4 bits over 2-d subspaces = 2 bits/dim; calibrated floor
    assert r >= 0.4, f"4-bit recall {r}"


def test_per_cluster_codebooks(dataset, truth10):
    data, queries = dataset
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16, codebook_kind=ivf_pq.PER_CLUSTER)
    index = ivf_pq.build(params, data)
    ids = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), index, queries, 10)[1]
    r = recall(ids, truth10)
    # one codebook shared across subspaces is coarser than per-subspace
    assert r >= 0.45, f"per-cluster recall {r}"
    # recon engines decode per-cluster codebooks correctly (exercises the
    # per-cluster branch of _decode_quantize)
    i_lut = np.asarray(ids)
    for mode in ("recon8", "recon8_list"):
        i_rec = np.asarray(
            ivf_pq.search(ivf_pq.SearchParams(n_probes=32, score_mode=mode), index, queries, 10)[1]
        )
        ov = np.mean([len(set(i_lut[r_]) & set(i_rec[r_])) / 10 for r_ in range(len(i_lut))])
        assert ov >= 0.9, f"{mode} per-cluster overlap {ov}"


@pytest.fixture(scope="module")
def index_ip(dataset):
    """Shared inner-product index (read-only consumers)."""
    data, _ = dataset
    return ivf_pq.build(
        ivf_pq.IndexParams(n_lists=32, pq_dim=32, metric="inner_product"),
        data)


def test_inner_product(dataset, index_ip):
    data, queries = dataset
    _, truth = brute_force.knn(data, queries, 10, metric="inner_product")
    r = recall(ivf_pq.search(ivf_pq.SearchParams(n_probes=32), index_ip, queries, 10)[1], truth)
    assert r >= 0.7, f"IP recall {r}"


def test_extend_separate(dataset, truth10):
    """Incremental extend must be EXACTLY equivalent to one-shot extend."""
    data, queries = dataset
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16, add_data_on_build=False)
    base = ivf_pq.build(params, data)
    assert base.size == 0
    one = ivf_pq.extend(base, data)
    two = ivf_pq.extend(ivf_pq.extend(base, data[:10000]), data[10000:])
    assert two.size == len(data)
    d1, i1 = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), one, queries, 10)
    d2, i2 = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), two, queries, 10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5)
    r = recall(i2, truth10)
    assert r >= 0.45, f"extend recall {r}"


def test_bf16_lut(dataset, truth10, index16):
    data, queries = dataset
    index = index16
    r32 = recall(ivf_pq.search(ivf_pq.SearchParams(n_probes=16), index, queries, 10)[1], truth10)
    rb = recall(
        ivf_pq.search(ivf_pq.SearchParams(n_probes=16, lut_dtype="bfloat16"), index, queries, 10)[1],
        truth10,
    )
    assert rb >= r32 - 0.05  # bf16 LUT costs little recall


def test_save_load(dataset, tmp_path, index16):
    data, queries = dataset
    index = index16
    f = str(tmp_path / "ivf_pq.bin")
    ivf_pq.save(f, index)
    loaded = ivf_pq.load(f)
    d0, i0 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), index, queries, 5)
    d1, i1 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), loaded, queries, 5)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-5)


def test_param_validation():
    with pytest.raises(ValueError):
        ivf_pq.IndexParams(pq_bits=9)
    with pytest.raises(ValueError):
        ivf_pq.IndexParams(codebook_kind="nope")
    # negative pq_dim rejects cleanly (0 is the documented auto sentinel;
    # without the guard a negative leaked into an XLA reshape error)
    with pytest.raises(ValueError, match="pq_dim"):
        ivf_pq.IndexParams(pq_dim=-3)
    assert ivf_pq.IndexParams(pq_dim=0).pq_dim == 0  # auto stays valid


def test_recon8_score_mode(dataset, truth10, index16):
    """int8 reconstruction scoring matches LUT scoring recall (TPU fast
    path; same math, decode-side int8 quantization only)."""
    data, queries = dataset
    index = index16
    r_lut = recall(
        ivf_pq.search(ivf_pq.SearchParams(n_probes=16), index, queries, 10)[1], truth10
    )
    r_rec = recall(
        ivf_pq.search(ivf_pq.SearchParams(n_probes=16, score_mode="recon8"), index, queries, 10)[1],
        truth10,
    )
    assert r_rec >= r_lut - 0.02, f"recon8 {r_rec} vs lut {r_lut}"
    assert index.recon8 is not None  # lazily built and cached
    # extend invalidates the cached reconstruction (new Index)
    ext = ivf_pq.extend(index, data[:10])
    assert ext.recon8 is None


def test_recon8_listmajor(dataset, truth10, index16):
    """List-major engine scores the same int8 reconstructions as the
    query-major recon8 engine — results must agree (modulo top-k ties) and
    pass the same recall floor."""
    data, queries = dataset
    index = index16
    i_qm = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8"), index, queries, 10
    )[1]
    d_lm, i_lm = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )
    i_qm, i_lm = np.asarray(i_qm), np.asarray(i_lm)
    overlap = np.mean(
        [len(set(i_qm[r]) & set(i_lm[r])) / 10 for r in range(len(i_qm))]
    )
    assert overlap >= 0.95, f"engine disagreement: overlap {overlap}"
    assert recall(i_lm, truth10) >= recall(i_qm, truth10) - 0.02
    assert np.all(np.diff(np.asarray(d_lm), axis=1) >= -1e-4)


def test_recon8_listmajor_int8_queries(dataset, truth10, index16):
    """score_dtype="int8" (symmetric int8 x int8 scoring) must track the
    bf16 list-major engine: the extra query-side quantization may shift
    near-tie candidates but not the recalled set materially."""
    data, queries = dataset
    index = index16
    i_bf = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )[1]
    d_i8, i_i8 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list", score_dtype="int8"),
        index, queries, 10,
    )
    i_bf, i_i8 = np.asarray(i_bf), np.asarray(i_i8)
    overlap = np.mean(
        [len(set(i_bf[r]) & set(i_i8[r])) / 10 for r in range(len(i_bf))]
    )
    assert overlap >= 0.9, f"int8 engine diverged: overlap {overlap}"
    assert recall(i_i8, truth10) >= recall(i_bf, truth10) - 0.03
    assert np.all(np.diff(np.asarray(d_i8), axis=1) >= -1e-4)


def test_recon8_listmajor_bf16_trim(dataset, truth10, index16):
    """internal_distance_dtype="bfloat16" trims the list-major engine in
    bf16 — near-tie ranking noise only; the recalled set must track f32."""
    data, queries = dataset
    index = index16
    i_f32 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )[1]
    d_bf, i_bf = ivf_pq.search(
        ivf_pq.SearchParams(
            n_probes=16, score_mode="recon8_list", internal_distance_dtype="bfloat16"
        ),
        index, queries, 10,
    )
    assert np.asarray(d_bf).dtype == np.float32  # returned distances stay f32
    i_f32, i_bf = np.asarray(i_f32), np.asarray(i_bf)
    overlap = np.mean(
        [len(set(i_f32[r]) & set(i_bf[r])) / 10 for r in range(len(i_f32))]
    )
    assert overlap >= 0.9, f"bf16 trim diverged: overlap {overlap}"
    assert recall(i_bf, truth10) >= recall(i_f32, truth10) - 0.03


def test_internal_distance_dtype_auto_resolves_f32_off_tpu(dataset, index16):
    """The "auto" default resolves to exact f32 trim on non-TPU backends
    (the bf16 tuned hint was measured on chip and is TPU-gated), so the
    default-params result is bit-identical to an explicit float32."""
    data, queries = dataset
    d_auto, i_auto = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"),
        index16, queries, 10,
    )
    d_f32, i_f32 = ivf_pq.search(
        ivf_pq.SearchParams(
            n_probes=16, score_mode="recon8_list",
            internal_distance_dtype="float32",
        ),
        index16, queries, 10,
    )
    np.testing.assert_array_equal(np.asarray(i_auto), np.asarray(i_f32))
    np.testing.assert_array_equal(np.asarray(d_auto), np.asarray(d_f32))
    with pytest.raises(ValueError, match="internal_distance_dtype"):
        ivf_pq.search(
            ivf_pq.SearchParams(n_probes=16, internal_distance_dtype="fp8"),
            index16, queries, 10,
        )


def test_recon8_listmajor_pallas_trim(dataset, truth10, index16):
    """trim_engine="pallas" (fused list-scan, interpret mode on CPU) must
    track the XLA approx-trim engine: same scores modulo bf16 matmul
    rounding and bin-collision trim noise."""
    data, queries = dataset
    index = index16
    i_x = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )[1]
    d_p, i_p = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list", trim_engine="pallas"),
        index, queries, 10,
    )
    i_x, i_p = np.asarray(i_x), np.asarray(i_p)
    overlap = np.mean([len(set(i_x[r]) & set(i_p[r])) / 10 for r in range(len(i_x))])
    # best+second-best per bin leaves only 3-way collisions as trim loss
    assert overlap >= 0.95, f"pallas trim diverged: overlap {overlap}"
    assert recall(i_p, truth10) >= recall(i_x, truth10) - 0.05
    assert np.all(np.diff(np.asarray(d_p), axis=1) >= -1e-4)
    assert np.asarray(d_p).dtype == np.float32


def test_recon8_listmajor_pallas_packed_fold(dataset, truth10, index16, monkeypatch):
    """pallas_fold="packed" tuned key routes the fused trim through the
    bf16-coarse packed fold end-to-end (fold_variant() wiring): results
    must track the exact-fold pallas engine at trim-noise level."""
    from raft_tpu.core import tuned

    data, queries = dataset
    index = index16
    p = ivf_pq.SearchParams(
        n_probes=16, score_mode="recon8_list", trim_engine="pallas"
    )
    # pin the baseline: a committed pallas_fold="packed" tuned key must
    # not silently turn this into packed-vs-packed
    monkeypatch.setitem(tuned._load(), "pallas_fold", "exact")
    i_exact = np.asarray(ivf_pq.search(p, index, queries, 10)[1])
    monkeypatch.setitem(tuned._load(), "pallas_fold", "packed")
    try:
        d_p, i_p = ivf_pq.search(p, index, queries, 10)
    finally:
        tuned.reload()
    i_p = np.asarray(i_p)
    overlap = np.mean(
        [len(set(i_exact[r]) & set(i_p[r])) / 10 for r in range(len(i_exact))]
    )
    assert overlap >= 0.9, f"packed fold diverged: overlap {overlap}"
    assert recall(i_p, truth10) >= recall(i_exact, truth10) - 0.05
    assert np.all(np.diff(np.asarray(d_p), axis=1) >= -1e-4)


def test_pallas_trim_validation(dataset, index16):
    data, queries = dataset
    index = index16
    with pytest.raises(ValueError, match="trim_engine"):
        ivf_pq.search(
            ivf_pq.SearchParams(score_mode="lut", trim_engine="pallas"),
            index, queries, 5,
        )
    with pytest.raises(ValueError, match="trim_engine"):
        ivf_pq.search(
            ivf_pq.SearchParams(score_mode="recon8_list", trim_engine="warp"),
            index, queries, 5,
        )


def test_pallas_trim_int8_queries(dataset, truth10, index16):
    """Symmetric int8 scoring inside the fused kernel: must track the XLA
    int8 engine (same quantization, different trim)."""
    data, queries = dataset
    index = index16
    i_x = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list",
                            score_dtype="int8"),
        index, queries, 10,
    )[1]
    d_p, i_p = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list",
                            score_dtype="int8", trim_engine="pallas"),
        index, queries, 10,
    )
    i_x, i_p = np.asarray(i_x), np.asarray(i_p)
    overlap = np.mean([len(set(i_x[r]) & set(i_p[r])) / 10 for r in range(len(i_x))])
    assert overlap >= 0.95, f"int8 pallas trim diverged: overlap {overlap}"
    assert recall(i_p, truth10) >= recall(i_x, truth10) - 0.05
    assert np.all(np.diff(np.asarray(d_p), axis=1) >= -1e-4)


def test_pallas_trim_inner_product(dataset):
    data, queries = dataset
    params = ivf_pq.IndexParams(
        n_lists=32, pq_dim=16, metric="inner_product", force_random_rotation=True
    )
    index = ivf_pq.build(params, data)
    i_x = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )[1]
    i_p = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list", trim_engine="pallas"),
        index, queries, 10,
    )[1]
    i_x, i_p = np.asarray(i_x), np.asarray(i_p)
    overlap = np.mean([len(set(i_x[r]) & set(i_p[r])) / 10 for r in range(len(i_x))])
    assert overlap >= 0.85, f"IP pallas trim diverged: overlap {overlap}"


def test_bad_score_dtype_raises(dataset, index16):
    data, queries = dataset
    index = index16
    with pytest.raises(ValueError, match="score_dtype"):
        ivf_pq.search(
            ivf_pq.SearchParams(score_mode="recon8_list", score_dtype="fp64"),
            index, queries, 5,
        )


def test_recon8_listmajor_inner_product(dataset, index_ip):
    data, queries = dataset
    _, truth = brute_force.knn(data, queries, 10, metric="inner_product")
    r = recall(
        ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32, score_mode="recon8_list"),
            index_ip, queries, 10
        )[1],
        truth,
    )
    assert r >= 0.7, f"IP list-major recall {r}"


def test_auto_score_mode(dataset, truth10, index16):
    """auto picks an engine by batch duplication factor; both regimes work."""
    data, queries = dataset
    index = index16
    # 80 queries * 16 probes / 32 lists = 40x duplication -> list-major
    i_auto = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="auto"), index, queries, 10
    )[1]
    i_lut = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="lut"), index, queries, 10
    )[1]
    assert recall(i_auto, truth10) >= recall(i_lut, truth10) - 0.03
    # single query -> query-major lut
    d, i = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="auto"), index, queries[:1], 10
    )
    assert np.asarray(i).shape == (1, 10)


def test_recon8_bad_mode(dataset, index16):
    data, queries = dataset
    index = index16
    with pytest.raises(ValueError):
        ivf_pq.search(ivf_pq.SearchParams(score_mode="nope"), index, queries, 5)


def test_integer_dtype_datasets():
    """int8/uint8 datasets build and search through the upcast path with
    reference-grade recall (ann_ivf_pq.cuh instantiates the full test
    grid for T in {float, int8_t, uint8_t}; the TPU build upcasts to f32
    at ingest — same results class, no separate kernel family needed)."""
    rng = np.random.default_rng(0)
    for dt, lo, hi in ((np.uint8, 0, 256), (np.int8, -128, 128)):
        data = rng.integers(lo, hi, (3000, 32)).astype(dt)
        q = data[:10]
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=16), data)
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx, q, 5)
        _, t = brute_force.knn(data.astype(np.float32),
                               q.astype(np.float32), 5)
        r = recall(i, np.asarray(t))
        assert r >= 0.85, (dt, r)
        # round-trip ids are valid rows of the integer dataset (min >= 0
        # also excludes the -1 invalid-id sentinel)
        assert np.asarray(i).min() >= 0 and np.asarray(i).max() < len(data)


def test_default_params_route_to_measured_engine(monkeypatch):
    """VERDICT r4 #5: a default-constructed SearchParams must land on the
    measured winner, never the device-faulting lut engine, on TPU."""
    p = ivf_pq.SearchParams()
    assert p.score_mode == "auto"
    # TPU resolution: small-dup batches fall back to the gather-free
    # recon8 engine, large-dup to recon8_list; NEVER lut — even when a
    # (CPU-fitted) tuned key says lut
    import jax

    from raft_tpu.core import tuned

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # pin the tuned state: a chip session may have committed a
    # pq_auto_engine key, and the heuristic asserts below assume none
    # (monkeypatch.setitem restores/deletes on teardown; no reload —
    # reload would re-read whatever is on disk mid-test)
    monkeypatch.setitem(tuned._load(), "pq_auto_engine", None)
    assert ivf_pq._resolve_score_mode(p, nq=1, n_probes=4, n_lists=64) == "recon8"
    assert (
        ivf_pq._resolve_score_mode(p, nq=4096, n_probes=32, n_lists=64)
        == "recon8_list"
    )
    monkeypatch.setitem(tuned._load(), "pq_auto_engine", "lut")
    assert ivf_pq._resolve_score_mode(p, nq=1, n_probes=4, n_lists=64) == "recon8"
    monkeypatch.setitem(tuned._load(), "pq_auto_engine", "recon8_list")
    assert (
        ivf_pq._resolve_score_mode(p, nq=1, n_probes=4, n_lists=64) == "recon8_list"
    )
    # CPU keeps the classic small-batch lut (no fault class there)
    monkeypatch.setitem(tuned._load(), "pq_auto_engine", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ivf_pq._resolve_score_mode(p, nq=1, n_probes=4, n_lists=64) == "lut"


def test_lut_fenced_on_tpu(dataset, index16, monkeypatch):
    """Explicit score_mode='lut' on TPU raises the documented guard; the
    env override (profiling-only) lifts it."""
    import jax

    data, queries = dataset
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="fenced on TPU"):
        ivf_pq.search(
            ivf_pq.SearchParams(score_mode="lut"), index16, queries[:4], 5
        )
    # the override lifts the fence WITH the backend still reading "tpu"
    # (the profiler's sanctioned fault-repro path); the engine itself
    # runs on this process's real CPU devices regardless of the mock
    monkeypatch.setenv(ivf_pq._LUT_TPU_OVERRIDE, "1")
    d, i = ivf_pq.search(
        ivf_pq.SearchParams(score_mode="lut", n_probes=8), index16, queries[:4], 5
    )
    assert np.asarray(i).shape == (4, 5)


@pytest.fixture(scope="module")
def trim_data():
    """3,000 rows in 64 lists (about 47 rows a list), so a probed list
    can hold fewer rows than k = 40."""
    data, _ = make_blobs(3000, 32, n_clusters=24, cluster_std=2.0, seed=41)
    q, _ = make_blobs(50, 32, n_clusters=24, cluster_std=2.0, seed=42)
    keep = np.random.default_rng(43).random(3000) < 0.15
    return np.asarray(data), np.asarray(q), keep


@pytest.fixture(scope="module")
def trim_pq(trim_data):
    return ivf_pq.build(ivf_pq.IndexParams(n_lists=64, pq_dim=16),
                        trim_data[0])


@pytest.fixture(scope="module")
def trim_flat(trim_data):
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.build(ivf_flat.IndexParams(n_lists=64), trim_data[0])


def _spy_trim(monkeypatch, jitted):
    """Record, on the host, what the list-major trim sees: the pair
    tables, the slot table and every scored block. `jitted` is cleared so
    the next call traces through the spy."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors import probe_invert

    rec = {"blocks": []}
    orig = probe_invert.score_and_select

    def spy(tables, block_fn, slot_rows, *args, **kw):
        def block(inp):
            s = block_fn(inp)
            jax.debug.callback(
                lambda *a: rec["blocks"].append([np.asarray(x) for x in a]),
                inp[0], inp[1], s)
            return s

        pv = (tables.pair_valid if tables.pair_valid is not None
              else jnp.ones(tables.g0.shape, bool))
        jax.debug.callback(
            lambda *a: rec.update(zip(("g0", "s0", "lof", "qid", "rows",
                                       "pv"),
                                      [np.asarray(x) for x in a])),
            tables.g0, tables.s0, tables.lof, tables.qid_tbl, slot_rows, pv)
        return orig(tables, block, slot_rows, *args, **kw)

    monkeypatch.setattr(probe_invert, "score_and_select", spy)
    jitted.clear_cache()
    return rec


def _trim_reference(rec, nq, n_probes, k, source_ids):
    """Each (query, probed list)'s exact top-kk by (score, slot), merged
    per query by (score, probe rank): the list-major contract in NumPy.
    A budget-masked pair gives kk candidates of score inf and id -1.
    Also returns how many live pairs had fewer than kk real candidates."""
    n_pad = len(rec["g0"]) // n_probes  # the pad id: the slice's size
    rows_of = {}
    for lofs, qids, s in rec["blocks"]:
        for c, r in zip(*np.nonzero(qids != n_pad)):
            rows_of[(int(lofs[c]), int(qids[c, r]))] = s[c, r].astype(
                np.float32)
    max_list = rec["rows"].shape[1]
    kk = min(k, max_list)
    slots = np.arange(max_list)
    vals, ids, short = [], [], 0
    for q in range(nq):
        cv, cr = [], []
        for p in range(n_probes):
            if not rec["pv"][q * n_probes + p]:
                cv.append(np.full(kk, np.inf, np.float32))
                cr.append(np.full(kk, -1))
                continue
            c, r = rec["g0"][q * n_probes + p], rec["s0"][q * n_probes + p]
            lst = int(rec["lof"][c])
            assert rec["qid"][c, r] == q
            sc = rows_of[(lst, q)]
            short += int(np.isfinite(sc).sum() < kk)
            top = np.lexsort((slots, sc))[:kk]
            cv.append(sc[top])
            cr.append(rec["rows"][lst][top])
        cv, cr = np.concatenate(cv), np.concatenate(cr)
        best = np.argsort(cv, kind="stable")[:k]
        vals.append(cv[best])
        ids.append(np.where(cr[best] >= 0,
                            source_ids[np.maximum(cr[best], 0)], -1))
    return np.stack(vals), np.stack(ids), short


TRIM_CASES = {
    # name: (index, internal_distance_dtype, k, prefilter or adaptive
    # probe budgets); "pq16" is the shared 20,000-row index, on which
    # recall is checked besides
    "pq-bf16-k40": ("pq", "bfloat16", 40, None),
    "pq-bf16-k10-prefilter": ("pq", "bfloat16", 10, "prefilter"),
    "pq-f32-k40": ("pq", "float32", 40, None),
    "pq-f32-k10-prefilter": ("pq", "float32", 10, "prefilter"),
    "pq16-recall": ("pq16", "float32", 10, None),
    "flat-k40": ("flat", "float32", 40, None),
    "flat-k10-prefilter": ("flat", "float32", 10, "prefilter"),
    "pq-f32-k40-budget": ("pq", "float32", 40, "budget"),
    "flat-k40-budget": ("flat", "float32", 40, "budget"),
}


@pytest.mark.parametrize("case", list(TRIM_CASES))
def test_listmajor_trim_is_exact(case, trim_data, trim_pq, trim_flat,
                                 dataset, truth10, index16, monkeypatch):
    """The list-major trim keeps each (query, probed list)'s exact top-kk,
    ties to the lower slot, and the merge then takes the best k: the
    answers equal a NumPy reference built from the engine's own scores,
    including lists with fewer than kk live rows and pairs dropped by
    adaptive probe budgets. Every IVF-PQ trim_engine name that runs the
    XLA trim returns the same bits."""
    from raft_tpu.neighbors import ivf_flat

    which, idd, k, extra = TRIM_CASES[case]
    _, queries, keep = trim_data
    filtered = extra == "prefilter"
    prefilter = keep if filtered else None
    budget = ({"adaptive": True, "budget_tau": 0.5} if extra == "budget"
              else {})
    index = {"pq": trim_pq, "pq16": index16, "flat": trim_flat}[which]
    n_probes = 8
    if which == "pq16":
        queries, n_probes = dataset[1], 16
    if which == "flat":
        jitted = ivf_flat._search_impl_listmajor

        def run(trim):
            return ivf_flat.search(
                ivf_flat.SearchParams(n_probes=n_probes, engine="list",
                                      **budget),
                index, queries, k, prefilter=prefilter)
    else:
        jitted = ivf_pq._search_impl_recon8_listmajor

        def run(trim):
            return ivf_pq.search(
                ivf_pq.SearchParams(n_probes=n_probes,
                                    score_mode="recon8_list",
                                    internal_distance_dtype=idd,
                                    trim_engine=trim, **budget),
                index, queries, k, prefilter=prefilter)

        d0, i0 = run("approx")
        for trim in ("auto", "exact"):
            d, i = run(trim)
            np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
            np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))

    rec = _spy_trim(monkeypatch, jitted)
    try:
        d, i = (np.asarray(x) for x in run("auto"))
    finally:
        monkeypatch.undo()
        jitted.clear_cache()
    ref_d, ref_i, short = _trim_reference(rec, len(queries), n_probes, k,
                                          np.asarray(index.source_ids))
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_array_equal(d, ref_d)
    if filtered:
        assert np.all(keep[i[i >= 0]])
    assert rec["pv"].all() != bool(budget), "budgets masked no pair"
    if which != "pq16":
        assert short > 0, "no probed list shorter than kk"
        return
    assert recall(i, truth10) >= 0.45  # measured 0.505
    assert np.all(np.diff(d, axis=1) >= -1e-5)
    assert i.min() >= 0
    # "exact" still names the list-major engine's trim
    with pytest.raises(ValueError, match="exact"):
        ivf_pq.search(
            ivf_pq.SearchParams(score_mode="recon8", trim_engine="exact"),
            index, queries, 10,
        )


def test_listmajor_setup_impl_equivalence(dataset, truth10, index16, monkeypatch):
    """The tuned setup impls (counting inversion, one-hot query rows) must
    not change the list-major engine's results: invert_impl=count and
    qs_impl=onehot_f32h are bit-preserving by construction (counting
    tables are bit-identical, f32-highest one-hot reproduces the gather),
    and onehot_bf16 may only move near-ties (overlap gate)."""
    from raft_tpu.core import tuned

    _, queries = dataset
    p = ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list")
    d_ref, i_ref = ivf_pq.search(p, index16, queries, 10)
    i_ref = np.asarray(i_ref)

    def force(invert, qs):
        real = tuned.get_choice

        def fake(key, allowed, default):
            if key == "invert_impl":
                return invert
            if key == "listmajor_qs_impl":
                return qs
            return real(key, allowed, default)

        monkeypatch.setattr(tuned, "get_choice", fake)
        out = ivf_pq.search(p, index16, queries, 10)
        monkeypatch.setattr(tuned, "get_choice", real)
        return out

    d_c, i_c = force("count", "onehot_f32h")
    assert np.array_equal(np.asarray(i_c), i_ref)
    np.testing.assert_allclose(np.asarray(d_c), np.asarray(d_ref), rtol=1e-6)

    _, i_b = force("count", "onehot_bf16")
    i_b = np.asarray(i_b)
    overlap = np.mean(
        [len(set(i_b[r]) & set(i_ref[r])) / 10 for r in range(len(i_ref))]
    )
    assert overlap >= 0.95, f"bf16 one-hot moved results: overlap {overlap}"


# -- quantizer-refactor bit-identity goldens ----------------------------

def test_refactor_bit_identical_to_prerefactor_goldens():
    """PR 6 moved codebook training + encode into the shared quantizer
    layer (neighbors/quantizer.py). This pins the refactor to goldens
    captured from the PRE-refactor code (tests/goldens/
    ivf_pq_prerefactor.json): codes, codebooks and all three engines'
    search results must stay BIT-identical. Any drift here means the
    'refactor' changed numerics and is a bug by definition."""
    import hashlib
    import json
    import os

    gold_path = os.path.join(os.path.dirname(__file__), "goldens",
                             "ivf_pq_prerefactor.json")
    with open(gold_path) as f:
        gold = json.load(f)
    data, _ = make_blobs(2000, 32, n_clusters=8, cluster_std=0.6, seed=5)
    data = np.asarray(data, np.float32)
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=4), data,
        seed=0)
    assert hashlib.sha256(
        np.asarray(idx.codes).tobytes()).hexdigest() == gold["codes_sha"]
    assert hashlib.sha256(
        np.asarray(idx.pq_centers, np.float32).tobytes()
    ).hexdigest() == gold["pq_centers_sha"]
    assert hashlib.sha256(
        np.asarray(idx.centers, np.float32).tobytes()
    ).hexdigest() == gold["centers_sha"]
    for mode in ("recon8", "recon8_list", "lut"):
        v, i = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=8, score_mode=mode,
                                internal_distance_dtype="float32"),
            idx, data[:8], 5)
        assert np.asarray(v, np.float32).tolist() == gold[mode]["values"], mode
        assert np.asarray(i, np.int32).tolist() == gold[mode]["ids"], mode
    # per-cluster codebooks cover the second trainer + encode path
    idx2 = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4,
                           codebook_kind="per_cluster"), data[:1000], seed=3)
    assert hashlib.sha256(
        np.asarray(idx2.codes).tobytes()).hexdigest() == gold["pc_codes_sha"]
    v, i = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=4, score_mode="lut",
                            internal_distance_dtype="float32"),
        idx2, data[:5], 4)
    assert np.asarray(v, np.float32).tolist() == gold["pc_lut"]["values"]
    assert np.asarray(i, np.int32).tolist() == gold["pc_lut"]["ids"]

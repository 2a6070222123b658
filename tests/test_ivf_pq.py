"""IVF-PQ tests: recall-gated against brute force (mirrors
cpp/test/neighbors/ann_ivf_pq.cuh:164-265 semantics: recall floor with
tolerance, serialization roundtrip inside fixtures)."""

import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, ivf_pq
from raft_tpu.random import make_blobs

from _ivf_pq_data import dataset, index16, recall, truth10  # noqa: F401


@pytest.fixture(scope="module")
def index32(dataset):
    """Shared pq_dim=32 index (the middle-quantization config): three
    tests read it, none mutates it — one build instead of three
    (full-suite cost discipline, VERDICT r3 #8)."""
    data, _ = dataset
    return ivf_pq.build(ivf_pq.IndexParams(n_lists=50, pq_dim=32), data)


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

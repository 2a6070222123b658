"""Integer fused scans (ISSUE 11): exact-agreement + dispatch suite.

The int8 PQ-recon list scan (`fused_list_topk_int8`, dispatch strategy
"fused_int8") on the TPU integer datapath: int8 x int8 -> int32 on the
MXU, per-row dequant, exact partial top-k — it must return the same
neighbors as the XLA int8 trim (same `_quantize_query_rows`
quantization; both trims exact), with scores equal to within the f32
rounding of the one add the two trims order differently.

Everything runs the kernels in interpret mode on CPU (the repo-wide
Pallas testing convention).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.matrix.select_k import (
    INT8_SCAN_KEY,
    list_scan_select_k,
    resolve_int8_trim_strategy,
)
from raft_tpu.neighbors import ivf_pq


def _grid(rng, shape, lo=-8, hi=8):
    return rng.integers(lo, hi, shape).astype(np.float32)


# -- int8 list kernel ---------------------------------------------------


def test_fused_list_topk_int8_matches_oracle(rng):
    """Per-(chunk row, list) exact top-k straight from the int8 kernel:
    int32 MXU accumulation, per-row dequant, deterministic
    smaller-slot ties."""
    from raft_tpu.ops.fused_scan import fused_list_topk_int8

    n_lists, L, rot, chunk, k = 5, 256, 24, 8, 16
    store = rng.integers(-127, 128, (n_lists, L, rot)).astype(np.int8)
    base = (store.astype(np.float32) ** 2).sum(2)[:, None, :]
    for l in range(n_lists):
        base[l, 0, L - 1 - l * 13:] = np.inf
    q8 = rng.integers(-127, 128, (11, chunk, rot)).astype(np.int8)
    scale = (rng.random((11, chunk, 1)).astype(np.float32) + 0.1) / 127.0
    lof = rng.integers(0, n_lists, 11).astype(np.int32)
    vals, slots = fused_list_topk_int8(
        jnp.asarray(lof), jnp.asarray(q8), jnp.asarray(store),
        jnp.asarray(base), jnp.asarray(scale), k, interpret=True,
    )
    vals, slots = np.asarray(vals), np.asarray(slots)
    assert vals.shape == (11, chunk, 128)  # kbuf = fused_kbuf(16)
    for c in range(11):
        idots = q8[c].astype(np.int32) @ store[lof[c]].astype(np.int32).T
        dots = idots.astype(np.float32) * scale[c]
        d = base[lof[c], 0][None, :] - 2.0 * dots
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        np.testing.assert_array_equal(slots[c][:, :k], order)
        np.testing.assert_array_equal(
            vals[c][:, :k], np.take_along_axis(d, order, axis=1)
        )


def test_list_scan_dispatch_validation():
    """The dispatch door rejects mismatched operands loudly: unknown
    strategies, missing/misplaced q_scale, non-int8 operands."""
    lof = jnp.zeros((1,), jnp.int32)
    q = jnp.zeros((1, 8, 16), jnp.float32)
    store = jnp.zeros((1, 128, 16), jnp.float32)
    base = jnp.zeros((1, 1, 128), jnp.float32)
    scale = jnp.ones((1, 8, 1), jnp.float32)
    with pytest.raises(ValueError, match="strategy"):
        list_scan_select_k(lof, q, store, base, 5, strategy="warpsort")
    with pytest.raises(ValueError, match="q_scale"):
        list_scan_select_k(lof, q, store, base, 5, strategy="fused_int8")
    with pytest.raises(ValueError, match="q_scale"):
        list_scan_select_k(lof, q, store, base, 5, strategy="fused",
                           q_scale=scale)
    with pytest.raises(ValueError, match="int8"):
        list_scan_select_k(lof, q, store, base, 5, strategy="fused_int8",
                           q_scale=scale, interpret=True)


# -- int8 fused trim vs the XLA int8 trim -------------------------------


@pytest.fixture(scope="module")
def pq_int8_setup():
    rng = np.random.default_rng(7)
    data = _grid(rng, (4000, 32))
    q = _grid(rng, (16, 32))
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, kmeans_n_iters=4, pq_dim=16), data
    )
    return data, q, idx


@pytest.mark.parametrize("k", [1, 10, 100])
def test_ivf_pq_int8_fused_bit_agrees_with_pallas_trim(pq_int8_setup, k):
    """The acceptance pin: the fused int8 recon scan agrees with the XLA
    int8 trim across the k ladder — the same neighbors in the same order
    (same quantization, both trims exact), and f32 distance values equal
    to the last bit but for the one re-associated add: the XLA trim sums
    (|q'|^2 - 2 q'.r) + |r|^2, the fused kernel (|r|^2 - 2 q'.r) + |q'|^2
    (measured: 12 of 1,600 values at k=100 one ulp apart, none at
    k=1, 10)."""
    _, q, idx = pq_int8_setup
    d_x, i_x = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list",
                            trim_engine="approx", score_dtype="int8"),
        idx, q, k,
    )
    d_f, i_f = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, trim_engine="fused",
                            score_dtype="int8"),
        idx, q, k,
    )
    np.testing.assert_array_max_ulp(np.asarray(d_x), np.asarray(d_f),
                                    maxulp=1)
    i_f = np.asarray(i_f)
    np.testing.assert_array_equal(np.asarray(i_x), i_f)
    # determinism: bit-identical across calls
    d_f2, i_f2 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, trim_engine="fused",
                            score_dtype="int8"),
        idx, q, k,
    )
    np.testing.assert_array_equal(np.asarray(d_f), np.asarray(d_f2))
    np.testing.assert_array_equal(i_f, np.asarray(i_f2))


def test_ivf_pq_int8_fused_prefilter_excludes(pq_int8_setup, rng):
    """valid-mask/tombstone exclusion: a prefilter must be invisible to
    the int8 fused trim's selection — no filtered id ever returns, and
    the surviving results match the XLA int8 trim's."""
    _, q, idx = pq_int8_setup
    keep = rng.random(4000) < 0.5
    d_f, i_f = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, trim_engine="fused",
                            score_dtype="int8"),
        idx, q, 10, prefilter=keep,
    )
    d_p, i_p = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list",
                            trim_engine="approx", score_dtype="int8"),
        idx, q, 10, prefilter=keep,
    )
    i_f = np.asarray(i_f)
    assert not np.isin(i_f[i_f >= 0], np.where(~keep)[0]).any()
    np.testing.assert_array_equal(np.asarray(d_f), np.asarray(d_p))


def test_ivf_pq_fused_kb_monotonic_growth(pq_int8_setup):
    """fused_kb grows monotonically with k and never shrinks — the
    silent-truncation bug class the ivf_flat lazy store pinned."""
    data, q, _ = pq_int8_setup
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, kmeans_n_iters=4, pq_dim=16), data
    )
    assert idx.fused_kb is None
    sp = lambda: ivf_pq.SearchParams(n_probes=4, trim_engine="fused",
                                     score_dtype="int8")
    ivf_pq.search(sp(), idx, q, 10)
    assert idx.fused_kb == 128
    ivf_pq.search(sp(), idx, q, 200)
    assert idx.fused_kb == 256
    ivf_pq.search(sp(), idx, q, 5)  # smaller k must NOT shrink it
    assert idx.fused_kb == 256


# -- dispatch contract --------------------------------------------------


def test_integer_dispatch_resolution(monkeypatch):
    """The int8 tuned key promotes its fused kernel ONLY on a TPU
    backend where the geometry fits; explicit strategies always win;
    out-of-envelope auto falls back silently."""
    from raft_tpu.core import config, tuned

    # explicit wins regardless of backend/tuned state
    assert resolve_int8_trim_strategy(256, 32, 10,
                                      strategy="fused_int8") == "fused_int8"
    with pytest.raises(ValueError, match="strategy"):
        resolve_int8_trim_strategy(256, 32, 10, strategy="warpsort")
    # no tuned winner -> no promotion
    assert resolve_int8_trim_strategy(256, 32, 10) is None
    monkeypatch.setitem(tuned._load(), INT8_SCAN_KEY, "fused_int8")
    # CPU backend: a chip-measured winner must not flip interpret mode
    assert resolve_int8_trim_strategy(256, 32, 10) is None
    monkeypatch.setattr(config, "is_tpu_backend", lambda: True)
    assert resolve_int8_trim_strategy(256, 32, 10) == "fused_int8"
    # past the envelope: auto falls back, never crashes
    assert resolve_int8_trim_strategy(1 << 16, 4096, 10) is None


def test_explicit_integer_engines_raise_past_envelope(rng):
    """An EXPLICIT integer-engine request past the kernel's caps raises
    loudly instead of silently degrading — the same contract as every
    other fused call site."""
    data = _grid(rng, (2000, 32))
    q = _grid(rng, (4, 32))
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=8, kmeans_n_iters=4, pq_dim=16), data
    )
    with pytest.raises(ValueError, match="caps per-list"):
        ivf_pq.search(
            ivf_pq.SearchParams(n_probes=8, trim_engine="fused",
                                score_dtype="int8"),
            idx, q, 300,
        )


def test_ivf_pq_auto_trim_promotes_on_tuned_chip_winner(monkeypatch,
                                                        pq_int8_setup):
    """trim_engine='auto' + score_dtype='int8' resolves through the
    dispatch layer: the tuned chip winner flips the fused trim in
    (changing which engine runs — verified via fused_kb, which only the
    fused trim records), and without the key the default approx trim
    stays."""
    from raft_tpu.core import config, tuned

    data, q, _ = pq_int8_setup
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, kmeans_n_iters=4, pq_dim=16), data
    )
    sp = ivf_pq.SearchParams(n_probes=4, score_mode="recon8_list",
                             score_dtype="int8")
    assert sp.trim_engine == "auto"
    ivf_pq.search(sp, idx, q, 10)
    assert idx.fused_kb is None  # no tuned key: approx trim ran
    monkeypatch.setitem(tuned._load(), INT8_SCAN_KEY, "fused_int8")
    ivf_pq.search(sp, idx, q, 10)
    assert idx.fused_kb is None  # CPU backend: still no flip
    monkeypatch.setattr(config, "is_tpu_backend", lambda: True)
    ivf_pq.search(sp, idx, q, 10)
    assert idx.fused_kb == 128  # the fused int8 trim ran

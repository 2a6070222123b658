"""Pallas kernels vs the XLA reference paths (interpret mode on CPU).

Mirrors how cpp/test/distance/*.cu validate the tiled kernel against naive
implementations; here the oracle is the jnp path already validated against
numpy/scipy in test_distance.py.
"""

import numpy as np
import pytest

from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.distance.pairwise import _pairwise_impl
from raft_tpu.ops.fused_l2_argmin import fused_l2_argmin_pallas
from raft_tpu.ops.pairwise_pallas import METRIC_OPS, pairwise_tiled

_METRIC_TO_ENUM = {
    "l1": DistanceType.L1,
    "linf": DistanceType.Linf,
    "l2_unexpanded": DistanceType.L2Unexpanded,
    "l2_sqrt_unexpanded": DistanceType.L2SqrtUnexpanded,
    "canberra": DistanceType.Canberra,
    "kl_divergence": DistanceType.KLDivergence,
    "hamming": DistanceType.HammingUnexpanded,
}


@pytest.mark.parametrize("metric", sorted(METRIC_OPS))
def test_pairwise_tiled_matches_xla(metric, rng):
    m, n, k = 33, 47, 10  # deliberately unaligned -> exercises padding
    if metric in ("kl_divergence",):
        x = rng.random((m, k)).astype(np.float32) + 0.01
        y = rng.random((n, k)).astype(np.float32) + 0.01
        x /= x.sum(axis=1, keepdims=True)
        y /= y.sum(axis=1, keepdims=True)
    elif metric == "hamming":
        x = rng.integers(0, 3, (m, k)).astype(np.float32)
        y = rng.integers(0, 3, (n, k)).astype(np.float32)
    else:
        x = rng.standard_normal((m, k)).astype(np.float32)
        y = rng.standard_normal((n, k)).astype(np.float32)

    got = np.asarray(pairwise_tiled(x, y, metric, bm=16, bn=128, interpret=True))
    want = np.asarray(_pairwise_impl(x, y, _METRIC_TO_ENUM[metric]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_l2_argmin_matches_oracle(rng):
    m, n, k = 70, 300, 12  # n not a multiple of bn -> padded cols masked
    x = rng.standard_normal((m, k)).astype(np.float32)
    y = rng.standard_normal((n, k)).astype(np.float32)
    dist, idx = fused_l2_argmin_pallas(x, y, bm=16, bn=128, interpret=True)
    full = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(idx), full.argmin(axis=1))
    np.testing.assert_allclose(np.asarray(dist), full.min(axis=1), rtol=1e-4, atol=1e-4)


def test_fused_l2_argmin_sqrt(rng):
    x = rng.standard_normal((20, 8)).astype(np.float32)
    y = rng.standard_normal((50, 8)).astype(np.float32)
    dist, idx = fused_l2_argmin_pallas(x, y, bm=16, bn=128, sqrt=True, interpret=True)
    full = np.sqrt(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1))
    np.testing.assert_allclose(np.asarray(dist), full.min(axis=1), rtol=1e-4, atol=1e-4)


def test_fused_l2_argmin_tie_break_lowest_index(rng):
    # Duplicate rows of y in different lanes: lowest column index must win,
    # matching jnp.argmin semantics (the XLA path).
    y = rng.standard_normal((300, 8)).astype(np.float32)
    y[130] = y[5]
    y[257] = y[5]
    x = y[[5]]
    _, idx = fused_l2_argmin_pallas(x, y, bm=16, bn=128, interpret=True)
    assert int(np.asarray(idx)[0]) == 5


def test_dispatch_glue_routes_through_pallas(rng):
    # Force the production dispatch (use_pallas + fits_pallas + interpret
    # threading) on CPU via the test hooks.
    from raft_tpu import ops
    from raft_tpu.distance.pairwise import pairwise_distance
    from raft_tpu.distance.fused_l2_nn import fused_l2_nn_argmin

    x = rng.standard_normal((40, 16)).astype(np.float32)
    y = rng.standard_normal((90, 16)).astype(np.float32)
    want_l1 = np.abs(x[:, None, :] - y[None, :, :]).sum(-1)
    want_ham = (x[:, None, :] != y[None, :, :]).mean(-1)
    full = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)

    ops.set_pallas_override(True)
    ops.set_pallas_interpret(True)
    try:
        got = np.asarray(pairwise_distance(x, y, metric="cityblock"))
        np.testing.assert_allclose(got, want_l1, rtol=1e-5, atol=1e-5)
        got = np.asarray(pairwise_distance(x, y, metric="hamming"))
        np.testing.assert_allclose(got, want_ham, rtol=1e-5, atol=1e-5)
        idx = np.asarray(fused_l2_nn_argmin(x, y))
        np.testing.assert_array_equal(idx, full.argmin(axis=1))
    finally:
        ops.set_pallas_override(None)
        ops.set_pallas_interpret(False)


def test_fused_l2_argmin_exact_duplicate(rng):
    # x rows present in y must map to themselves with ~zero distance.
    y = rng.standard_normal((100, 16)).astype(np.float32)
    x = y[[3, 42, 99]]
    dist, idx = fused_l2_argmin_pallas(x, y, bm=16, bn=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(idx), [3, 42, 99])
    assert np.asarray(dist).max() < 1e-5

"""The plain reference: exact L2 k-NN, independent of raft_tpu, and the
comparison that decides `correct`.

The device screens every corpus row for each query with a float32
matmul at HIGHEST precision and keeps the best `SCREEN` candidates; the
host then ranks those candidates in float64 and breaks ties by id. A
true top-k row is never screened out: the screen's rounding (about
1e-6) is far below the gap between the k-th and the `SCREEN`-th
neighbour. It runs after the window, in blocks of `QBLOCK` queries by
row tiles of at most `TILE` rows, so it fits beside the corpus.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SCREEN = 64
QBLOCK = 512
TILE = 262_144


def _tile(n: int) -> int:
    """The largest divisor of n that is at most TILE."""
    best = 1
    for d in range(1, int(n ** 0.5) + 1):
        if n % d == 0:
            for c in (d, n // d):
                if c <= TILE:
                    best = max(best, c)
    return best


@functools.partial(jax.jit, static_argnames=("screen", "tile"))
def _screen(data, q, *, screen: int, tile: int):
    n, dim = data.shape
    tiles = data.reshape(n // tile, tile, dim)

    def step(carry, xs):
        best_v, best_i = carry
        t, x = xs
        d = jnp.sum(x * x, axis=1)[None, :] - 2.0 * jnp.dot(
            q, x.T, precision=jax.lax.Precision.HIGHEST)
        v, i = jax.lax.top_k(-d, screen)
        cv = jnp.concatenate([best_v, v], axis=1)
        ci = jnp.concatenate([best_i, i + t * tile], axis=1)
        v, j = jax.lax.top_k(cv, screen)
        return (v, jnp.take_along_axis(ci, j, axis=1)), None

    m = q.shape[0]
    init = (jnp.full((m, screen), -jnp.inf, jnp.float32),
            jnp.full((m, screen), -1, jnp.int32))
    (_, ids), _ = jax.lax.scan(step, init, (jnp.arange(n // tile), tiles))
    return ids


def _rows64(data, ids: np.ndarray) -> np.ndarray:
    """Corpus rows `ids` (any shape) as float64 on the host."""
    flat = np.clip(ids.reshape(-1), 0, data.shape[0] - 1)
    rows = np.asarray(jax.device_get(data[jnp.asarray(flat)]), np.float64)
    return rows.reshape(ids.shape + (data.shape[1],))


def sqdist64(data, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Float64 squared L2 distance of each query to each of its ids;
    an id outside the corpus (such as -1) is infinitely far."""
    q = np.asarray(queries, np.float64)
    d = np.sum((_rows64(data, ids) - q[:, None, :]) ** 2, axis=2)
    return np.where((ids >= 0) & (ids < data.shape[0]), d, np.inf)


def exact_knn(data, queries: np.ndarray, k: int):
    """(ids, float64 squared distances), each (m, k), best first."""
    q = np.asarray(queries, np.float32)
    m = q.shape[0]
    tile = _tile(int(data.shape[0]))
    screen = min(SCREEN, tile)
    cand = np.empty((m, screen), np.int64)
    for lo in range(0, m, QBLOCK):
        block = np.zeros((QBLOCK, q.shape[1]), np.float32)
        part = q[lo:lo + QBLOCK]
        block[:len(part)] = part
        ids = _screen(data, jnp.asarray(block), screen=screen, tile=tile)
        cand[lo:lo + len(part)] = np.asarray(ids)[:len(part)]
    d = sqdist64(data, q, cand)
    order = np.lexsort((cand, d), axis=1)[:, :k]
    return (np.take_along_axis(cand, order, axis=1),
            np.take_along_axis(d, order, axis=1))


def compare(data, queries: np.ndarray, vals: np.ndarray, ids: np.ndarray,
            k: int) -> dict:
    """The numbers that decide `correct` for one set of served rows:

    recall    mean share of each row's exact top-k ids that were served;
    dist_err  widest gap between a served distance and the float64
              squared distance of the id served with it, over the row's
              exact k-th distance (the row's own scale).
    """
    ids = np.asarray(ids, np.int64)
    vals = np.asarray(vals, np.float64)
    ref_ids, ref_d = exact_knn(data, queries, k)
    hits = [len(set(ids[i].tolist()) & set(ref_ids[i].tolist()))
            for i in range(len(ids))]
    true_d = sqdist64(data, queries, ids)
    scale = np.maximum(ref_d[:, k - 1], 1e-12)[:, None]
    with np.errstate(invalid="ignore"):  # inf - inf: an id off the corpus
        err = np.abs(vals - true_d) / scale
    return {"recall": float(np.sum(hits) / (k * len(ids))),
            "dist_err": float(np.max(np.where(np.isfinite(err), err,
                                              np.inf)))}


def judge(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "rule", "ok"}} for every limited
    number; a missing or non-finite number fails."""
    out = {}
    for name, (rule, limit) in limits.items():
        v = numbers.get(name)
        ok = v is not None and np.isfinite(v) and (
            v >= limit if rule == ">=" else v <= limit)
        out[name] = {"value": v, "limit": limit, "rule": rule,
                     "ok": bool(ok)}
    return out

"""Deep-shaped corpus and queries, made on the device from the seed.

The benchmark's own generator, independent of raft_tpu: Gaussian blobs
with centers uniform in [center_lo, center_hi] and `spread` standard
deviation, rows optionally scaled to unit norm (Deep1B's descriptors
are). Corpus and queries share the centers, so queries come from the
corpus distribution. A configuration may fix `data.corpus_seed`: its
corpus and query pool then come from that seed, and the run's seed only
orders and samples the requests (see `corpus_seed`). Rows are made in
blocks of gcd(rows, queries,
`BLOCK`) rows inside one jitted call, so a 10M-row corpus never holds
more than one block of temporaries beside its output.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

BLOCK = 10_000


def key_for(seed: int):
    """A PRNG key from any whole number: the low and high 32 bits both
    reach the key, so seeds past 2**32 stay distinct."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


@functools.partial(jax.jit, static_argnames=(
    "rows", "nq", "dim", "blobs", "lo", "hi", "spread", "unit_norm", "block"))
def _make(key, *, rows, nq, dim, blobs, lo, hi, spread, unit_norm, block):
    kc, kd, kq = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (blobs, dim), jnp.float32, lo, hi)

    def draw(base):
        def one(i):
            kl, kn = jax.random.split(jax.random.fold_in(base, i))
            lab = jax.random.randint(kl, (block,), 0, blobs)
            x = centers[lab] + spread * jax.random.normal(
                kn, (block, dim), jnp.float32)
            if unit_norm:
                x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
            return x
        return one

    data = jax.lax.map(draw(kd), jnp.arange(rows // block))
    queries = jax.lax.map(draw(kq), jnp.arange(nq // block))
    return data.reshape(rows, dim), queries.reshape(nq, dim)


def corpus_seed(cfg: dict, seed: int) -> int:
    """The seed of the corpus and query pool: the configuration's fixed
    `corpus_seed` where it states one, else the run's seed. IVF-PQ fixes
    it because the program sizes its index programs by the data (the
    longest list), so every new corpus recompiles for about 75 s and
    splits set-up and build rate into two modes (PERF.md)."""
    return int(cfg["data"].get("corpus_seed", seed))


def make(cfg: dict, seed: int):
    """(corpus, queries) as float32 device arrays, ready."""
    rows, nq = int(cfg["rows"]), int(cfg["queries"])
    d = cfg["data"]
    out = _make(key_for(corpus_seed(cfg, seed)), rows=rows, nq=nq, block=math.gcd(rows, nq, BLOCK),
                dim=int(cfg["dim"]), blobs=int(d["blobs"]),
                lo=float(d["center_lo"]), hi=float(d["center_hi"]),
                spread=float(d["spread"]), unit_norm=bool(d["unit_norm"]))
    return jax.block_until_ready(out)

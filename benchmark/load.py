"""Load generation: the schedule a traffic file asks for, from the seed.

One general generator reads every traffic file. Keys:

    loop            "closed" (the next call waits for the last) or
                    "open" (requests go out on a schedule, whatever the
                    server does)
    rows_per_call   closed: query rows in each call
    rate_per_s      open: mean arrivals per second (Poisson)
    one_row_share   open: share of requests that carry one query row
    rows_lo/rows_hi open: the other requests' rows, log-uniform between
    k               neighbours asked for
    query_pool      rows of the query set that requests draw from
    queue_rows      open: the admission queue's room, in rows
    check_rows      closed: rows compared with the reference
    check_requests  open: requests compared with the reference

An open schedule gives every seed the same multiset of gaps (quantiles
of the exponential distribution) and of request sizes, in an order and
with query rows drawn from the seed: the seed changes which work comes
when, not how much work there is.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator from any whole number; streams of one seed differ."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


class OpenSchedule(NamedTuple):
    due_s: np.ndarray        # (n,) seconds from the window's start
    rows: List[np.ndarray]   # per request, its rows of the query pool


def open_schedule(traffic: dict, seed: int, seconds: float) -> OpenSchedule:
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps) - gaps  # the first request is due at 0
    n_one = int(round(float(traffic["one_row_share"]) * n))
    m = n - n_one
    lo, hi = int(traffic["rows_lo"]), int(traffic["rows_hi"])
    v = np.exp(np.log(lo) + (np.arange(m) + 0.5) / m * np.log((hi + 1) / lo))
    sizes = np.concatenate([np.ones(n_one, np.int64),
                            np.clip(np.floor(v), lo, hi).astype(np.int64)])
    sizes = rng.permutation(sizes)
    pool = int(traffic["query_pool"])
    rows = [rng.integers(0, pool, int(s)) for s in sizes]
    return OpenSchedule(due, rows)


def lateness_ms(late_s: np.ndarray) -> dict:
    """How late the generator sent, from each request's due time."""
    late = np.asarray(late_s, np.float64) * 1e3
    return {"late_p50_ms": float(np.percentile(late, 50)),
            "late_p99_ms": float(np.percentile(late, 99)),
            "late_max_ms": float(late.max())}

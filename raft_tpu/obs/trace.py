"""Request-scope tracing for the serve path.

The serve engine's spans answer "where did this *batch*'s wall-clock
go"; they cannot answer the tail-latency question "where did this
*request*'s 40 ms go" — queue wait vs coalesce linger vs device time vs
scatter are different fixes, and p99 work needs the attribution per
request, not per batch. A `TraceCtx` minted at `submit()` rides the
`_Request` -> `Batch` -> dispatch -> scatter path and stamps a
monotonic mark at each stage boundary:

    admitted    request validated and queued (inside submit)
    coalesced   popped off the queue into a micro-batch
    dispatched  batch chosen a bucket / compile key, entering device call
    fenced      `block_until_ready` returned (device work complete)
    scattered   this request's reply sliced out and delivered

Consecutive-mark deltas aggregate into per-stage histograms
(`serve.stage.queue_wait_s`, `.linger_s`, `.device_s`, `.scatter_s`) —
the deltas telescope, so their sum IS the end-to-end latency, which is
what makes the attribution trustworthy — and each completed request
lands one "trace" event on the bus carrying its stage attrs (bucket, k,
probe plan, compile hit/miss, coverage, outcome).

Determinism: trace ids are 64-bit values from a seeded counter run
through a splitmix64 finalizer — no wall-clock, no randomness — so a
replayed drill mints the identical id sequence and tests can pin traces
exactly. `obs.reset()` resets the mint.

Chaos: every stamp passes through `faults.fault_point(STAMP_SITE)`; an
injected corruption marks the ctx dead and the request degrades to
*untraced* — results stay bit-identical, because tracing only ever
observes the request, never steers it.

For a timeline, record a profiler trace (`obs.trace_session`): the
serve worker's spans (`raft_tpu.serve.*`) sit there beside the device
ops, on one clock.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from raft_tpu.core import faults
from raft_tpu.obs import bus as _bus_mod
from raft_tpu.obs import registry as _reg_mod

#: fault-injection site guarding every stage stamp (chaos drills corrupt
#: it to prove a broken tracer degrades to untraced, bit-identical serving)
STAMP_SITE = "serve.trace.stamp"

#: stage marks in pipeline order; deltas between consecutive present
#: marks telescope to the end-to-end latency
STAGES = ("admitted", "coalesced", "dispatched", "fenced", "scattered")

#: histogram fed by each consecutive-stage delta
STAGE_HISTOGRAMS = {
    ("admitted", "coalesced"): "serve.stage.queue_wait_s",
    ("coalesced", "dispatched"): "serve.stage.linger_s",
    ("dispatched", "fenced"): "serve.stage.device_s",
    ("fenced", "scattered"): "serve.stage.scatter_s",
}

_MASK64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    """Finalizer from splitmix64: bijective on 64-bit ints, so distinct
    (seed, n) pairs give distinct, well-scattered ids."""
    x = (x + 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


def trace_id(seed: int, n: int) -> int:
    """The n-th (1-based) id minted under `seed` — a pure function, so
    tests can pin the exact ids a replayed run must produce."""
    return _splitmix64(((int(seed) & _MASK64) << 20) ^ int(n))


class _Mint:
    """Seeded, lock-serialized id source. No wall-clock, no randomness:
    the i-th id after a reset is always `trace_id(seed, i)`."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = int(seed)
        self._n = 0

    def mint(self) -> int:
        with self._lock:
            self._n += 1
            return trace_id(self._seed, self._n)

    def reset(self, seed: Optional[int] = None) -> None:
        with self._lock:
            if seed is not None:
                self._seed = int(seed)
            self._n = 0


_MINT = _Mint()


def reset(seed: Optional[int] = None) -> None:
    """Restart the id mint (wired into `obs.reset()` so a replayed
    drill re-mints the identical id sequence)."""
    _MINT.reset(seed)


class TraceCtx:
    """Per-request trace state riding the `_Request`. Mutated only from
    the single thread currently owning the request (submitter until
    queued, then the worker that popped it), so no lock is needed."""

    __slots__ = ("trace_id", "marks", "attrs", "dead")

    def __init__(self, tid: int):
        self.trace_id = int(tid)
        self.marks: List[tuple] = []  # [(stage, monotonic_t)] in stamp order
        self.attrs: dict = {}
        self.dead = False

    def stamp(self, stage: str, **attrs) -> None:
        """Record one stage mark. An injected fault at STAMP_SITE kills
        the ctx (marks discarded, later stamps no-ops): the request
        degrades to untraced but is otherwise untouched. Dead-check
        BEFORE the fault hook so a dead ctx stops consuming injection
        arms — drills stay deterministic per request, not per stamp."""
        if self.dead:
            return
        try:
            faults.fault_point(STAMP_SITE)
        except faults.FaultInjected:
            self.dead = True  # raftlint: disable=publication-safety  -- TraceCtx is single-owner: exactly one thread holds a request's ctx at a time (class docstring)
            self.marks = []
            self.attrs = {}
            return
        self.marks.append((str(stage), time.monotonic()))  # raftlint: disable=shared-state-race  -- single-owner handoff: the ctx travels with the request, never shared concurrently
        if attrs:
            self.attrs.update(attrs)  # raftlint: disable=shared-state-race  -- single-owner handoff, same contract as marks above


def begin() -> Optional[TraceCtx]:
    """Mint a ctx for one request; None when obs is disabled (the
    untraced fast path costs this one call and a branch)."""
    from raft_tpu import obs

    if not obs.enabled():
        return None
    return TraceCtx(_MINT.mint())


def complete(ctx: Optional[TraceCtx], outcome: str = "ok", **attrs) -> None:
    """Close a request's trace: observe every consecutive-stage delta
    into its histogram and publish one "trace" bus event. Timestamps
    live under the event's "marks" field so replay-identity tests can
    strip them the way they strip "t"/"dur_s"."""
    if ctx is None or ctx.dead:
        return
    if attrs:
        ctx.attrs.update(attrs)
    times = dict(ctx.marks)
    for pair, hist in STAGE_HISTOGRAMS.items():
        a, b = pair
        if a in times and b in times:
            _reg_mod.GLOBAL.histogram(hist).observe(times[b] - times[a])
    _bus_mod.GLOBAL.publish(
        "trace",
        trace_id=ctx.trace_id,
        outcome=str(outcome),
        stages=[s for s, _ in ctx.marks],
        marks={s: t for s, t in ctx.marks},
        worker=threading.current_thread().name,
        **ctx.attrs,
    )

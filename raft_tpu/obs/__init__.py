"""raft_tpu.obs — library-wide observability.

The cross-cutting layer the ROADMAP's serving/perf work reads its
numbers from: a thread-safe metric registry (counters / gauges /
histograms), structured nested spans, and an ordered event bus that the
comms collectives, MNMG drivers, neighbors entry points, the serving
engine, `core.faults` chaos injections, and `core.logger` all feed.
Exporters render the joined state as a JSON snapshot, Prometheus
exposition text, or a `jax.profiler` trace session, in which every span
and every garbage collection (`raft_tpu.gc`) shows beside the device
ops; `python -m raft_tpu.obs.report` turns a snapshot into a
human-readable run report.

Gating: everything is OFF by default. Enable with `RAFT_TPU_OBS=1` in
the environment or `obs.enable()` at runtime. Disabled, every
instrumentation hook is one module-attribute read and a branch —
measured within noise of the pre-instrumentation library (see
docs/observability.md) — and traced programs are byte-identical either
way (instrumentation is host-side only; nothing is ever inserted into
a jaxpr). The stage names of the search programs (`jax.named_scope`,
e.g. `ivf_pq.scan`) are not gated: they are op metadata, always there.

Enabled, a `gc.callbacks` hook writes every garbage collection into the
profiler trace as a `raft_tpu.gc` span carrying its generation;
`obs.disable()` removes it.

Counting semantics under jit: collective instruments count at TRACE
time (XLA owns execution; a cached executable re-runs without
re-tracing), so "comms.allreduce.calls" answers "how many allreduce ops
did the programs traced during this window contain", which is the
deterministic number a test can pin. Spans and serve/fault events are
host-side and count per call.

Public surface:

    obs.enable() / obs.disable() / obs.enabled()
    obs.registry() -> Registry       obs.counter/gauge/histogram(name)
    obs.bus() -> EventBus            obs.event(kind, **fields)
    obs.span(name, **attrs)          obs.capture_spans()
    obs.span_cost(flops=, bytes=)    (analytic-cost hook; obs.perf formulas)
    obs.trace_range / obs.annotate   (re-exported from core.tracing)
    obs.collective(op, x, axis=..., world=...)  (comms hook)
    obs.snapshot() / obs.save_snapshot(path)
    obs.render_prometheus(...) / obs.render_registry_prometheus()
    obs.trace_session(logdir)
    obs.reset()
"""

from __future__ import annotations

import gc
import os

import jax

# submodule-path imports keep this package safe to import from inside
# raft_tpu.core's own init (core.faults -> obs -> core.tracing)
from raft_tpu.core.tracing import annotate, trace_range  # noqa: F401
from raft_tpu.obs import bus as _bus_mod
from raft_tpu.obs import registry as _reg_mod
from raft_tpu.obs.export import (  # noqa: F401
    prom_name,
    render_prometheus,
    render_registry_prometheus,
    save_snapshot,
    snapshot,
    trace_session,
)
from raft_tpu.obs import flight, ledger, perf, slo, trace  # noqa: F401
from raft_tpu.obs.registry import Counter, Gauge, Histogram, Registry  # noqa: F401
from raft_tpu.obs.spans import (  # noqa: F401
    NULL_SPAN,
    SpanCapture,
    capture_spans,
    current_span,
    open_spans,
    span_impl,
)
from raft_tpu.obs.trace import TraceCtx  # noqa: F401

ENV_FLAG = "RAFT_TPU_OBS"

_ENABLED = False
_LOG_HANDLER = None


def enabled() -> bool:
    return _ENABLED


def enable(flag: bool = True) -> None:
    """Turn observability on (or off with `flag=False`). Enabling also
    bridges `core.logger` records onto the event bus; disabling removes
    the bridge. Idempotent."""
    global _ENABLED
    _ENABLED = bool(flag)
    _bridge_logger(_ENABLED)
    _gc_hook(_ENABLED)
    if _ENABLED:
        # RAFT_TPU_FLIGHT_DIR auto-arms the crash flight recorder
        flight.maybe_env_install()


def disable() -> None:
    enable(False)


def _bridge_logger(install: bool) -> None:
    """Install/remove the logging.Handler that routes raft_tpu log
    records to the bus as kind="log" events. Lives here (not in
    core/logger) so the logger has zero obs dependency and the disabled
    path pays nothing."""
    global _LOG_HANDLER
    import importlib
    import logging

    # NOT `import raft_tpu.core.logger as m`: the core package re-binds
    # the attribute `logger` to the Logger OBJECT, shadowing the module
    # for every attribute-based import form
    _logger_mod = importlib.import_module("raft_tpu.core.logger")

    if install:
        if _LOG_HANDLER is None:
            class _BusHandler(logging.Handler):
                def emit(self, record):
                    try:
                        event("log", level=record.levelname,
                              logger=record.name, msg=record.getMessage())
                    except Exception:
                        self.handleError(record)

            _LOG_HANDLER = _BusHandler()
        if _LOG_HANDLER not in _logger_mod.logger.handlers:
            _logger_mod.logger.addHandler(_LOG_HANDLER)
    elif _LOG_HANDLER is not None:
        _logger_mod.logger.removeHandler(_LOG_HANDLER)


_GC_OPEN = None  # profiler annotation of the collection in progress


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook: each collection is one `raft_tpu.gc`
    profiler span. Collections never overlap (the collector holds the
    GIL throughout), so one open slot suffices; the hook takes no lock,
    since a collection can start inside any locked section."""
    global _GC_OPEN
    if phase == "start":
        _GC_OPEN = jax.profiler.TraceAnnotation(
            "raft_tpu.gc", generation=int(info.get("generation", -1)))
        _GC_OPEN.__enter__()
    elif _GC_OPEN is not None:
        ann, _GC_OPEN = _GC_OPEN, None
        ann.__exit__(None, None, None)


def _gc_hook(install: bool) -> None:
    if install:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
    elif _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def registry() -> Registry:
    return _reg_mod.GLOBAL


def bus() -> _bus_mod.EventBus:
    return _bus_mod.GLOBAL


def counter(name: str) -> Counter:
    return _reg_mod.GLOBAL.counter(name)


def gauge(name: str) -> Gauge:
    return _reg_mod.GLOBAL.gauge(name)


def histogram(name: str) -> Histogram:
    return _reg_mod.GLOBAL.histogram(name)


def event(kind: str, **fields):
    """Publish one event when enabled; returns its seq (None when
    disabled). The one hook every instrumented site calls."""
    if not _ENABLED:
        return None
    return _bus_mod.GLOBAL.publish(kind, **fields)


def span(name: str, **attrs):
    """Nested timed scope (see `obs.spans`). Disabled: yields an inert
    singleton without entering a generator frame."""
    if not _ENABLED:
        return _NULL_CTX
    return span_impl(name, **attrs)


class _ReusableNullCtx:
    """Allocation-free disabled-path context manager (a fresh
    generator per call would dominate the disabled cost)."""

    __slots__ = ()

    def __enter__(self):
        return NULL_SPAN

    def __exit__(self, *exc):
        return False


_NULL_CTX = _ReusableNullCtx()


def spanned(name: str, **attrs):
    """Decorator form of `span` (the obs counterpart of
    `tracing.annotate`): wraps entry points so every call lands one
    timed span. Disabled, the wrapper costs one attribute read and a
    branch before tail-calling the target."""
    import functools

    def deco(f):
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            if not _ENABLED:
                return f(*args, **kwargs)
            with span_impl(name, **attrs):
                return f(*args, **kwargs)

        return wrapper

    return deco


def span_cost(flops=None, bytes=None, dtype=None, flops_by_dtype=None,
              **attrs):
    """Charge analytic cost (an `obs.perf` formula's kwargs) to the
    innermost open span on this thread; no-op when disabled or outside
    any span. Composite formulas pass their per-dtype flops split as
    `flops_by_dtype` so mixed-dtype spans (int8 scan + f32 coarse +
    uint32 popcount) weigh each component against its own peak. Returns
    the span (None when nothing was charged)."""
    if not _ENABLED:
        return None
    sp = current_span()
    if sp is not None:
        sp.cost(flops=flops, bytes=bytes, dtype=dtype,
                flops_by_dtype=flops_by_dtype, **attrs)
    return sp


def collective(op: str, x, axis: str = "", world=None, wire_bytes=None,
               wire_dtype=None) -> None:
    """Comms instrumentation hook: account one collective op of payload
    `x` (array or tracer — only .shape/.dtype are touched, so this is
    trace-safe and never materializes anything). With `world`, the
    modeled per-rank wire traffic (obs.perf.collective_wire_bytes) is
    additionally counted — the byte history EQuARX-style wire-savings
    claims are judged against.

    Quantized transports (comms/quantized) pass `wire_bytes` — the
    ACTUAL per-rank bytes moved (quantized payload + scale sidecars,
    summed over ring hops) — overriding the `world` model, plus
    `wire_dtype` naming the wire representation; `x` stays the LOGICAL
    payload, so `comms.<op>.bytes` keeps counting what callers asked to
    move while `comms.<op>.wire_bytes` counts what the wire carried."""
    if not _ENABLED:
        return
    try:
        shape = getattr(x, "shape", ())
        dtype = getattr(x, "dtype", None)
        itemsize = getattr(dtype, "itemsize", None)
        if itemsize is None:
            import numpy as _np

            itemsize = _np.dtype(dtype if dtype is not None else _np.float32).itemsize
        nbytes = int(itemsize)
        for dim in shape:
            nbytes *= int(dim)
    except (TypeError, ValueError):
        nbytes = 0
    _reg_mod.GLOBAL.counter(f"comms.{op}.calls").inc()
    _reg_mod.GLOBAL.counter(f"comms.{op}.bytes").inc(nbytes)
    fields = {}
    if wire_bytes is not None:
        wire = int(wire_bytes)
        _reg_mod.GLOBAL.counter(f"comms.{op}.wire_bytes").inc(wire)
        fields["wire_bytes"] = wire
        if wire_dtype is not None:
            fields["wire_dtype"] = str(wire_dtype)
        if world is not None:
            fields["world"] = int(world)
    elif world is not None:
        wire = perf.collective_wire_bytes(op, nbytes, int(world))
        _reg_mod.GLOBAL.counter(f"comms.{op}.wire_bytes").inc(wire)
        fields["wire_bytes"] = wire
        fields["world"] = int(world)
    _bus_mod.GLOBAL.publish("collective", op=op, bytes=nbytes, axis=axis,
                            **fields)


def reset() -> None:
    """Zero every global metric, clear the event log, restart the
    trace-id mint, and clear the flight ring (test hygiene;
    enabled/disabled state is untouched). The mint reset is what makes
    a replayed drill re-mint the identical trace-id sequence."""
    _reg_mod.GLOBAL.reset()
    _bus_mod.GLOBAL.clear()
    trace.reset()
    flight.reset()


# honor the environment gate at import time so `RAFT_TPU_OBS=1 python
# -m ...` needs no code change to light the whole library up
if os.environ.get(ENV_FLAG, "").strip().lower() not in ("", "0", "false", "off"):
    enable()


__all__ = [
    "ENV_FLAG",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "SpanCapture",
    "annotate",
    "bus",
    "capture_spans",
    "collective",
    "counter",
    "current_span",
    "disable",
    "flight",
    "enable",
    "enabled",
    "event",
    "gauge",
    "histogram",
    "ledger",
    "open_spans",
    "perf",
    "prom_name",
    "registry",
    "render_prometheus",
    "render_registry_prometheus",
    "reset",
    "save_snapshot",
    "slo",
    "snapshot",
    "span",
    "span_cost",
    "spanned",
    "trace",
    "trace_range",
    "trace_session",
    "TraceCtx",
]

"""Tracing / profiling annotations.

Reference parity: NVTX RAII ranges (core/nvtx.hpp:25-76) annotate every major
entry point, compiled away unless enabled. The TPU equivalents are
`jax.profiler.TraceAnnotation` (host timeline) and `jax.named_scope`
(names carried into the XLA HLO, visible in the TPU profiler). `trace_range`
combines both and is cheap enough to leave on.

These are the *profiler-timeline* scopes; the *accounting* scopes
(timed spans, event bus, metric registry) live in `raft_tpu.obs`, which
re-exports `trace_range`/`annotate` so call sites need one import
surface for both.
"""

from __future__ import annotations

import contextlib
import functools

import jax


_ENABLED = True


def enable(flag: bool = True) -> None:
    global _ENABLED
    _ENABLED = flag


@contextlib.contextmanager
def trace_range(name: str, **kwargs):
    """RAII-style scope: host trace annotation + HLO named scope.

    Usage (mirrors `common::nvtx::range fun_scope("fn")`):

        with trace_range("raft_tpu.distance.pairwise"):
            ...

    `**kwargs` forward to `jax.profiler.TraceAnnotation` (e.g. trace
    arguments); the disabled path accepts the same signature so
    flipping `enable(False)` can never turn a working call site into a
    TypeError.
    """
    if not _ENABLED:
        yield
        return
    with jax.profiler.TraceAnnotation(name, **kwargs):
        with jax.named_scope(name):
            yield


def scoped(name: str):
    """Decorator: trace `f` inside `jax.named_scope(name)`. Under
    `jax.jit` every op of the program carries `name` in its metadata
    (the profiler's `tf_op`), and nothing else about the program
    changes. A fresh scope per call: `jax.named_scope(...)` used as a
    decorator keeps one saved name stack for every thread that traces."""
    def deco(f):
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)

        return wrapper

    return deco


def annotate(name: str, **kwargs):
    """Decorator form of trace_range; `**kwargs` forward to it."""
    def deco(f):
        @functools.wraps(f)
        def wrapper(*args, **fn_kwargs):
            with trace_range(name, **kwargs):
                return f(*args, **fn_kwargs)

        return wrapper

    return deco

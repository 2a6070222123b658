"""Exporters: JSON snapshots, Prometheus exposition text, profiler
trace sessions.

Three consumers, three formats:
  - `snapshot()` / `save_snapshot()`: the machine-readable joined view
    (registry metrics + bus events) a test asserts on and
    `python -m raft_tpu.obs.report` renders for humans;
  - `render_prometheus()`: flat `name value` exposition text for a
    scrape endpoint — ONE formatter, shared with
    `serve.metrics.ServerMetrics.render_text` so the two surfaces can't
    drift (the pre-obs ServerMetrics carried its own copy);
  - `trace_session()`: a `jax.profiler.trace` wrapper so "give me a TPU
    timeline for this block" is one line next to the span API instead
    of profiler boilerplate.
"""

from __future__ import annotations

import contextlib
import json
import re
from typing import Optional

from raft_tpu.obs import bus as _bus_mod
from raft_tpu.obs import registry as _reg_mod

_NAME_OK = re.compile(r"[^a-zA-Z0-9_:]")


def snapshot(registry: Optional[_reg_mod.Registry] = None,
             bus: Optional[_bus_mod.EventBus] = None,
             rank: Optional[int] = None,
             world: Optional[int] = None,
             label: Optional[str] = None) -> dict:
    """Joined point-in-time view: {"metrics": ..., "events": [...],
    "platform": ...} plus optional rank/world/label identity fields (the
    per-rank captures `obs.report --merge` aligns).

    Ordering is deterministic — metrics sort by name, events by seq —
    so two runs of the same seeded drill differ only in clock fields
    ("t", "dur_s", histogram timing aggregates), which tests strip. The
    embedded platform record (obs.perf.platform_info) pins which peak
    table any MFU derived from this snapshot was computed against.
    """
    reg = registry if registry is not None else _reg_mod.GLOBAL
    b = bus if bus is not None else _bus_mod.GLOBAL
    snap = {"metrics": reg.snapshot(), "events": b.events()}
    try:
        from raft_tpu.obs import perf as _perf

        snap["platform"] = _perf.platform_info()
    except Exception:  # pragma: no cover - defensive
        pass
    if rank is not None:
        snap["rank"] = int(rank)
    if world is not None:
        snap["world"] = int(world)
    if label is not None:
        snap["label"] = str(label)
    return snap


def save_snapshot(path: str, **kwargs) -> dict:
    """Write `snapshot()` to `path` as JSON; returns the snapshot.
    The write is atomic (tmp + rename): a reader can never observe a
    torn snapshot, and a crash mid-write leaves any previous snapshot
    intact — the contract every obs JSON writer honors (machine-checked
    by raftlint's `hygiene-obs-torn-write`)."""
    snap = snapshot(**kwargs)
    from raft_tpu.core.serialize import atomic_write

    with atomic_write(path) as tmp:
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=1, default=repr)
    return snap


def prom_name(name: str, prefix: str = "") -> str:
    """Sanitize a dotted metric name into the Prometheus charset."""
    return _NAME_OK.sub("_", prefix + name)


def _prom_value(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    if isinstance(v, float):
        return f"{v:.6g}"
    raise TypeError(f"non-numeric metric value {v!r}")


def render_prometheus(values: dict, prefix: str = "raft_tpu_") -> str:
    """Flat dict -> Prometheus exposition text (`name value` lines,
    sorted by name; None values are skipped — exposition has no null).
    NaN renders as `nan`, which Prometheus' float parser accepts."""
    lines = []
    for key in sorted(values):
        val = values[key]
        if val is None:
            continue
        lines.append(f"{prom_name(key, prefix)} {_prom_value(val)}")
    return "\n".join(lines) + "\n"


def render_registry_prometheus(registry: Optional[_reg_mod.Registry] = None,
                               prefix: str = "raft_tpu_") -> str:
    """The whole registry as exposition text: counters and gauges as-is,
    histograms as real Prometheus histogram families — cumulative
    `<name>_bucket{le="..."}` series plus `<name>_sum`/`<name>_count` —
    with the `min`/`max`/`mean`/`last` aggregates kept as companion
    gauges, and collector sections under `<collector>_<key>`."""
    reg = registry if registry is not None else _reg_mod.GLOBAL
    snap = reg.snapshot()
    flat = {}
    flat.update(snap["counters"])
    flat.update(snap["gauges"])
    for cname, section in snap.get("collectors", {}).items():
        if not isinstance(section, dict):
            continue
        for key, v in section.items():
            if isinstance(v, (int, float, bool)):
                flat[f"{cname}.{key}"] = v
    bucket_lines = []
    # each histogram family comes from ONE locked read (export_state) so
    # its _count/_sum can never disagree with its _bucket{+Inf} under a
    # concurrent observe — Prometheus scrape-atomicity per family
    for name, hist in reg.histogram_items():
        agg, buckets = hist.export_state()
        for stat, v in agg.items():
            # Prometheus histogram convention: the observation total is
            # the `_sum` series (the aggregate dict calls it "total")
            flat[f"{name}.{'sum' if stat == 'total' else stat}"] = v
        base = prom_name(f"{name}.bucket", prefix)
        bucket_lines.extend(f'{base}{{le="{le}"}} {n}'
                            for le, n in buckets)
    lines = render_prometheus(flat, prefix).splitlines()
    return "\n".join(lines + bucket_lines) + "\n"


@contextlib.contextmanager
def trace_session(logdir: str, create_perfetto_link: bool = False):
    """Profiler trace session: everything inside the block lands in a
    `jax.profiler` trace under `logdir` (viewable with TensorBoard /
    Perfetto). With obs enabled every span shows there as a
    `raft_tpu.<name>` host event, and every garbage collection as
    `raft_tpu.gc`, on the device ops' clock; the search programs' stage
    scopes (`ivf_pq.scan`, `brute_force.select`, ...) name the device
    ops themselves.

        with obs.trace_session("/tmp/tb"):
            ivf_flat.search(p, index, q, k=10)
    """
    import jax

    with jax.profiler.trace(logdir, create_perfetto_link=create_perfetto_link):
        yield logdir

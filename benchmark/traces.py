"""Reduction of a profiler trace to the numbers a traced run reports.

Reads the `.xplane.pb` that `jax.profiler` writes, with nothing but
JAX's own `ProfileData`, into flat events; then, inside the window that
the benchmark's host span `WINDOW` marks:

    busy_s      the union of the intervals in which an operation ran on
                a device, averaged over the devices that ran any;
    window_s    the span's length;
    device_ops  the operations with the most device time, named
                "<program>/<op>" from the names the trace gives (the
                "XLA Modules" event around the op, without its id, and
                the op's HLO name);
    idle_gaps   device idle time, summed by what the host was doing:
                the names of the host spans (`HOST_PREFIXES`) open at
                each gap's middle.

Device operations are the events of a device plane's "XLA Ops" line.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
HOST_PREFIXES = ("bench.", "raft_tpu.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {logdir}")
    return paths[0]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in pd.planes for line in plane.lines
            for ev in line.events]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _window(events: List[Event]) -> Optional[Tuple[float, float]]:
    spans = [e for e in events if e.name == WINDOW
             and not e.plane.startswith("/device:")]
    if not spans:
        return None
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def _labels(host: List[Event], mids) -> List[str]:
    """For each time in `mids`, the sorted names of the host spans open
    then, joined by "+". Spans of one name on one thread do not nest,
    so each (thread, name) group answers by one binary search."""
    groups = defaultdict(list)
    for h in host:
        groups[(h.plane, h.line, h.name)].append((h.start_ns, h.end_ns))
    mids = np.asarray(mids, np.float64)
    names = [set() for _ in range(len(mids))]
    for (_, _, name), iv in groups.items():
        iv.sort()
        starts = np.array([a for a, _ in iv])
        ends = np.array([b for _, b in iv])
        j = np.searchsorted(starts, mids, side="right") - 1
        inside = (j >= 0) & (ends[np.maximum(j, 0)] > mids)
        for i in np.nonzero(inside)[0]:
            names[i].add(name)
    return ["+".join(sorted(n)) or "no host span" for n in names]


def _op_names(events: List[Event], ops: List[Event]) -> List[str]:
    """"<program>/<op>" for each device op: the module event of the same
    device that holds its start, and the op's HLO name."""
    mods = defaultdict(list)
    for e in events:
        if e.plane.startswith("/device:") and e.line == MODULES_LINE:
            mods[e.plane].append((e.start_ns, e.end_ns,
                                  e.name.split("(")[0]))
    for m in mods.values():
        m.sort()
    starts = {d: np.array([a for a, _, _ in m]) for d, m in mods.items()}
    out = []
    for e in ops:
        op = e.name.split(" = ")[0]
        m = mods.get(e.plane)
        if m:
            j = int(np.searchsorted(starts[e.plane], e.start_ns, "right")) - 1
            if j >= 0 and m[j][1] >= e.start_ns:
                op = f"{m[j][2]}/{op}"
        out.append(op)
    return out


def reduce(events: List[Event]) -> Optional[dict]:
    """The traced window's numbers, or None when the trace holds no
    window or no device operation inside it."""
    win = _window(events)
    if win is None:
        return None
    w0, w1 = win
    ops = [e for e in events if e.plane.startswith("/device:")
           and e.line == OPS_LINE and min(e.end_ns, w1) > max(e.start_ns, w0)]
    per_dev = defaultdict(list)
    op_time = defaultdict(float)
    for e, name in zip(ops, _op_names(events, ops)):
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        per_dev[e.plane].append((s, t))
        op_time[name] += t - s
    if not per_dev:
        return None
    n_dev = len(per_dev)
    unions = {d: _union(iv) for d, iv in per_dev.items()}
    busy_ns = sum(sum(t - s for s, t in u) for u in unions.values()) / n_dev
    # idle gaps of the first device, labelled by the host's open spans
    edges = [w0] + [x for iv in unions[min(unions)] for x in iv] + [w1]
    gaps = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
    host = [e for e in events if not e.plane.startswith("/device:")
            and e.name.startswith(HOST_PREFIXES) and e.name != WINDOW]
    idle = defaultdict(float)
    for (s, t), label in zip(gaps, _labels(host, [(s + t) / 2
                                                  for s, t in gaps])):
        idle[label] += (t - s) / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_ns / 1e9, "window_s": (w1 - w0) / 1e9,
            "devices": n_dev,
            "device_ops": [[n, v / n_dev / 1e9] for n, v in top_ops],
            "idle_gaps": [[n, v] for n, v in top_gaps]}

"""Device time by stage scope, and the program's host spans, inside a
traced window.

The search programs name their stages with `jax.named_scope`
(`ivf_pq.scan`, `brute_force.select`, ...). The names reach each HLO
op's `op_name` metadata, which the profiler keeps as the `tf_op` stat of
the op's event metadata, not of its events, so `jax.profiler.ProfileData`
does not show it. `tf_ops` reads it by a walk of the `.xplane.pb`'s
protobuf wire format (XSpace -> XPlane.event_metadata, stat_metadata),
and `device_scopes` joins it to the events of `traces.load` by plane and
op name:

    device_scopes  device self time inside the window by program and
                   stage scope, in seconds averaged over the devices.
                   An op's self time is its time less that of the ops
                   nested inside it on the same line, so a `while` does
                   not count its body twice. An op takes the innermost
                   stage scope on its `tf_op` path. An op the compiler
                   made, with no `tf_op` (a copy that changes a layout),
                   belongs to the stage of the value it moves: that of
                   its first operand with a scope, else that of the first
                   op with a scope that takes it as an operand. Any other
                   op counts as `UNSCOPED`.
    host_spans     {name: (count, seconds)} of the program's profiler
                   spans (`raft_tpu.<name>`, written by `obs.span` and
                   the garbage-collector hook while obs is enabled)
                   that overlap the window, clipped to it.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import traces

#: the stage scopes of the search programs (raft_tpu.neighbors)
STAGES = ("ivf_pq.coarse", "ivf_pq.invert", "ivf_pq.scan", "ivf_pq.trim",
          "ivf_pq.merge", "refine", "brute_force.distance",
          "brute_force.select")
UNSCOPED = "unscoped"
SPAN_PREFIX = "raft_tpu."
TF_OP = "tf_op"
TEXT = 240  # characters of an op's HLO text kept with it
_OPERAND = re.compile(r"(?<=\s)%[\w.\-]+")

# protobuf field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_VALUE = 2
_MD_NAME, _MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, val


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", errors="replace")


def _map_values(buf) -> Iterator[memoryview]:
    for field, val in _fields(buf):
        if field == _MAP_VALUE:
            yield val


def tf_ops(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {op name: tf_op}} from the trace's event metadata;
    where two metadata of one plane share a name, the first is kept."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != _SPACE_PLANES:
            continue
        name, event_md, stat_md = "", [], {}
        for pf, val in _fields(plane):
            if pf == _PLANE_NAME:
                name = _text(val)
            elif pf == _PLANE_EVENT_MD:
                event_md.extend(_map_values(val))
            elif pf == _PLANE_STAT_MD:
                for md in _map_values(val):
                    fields = dict(_fields(md))
                    stat_md[fields.get(1, 0)] = _text(fields.get(2, b""))
        if not name.startswith("/device:"):
            continue
        tf_id = next((i for i, n in stat_md.items() if n == TF_OP), None)
        ops = out.setdefault(name, {})
        for md in event_md:
            op, tf_op = None, None
            for mf, val in _fields(md):
                if mf == _MD_NAME:
                    op = _text(val)
                elif mf == _MD_STATS and tf_id is not None:
                    stat = dict(_fields(val))
                    if stat.get(_STAT_MD_ID) == tf_id:
                        if _STAT_STR in stat:
                            tf_op = _text(stat[_STAT_STR])
                        elif _STAT_REF in stat:
                            tf_op = stat_md.get(stat[_STAT_REF])
            if op is not None and tf_op and op not in ops:
                ops[op] = tf_op
    return out


def scope_of(tf_op: Optional[str], stages=STAGES) -> str:
    """The innermost stage scope on a `tf_op` path such as
    "jit(f)/ivf_pq.scan/while/body/ivf_pq.trim/approx_top_k:"."""
    if tf_op:
        for part in reversed(tf_op.split(":")[0].split("/")):
            if part in stages:
                return part
    return UNSCOPED


def _operands(text: str) -> List[str]:
    """The operands of an op's HLO text ("%copy.3 = f32[8]{0}
    copy(f32[8]{0} %pad.1)"); attribute references such as
    "calls=%fused_computation" are not operands."""
    return _OPERAND.findall(text.partition(" = ")[2])


def _moved_scope(text: str, by_op: Dict[str, str],
                 by_consumer: Dict[str, str]) -> str:
    """The scope of a compiler-made op: its first operand's with one,
    else that of its first consumer with one."""
    for ref in _operands(text):
        if by_op.get(ref, UNSCOPED) != UNSCOPED:
            return by_op[ref]
    return by_consumer.get(text.split(" = ")[0], UNSCOPED)


def _self_times(spans: List[Tuple[float, float]]) -> List[float]:
    """Each interval's length less that of the intervals nested in it
    (one line's events nest or follow each other)."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][0], -spans[i][1]))
    own = [e - s for s, e in spans]
    stack: List[int] = []
    for i in order:
        s, e = spans[i]
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def device_scopes(events: List[traces.Event], ops_md: dict,
                  window: Tuple[float, float], stages=STAGES,
                  ops: Optional[Dict[str, list]] = None
                  ) -> Dict[str, Dict[str, float]]:
    """{program: {stage scope: seconds}} of device self time inside the
    window (programs named as `traces` names them; "" outside any).
    `ops`, where given, gathers each op's [scope, `tf_op`, HLO text, self
    time in ns summed over the devices] by "<program>/<op>"."""
    w0, w1 = window
    lines = defaultdict(list)
    for e in events:
        if (e.plane.startswith("/device:") and e.line == traces.OPS_LINE
                and min(e.end_ns, w1) > max(e.start_ns, w0)):
            lines[e.plane].append(e)
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for plane, line in lines.items():
        md = ops_md.get(plane, {})
        by_op = {n.split(" = ")[0]: scope_of(tf, stages)
                 for n, tf in md.items()}
        by_consumer: Dict[str, str] = {}
        for n in md:
            scope = by_op[n.split(" = ")[0]]
            if scope != UNSCOPED:
                for ref in _operands(n):
                    by_consumer.setdefault(ref, scope)
        spans = [(max(e.start_ns, w0), min(e.end_ns, w1)) for e in line]
        names = traces._op_names(events, line)
        for e, name, own in zip(line, names, _self_times(spans)):
            program = name.rpartition("/")[0]
            scope = (scope_of(md[e.name], stages) if md.get(e.name)
                     else _moved_scope(e.name, by_op, by_consumer))
            out[program][scope] += own
            if ops is not None:
                row = ops.setdefault(name.split(" = ")[0], [
                    scope, md.get(e.name, ""), e.name[:TEXT], 0.0])
                row[3] += own
    n_dev = max(1, len(lines))
    return {p: {s: v / n_dev / 1e9 for s, v in sorted(by.items())}
            for p, by in sorted(out.items())}


def totals(by_program: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    """Seconds per stage scope, over every program."""
    out: Dict[str, float] = defaultdict(float)
    for by in by_program.values():
        for scope, v in by.items():
            out[scope] += v
    return dict(sorted(out.items()))


def host_spans(events: List[traces.Event], window: Tuple[float, float]
               ) -> Dict[str, Tuple[int, float]]:
    """{span name less `SPAN_PREFIX`: (count, seconds)} of the program's
    host spans (`raft_tpu.*`, `raft_tpu.gc` among them) in the window,
    each clipped to it."""
    w0, w1 = window
    out: Dict[str, List] = defaultdict(lambda: [0, 0.0])
    for e in events:
        if (e.plane.startswith("/device:")
                or not e.name.startswith(SPAN_PREFIX)):
            continue
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t > s or w0 <= e.start_ns < w1:
            row = out[e.name[len(SPAN_PREFIX):]]
            row[0] += 1
            row[1] += max(0.0, t - s) / 1e9
    return {n: (c, v) for n, (c, v) in sorted(out.items())}


def python_under(path: str, window: Tuple[float, float], span: str,
                 top: int = 15) -> Optional[dict]:
    """What the Python code did inside the host span `span` (its full
    name, e.g. "raft_tpu.serve.scatter") in the window: the functions
    of the profiler's Python tracer ("$file:line name" events) by self
    time on the span's own thread. None when the trace holds neither."""
    from jax.profiler import ProfileData

    w0, w1 = window
    by_fn: Dict[str, float] = defaultdict(float)
    inside = 0.0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name) for e in line.events]
            under = traces._union([(max(a, w0), min(b, w1))
                                   for a, b, n in evs
                                   if n == span and min(b, w1) > max(a, w0)])
            if not under:
                continue
            inside += sum(b - a for a, b in under)
            starts = [a for a, _ in under]
            own = _self_times([(a, b) for a, b, _ in evs])
            for (a, b, name), t in zip(evs, own):
                j = bisect.bisect_right(starts, a) - 1
                if name.startswith("$") and j >= 0 and a < under[j][1]:
                    by_fn[name] += t
    if not by_fn:
        return None
    ranked = sorted(by_fn.items(), key=lambda kv: -kv[1])[:top]
    return {"span": span, "seconds": inside / 1e9,
            "python_s": sum(by_fn.values()) / 1e9,
            "top": [[n, v / 1e9] for n, v in ranked]}


def reduce(path: str, events: Optional[List[traces.Event]] = None
           ) -> Optional[dict]:
    """The window's numbers by stage and span, or None when the trace
    holds no window: `scopes` (seconds per stage scope, with
    `UNSCOPED`), `scopes_by_program`, `top_ops` (the ops with the most
    self time: "<program>/<op>", scope, `tf_op`, seconds),
    `unscoped_ops` (the same among the unscoped, with the op's HLO text
    for its operands) and `spans`."""
    events = traces.load(path) if events is None else events
    window = traces._window(events)
    if window is None:
        return None
    ops: Dict[str, list] = {}
    by_program = device_scopes(events, tf_ops(path), window, ops=ops)
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][3])
    return {"scopes": totals(by_program), "scopes_by_program": by_program,
            "top_ops": [[n, s, tf, ns / 1e9]
                        for n, (s, tf, _, ns) in ranked[:traces.TOP]],
            "unscoped_ops": [[n, text, ns / 1e9]
                             for n, (s, _, text, ns) in ranked
                             if s == UNSCOPED][:traces.TOP],
            "spans": host_spans(events, window)}


def per_call_ms(run, *stages) -> Optional[float]:
    """Device self time of `stages` in the window per device call (a
    search call, or a served batch), in ms; None without a scope table
    that names any of them, or without a call."""
    table = (run.trace or {}).get("scopes") or {}
    if not run.calls or not any(s in table for s in stages):
        return None
    return 1e3 * sum(table.get(s, 0.0) for s in stages) / len(run.calls)

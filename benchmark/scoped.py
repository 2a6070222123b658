"""One cell run with the program's instrumentation on, read by stage
and span: device self time per stage scope, the program's spans in the
window (the serve worker's steps, garbage collections), the build's
stages in set-up, and the per-layer metrics read from them.

    python3 -m benchmark.scoped --workload <cell> --seed <n> --seconds <s>

It runs the cell through `harness.Cell` as `benchmark.run --trace 1`
does, with three differences: obs is enabled from the start, so
set-up's spans are recorded too; the profiler's Python tracer is off
unless `--python-tracer 1` asks for it (and with it, for the Python
functions inside `serve.scatter`); and the trace is read by `scopes`
as well as by `traces`. Its metrics are the cell's own and those of
`READERS`. The end-to-end numbers of such a run carry the
instrumentation's cost and are not reported. The last line of standard
output is the result's JSON object.

This module stands in for what `harness.py` does not feed its readers
yet (`run.spans`, `run.setup_spans`, the trace's `scopes`, the Python
tracer off); the benchmark change that feeds them there deletes it.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from types import SimpleNamespace

from benchmark import run

#: the per-layer readers of the program's spans and stage scopes; each
#: reads None in a cell that does not run its code
READERS = ("ivf_pq_search.scan_ms", "ivf_pq_search.select_ms",
           "brute_force.select_ms", "serve.host_ms_per_batch",
           "ivf_pq_build.kmeans_s", "host.gc_ms")
#: the serve worker's span whose Python is broken down by function
SCATTER = "raft_tpu.serve.scatter"


def _span_totals(obs) -> dict:
    return {name[len("span."):]: (h.count, h.total)
            for name, h in obs.registry().histogram_items()
            if name.startswith("span.")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=0,
                    help="the profiler's Python tracer, off (the spans "
                         "then time the program without its cost) or on, "
                         "as in `benchmark.run --trace 1`")
    args = ap.parse_args(argv)

    from benchmark import spec

    bench = spec.load_bench(run.ROOT)
    run.configure_jax()
    run.preflight(int(spec.workload(bench, args.workload)["chips"]))
    import jax

    from benchmark import harness, reference, scopes, traces
    from raft_tpu import obs

    obs.enable()
    cell = harness.Cell(run.ROOT, args.workload, args.seed, run.T_START)
    before = _span_totals(obs)
    cell.setup()
    setup_spans = {n: (c - before.get(n, (0, 0.0))[0],
                       t - before.get(n, (0, 0.0))[1])
                   for n, (c, t) in _span_totals(obs).items()
                   if c > before.get(n, (0, 0.0))[0]}
    logdir = tempfile.mkdtemp(prefix="bench_scoped_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = args.python_tracer
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            win = cell.window(args.seconds)
        finally:
            jax.profiler.stop_trace()
        path = traces.find_xplane(logdir)
        events = traces.load(path)
        summary = traces.reduce(events)
        staged = scopes.reduce(path, events)
        scatter = (scopes.python_under(path, traces._window(events),
                                       SCATTER)
                   if staged is not None and args.python_tracer else None)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    if summary is None or staged is None:
        raise SystemExit("benchmark.scoped: the trace holds no device "
                         "operation in the window")
    summary.update(scopes=staged["scopes"])
    cell.free_program()
    checks = reference.judge(cell.compare(win), cell.cfg["limits"])
    rd = SimpleNamespace(
        cfg=cell.cfg, traffic=cell.traffic, cell=cell.cell, calls=win.calls,
        dispatched=win.dispatched, stages=win.stages, trace=summary,
        peak=spec.peaks(run.ROOT, jax.devices()[0].device_kind),
        counts=cell.counts, spans=staged["spans"], setup_spans=setup_spans)
    names = {m["name"] for m in spec.metrics(bench, "per_layer",
                                             args.workload)} | set(READERS)
    metrics = {}
    for name in sorted(names):
        v = spec.module(run.ROOT, "layers", name).read(rd)
        if v is not None:
            metrics[name] = float(v)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": all(c["ok"] for c in checks.values()),
        "metrics": metrics, "calls": len(win.calls),
        "busy_s": summary["busy_s"], "window_s": summary["window_s"],
        "idle_gaps": summary["idle_gaps"],
        "device_ops": summary["device_ops"],
        "scopes": staged["scopes"],
        "scopes_by_program": staged["scopes_by_program"],
        "top_ops": staged["top_ops"],
        "unscoped_ops": staged["unscoped_ops"],
        "spans": staged["spans"], "setup_spans": setup_spans,
        "python_in_scatter": scatter,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

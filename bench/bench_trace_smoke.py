"""Hermetic serve-tracing smoke: the CI drill behind `ci/test.sh obs`.

Drives a step-mode `SearchServer` through ~1k traced requests (a mix of
served and deadline-expired traffic) with the full ISSUE-18 stack armed
— request-scope tracing, the flight recorder, and an attached SLO
watchtower — then proves the exporter contracts in-process:

  * the flight dump must land as one readable atomic JSON file (no
    `*.tmp.*` droppings) whose ring carries the run's events;
  * the obs snapshot saved to `--out` must carry trace records, all
    four per-stage histograms, terminal-outcome counters, and at least
    one SLO transition — `ci/test.sh obs` renders `obs.report` over it
    twice and `cmp`s the bytes.

Step mode keeps the run single-threaded and the clock monotonic-only,
so everything the snapshot pins (ids, counts, event order) replays
bit-for-bit. Exits non-zero on any violated contract.

Usage: python bench/bench_trace_smoke.py [--out DIR] [--requests N]
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="artifact dir (default: RAFT_TPU_BENCH_OUT or "
                         "a fresh temp dir)")
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--rows", type=int, default=2000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args()
    out = (args.out or os.environ.get("RAFT_TPU_BENCH_OUT", "").strip()
           or tempfile.mkdtemp(prefix="trace_smoke_"))
    os.makedirs(out, exist_ok=True)

    from raft_tpu import obs, serve
    from raft_tpu.obs import export, flight, slo, trace

    obs.enable()
    obs.reset()
    trace.reset(seed=0)
    flight.install(maxlen=2048, dump_dir=out)

    rng = np.random.default_rng(0)
    data = rng.standard_normal((args.rows, args.dim)).astype(np.float32)
    queries = rng.standard_normal(
        (256, args.dim)).astype(np.float32)

    server = serve.SearchServer(
        data, serve.ServerConfig(buckets=(8, 32), max_wait_ms=0.0))
    # fast+slow windows both see the whole (sub-second) run, so the
    # expiry burst below breaches error_rate on both at once — the SLO
    # section in the report gets a real transition to render
    server.attach_watchtower(slo.Watchtower(slo.serve_objectives()))

    served = expired = 0
    i = 0
    while served + expired < args.requests:
        futs = []
        # one micro-batch per step: three live requests and, every
        # fourth group, one whose deadline already passed (admission
        # must kill it — the drop_wait/outcome story needs casualties)
        for j in range(3):
            n = 1 + (i + j) % 4
            q = queries[(i + j) % 256][None, :].repeat(n, axis=0)
            futs.append((server.submit(q, k=args.k), False))
        if i % 4 == 0:
            futs.append((server.submit(queries[i % 256][None, :],
                                       k=args.k, deadline_s=0.0), True))
        server.step()
        for fut, doomed in futs:
            try:
                fut.result(timeout=30.0)
                served += 1
            except serve.DeadlineExceeded:
                expired += 1
                if not doomed:
                    raise
        i += len(futs)

    # -- contract 1: the flight dump is one readable atomic file -------
    dump_path = flight.maybe_dump("bench_trace_smoke",
                                  served=served, expired=expired)
    if dump_path is None or not os.path.exists(dump_path):
        raise SystemExit("flight dump did not land")
    droppings = [p for p in os.listdir(out) if ".tmp." in p]
    if droppings:
        raise SystemExit(f"atomic_write left temp droppings: {droppings}")
    with open(dump_path) as f:
        dump = json.load(f)
    if not dump["events"]:
        raise SystemExit("flight ring dumped empty")

    # -- contract 2: the snapshot carries the full tracing surface ----
    snap_path = os.path.join(out, "obs_snapshot.json")
    snap = export.save_snapshot(snap_path)
    counters = snap["metrics"]["counters"]
    hists = snap["metrics"]["histograms"]
    traces = [e for e in snap["events"] if e.get("kind") == "trace"]
    problems = []
    if counters.get("serve.outcome.ok", 0) != served:
        problems.append("serve.outcome.ok != served")
    if counters.get("serve.outcome.expired", 0) != expired:
        problems.append("serve.outcome.expired != expired")
    if counters.get("slo.breach", 0) < 1:
        problems.append("no slo.breach fired")
    for name in ("serve.stage.queue_wait_s", "serve.stage.linger_s",
                 "serve.stage.device_s", "serve.stage.scatter_s"):
        if hists.get(name, {}).get("count", 0) == 0:
            problems.append(f"{name} empty")
    if hists.get("serve.drop_wait_s", {}).get("count", 0) != expired:
        problems.append("serve.drop_wait_s count != expired")
    if not traces:
        problems.append("no trace events survived the bus window")
    if problems:
        raise SystemExit("snapshot contract violated: " + "; ".join(problems))

    print(json.dumps({
        "suite": "trace_smoke", "served": served, "expired": expired,
        "trace_events_on_bus": len(traces),
        "flight_ring_events": len(dump["events"]),
        "snapshot": snap_path, "flight_dump": dump_path,
    }, sort_keys=True))


if __name__ == "__main__":
    main()

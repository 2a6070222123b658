"""Tiny in-process perf bench: the ledger's heartbeat.

Runs in seconds on any backend (CPU included) and banks a brute-force
kNN row and an IVF-PQ search row through `common.Banker` — which means
every run appends honestly-tagged rows (git SHA, platform, span phases
with cost-model MFU) to BENCH_LEDGER.jsonl. `ci/test.sh perf` points
RAFT_TPU_BENCH_LEDGER at a temp file and runs this, then gates the
fresh rows with `python -m tools.perfgate --json` — so every future PR
banks fresh numbers and sees drift the moment it lands, even when the
chip queue is down (ROADMAP item 5a).

The two cases run as stages of a `raft_tpu.jobs.Job` (ISSUE 8): each
stage is watchdog-supervised (a stalled case is killed as a typed
StageTimeout instead of hanging the session) and every run leaves a
job timeline on the obs bus. The JobDir is a fresh temp dir by default
— a heartbeat bench should re-measure every run, never skip — or
RAFT_TPU_JOB_DIR for a durable, resumable sweep.

Observability is force-enabled in-process: the whole point of these
rows is the per-phase attribution and MFU they carry.

Usage: python bench/bench_perf_smoke.py [--rows N] [--queries N]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from common import Banker, run_case


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--n-lists", type=int, default=32)
    args = ap.parse_args()

    from raft_tpu import obs
    from raft_tpu.neighbors import brute_force, ivf_pq

    obs.enable()

    # RAFT_TPU_BENCH_OUT redirects the results file (hermetic CI/tests);
    # the ledger path has its own env override (RAFT_TPU_BENCH_LEDGER)
    out_dir = os.environ.get("RAFT_TPU_BENCH_OUT", "").strip() or \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bank = Banker(
        os.path.join(out_dir, "BENCH_perf_smoke.json"),
        meta={"dataset_rows": args.rows, "dim": args.dim,
              "queries": args.queries, "k": args.k, "n_lists": args.n_lists},
    )

    rng = np.random.default_rng(7)
    data = rng.random((args.rows, args.dim), dtype=np.float32)
    q = rng.random((args.queries, args.dim), dtype=np.float32)

    from common import job_dir_or_temp

    from raft_tpu import jobs

    # a wall-clock deadline, not a stall timeout: these stages run one
    # opaque compile+measure call and never beat the heartbeat, so a
    # stall knob would just be a mislabeled deadline
    deadline_s = float(
        os.environ.get("RAFT_TPU_PERF_SMOKE_DEADLINE_S", "600"))

    def bf_knn(ctx):
        rec = run_case(
            "perf_smoke",
            f"bf_knn_{args.rows}x{args.dim}_q{args.queries}_k{args.k}",
            lambda: brute_force.knn(data, q, k=args.k),
            iters=3, warmup=1, items=float(args.queries), unit="qps")
        bank.add(rec, echo=False)
        return {"qps": rec.get("value")}

    def bf_knn_fused(ctx):
        # the ISSUE 10 fused scan+select engine, raced in the same group
        # so the ledger carries fused-vs-baseline at every SHA (its span
        # cost charges the fused geometry: no score-matrix bytes)
        rec = run_case(
            "perf_smoke",
            f"bf_knn_fused_{args.rows}x{args.dim}_q{args.queries}_k{args.k}",
            lambda: brute_force.knn(data, q, k=args.k, engine="pallas"),
            iters=3, warmup=1, items=float(args.queries), unit="qps")
        bank.add(rec, echo=False)
        return {"qps": rec.get("value")}

    def pq_search(ctx):
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=args.n_lists, kmeans_n_iters=4,
                               pq_dim=args.dim // 2), data)
        sp = ivf_pq.SearchParams(n_probes=8)
        rec = run_case(
            "perf_smoke",
            f"ivf_pq_search_{args.rows}_q{args.queries}_k{args.k}_probes8",
            lambda: ivf_pq.search(sp, idx, q, args.k),
            iters=3, warmup=1, items=float(args.queries), unit="qps")
        bank.add(rec, echo=False)
        return {"qps": rec.get("value")}

    def pq_int8_fused(ctx):
        # the ISSUE 11 int8 fused trim, measured every session so the
        # first chip queue carries fused-vs-baseline at every SHA (the
        # span cost charges int8 MXU flops against the int8 peak, no
        # score-matrix bytes; honesty-tagged interpret rows on CPU are
        # expected to lose to XLA locally)
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=args.n_lists, kmeans_n_iters=4,
                               pq_dim=args.dim // 2), data)
        sp = ivf_pq.SearchParams(n_probes=8, trim_engine="fused",
                                 score_dtype="int8")
        rec = run_case(
            "perf_smoke",
            f"ivf_pq_int8_fused_{args.rows}_q{args.queries}_k{args.k}_probes8",
            lambda: ivf_pq.search(sp, idx, q, args.k),
            iters=3, warmup=1, items=float(args.queries), unit="qps")
        bank.add(rec, echo=False)
        return {"qps": rec.get("value")}

    geometry = {"rows": args.rows, "dim": args.dim,
                "queries": args.queries, "k": args.k}
    env_dir = os.environ.get("RAFT_TPU_JOB_DIR", "").strip() or None
    with job_dir_or_temp(env_dir, "raft_tpu_perf_smoke_") as jd:
        job = jobs.Job("perf_smoke", jd)
        job.add_stage("bf_knn", bf_knn, inputs=geometry,
                      deadline_s=deadline_s)
        job.add_stage("bf_knn_fused", bf_knn_fused, inputs=geometry,
                      deadline_s=deadline_s)
        job.add_stage("ivf_pq_search", pq_search,
                      inputs={**geometry, "n_lists": args.n_lists},
                      deadline_s=deadline_s)
        job.add_stage("ivf_pq_int8_fused", pq_int8_fused,
                      inputs={**geometry, "n_lists": args.n_lists,
                              "engine": "fused_int8"},
                      deadline_s=deadline_s)
        # independent cases: one timed-out case must not zero the whole
        # sweep — bank what completes, then fail loudly below
        try:
            statuses = job.run(continue_on_error=True)
        except jobs.JobPreempted:
            # SIGTERM = graceful suspend, not a crash: exit through the
            # shared preemption protocol so callers can tell them apart
            from common import PREEMPT_EXIT

            print("preempted; re-run with RAFT_TPU_JOB_DIR set to "
                  "resume", file=sys.stderr)
            sys.exit(PREEMPT_EXIT)

    print(f"banked -> {bank.path}")
    failed = sorted(s for s, st in statuses.items() if st == "failed")
    if failed:
        print(f"FAILED stages: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

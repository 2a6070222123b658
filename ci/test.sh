#!/bin/bash
# Test entry point (reference ci/test_cpp.sh + ci/test_python.sh analogue).
#
#   ci/test.sh quick   — the <2 min tier (skips compile-heavy ANN suites)
#   ci/test.sh full    — everything (default)
#   ci/test.sh chaos   — the fault-injection/resilience suite + the
#                        replica-failover / rejoin / checkpoint-heal
#                        drills (tests/test_replication.py), replayed
#                        under a 3-seed RAFT_TPU_FAULT_SEED matrix so a
#                        drill that only survives one lucky seed fails
#   ci/test.sh serve   — the serving-engine suite (incl. its seeded
#                        chaos cases: slow-rank degraded serving, slow
#                        batch dispatch) + the batch_loader padding
#                        contract the serve batcher reuses
#   ci/test.sh obs     — the observability suite (span/registry/event
#                        determinism, exporters, report CLI, the
#                        chaos-drill timeline contract)
#   ci/test.sh lint    — the static-analysis tier: tools/raftlint over
#                        the whole repo (trace safety, lock discipline +
#                        lock-order deadlock, fault-site drift, layer
#                        purity, hygiene, SPMD collective divergence/
#                        order, commit ordering, and the raftlint 3.0
#                        kernelcheck families: VMEM envelope
#                        cross-check, BlockSpec/scalar-prefetch
#                        consistency, kernel dtype flow, fused dispatch
#                        envelope guards, plus the tuned-key registry,
#                        and the raftlint 4.0 statecheck families:
#                        cache-key completeness over the memoized
#                        serving wrappers, the CKPT_SCHEMA checkpoint
#                        registry, and the DIGEST_FIELDS scrub-coverage
#                        registry, and the raftlint 5.0 threadcheck
#                        families: THREAD_ROOTS registry drift,
#                        whole-program shared-state races, and the
#                        publication-safety zero-dip contract),
#                        --json archived and run
#                        twice + cmp'd (byte-determinism is a
#                        documented contract), per-family --stats
#                        archived with a 10 s soft budget per engine,
#                        wall-time gated under 30 s so the gate never
#                        becomes the slow tier, plus the raftlint unit,
#                        CFG-engine, kernelcheck-interpreter,
#                        statecheck, and threadcheck suites (incl. the
#                        real-source mutation smoke tests)
#   ci/test.sh schedfuzz— the deterministic-interleaving tier: the
#                        schedfuzz scheduler contract (same seed =>
#                        byte-identical schedule trace) and the three
#                        pinned ordering drills (zero-dip mutation swap
#                        vs in-flight batch, flight-recorder dump
#                        racing publication, metrics snapshot during
#                        scrape) plus the pre-fix reproducing schedules
#                        for every race ISSUE-20 fixed, under the
#                        3-seed RAFT_TPU_FAULT_SEED matrix
#   ci/test.sh rabitq  — the quantizer-subsystem tier: the quantizer
#                        abstraction property suite (estimator
#                        unbiasedness, pack/unpack round-trips, the PQ
#                        bit-identity goldens) + the IVF-RaBitQ index
#                        suite (build/search/extend/save, MNMG degraded
#                        + failover + ckpt-heal, serve bit-identity)
#   ci/test.sh perf    — the perf-watchtower tier: a tiny in-process
#                        bench banks fresh rows (span phases + cost-model
#                        MFU) to a temp ledger, tools/perfgate gates them
#                        in report-only mode (and must be byte-identical
#                        across two runs), then the cost-model /
#                        ledger / perfgate unit suites run
#   ci/test.sh fused   — the fused scan+select-k tier: exact-agreement
#                        tests of the fused Pallas kernel family vs the
#                        two-phase reference (ids AND values, min/max,
#                        k ladder, ragged tails, adversarial-tie
#                        recall), the INTEGER fused kernels (int8
#                        PQ-recon trim bit-agreement vs the pallas
#                        trim, RaBitQ bit-plane scan vs the XLA
#                        estimator reference, fused_kb growth,
#                        tombstone exclusion), the scan_select_k /
#                        list-scan dispatch contracts, and the select_k
#                        strategy suite (slow-marked kernel sweeps
#                        excluded)
#   ci/test.sh adaptive— the adaptive-probing tier (ISSUE 12): the
#                        probe-budget suite (saturation bit-identity
#                        on all three engines + MNMG, early-term
#                        oracle, truthful scanned_lists accounting,
#                        serve recall_target plumbing), then the
#                        recall-vs-scanned frontier bench at smoke
#                        scale into a hermetic ledger, gated through
#                        tools/perfgate --json run twice + cmp'd
#                        (byte-determinism over the appended rows)
#   ci/test.sh qcomms  — the quantized-collectives tier (ISSUE 17): the
#                        codec / bit-identity-pin / recall-parity /
#                        wire-accounting suite (tests/test_qcomms.py,
#                        slow driver pins included), then the wire +
#                        recall + mode-race bench at smoke scale into a
#                        hermetic ledger, gated through
#                        tools/perfgate --json run twice + cmp'd
#   ci/test.sh jobs    — the preemption-safety tier: the resumable job
#                        runner + watchdog drills (tests/test_jobs.py),
#                        incl. the child-process SIGKILL kill-and-resume
#                        bit-identity drills over ivf_flat/pq/rabitq and
#                        the kill-mid-make_data datagen drill, replayed
#                        under the 3-seed RAFT_TPU_FAULT_SEED matrix
#   ci/test.sh mutation— the live-mutable-index tier (ISSUE 16): the
#                        mutation suite (tombstone semantics, the
#                        crash-atomic mutation log, zero-dip serving
#                        single-chip + MNMG, and the child-process
#                        SIGKILL mid-upsert/mid-delete resume-
#                        bit-identity drills over all three kinds)
#                        under the 3-seed RAFT_TPU_FAULT_SEED matrix,
#                        then the recall-under-churn / ingest-
#                        throughput bench at smoke scale into a
#                        hermetic ledger, gated through
#                        tools/perfgate --json run twice + cmp'd
#   ci/test.sh integrity— the integrity-watchdog tier (ISSUE 19): the
#                        scrub/quarantine/PITR suite
#                        (tests/test_integrity.py — digest lifecycle,
#                        rot conviction, quarantine bit-identity,
#                        zero-dip serve repair, MNMG mirror repair,
#                        restore byte-identity, and the child-process
#                        SIGKILL mid-scrub resume drills) under the
#                        3-seed RAFT_TPU_FAULT_SEED matrix, then the
#                        scrub-under-churn bench row at smoke scale
#                        into a hermetic ledger, gated through
#                        tools/perfgate --json run twice + cmp'd
#
# Tests force the CPU backend with an 8-device virtual mesh via
# tests/conftest.py; no TPU is touched.
#
# The chaos suite (tests/test_resilience.py) replays seeded FaultPlans;
# CI pins the seed so a failing drill reproduces bit-for-bit locally
# (override RAFT_TPU_FAULT_SEED to fuzz other seeds).
set -euo pipefail
cd "$(dirname "$0")/.."

export RAFT_TPU_FAULT_SEED="${RAFT_TPU_FAULT_SEED:-1234}"

tier="${1:-full}"
case "$tier" in
  # quick: fast-compile mode (most XLA opt passes skipped) + "not slow";
  # the full tier keeps production optimization levels
  quick) exec env RAFT_TPU_TEST_FAST_COMPILE=1 python -m pytest tests/ -q -m "not slow" ;;
  # --durations: keep the slowest-test ledger in every full run so the
  # ~20 min tier budget is enforced from data, not memory
  full)  exec python -m pytest tests/ -q --durations=15 ;;
  chaos)
    # seed matrix: the pinned CI seed first (bit-for-bit repro of CI
    # failures), then two fixed alternates — the failover election,
    # corrupt-file sector draws, and retry jitter all derive from the
    # seed, so the drills must hold across seeds, not just one
    for seed in "${RAFT_TPU_FAULT_SEED}" 7 2025; do
      echo "=== chaos tier @ RAFT_TPU_FAULT_SEED=${seed} ==="
      env RAFT_TPU_FAULT_SEED="${seed}" \
        python -m pytest tests/test_resilience.py tests/test_replication.py -q
    done
    ;;
  serve) exec python -m pytest tests/test_serve.py tests/test_batch_loader.py -q ;;
  obs)
    # seed matrix mirrors the chaos tier: the flaky-dump and
    # corrupt-stamp drills (obs.flight.dump / serve.trace.stamp) arm
    # from the seed, so degrade-not-die must hold across seeds
    for seed in "${RAFT_TPU_FAULT_SEED}" 7 2025; do
      echo "=== obs tier @ RAFT_TPU_FAULT_SEED=${seed} ==="
      env RAFT_TPU_FAULT_SEED="${seed}" \
        python -m pytest tests/test_obs.py tests/test_trace.py -q
    done
    tmp="$(mktemp -d)"
    # hermetic tracing smoke: ~1k traced requests through a step-mode
    # server with the flight recorder + SLO watchtower armed; the
    # script itself enforces the atomic-dump contract, then the run
    # report over its snapshot is
    # rendered twice + cmp'd (byte-determinism is the contract) and
    # must carry the tracing + SLO sections
    env RAFT_TPU_OBS=1 JAX_PLATFORMS=cpu \
      python bench/bench_trace_smoke.py --out "${tmp}"
    python -m raft_tpu.obs.report "${tmp}/obs_snapshot.json" \
      > "${tmp}/report1.txt"
    python -m raft_tpu.obs.report "${tmp}/obs_snapshot.json" \
      > "${tmp}/report2.txt"
    cmp "${tmp}/report1.txt" "${tmp}/report2.txt"  # acceptance: deterministic
    grep -q "Request tracing" "${tmp}/report1.txt"
    grep -q "SLO watchtower" "${tmp}/report1.txt"
    # fresh perf-smoke rows (now carrying obs overhead from the traced
    # serve path's instruments) into a hermetic ledger, then the
    # perfgate determinism contract over the appended rows
    env RAFT_TPU_OBS=1 JAX_PLATFORMS=cpu \
      RAFT_TPU_BENCH_LEDGER="${tmp}/ledger.jsonl" \
      RAFT_TPU_BENCH_OUT="${tmp}" \
      python bench/bench_perf_smoke.py
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate1.json"
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate2.json"
    cmp "${tmp}/gate1.json" "${tmp}/gate2.json"  # acceptance: deterministic
    cat "${tmp}/gate1.json"
    ;;
  lint)
    tmp="$(mktemp -d)"
    # full-tree lint, --json archived (diffable next to BENCH artifacts)
    # and run twice + cmp'd: byte-determinism is part of the contract.
    # The exit code is captured, not fatal, so a failing gate still
    # archives and PRINTS its findings instead of dying into a tmp file
    lint_rc=0
    lint_t0=$SECONDS
    # --stats lands on stderr only (stdout stays the byte-deterministic
    # json): per-rule-family wall times, archived so a slow ENGINE is
    # attributable the day the 30 s repo gate trips
    python -m tools.raftlint --json --stats raft_tpu bench tests tools \
      > "${tmp}/raftlint.json" 2> "${tmp}/raftlint_stats.txt" || lint_rc=$?
    lint_secs=$(( SECONDS - lint_t0 ))
    if [ -n "${RAFT_TPU_CI_ARTIFACTS:-}" ]; then
      mkdir -p "${RAFT_TPU_CI_ARTIFACTS}"
      cp "${tmp}/raftlint.json" "${RAFT_TPU_CI_ARTIFACTS}/raftlint.json"
      cp "${tmp}/raftlint_stats.txt" "${RAFT_TPU_CI_ARTIFACTS}/raftlint_stats.txt"
    fi
    echo "raftlint: json archived at ${RAFT_TPU_CI_ARTIFACTS:-${tmp}}/raftlint.json"
    cat "${tmp}/raftlint_stats.txt"
    # per-family SOFT budget: any single engine past 10 s is called out
    # (warning, not failure — the hard gate is the 30 s repo wall below)
    awk -F'wall=' '/stats: family=/ {
      split($2, a, "s"); fam=$1; sub(/.*family=/, "", fam); sub(/ .*/, "", fam)
      if (a[1] + 0 >= 10)
        printf "raftlint: WARNING: family %s took %ss (soft budget 10s)\n", fam, a[1]
    }' "${tmp}/raftlint_stats.txt" >&2
    if [ "${lint_rc}" -ne 0 ]; then
      echo "raftlint: findings (exit ${lint_rc}):" >&2
      cat "${tmp}/raftlint.json" >&2
      exit "${lint_rc}"
    fi
    python -m tools.raftlint --json raft_tpu bench tests tools \
      > "${tmp}/raftlint2.json"
    cmp "${tmp}/raftlint.json" "${tmp}/raftlint2.json"
    echo "raftlint: repo-wide wall time ${lint_secs}s (budget 30s)"
    # the lint gate must stay the FAST tier: interprocedural analysis
    # that creeps past 30 s gets split or bounded, not waited on
    if [ "${lint_secs}" -ge 30 ]; then
      echo "raftlint: repo-wide lint took ${lint_secs}s (>= 30s budget)" >&2
      exit 1
    fi
    exec python -m pytest tests/test_raftlint.py tests/test_raftlint_cfg.py \
      tests/test_raftlint_kernels.py tests/test_raftlint_statecheck.py \
      tests/test_raftlint_threads.py -q
    ;;
  schedfuzz)
    # seed matrix mirrors the chaos tier: every scheduling decision
    # derives from the seed, so the pinned ordering drills and the
    # reproducing-schedule regressions must hold across seeds — and the
    # byte-identical-trace contract is itself asserted per seed
    for seed in "${RAFT_TPU_FAULT_SEED}" 7 2025; do
      echo "=== schedfuzz tier @ RAFT_TPU_FAULT_SEED=${seed} ==="
      env RAFT_TPU_FAULT_SEED="${seed}" \
        python -m pytest tests/test_schedfuzz.py -q
    done
    exit 0
    ;;
  rabitq)
    exec python -m pytest tests/test_quantizer.py tests/test_ivf_rabitq.py -q
    ;;
  fused)
    exec python -m pytest tests/test_fused_scan.py \
      tests/test_fused_int_scan.py tests/test_select_k.py \
      -q -m "not slow"
    ;;
  jobs)
    # seed matrix mirrors the chaos tier: the crash-site visit counts,
    # stall schedules, and retry jitter all derive from the seed, so the
    # kill-and-resume drills must hold across seeds, not just one
    for seed in "${RAFT_TPU_FAULT_SEED}" 7 2025; do
      echo "=== jobs tier @ RAFT_TPU_FAULT_SEED=${seed} ==="
      env RAFT_TPU_FAULT_SEED="${seed}" \
        python -m pytest tests/test_jobs.py -q
    done
    ;;
  mutation)
    # seed matrix mirrors the chaos/jobs tiers: the flaky-drill arming,
    # SIGKILL visit counts, and churn scripts all derive from the seed,
    # so the crash-atomicity drills must hold across seeds, not just one
    for seed in "${RAFT_TPU_FAULT_SEED}" 7 2025; do
      echo "=== mutation tier @ RAFT_TPU_FAULT_SEED=${seed} ==="
      env RAFT_TPU_FAULT_SEED="${seed}" \
        python -m pytest tests/test_mutation.py -q
    done
    tmp="$(mktemp -d)"
    # churn bench at smoke scale into a hermetic ledger (report-only CI
    # must not write the repo ledger), then the perfgate determinism
    # contract over the appended rows
    env RAFT_TPU_OBS=1 JAX_PLATFORMS=cpu \
      RAFT_TPU_BENCH_LEDGER="${tmp}/ledger.jsonl" \
      RAFT_TPU_BENCH_OUT="${tmp}" \
      python bench/bench_mutation.py --smoke
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate1.json"
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate2.json"
    cmp "${tmp}/gate1.json" "${tmp}/gate2.json"  # acceptance: deterministic
    cat "${tmp}/gate1.json"
    ;;
  integrity)
    # seed matrix mirrors the chaos/jobs/mutation tiers: the rot victim
    # draws, SIGKILL visit counts, and flaky-drill arming all derive
    # from the seed, so the scrub/quarantine/PITR drills must hold
    # across seeds, not just one
    for seed in "${RAFT_TPU_FAULT_SEED}" 7 2025; do
      echo "=== integrity tier @ RAFT_TPU_FAULT_SEED=${seed} ==="
      env RAFT_TPU_FAULT_SEED="${seed}" \
        python -m pytest tests/test_integrity.py -q
    done
    tmp="$(mktemp -d)"
    # the mutation bench (now carrying the scrub_serve stage: sidecar
    # re-hash lists/s + served-QPS dip) at smoke scale into a hermetic
    # ledger (report-only CI must not write the repo ledger), then the
    # perfgate determinism contract over the appended rows
    env RAFT_TPU_OBS=1 JAX_PLATFORMS=cpu \
      RAFT_TPU_BENCH_LEDGER="${tmp}/ledger.jsonl" \
      RAFT_TPU_BENCH_OUT="${tmp}" \
      python bench/bench_mutation.py --smoke
    grep -q scrub_under_churn "${tmp}/ledger.jsonl"
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate1.json"
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate2.json"
    cmp "${tmp}/gate1.json" "${tmp}/gate2.json"  # acceptance: deterministic
    cat "${tmp}/gate1.json"
    ;;
  adaptive)
    tmp="$(mktemp -d)"
    python -m pytest tests/test_probe_budget.py -q
    # frontier bench at smoke scale into a hermetic ledger (report-only
    # CI must not write the repo ledger), then the perfgate determinism
    # contract over the appended rows
    env RAFT_TPU_OBS=1 JAX_PLATFORMS=cpu \
      RAFT_TPU_BENCH_LEDGER="${tmp}/ledger.jsonl" \
      RAFT_TPU_BENCH_OUT="${tmp}" \
      python bench/bench_adaptive_probes.py --smoke
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate1.json"
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate2.json"
    cmp "${tmp}/gate1.json" "${tmp}/gate2.json"  # acceptance: deterministic
    cat "${tmp}/gate1.json"
    ;;
  qcomms)
    tmp="$(mktemp -d)"
    # the full quantized suite, slow driver bit-identity pins included
    python -m pytest tests/test_qcomms.py -q
    # wire/recall/race bench at smoke scale into a hermetic ledger
    # (report-only CI must not write the repo ledger), then the perfgate
    # determinism contract over the appended rows
    env RAFT_TPU_OBS=1 JAX_PLATFORMS=cpu \
      RAFT_TPU_BENCH_LEDGER="${tmp}/ledger.jsonl" \
      RAFT_TPU_BENCH_OUT="${tmp}" \
      python bench/bench_qcomms.py --smoke
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate1.json"
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate2.json"
    cmp "${tmp}/gate1.json" "${tmp}/gate2.json"  # acceptance: deterministic
    cat "${tmp}/gate1.json"
    ;;
  perf)
    tmp="$(mktemp -d)"
    # fresh rows into a hermetic ledger (report-only CI must not write
    # the repo ledger; real runs do — that's how BENCH_LEDGER.jsonl
    # grows one honest row per bench session)
    env RAFT_TPU_OBS=1 JAX_PLATFORMS=cpu \
      RAFT_TPU_BENCH_LEDGER="${tmp}/ledger.jsonl" \
      RAFT_TPU_BENCH_OUT="${tmp}" \
      python bench/bench_perf_smoke.py
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate1.json"
    python -m tools.perfgate --ledger "${tmp}/ledger.jsonl" --json \
      > "${tmp}/gate2.json"
    cmp "${tmp}/gate1.json" "${tmp}/gate2.json"  # acceptance: deterministic
    cat "${tmp}/gate1.json"
    exec python -m pytest tests/test_perf.py tests/test_perfgate.py -q
    ;;
  *) echo "usage: ci/test.sh [quick|full|chaos|serve|obs|lint|schedfuzz|rabitq|fused|perf|jobs|adaptive|mutation|qcomms|integrity]" >&2; exit 2 ;;
esac

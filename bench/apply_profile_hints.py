"""Turn TPU_PROFILE_RESULTS.json into recommended default flips.

Reads the profiler's record (bench/tpu_profile.py) and prints, as JSON
lines, which engine/precision defaults the numbers support changing and
which measurements are still missing. Decision rules mirror NOTES.md's
on-chip queue:

- score_dtype: int8 becomes the recon8_list default if it beats bf16
  by >10% QPS at equal (±0.01) recall.
- IVF-Flat engine: the fastest of query/list/pallas at >= query-engine
  recall - 0.01.
- trainer precision: bf16 trainer OK if its inertia is within 0.5% of
  HIGHEST.

Usage: python bench/apply_profile_hints.py [path-to-results.json]
"""

import json
import sys, os


def _qps(rec):
    return rec.get("qps") if isinstance(rec, dict) else None


def _recall(rec):
    return rec.get("recall") if isinstance(rec, dict) else None


def hint(out, name, winner, detail):
    out.append({"hint": name, "recommend": winner, "detail": detail})


_EXPECTED_KEYS = (
    "search_recon8_list_bf16_float32_approx_np32",
    "search_recon8_list_int8_float32_approx_np32",
    "search_recon8_list_bf16_bfloat16_approx_np32",
    "search_lut_bf16_float32_approx_np32",
    "search_recon8_list_int8_bfloat16_exact_np32",
    "search_unrefined_np8_approx",
    "search_unrefined_np8_exact",
    "search_refined_np8",
    "flat_search_query_np32",
    "flat_search_list_np32",
    "flat_search_pallas_np32",
    "bf_tiled_1M",
    "bf_pallas_1M",
    "inertia_highest",
    "inertia_bf16",
    "micro_bf16",
    "micro_int8",
)


def main(path: str):
    try:
        with open(path) as f:
            R = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        # the profiler may have been skipped entirely (dead transport):
        # report that as a decision input rather than crashing the queue
        print(json.dumps({"hint": "no_profile_results", "detail": str(e)[:200]}))
        return
    out = []
    missing = [k for k, v in R.items() if isinstance(v, dict) and "error" in v]
    missing += [k for k in _EXPECTED_KEYS if k not in R]
    compared = [0]  # comparisons that ran (even with no clear winner)

    def cmp(name, a_key, b_key, label_a, label_b):
        a, b = R.get(a_key), R.get(b_key)
        if not (_qps(a) and _qps(b)):
            return
        compared[0] += 1
        ra, rb = _recall(a) or 0.0, _recall(b) or 0.0
        if abs(ra - rb) <= 0.01:
            if _qps(b) > 1.1 * _qps(a):
                hint(out, name, label_b,
                     f"{label_b} {_qps(b):.0f} qps vs {label_a} {_qps(a):.0f} "
                     f"at recall {rb:.3f}/{ra:.3f}")
            elif _qps(a) > 1.1 * _qps(b):
                hint(out, name, label_a,
                     f"{label_a} {_qps(a):.0f} qps vs {label_b} {_qps(b):.0f}")
        else:
            hint(out, name, "inspect",
                 f"recall gap {ra:.3f} vs {rb:.3f} — not a pure speed trade")

    base = "search_recon8_list_bf16_float32_approx_np32"
    cmp("bf_engine_default", "bf_tiled_1M", "bf_pallas_1M", "tiled", "pallas")
    cmp("score_dtype_default", base,
        "search_recon8_list_int8_float32_approx_np32", "bf16", "int8")

    def pick_best(records, baseline=None, ref_recall=None, margin=1.1):
        """Shared pick-best among measured records: drop entries more
        than 0.01 recall under the reference (baseline's recall, or the
        max measured), take the QPS argmax of the survivors; the
        baseline (when it survived the recall floor) keeps the win
        unless a challenger beats it by `margin`. Returns (winner,
        detail) or (None, None) with <2 measured."""
        valid = {e: v for e, v in records.items() if _qps(v)}
        if len(valid) < 2:
            return None, None
        compared[0] += 1
        if ref_recall is None:
            ref_recall = _recall(valid.get(baseline)) or max(
                _recall(v) or 0.0 for v in valid.values()
            )
        ok = {e: v for e, v in valid.items()
              if (_recall(v) or 0.0) >= ref_recall - 0.01}
        winner = max(ok, key=lambda e: _qps(ok[e]))
        if baseline in ok and winner != baseline \
                and _qps(ok[winner]) <= margin * _qps(ok[baseline]):
            winner = baseline
        detail = {e: (_qps(v), _recall(v)) for e, v in valid.items()}
        absent = sorted(set(records) - set(valid), key=str)
        if absent:
            detail["unmeasured"] = absent
        return winner, detail

    # decide among the flat engines that DID measure (a Mosaic rejection
    # of the pallas config must not suppress the query-vs-list decision)
    flat = {e: R.get(f"flat_search_{e}_np32") for e in ("query", "list", "pallas")}
    w, detail = pick_best(flat, baseline="query", margin=1.0)
    if w is not None:
        hint(out, "ivf_flat_engine_default", w, detail)

    ih, ib = R.get("inertia_highest"), R.get("inertia_bf16")
    if ih and ib:
        rel = (ib - ih) / abs(ih)
        hint(out, "trainer_precision",
             "bf16 (DEFAULT)" if rel <= 0.005 else "keep HIGHEST",
             f"bf16 inertia {rel:+.4%} vs HIGHEST")

    for h in out:
        print(json.dumps(h))
    if missing:
        print(json.dumps({"hint": "missing_measurements", "keys": missing}))
    if not out:
        detail = (
            "measured, but no pair cleared the 10% threshold — keep current defaults"
            if compared[0] else "profile record lacks the ladder keys"
        )
        print(json.dumps({"hint": "no_decisions", "detail": detail}))
    return out


# hints whose winners the library's "auto" paths consult directly
# (raft_tpu/core/tuned.py); everything else stays informational.
# value = (tuned key, caster applied to the recommend before writing)
_TUNABLE = {
    "ivf_flat_engine_default": ("flat_auto_engine", str),
}


def apply_hints(out):
    """Merge the decided winners into raft_tpu/tuned_defaults.json — the
    committed artifact the library's auto dispatch reads. Only concrete
    engine winners are applied; 'inspect' verdicts and informational
    hints land under "hints" for the next session to read. MERGE, not
    overwrite: a partial/aborted profile must not erase winners an
    earlier good session measured (the queue runs --apply even when the
    profiler was skipped), and an empty decision set writes nothing."""
    from raft_tpu.core import tuned

    if not out:
        print(json.dumps({"applied": None,
                          "detail": "no decisions; tuned file left untouched"}))
        return
    updates = {"hints": {h["hint"]: h["recommend"] for h in out}}
    for hint_name, (key, caster) in _TUNABLE.items():
        for h in out:
            if h["hint"] != hint_name or h["recommend"] == "inspect":
                continue
            try:
                updates[key] = caster(h["recommend"])
            except (TypeError, ValueError):
                continue
    # runtime belt matching the lint-time tuned-key-registry check: a
    # _TUNABLE entry drifting from the registry — or a recommend value
    # outside a choice key's registered set — must not bank a winner
    # every reader will reject (the lint rule cannot see computed
    # values, so this is the only enforcement point for them)
    for k in sorted(k for k in updates if k != "hints"):
        entry = tuned.TUNED_KEYS.get(k)
        if entry is None:
            print(json.dumps({"skipped_unregistered_key": k}))
            del updates[k]
        elif entry["kind"] == "choice" and updates[k] not in entry["choices"]:
            print(json.dumps({"skipped_out_of_set_value": k,
                              "value": updates[k],
                              "choices": list(entry["choices"])}))
            del updates[k]
    tuned.merge(updates)
    print(json.dumps({"applied": tuned.path(),
                      "keys": [k for k in updates if k != "hints"]}))


if __name__ == "__main__":
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    args = [a for a in sys.argv[1:] if a != "--apply"]
    hints = main(args[0] if args else
                 os.path.join(repo, "TPU_PROFILE_RESULTS.json"))
    if "--apply" in sys.argv[1:]:
        apply_hints(hints or [])

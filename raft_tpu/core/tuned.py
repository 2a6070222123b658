"""Measured-on-chip tuned defaults.

`bench/apply_profile_hints.py --apply` turns profiler measurements into
`raft_tpu/tuned_defaults.json` (committed alongside the code), and the
library's `"auto"` dispatch paths consult it here — closing the
measure→flip loop without hand-editing dispatch constants.

Scope is deliberately narrow: only `"auto"` engine selections read tuned
keys, because their contract already lets the library pick among engines
(including approximately-trimming ones). Explicit engine/params choices
are never overridden, so a caller who pinned behavior keeps it.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tuned_defaults.json",
)

#: Machine-readable registry of every tuned key (the FAULT_SITES
#: pattern): key -> {"kind", "choices", "bench"}. Read BY AST by
#: raftlint's `tuned-key-registry` rule (tools/raftlint/rules/
#: tuned_keys.py) — keep it a literal dict. The rule enforces that
#: every `tuned.get`/`tuned.get_choice` literal and every `*_KEY`
#: constant is registered, every registered key is read somewhere, and
#: every bench --apply writer writes only registered keys with allowed
#: values — so a typo'd key can never silently strand a chip session's
#: measured winner where no reader finds it.
#:
#: kinds: "choice" (enumerated values, validated at write sites),
#: "int" / "float" / "bool" (numeric knobs), "dict" (structured
#: policies), "hints" (the free-form provenance sub-dict, read only
#: through `tuned.hints()`). "bench" names the --apply writer that owns
#: the key (None = hand-set override with no measuring bench).
TUNED_KEYS = {
    "adaptive_probe_policy": {
        "kind": "dict", "choices": None,
        "bench": "bench/bench_adaptive_probes.py"},
    "comms_quant_block": {
        "kind": "choice", "choices": (16, 32, 64, 128),
        "bench": "bench/bench_qcomms.py"},
    "comms_quant_mode": {
        "kind": "choice", "choices": ("off", "int8", "bf16"),
        "bench": "bench/bench_qcomms.py"},
    "flat_auto_engine": {
        "kind": "choice", "choices": ("query", "list", "pallas", "fused"),
        "bench": "bench/apply_profile_hints.py"},
    "grouped_reduce_crossover": {
        "kind": "float", "choices": None, "bench": "bench/bench_comms.py"},
    "grouped_reduce_schedule": {
        "kind": "choice", "choices": ("ring", "planes"),
        "bench": "bench/bench_comms.py"},
    "hints": {
        "kind": "hints", "choices": None, "bench": None},
    "mnmg_query_sharded_min_nq": {
        "kind": "int", "choices": None, "bench": "bench/bench_mnmg_merge.py"},
    "mnmg_query_sharded_min_nq_per_k": {
        "kind": "float", "choices": None,
        "bench": "bench/bench_mnmg_merge.py"},
    "mnmg_replicated_merge_schedule": {
        "kind": "choice", "choices": ("tournament", "allgather"),
        "bench": "bench/bench_comms.py"},
    "rabitq_query_bits": {
        "kind": "int", "choices": None, "bench": "bench/bench_ivf_rabitq.py"},
    "rabitq_rerank_mult": {
        "kind": "int", "choices": None, "bench": "bench/bench_ivf_rabitq.py"},
    "select_k_auto_strategy": {
        "kind": "choice", "choices": ("counting",),
        "bench": "bench/bench_select_k_strategies.py"},
    "select_k_chunk_threshold": {
        "kind": "int", "choices": None,
        "bench": "bench/bench_select_k_strategies.py"},
    "select_k_strategy": {
        "kind": "choice", "choices": ("topk", "two_phase", "counting",
                                      "fused"),
        "bench": "bench/bench_select_k_strategies.py"},
    "select_k_strategy_int8": {
        "kind": "choice", "choices": ("fused_int8",),
        "bench": "bench/bench_select_k_strategies.py"},
}

#: Canonical key-constant spellings (the ONE definition each; the
#: dispatch modules re-export these rather than repeating the literal —
#: `tuned-key-registry` flags any `*_KEY` constant whose value is not
#: registered above).
INT8_SCAN_KEY = "select_k_strategy_int8"
POLICY_KEY = "adaptive_probe_policy"


def known_keys() -> tuple:
    """Sorted registered key names (mirrors faults.known_sites())."""
    return tuple(sorted(TUNED_KEYS))


@functools.lru_cache(maxsize=1)
def _load() -> dict:
    try:
        with open(_PATH) as f:
            d = json.load(f)
        return d if isinstance(d, dict) else {}
    except (OSError, ValueError):
        return {}


def get(key: str, default: Any = None) -> Any:
    """Tuned value for `key`, or `default` when no tuned file exists (the
    state until a chip session has produced measurements)."""
    return _load().get(key, default)


def get_choice(key: str, allowed, default):
    """Tuned value for `key` validated against an allowed set; falls back
    to `default` on a missing or out-of-set value. Shared by dispatch
    sites that must agree on the honored set."""
    v = get(key, default)
    return v if v in allowed else default


def hints() -> dict:
    """The free-form "hints" sub-dict; {} when the tuned file, the key,
    or the value is missing/null/corrupt. The ONE access path for
    hints — `tuned.get("hints", {})` and `tuned.get("hints") or {}`
    used to coexist and disagreed on a hand-edited `"hints": null`
    (enforced by raftlint's `tuned-key-registry`)."""
    h = get("hints")
    return h if isinstance(h, dict) else {}


def path() -> str:
    return _PATH


def reload() -> None:
    """Drop the cache (tests / after --apply writes a new file)."""
    _load.cache_clear()


def merge(updates: dict) -> None:
    """Merge keys into the tuned file (never clobbers other sessions'
    winners) and reload. The writer every bench --apply mode shares."""
    try:
        with open(_PATH) as f:
            record = json.load(f)
        if not isinstance(record, dict):
            record = {}
    except (OSError, ValueError):
        record = {}
    for k, v in updates.items():
        if k == "hints" and isinstance(v, dict):
            if not isinstance(record.get("hints"), dict):
                record["hints"] = {}  # heal a hand-edited non-dict value
            record["hints"].update(v)
        else:
            record[k] = v
    # atomic replace: a crash mid-write must not leave truncated JSON
    # that silently resets every winner to the heuristics (shared
    # temp-then-rename protocol, which also unlinks the temp on failure)
    from raft_tpu.core.serialize import atomic_write

    with atomic_write(_PATH) as tmp:
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
    reload()

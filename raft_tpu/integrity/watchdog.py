"""Quarantine + repair: the containment half of the integrity story.

`IntegrityWatchdog` wraps a `Scrubber` with the serve-loop contract:
one bounded slice per `step(index)` call, and when a slice names a bad
list the watchdog immediately masks it through the existing
tombstone/`valid` path (every engine already skips dead cells — the
quarantined index serves bit-identically to one that never held those
rows, and `coverage()` reports the loss honestly instead of returning
garbage), then repairs zero-dip between batches through a pluggable
`repair` callable — checkpoint replay locally (`checkpoint_repairer`),
a replica mirror under MNMG (`repair_ranks`). A repaired index is
digest-verified (`digest.check_fresh`) before it replaces the
quarantined one; a repair that fails verification is rejected and the
quarantine stands.

Quarantine deliberately masks EVERY cell of the bad list, not just the
live ones: the rot may sit in `slot_rows` itself, so occupancy cannot
be trusted — and masking unoccupied/dead cells is a no-op to the scan.

MNMG rot is per-rank, not per-list (the sharded primaries are
rank-major blocks): `mnmg_digests` snapshots one digest per (attr,
rank), `verify_mnmg` names rotted ranks, and `repair_ranks` reuses the
PR-4 election + patched-view machinery (`comms.recovery.heal`) to
restore them from their ring mirrors.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from raft_tpu import obs
from raft_tpu.integrity import digest
from raft_tpu.integrity.scrub import ROT_SITE, Scrubber


def quarantine(index, list_id: int, kind: Optional[str] = None):
    """Mask every cell of `list_id` dead on a CLONE (zero-dip swap
    semantics: in-flight scans keep the old object). Returns the new
    index; its tombstones digest rows refresh through the normal
    incremental path, the rotted payload rows intentionally keep their
    stale (mismatching) digests — the scrubber skips quarantined lists
    instead of re-flagging them."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import mutation

    kind = kind or digest.kind_of(index)
    mask = mutation._tomb_mask(index).copy()
    mask[int(list_id), :] = True
    out = mutation._clone(index)
    out.tombstones = jnp.asarray(mask)
    for name in mutation._DERIVED_ATTRS:
        if getattr(out, name, None) is not None:
            setattr(out, name, None)
    digest.refresh(out, index, kind)
    if obs.enabled():
        obs.counter("integrity.quarantines").inc()
        obs.event("integrity.quarantine", list=int(list_id))
    return out


def checkpoint_repairer(root: str) -> Callable:
    """Repair callable for local serving: rebuild the index from the
    mutation root's checkpoint + log replay (integrity.restore) to the
    log's committed state — a serve loop applying uncommitted feed
    batches should commit before repair so the restored state matches
    what it serves. The restored object is digest-verified by the
    watchdog before swap-in like any other repair."""
    def _repair(index):
        import importlib

        # importlib, not `from ... import restore`: the package re-binds
        # `restore` to the FUNCTION, shadowing the module
        restore_mod = importlib.import_module("raft_tpu.integrity.restore")
        restored, _ = restore_mod.restore(root, verify=True)
        return restored

    return _repair


class IntegrityWatchdog:
    """Serve-side integrity driver. `step(index)` runs one scrub slice
    and handles any mismatch; it returns the index to serve next —
    usually the one passed in, a quarantined clone on detection, a
    verified repair when one succeeds. `coverage()` in [0, 1] is the
    fraction of lists not quarantined (1.0 = full coverage), which the
    serve adapters surface as the result coverage so degradation is
    visible at the dispatch layer."""

    def __init__(self, kind: Optional[str] = None, *, budget_lists: int = 8,
                 repair: Optional[Callable] = None):
        self.scrubber = Scrubber(kind, budget_lists=budget_lists)
        self.repair = repair
        self.quarantined: Set[int] = set()
        self.table_alarms: Set[str] = set()
        self.repairs = 0
        self.failed_repairs = 0

    def coverage(self) -> float:
        if not self.quarantined:
            return 1.0
        n = max(int(self._n_lists), 1)
        return max(0.0, 1.0 - len(self.quarantined) / n)

    _n_lists = 0

    def step(self, index):
        """One watchdog tick (call between serve batches)."""
        kind = self.scrubber.kind or digest.kind_of(index)
        self._n_lists = int(index.n_lists)
        bad = self.scrubber.slice_scan(index, skip=self.quarantined)
        for field, lid in bad:
            if lid < 0:
                # table-granularity rot has no smaller containment
                # mask than "repair": remember the alarm, degrade-free
                # serving resumes only after a verified repair
                self.table_alarms.add(field)
                continue
            if lid in self.quarantined:
                continue
            index = quarantine(index, lid, kind)
            self.quarantined.add(lid)
        if (self.quarantined or self.table_alarms) and self.repair is not None:
            index = self._try_repair(index, kind)
        return index

    def _try_repair(self, index, kind: str):
        try:
            repaired = self.repair(index)
            if repaired is None:
                return index
            digest.check_fresh(repaired, kind)
        except Exception as e:  # noqa: BLE001 — quarantine must outlive
            # a failed repair: serving stays degraded-but-honest
            self.failed_repairs += 1
            if obs.enabled():
                obs.counter("integrity.failed_repairs").inc()
                obs.event("integrity.repair", ok=False, error=str(e)[:200])
            return index
        self.repairs += 1
        n_lists = int(repaired.n_lists)
        if obs.enabled():
            obs.counter("integrity.repairs").inc()
            obs.event("integrity.repair", ok=True,
                      lists=sorted(self.quarantined),
                      tables=sorted(self.table_alarms))
        self.quarantined.clear()
        self.table_alarms.clear()
        self._n_lists = n_lists
        return repaired


# ---------------------------------------------------------------------------
# MNMG: per-rank shard digests + mirror repair
# ---------------------------------------------------------------------------


def mnmg_digests(index) -> Dict[str, np.ndarray]:
    """One CRC-32C per (replicated attr, rank) over the rank-major
    primary shards — the MNMG sidecar (per-rank because that is the
    repair granularity the mirrors provide)."""
    from raft_tpu.comms.replication import _replicated_attrs

    out: Dict[str, np.ndarray] = {}
    for name in _replicated_attrs(index):
        out[name] = digest.crc32c_rows(np.asarray(getattr(index, name)))
    return out


def verify_mnmg(index, baseline: Dict[str, np.ndarray]) -> List[int]:
    """Re-hash the shards against a `mnmg_digests` baseline; returns
    the sorted rotted ranks (any attr mismatching convicts the rank)."""
    bad: Set[int] = set()
    current = mnmg_digests(index)
    for name, want in baseline.items():
        got = current.get(name)
        if got is None or got.shape != np.asarray(want).shape:
            bad.update(range(int(index.comms.get_size())))
            continue
        bad.update(int(r) for r in np.flatnonzero(got != np.asarray(want)))
    if obs.enabled():
        obs.counter("integrity.scans").inc()
        for r in sorted(bad):
            obs.counter("integrity.mismatches").inc()
            obs.event("integrity.mismatch", field="shard", rank=int(r))
    return sorted(bad)


def rot_rank(index, rank: int, *, frac: float = 0.05, seed: int = 0) -> None:
    """Rot one rank's primary payload shard in place (MNMG drill
    helper; the FaultPlan-driven flavor seeds through `maybe_rot_mnmg`)."""
    import jax.numpy as jnp

    from raft_tpu.comms.replication import _replicated_attrs

    name = _replicated_attrs(index)[0]  # the payload table
    arr = np.ascontiguousarray(np.asarray(getattr(index, name))).copy()
    rng = np.random.default_rng(seed)
    cells = arr[int(rank)].reshape(-1)
    n = max(1, int(frac * cells.size))
    sel = rng.choice(cells.size, size=min(n, cells.size), replace=False)
    view = cells.view(np.uint8).reshape(cells.size, arr.itemsize)
    view[sel, 0] ^= 0xFF
    setattr(index, name, jnp.asarray(arr))
    if obs.enabled():
        obs.counter("integrity.rot_injected").inc()
        obs.event("integrity.rot", field=name, rank=int(rank))


def maybe_rot_mnmg(index, *, salt: int = 0) -> List[int]:
    """FaultPlan-driven MNMG shard rot at ``integrity.table.rot``
    (`corrupt_shard` faults; `rank` picks the victim, -1 draws one
    seeded). Returns the rotted ranks."""
    from raft_tpu.core import faults

    plan = faults.active_plan()
    if plan is None:
        return []
    hits = plan.matching(ROT_SITE, "corrupt_shard")
    if not hits:
        return []
    world = int(index.comms.get_size())
    rotted: List[int] = []
    for fi, f in enumerate(hits):
        rng = np.random.default_rng((plan.site_seed(ROT_SITE), salt, fi))
        rank = int(f.rank) if f.rank >= 0 else int(rng.integers(world))
        rot_rank(index, rank, frac=max(float(f.fraction), 1e-3),
                 seed=int(rng.integers(1 << 31)))
        rotted.append(rank)
    return sorted(set(rotted))


def repair_ranks(index, ranks, checkpoint: Optional[str] = None,
                 timeout_s: float = 30.0):
    """Mirror repair for rotted ranks: synthesize a RankHealth with the
    convicted ranks unhealthy and run the PR-4 heal loop (replica
    patch ppermute, checkpoint rehydration fallback, one verified
    barrier). Returns the repaired index."""
    from raft_tpu.comms import recovery
    from raft_tpu.comms.resilience import RankHealth

    health = RankHealth.all_healthy(int(index.comms.get_size()))
    for r in ranks:
        health.mark_unhealthy(int(r))
    index, _ = recovery.heal(index.comms, health, index,
                             checkpoint=checkpoint, timeout_s=timeout_s)
    if obs.enabled():
        obs.counter("integrity.repairs").inc()
        obs.event("integrity.repair", ok=True, ranks=sorted(int(r) for r in ranks))
    return index

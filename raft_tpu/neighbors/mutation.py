"""Live mutable indexes: crash-atomic upsert/delete with tombstones.

Every index in the library is a padded list-major store whose engines
mask candidate scores to the worst value wherever the slot table reads
-1 — the same mechanism that implements pads and prefilters. Mutation
rides it end to end:

- **delete** marks the victim's (list, slot) cells in a per-index
  `tombstones` mask; `core.bitset.make_slot_filter` folds the mask into
  the slot-table view every engine scans, so dead rows vanish from the
  fused Pallas kernels (`valid`/`chunk_valid` skip them), the XLA
  references (masked to +inf/-1), and refine/regroup_merge (a dead row
  is never a candidate, so nothing can resurrect it). `tombstones is
  None` = all-live: an unmutated index traces the identical program
  bit-for-bit.
- **upsert** tombstones every live slot holding the id, then appends
  the new row through the index's own `extend` (label + encode +
  scatter) — rows land in reserved tail slots (`ensure_append_slack`)
  so steady-state churn never re-pads the store, and the
  `resid_bf16`/`recon8` lazy-store + `fused_kb` invalidation
  contracts do the rest.
- **rebalance** compacts tombstone-heavy lists: live rows pack left in
  slot order (deterministic), the store re-pads to the live geometry
  plus the reserved slack, and the mask drops back to None.

Crash-atomicity is the jobs/streaming batch-boundary protocol applied
to mutation (`Mutator`): each batch's payload is a CRC'd container
(`_save_batch`, `serialize.atomic_write` — never torn) written BEFORE
its line is appended to the CRC'd `mutlog.jsonl` (torn-line-terminating
appends, the MANIFEST.jsonl pattern), and checkpoint commits save the
whole index with `mut_cursor` = applied-entry count. A SIGKILL at ANY
point resumes bit-identically: the log's valid dense prefix is the
ground truth, the checkpoint is a replay shortcut, and a re-issued
driver sequence dedupes against the log by sequence number. Chaos
sites: `mutation.log.commit` (`crash_point` fires both after a log
append and after a checkpoint commit — the two SIGKILL windows),
`mutation.tombstone`, `mutation.rebalance`.

Layer contract (tools/raftlint/rules/layers.py): this module is
orchestration ABOVE the index modules — they are resolved lazily at
call time (`MODULE_CYCLE_BAN`), module scope touches only core/obs
(`MODULE_ALLOWED`).
"""

from __future__ import annotations

import json
import os
from typing import Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.core import faults
from raft_tpu.core.serialize import crc32c

#: chaos sites (core.faults.FAULT_SITES)
LOG_COMMIT_SITE = "mutation.log.commit"
TOMBSTONE_SITE = "mutation.tombstone"
REBALANCE_SITE = "mutation.rebalance"

#: index kinds the mutation protocol understands
KINDS = ("ivf_flat", "ivf_pq", "ivf_rabitq")

LOG_NAME = "mutlog.jsonl"
CKPT_NAME = "index.ckpt"

#: slot-group width of every list store (the kIndexGroupSize=32 lane
#: contract `_pack_lists`/`_append_slots` round to)
GROUP = 32


class MutationLogError(RuntimeError):
    """The mutation log and its checkpoint disagree in a way replay
    cannot reconcile (externally truncated log, payload/line op
    mismatch, an op this build does not know) — resuming would diverge
    from the pre-crash state, so the open refuses, typed."""


def _index_module(kind: str):
    """The `neighbors` module for a mutable index kind (lazy: mutation
    orchestrates the index modules, so they resolve at call time — the
    jobs/streaming idiom, enforced by MODULE_CYCLE_BAN)."""
    if kind == "ivf_flat":
        from raft_tpu.neighbors import ivf_flat as mod
    elif kind == "ivf_pq":
        from raft_tpu.neighbors import ivf_pq as mod
    elif kind == "ivf_rabitq":
        from raft_tpu.neighbors import ivf_rabitq as mod
    else:
        raise ValueError(f"unknown index kind {kind!r}; one of {KINDS}")
    return mod


def kind_of(index) -> str:
    """Index kind from the instance's defining module."""
    mod = type(index).__module__.rsplit(".", 1)[-1]
    if mod not in KINDS:
        raise TypeError(f"not a mutable index: {type(index)!r}")
    return mod


def _payload_attrs(kind: str) -> Tuple[str, ...]:
    """The per-kind list-major payload tables that share slot geometry
    with `slot_rows` (axis 1 = slots)."""
    if kind == "ivf_flat":
        return ("list_data",)
    if kind == "ivf_pq":
        return ("codes",)
    return ("codes", "aux")


#: derived (runtime) stores invalidated by any slot-geometry change —
#: each rebuilds lazily on first use; `fused_kb` survives (monotone
#: candidate-buffer contract, ivf_flat `_pad_store_to_lanes`)
_DERIVED_ATTRS = ("resid_bf16", "resid_norm", "recon8", "recon_scale",
                  "recon_norm", "slot_rows_pad", "_list_radii")


def _clone(index):
    """Shallow copy: mutation returns a NEW index object (the serve
    layer swaps the reference between device batches — in-flight
    searches keep scanning the old object, zero-dip)."""
    import copy

    return copy.copy(index)


def _tomb_mask(index) -> np.ndarray:
    t = index.tombstones
    if t is None:
        return np.zeros(np.asarray(index.slot_rows).shape, bool)
    return np.asarray(t).astype(bool)


def live_rows(index) -> int:
    """Occupied slots minus tombstones — the truthful row count of a
    mutated index (`index.size` counts every appended row, including
    superseded upsert versions)."""
    sr = np.asarray(index.slot_rows)
    return int(((sr >= 0) & ~_tomb_mask(index)).sum())


def tombstone(index, ids):
    """Mark every LIVE slot holding one of `ids` dead; returns
    (new_index, n_dead). Ids absent from the index (or already dead)
    are ignored — delete is idempotent. The slot table itself is
    untouched (placement survives for compaction); only the mask grows,
    so unaffected queries stay bit-identical modulo the mask operand."""
    faults.fault_point(TOMBSTONE_SITE)
    sr = np.asarray(index.slot_rows)
    sid = np.asarray(index.source_ids)
    t = _tomb_mask(index)
    ids = np.unique(np.asarray(ids, sid.dtype).ravel())
    # positions whose id is a victim -> their (list, slot) cells; an
    # upserted id holds several positions, but only live slots flip
    victim_pos = np.isin(sid, ids)
    dead_new = victim_pos[np.maximum(sr, 0)] & (sr >= 0) & ~t
    n = int(dead_new.sum())
    if n == 0:
        return index, 0
    out = _clone(index)
    out.tombstones = jnp.asarray(t | dead_new)
    from raft_tpu.integrity.digest import refresh as _refresh_digests

    _refresh_digests(out, index)  # only the flipped mask rows re-digest
    if obs.enabled():
        obs.counter("mutation.tombstones").inc(n)
        obs.event("mutation", op="delete", index_kind=kind_of(index), n=n)
    return out, n


def delete(index, ids):
    """Online delete: tombstone `ids`. Returns the new index."""
    out, _ = tombstone(index, ids)
    return out


def upsert(index, vectors, ids=None):
    """Online upsert: retire any live row holding each id, then append
    the new rows through the index's own `extend` (label + encode +
    scatter into reserved tail slots). `ids=None` assigns fresh ids
    (`index.id_bound` onward) — a pure insert. Returns the new index;
    the OLD object keeps serving unchanged (zero-dip swap contract)."""
    kind = kind_of(index)
    mod = _index_module(kind)
    vectors = np.asarray(vectors)
    if ids is None:
        base = index.id_bound
        ids = np.arange(base, base + vectors.shape[0], dtype=np.int32)
    ids = np.asarray(ids, np.int32).ravel()
    if ids.shape[0] != vectors.shape[0]:
        raise ValueError(
            f"{vectors.shape[0]} vectors but {ids.shape[0]} ids")
    out, _ = tombstone(index, ids)
    out = mod.extend(out, vectors, new_indices=jnp.asarray(ids))
    if obs.enabled():
        obs.counter("mutation.upserts").inc(int(ids.shape[0]))
        obs.event("mutation", op="upsert", index_kind=kind, n=int(ids.shape[0]))
    return out


def ensure_append_slack(index, slack: int):
    """Reserve at least `slack` free tail slots in every list (rounded
    to the 32-slot group), so upsert batches scatter into existing pad
    columns instead of re-padding the store each time. Grow-only (the
    `extend` never-shrink contract); derived fused stores invalidate
    and rebuild lazily at the wider geometry. Returns the new index
    (the input when already wide enough)."""
    slack = int(slack)
    if slack < 0:
        raise ValueError(f"slack must be >= 0, got {slack}")
    kind = kind_of(index)
    sizes = np.asarray(index.list_sizes, np.int64)
    width = int(np.asarray(index.slot_rows).shape[1])
    need = int(sizes.max() if sizes.size else 0) + slack
    need = -(-max(need, 1) // GROUP) * GROUP
    if need <= width:
        if index.append_slack != slack:
            index = _clone(index)
            index.append_slack = slack
        return index
    extra = need - width
    out = _clone(index)
    for name in _payload_attrs(kind):
        tbl = getattr(index, name)
        setattr(out, name, jnp.pad(
            tbl, ((0, 0), (0, extra)) + ((0, 0),) * (tbl.ndim - 2)))
    out.slot_rows = jnp.pad(index.slot_rows, ((0, 0), (0, extra)),
                            constant_values=-1)
    from raft_tpu.core.bitset import carry_tombstones

    out.tombstones = carry_tombstones(index.tombstones, need)
    out.append_slack = slack
    for name in _DERIVED_ATTRS:
        if hasattr(out, name):
            setattr(out, name, None)
    from raft_tpu.integrity.digest import refresh as _refresh_digests

    _refresh_digests(out, index)  # geometry grew: full re-digest
    return out


def compact(index, *, slack: Optional[int] = None):
    """Drop tombstoned rows: live slots pack left in slot order (a
    deterministic host-side repack), the store width shrinks to the
    live geometry plus the reserved `slack` (default: the index's
    recorded `append_slack`), and the mask returns to None. Superseded
    `source_ids` entries stay (they are unreferenced; positions must
    not shift — slot values index into `source_ids`). `list_radii`
    stay: a max over former members still bounds the survivors."""
    kind = kind_of(index)
    slack = index.append_slack if slack is None else int(slack)
    sr = np.asarray(index.slot_rows)
    t = _tomb_mask(index)
    live = (sr >= 0) & ~t
    live_sizes = live.sum(axis=1).astype(np.int32)
    new_max = int(live_sizes.max() if live_sizes.size else 0) + slack
    new_max = -(-max(new_max, 1) // GROUP) * GROUP
    # stable left-pack: argsort on "dead" puts live slots first in
    # original slot order (kind='stable'), one shared gather for every
    # payload table
    order = np.argsort(~live, axis=1, kind="stable")
    packed_live = np.take_along_axis(live, order, axis=1)
    new_sr = np.where(packed_live, np.take_along_axis(sr, order, axis=1), -1)
    out = _clone(index)
    if new_max <= sr.shape[1]:
        new_sr = new_sr[:, :new_max]
        cut = order[:, :new_max]
    else:
        pad = new_max - sr.shape[1]
        new_sr = np.pad(new_sr, ((0, 0), (0, pad)), constant_values=-1)
        cut = np.pad(order, ((0, 0), (0, pad)), mode="edge")
    for name in _payload_attrs(kind):
        tbl = np.asarray(getattr(index, name))
        gathered = np.take_along_axis(
            tbl, cut.reshape(cut.shape + (1,) * (tbl.ndim - 2)), axis=1)
        if new_max > sr.shape[1]:
            gathered[:, sr.shape[1]:] = 0
        setattr(out, name, jnp.asarray(gathered))
    out.slot_rows = jnp.asarray(new_sr.astype(sr.dtype))
    out.list_sizes = jnp.asarray(live_sizes)
    out.tombstones = None
    out.append_slack = slack
    for name in _DERIVED_ATTRS:
        if hasattr(out, name):
            setattr(out, name, None)
    from raft_tpu.integrity.digest import refresh as _refresh_digests

    _refresh_digests(out, index)  # repack moved slots: re-digest them
    if obs.enabled():
        obs.counter("mutation.rebalances").inc()
        obs.event("mutation", op="rebalance", index_kind=kind,
                  n=int(t.sum()), width=new_max)
    return out


def rebalance(index, *, min_dead_frac: float = 0.0,
              slack: Optional[int] = None):
    """Compact when the store is tombstone-heavy enough to pay for it:
    dead slots / occupied slots >= `min_dead_frac` (0.0 = always).
    Returns (index, compacted_bool). The background-maintenance entry
    point — `Mutator.rebalance` logs it, `jobs.resumable_mutate` runs
    it preemptibly."""
    faults.fault_point(REBALANCE_SITE)
    sr = np.asarray(index.slot_rows)
    occupied = int((sr >= 0).sum())
    dead = int((_tomb_mask(index) & (sr >= 0)).sum())
    if occupied == 0 or dead == 0 or dead < min_dead_frac * occupied:
        return index, False
    return compact(index, slack=slack), True


# ---------------------------------------------------------------------------
# crash-atomic mutation log
# ---------------------------------------------------------------------------


def _save_batch(path: str, op: str, seq: int, ids: np.ndarray,
                vectors: Optional[np.ndarray]) -> None:
    """One mutation batch's payload container (CRC'd, atomic — a kill
    mid-write leaves NO file, so a payload either exists whole or its
    log line was never appended)."""
    from raft_tpu.core.serialize import serialize_arrays

    arrays = {"ids": jnp.asarray(ids, jnp.int32)}
    if vectors is not None:
        arrays["vectors"] = jnp.asarray(vectors, jnp.float32)
    serialize_arrays(path, arrays, {
        "kind": "mutation_batch",
        "version": 1,
        "op": op,
        "seq": int(seq),
    })


def _load_batch(path: str):
    """Read one payload container back; returns (op, seq, ids, vectors
    — None for deletes/rebalances)."""
    from raft_tpu.core.serialize import read_ckpt

    arrays, meta = read_ckpt(path, "mutation_batch")
    ids = np.asarray(arrays["ids"])
    vectors = arrays.get("vectors")
    if vectors is not None:
        vectors = np.asarray(vectors)
    return meta["op"], int(meta["seq"]), ids, vectors


class MutationLog:
    """Append-only CRC'd mutation journal (`mutlog.jsonl`).

    One line per committed batch: ``{"v", "seq", "op", "payload",
    "crc"}`` where `crc` is CRC-32C over the line's canonical encoding
    without the crc field (torn or rotted lines are skipped on read).
    Appends terminate a torn final line first (the MANIFEST.jsonl /
    obs.ledger discipline), and the payload container is written BEFORE
    its line — so the set of valid lines whose seq forms a dense prefix
    is exactly the set of durable mutations."""

    def __init__(self, root: str):
        self.root = os.path.abspath(os.fspath(root))
        os.makedirs(self.root, exist_ok=True)

    @property
    def path(self) -> str:
        return os.path.join(self.root, LOG_NAME)

    def payload_path(self, seq: int) -> str:
        return os.path.join(self.root, f"mut_{int(seq):06d}.ckpt")

    @staticmethod
    def _line_crc(entry: dict) -> int:
        body = {k: v for k, v in entry.items() if k != "crc"}
        blob = json.dumps(body, sort_keys=True,
                          separators=(",", ":")).encode()
        return crc32c(blob)

    def entries(self) -> list:
        """Valid entries, as the longest dense seq prefix. Torn or
        CRC-rotted lines are SKIPPED — a kill mid-append leaves a torn
        tail, and the resumed run legitimately appends its re-issued
        copy of that seq right after it. Safety comes from the seq
        rule: a valid line whose seq is not the next expected one ends
        the log THERE (a skipped line in the MIDDLE leaves a gap, so
        externally-damaged state can never be bridged silently)."""
        if not os.path.exists(self.path):
            return []
        out = []
        with open(self.path, "r", encoding="utf-8", errors="replace") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    e = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn line; the next line may be its redo
                if not isinstance(e, dict) or e.get("crc") != self._line_crc(e):
                    continue  # rotted line; ditto
                if int(e.get("seq", -1)) != len(out):
                    break
                out.append(e)
        return out

    def append(self, op: str, seq: int, payload: Optional[str]) -> dict:
        entry = {"v": 1, "seq": int(seq), "op": op, "payload": payload}
        entry["crc"] = self._line_crc(entry)
        line = json.dumps(entry, sort_keys=True)
        with open(self.path, "a+b") as fh:
            fh.seek(0, os.SEEK_END)
            if fh.tell() > 0:
                fh.seek(-1, os.SEEK_END)
                if fh.read(1) != b"\n":
                    fh.write(b"\n")  # terminate a torn predecessor
            fh.write(line.encode() + b"\n")
            fh.flush()
            os.fsync(fh.fileno())
        return entry


class Mutator:
    """Crash-atomic online mutation of one index (module docstring).

    Layout under `root`: `mutlog.jsonl` + `mut_<seq>.ckpt` payloads +
    `index.ckpt` (the committed checkpoint, carrying `mut_cursor` =
    applied-entry count). Open with the cold-start index; when a
    committed checkpoint exists it REPLACES the argument (the
    jobs/streaming resume contract) and the log's tail beyond the
    cursor replays deterministically, so a SIGKILL at any point —
    payload write, log append, checkpoint commit — resumes to the
    bit-identical state.

    A re-run driver re-issues its mutation sequence from the top; calls
    whose seq is already in the log are skipped (their effect is either
    in the checkpoint or was just replayed), which is what makes the
    kill-and-rerun drill converge. `ckpt_every` batches between
    checkpoint commits bounds replay work; `slack` is the per-list
    append reserve (`ensure_append_slack`) renewed at each commit."""

    def __init__(self, root: str, index=None, *, kind: Optional[str] = None,
                 ckpt_every: int = 8, slack: int = 0, retain: int = 0):
        self.log = MutationLog(root)
        self.ckpt_every = max(1, int(ckpt_every))
        self.slack = int(slack)
        # point-in-time recovery window (raft_tpu/integrity): keep the
        # `retain` newest commit checkpoints as cursor-stamped
        # snapshots; payload GC then sweeps only below the oldest
        # retained cursor so every retained base can replay forward.
        # 0 = no window, the pre-PITR behavior verbatim.
        self.retain = max(0, int(retain))
        ckpt = os.path.join(self.log.root, CKPT_NAME)
        if os.path.exists(ckpt):
            if kind is None:
                kind = kind_of(index) if index is not None else None
            if kind is None:
                raise ValueError("resume needs kind= (or an index)")
            index = _index_module(kind).load(ckpt)
        elif index is None:
            raise ValueError("no committed checkpoint: pass the index")
        self.kind = kind or kind_of(index)
        self.index = index
        if self.slack:
            self.index = ensure_append_slack(self.index, self.slack)
        entries = self.log.entries()
        cursor = int(self.index.mut_cursor)
        if cursor > len(entries):
            raise MutationLogError(
                f"checkpoint cursor {cursor} beyond the log "
                f"({len(entries)} entries) — the log was truncated "
                "externally; refusing a divergent resume")
        for e in entries[cursor:]:
            self._apply(e)
        self.applied = len(entries)
        self._issued = 0

    # -- protocol ------------------------------------------------------
    @property
    def ckpt_path(self) -> str:
        return os.path.join(self.log.root, CKPT_NAME)

    def _apply(self, entry: dict) -> None:
        """Deterministically apply one logged entry to the in-memory
        index (the replay path and the live path share it)."""
        op = entry["op"]
        if op == "rebalance":
            self.index, _ = rebalance(self.index, slack=self.slack or None)
            return
        op2, _, ids, vectors = _load_batch(
            self.log.payload_path(entry["seq"]))
        if op2 != op:
            raise MutationLogError(
                f"payload op {op2!r} != log op {op!r} at seq "
                f"{entry['seq']}")
        if op == "upsert":
            self.index = upsert(self.index, vectors, ids)
        elif op == "delete":
            self.index = delete(self.index, ids)
        else:
            raise MutationLogError(f"unknown logged op {op!r}")

    def _submit(self, op: str, ids, vectors=None):
        seq = self._issued
        self._issued += 1
        if seq < self.applied:
            return self.index  # already durable (pre-kill run logged it)
        if vectors is not None or op in ("upsert", "delete"):
            _save_batch(self.log.payload_path(seq), op, seq,
                        np.asarray(ids, np.int32), vectors)
        self.log.append(op, seq, None if op == "rebalance"
                        else os.path.basename(self.log.payload_path(seq)))
        entry = {"op": op, "seq": seq}
        self._apply(entry)
        self.applied += 1
        # SIGKILL window 1: the log is ahead of the checkpoint — the
        # resume must replay this entry (count-th visit kills; see
        # core.faults.crash_point)
        faults.crash_point(LOG_COMMIT_SITE)
        if self.applied - int(self.index.mut_cursor) >= self.ckpt_every:
            self.commit()
        return self.index

    def upsert(self, vectors, ids):
        """Log + apply one upsert batch. Returns the current index."""
        return self._submit("upsert", ids, np.asarray(vectors, np.float32))

    def delete(self, ids):
        """Log + apply one delete batch. Returns the current index."""
        return self._submit("delete", ids)

    def rebalance(self):
        """Log + apply a compaction, then commit immediately (the
        geometry change makes checkpointing now strictly cheaper than
        replaying it later). Returns the current index."""
        out = self._submit("rebalance", np.empty((0,), np.int32))
        self.commit()
        return out

    def commit(self):
        """Checkpoint the index with `mut_cursor` = applied entries
        (one atomic file — the batch-boundary commit), then sweep the
        payload containers the checkpoint superseded."""
        if int(self.index.mut_cursor) != self.applied:
            idx = _clone(self.index)
            idx.mut_cursor = self.applied
            idx.append_slack = self.slack
            from raft_tpu.integrity.digest import attach as _attach_digests

            if getattr(idx, "list_digests", None) is None:
                # mutation-commit digest hook: an index that predates
                # the sidecar (legacy checkpoint) gains one here, so
                # every committed checkpoint is scrub-coverable
                _attach_digests(idx, self.kind)
            _index_module(self.kind).save(self.ckpt_path, idx)
            self.index = idx
            sweep_below = self.applied
            if self.retain:
                import importlib
                import shutil

                # importlib, not `from ... import restore`: the package
                # re-binds `restore` to the FUNCTION, shadowing the module
                _pitr = importlib.import_module(
                    "raft_tpu.integrity.restore")

                # a byte-for-byte copy of the commit IS the snapshot —
                # the PITR byte-identity claim needs no second writer
                shutil.copyfile(self.ckpt_path,
                                _pitr.snapshot_path(self.log.root,
                                                    self.applied))
                kept = _pitr.prune(self.log.root, keep=self.retain)
                sweep_below = min(kept) if kept else self.applied
            for seq in range(sweep_below):
                p = self.log.payload_path(seq)
                if os.path.exists(p):
                    try:
                        os.remove(p)
                    except OSError:
                        pass  # an orphan payload is ignored garbage
            if obs.enabled():
                obs.event("mutation", op="commit", index_kind=self.kind,
                          cursor=self.applied)
        # SIGKILL window 2: after the checkpoint commit — the resume
        # must NOT replay (cursor == log length)
        faults.crash_point(LOG_COMMIT_SITE)
        return self.index


# ---------------------------------------------------------------------------
# serve-layer feed (zero-dip swap-in)
# ---------------------------------------------------------------------------


class MutationFeed:
    """Thread-safe queue of committed mutation batches for the serve
    layer: a mutator (any thread) `publish`es, the serving loop drains
    BETWEEN device batches (`serve.engine` `_heal_between_batches`) and
    swaps its index reference — in-flight traffic keeps the old object,
    so coverage never dips and unaffected queries stay bit-identical.

    Batches are the `apply_batch` shapes: ``("upsert", vectors, ids)``,
    ``("delete", ids)``, ``("rebalance",)``."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self._pending: list = []

    def publish(self, batch: tuple) -> None:
        if not batch or batch[0] not in ("upsert", "delete", "rebalance"):
            raise ValueError(f"unknown mutation batch {batch!r:.60}")
        with self._lock:
            self._pending.append(batch)

    def drain(self) -> list:
        with self._lock:
            out, self._pending = self._pending, []
        return out


def apply_batch(index, batch: tuple):
    """Apply one feed batch to an index, returning the new index."""
    op = batch[0]
    if op == "upsert":
        return upsert(index, batch[1], batch[2])
    if op == "delete":
        return delete(index, batch[1])
    if op == "rebalance":
        out, _ = rebalance(index)
        return out
    raise ValueError(f"unknown mutation op {op!r}")

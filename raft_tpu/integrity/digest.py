"""Content digests for live indexes: per-list / per-table CRC-32C
sidecars over the payload of the three local index kinds.

The checkpoint CRC (core/serialize) only proves bytes survived the
*disk* round trip; these sidecars cover the tables while they are live
— computed at build/extend time, kept incrementally fresh by the
mutation ops (only touched lists re-digest), carried through save/load
as first-class `CKPT_SCHEMA` fields, and re-checked online by the
scrubber (integrity/scrub) between serve batches.

Granularity is the containment unit: "list" fields digest per IVF list
row (one uint32 per list — a mismatch names the list to quarantine),
"table" fields digest whole (one uint32 — a mismatch means
repair-from-mirror/checkpoint, there is no smaller mask).

DIGEST_FIELDS is a PURE LITERAL: tools/raftlint AST-parses it (like
CKPT_SCHEMA) and fails closed if it stops being one. The lint rule
`integrity-digest-registry` pins it against CKPT_SCHEMA — every array
field of a digestable kind must carry a digest row, so a new
serialized table cannot silently ship without scrub coverage.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from raft_tpu.core.serialize import crc32c, crc32c_rows

# kind -> {serialized array field -> digest granularity}.
# The sidecar fields themselves ("list_digests" array, "table_digests"
# meta) are exempt — a digest of the digests adds detection power only
# against rot of the sidecar, which a mismatch already surfaces.
DIGEST_FIELDS = {
    "ivf_flat": {
        "centers": "table",
        "list_data": "list",
        "slot_rows": "list",
        "list_sizes": "table",
        "source_ids": "table",
        "list_radii": "table",
        "tombstones": "list",
    },
    "ivf_pq": {
        "rotation": "table",
        "centers": "table",
        "pq_centers": "table",
        "codes": "list",
        "slot_rows": "list",
        "list_sizes": "table",
        "source_ids": "table",
        "list_radii": "table",
        "tombstones": "list",
    },
    "ivf_rabitq": {
        "rotation": "table",
        "centers": "table",
        "codes": "list",
        "aux": "list",
        "slot_rows": "list",
        "list_sizes": "table",
        "source_ids": "table",
        "tombstones": "list",
    },
}


class IntegrityError(RuntimeError):
    """A digest check failed where the caller required a clean result
    (verified restore, post-repair verification)."""


def kind_of(index) -> str:
    """Local index kind from the payload attrs (the mutation-layer
    convention: pq carries pq_centers, rabitq aux without list_data)."""
    if getattr(index, "pq_centers", None) is not None:
        return "ivf_pq"
    if hasattr(index, "aux") and not hasattr(index, "list_data"):
        return "ivf_rabitq"
    if hasattr(index, "list_data"):
        return "ivf_flat"
    raise TypeError(f"not a digestable local index: {type(index).__name__}")


def _canon(field: str, arr) -> np.ndarray:
    # digest the SERIALIZED representation: tombstones live as bool in
    # memory but ship as u8 (ivf_*.save) — canonicalizing here keeps a
    # digest computed before save valid against one recomputed after
    # load, and the sidecar meaningful across the boundary
    a = np.asarray(arr)
    if field == "tombstones":
        a = a.astype(np.uint8)
    return np.ascontiguousarray(a)


def _row_digests(field: str, arr, rows) -> np.ndarray:
    a = _canon(field, arr)
    idx = np.asarray(rows, np.int64).reshape(-1)
    if idx.size == 0:
        return np.empty(0, np.uint32)
    return crc32c_rows(a[idx])


def _table_digest(field: str, arr) -> int:
    return int(crc32c(_canon(field, arr)))


def compute(index, kind: Optional[str] = None
            ) -> Tuple[Dict[str, np.ndarray], Dict[str, int]]:
    """Full digest pass. Returns (lists, tables): lists maps each
    present list-granularity field to a (n_lists,) uint32 row-digest
    vector, tables maps each present table-granularity field to one
    digest. Absent (None) optional fields simply have no entry — the
    invariant `set(lists) == present list fields` is what lets the
    packed sidecar round-trip save/load without a field manifest."""
    kind = kind or kind_of(index)
    spec = DIGEST_FIELDS[kind]
    n_lists = int(index.n_lists)
    all_rows = range(n_lists)
    lists: Dict[str, np.ndarray] = {}
    tables: Dict[str, int] = {}
    for field, gran in spec.items():
        arr = getattr(index, field, None)
        if arr is None:
            continue
        if gran == "table":
            tables[field] = _table_digest(field, arr)
        else:
            lists[field] = _row_digests(field, arr, all_rows)
    return lists, tables


def attach(index, kind: Optional[str] = None) -> None:
    """Compute and attach the sidecar in place (build-time hook)."""
    lists, tables = compute(index, kind)
    index.list_digests = lists
    index.table_digests = tables


def refresh(out, old, kind: Optional[str] = None) -> None:
    """Incrementally refresh `out`'s sidecar after a mutation that
    derived it from `old` (extend / tombstone / compact / rebalance).
    No-op when `old` carries no sidecar (legacy index).

    Touched-row detection leans on the mutation protocol's shape: every
    legitimate op moves `slot_rows` (appends, compaction) and/or the
    tombstone mask (deletes) for exactly the lists it touched, and a
    geometry change (regrow, rebalance) invalidates everything. Rot
    does neither — which is precisely why it stays detectable: nothing
    here ever re-digests a list no op legitimately touched."""
    if old is None or getattr(old, "list_digests", None) is None:
        return
    kind = kind or kind_of(out)
    spec = DIGEST_FIELDS[kind]
    n_lists = int(out.n_lists)
    old_sr = _canon("slot_rows", old.slot_rows)
    new_sr = _canon("slot_rows", out.slot_rows)
    if old_sr.shape != new_sr.shape or int(old.n_lists) != n_lists:
        attach(out, kind)  # geometry changed: every slot moved
        return
    touched = np.flatnonzero((old_sr != new_sr).any(axis=1))
    ot, nt = getattr(old, "tombstones", None), getattr(out, "tombstones", None)
    if (ot is None) != (nt is None):
        tomb_touched = np.arange(n_lists)
    elif nt is None:
        tomb_touched = np.zeros(0, np.int64)
    else:
        om, nm = _canon("tombstones", ot), _canon("tombstones", nt)
        if om.shape != nm.shape:
            tomb_touched = np.arange(n_lists)
        else:
            tomb_touched = np.flatnonzero((om != nm).any(axis=1))
    lists = dict(old.list_digests)
    tables = dict(getattr(old, "table_digests", None) or {})
    for field, gran in spec.items():
        arr = getattr(out, field, None)
        oarr = getattr(old, field, None)
        if arr is None:
            lists.pop(field, None)
            tables.pop(field, None)
            continue
        if gran == "table":
            if oarr is None or arr is not oarr or field not in tables:
                tables[field] = _table_digest(field, arr)
            continue
        rows = tomb_touched if field == "tombstones" else touched
        prev = lists.get(field)
        if oarr is None or prev is None or prev.shape[0] != n_lists:
            lists[field] = _row_digests(field, arr, range(n_lists))
        elif arr is not oarr and len(rows):
            d = prev.copy()
            d[rows] = _row_digests(field, arr, rows)
            lists[field] = d
        # identical object (clone shared the ref) -> digests still hold
    out.list_digests = lists
    out.table_digests = tables


def verify_lists(index, list_ids, kind: Optional[str] = None
                 ) -> List[Tuple[str, int]]:
    """Re-hash the given lists against the sidecar. Returns
    [(field, list_id), ...] mismatches (empty = clean slice)."""
    kind = kind or kind_of(index)
    sidecar = getattr(index, "list_digests", None)
    if not sidecar:
        return []
    bad: List[Tuple[str, int]] = []
    for field, want in sidecar.items():
        arr = getattr(index, field, None)
        if arr is None:
            continue
        got = _row_digests(field, arr, list_ids)
        for j, i in enumerate(list_ids):
            if got[j] != want[int(i)]:
                bad.append((field, int(i)))
    return bad


def verify_tables(index, kind: Optional[str] = None) -> List[str]:
    """Re-hash the table-granularity fields. Returns mismatched field
    names (empty = clean)."""
    kind = kind or kind_of(index)
    sidecar = getattr(index, "table_digests", None)
    if not sidecar:
        return []
    return [f for f, want in sidecar.items()
            if getattr(index, f, None) is not None
            and _table_digest(f, getattr(index, f)) != int(want)]


def verify(index, kind: Optional[str] = None) -> List[Tuple[str, int]]:
    """Full verification pass: every list of every list field plus all
    tables. Table mismatches report list id -1."""
    kind = kind or kind_of(index)
    bad = verify_lists(index, range(int(index.n_lists)), kind)
    bad.extend((f, -1) for f in verify_tables(index, kind))
    return bad


def check_fresh(index, kind: Optional[str] = None) -> None:
    """Raise IntegrityError unless the attached sidecar matches the
    content exactly (the verified-restore / post-repair gate)."""
    kind = kind or kind_of(index)
    if getattr(index, "list_digests", None) is None:
        raise IntegrityError(f"{kind}: no digest sidecar attached")
    bad = verify(index, kind)
    if bad:
        raise IntegrityError(f"{kind}: digest mismatch at {bad[:8]!r}"
                             f" ({len(bad)} total)")


# ---------------------------------------------------------------------------
# checkpoint packing: the per-list vectors ride as ONE (n_fields,
# n_lists) uint32 array field; per-table digests ride in the meta JSON
# ---------------------------------------------------------------------------


def _packed_order(index, kind: str) -> List[str]:
    # deterministic row order WITHOUT a manifest: sorted list-field
    # names, restricted to fields present on the index. Save-side
    # presence (digest entry exists) and load-side presence (attr is
    # not None) agree by the compute/refresh invariant.
    spec = DIGEST_FIELDS[kind]
    return [f for f in sorted(spec) if spec[f] == "list"
            and getattr(index, f, None) is not None]


def pack_lists(index, kind: str) -> Optional[np.ndarray]:
    """Sidecar -> one stacked uint32 array for serialization (None when
    no sidecar is attached)."""
    sidecar = getattr(index, "list_digests", None)
    if sidecar is None:
        return None
    order = _packed_order(index, kind)
    if not all(f in sidecar for f in order):
        return None  # stale sidecar: do not serialize a partial one
    if not order:
        return np.zeros((0, int(index.n_lists)), np.uint32)
    return np.stack([np.asarray(sidecar[f], np.uint32) for f in order])


def unpack_lists(index, kind: str, packed, table_meta) -> None:
    """Load-side inverse of pack_lists: attach the sidecar from the
    checkpoint fields, or leave it absent (None) when the file predates
    digests or the packed shape no longer matches the field set."""
    index.list_digests = None
    index.table_digests = None
    if packed is None:
        return
    order = _packed_order(index, kind)
    packed = np.asarray(packed, np.uint32)
    if packed.ndim != 2 or packed.shape[0] != len(order) \
            or packed.shape[1] != int(index.n_lists):
        return  # foreign/old field layout: degrade to no sidecar
    index.list_digests = {f: packed[i].copy() for i, f in enumerate(order)}
    index.table_digests = {str(k): int(v)
                           for k, v in (table_meta or {}).items()}

"""Versioned binary serialization of named array containers.

Reference parity: `raft::serialize_mdspan` writes numpy .npy-format payloads
into iostreams (core/serialize.hpp:34, detail/mdspan_numpy_serializer.hpp);
index types layer versioned scalar+mdspan streams on top
(detail/ivf_pq_serialize.cuh:36, kSerializationVersion=3).

Here: one container format shared by every index / model:

    magic  8 bytes  b"RAFTTPU\\0"
    u32    container version
    u64    header length
    header JSON: {"meta": {...}, "fields": [{name,dtype,shape,offset,nbytes,
                                             crc32c}]}
    raw little-endian buffers, 64-byte aligned

Integrity: every field carries a CRC-32C (Castagnoli) checksum of its raw
buffer, verified on read (`ChecksumError` names the file and the corrupt
fields) — the detection half of the checkpoint self-healing story
(comms/mnmg_ckpt heals a corrupt shard from a peer's mirror slice).
Containers written before checksums existed simply lack the field and skip
verification. Durability: path writes go through `atomic_write` —
write-to-temp-then-`os.replace` — so a mid-write crash leaves the previous
container intact and never a torn file under the final name.

A native (C++) codec for the same format lives in cpp/raft_tpu_native.cc
(`rt_write_container`) and is used for the write path when built (see
raft_tpu.native); this pure-Python path is the always-available fallback and
the format definition of record.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import struct
from typing import Any, Dict, List, Mapping, Optional, Tuple, Union

import numpy as np
import jax

MAGIC = b"RAFTTPU\x00"
CONTAINER_VERSION = 1
_ALIGN = 64

# -- the checkpoint schema registry ------------------------------------
#
# Machine-readable registry of every checkpoint field, per index kind —
# the FAULT_SITES/TUNED_KEYS pattern, read by AST by tools/raftlint's
# ``ckpt-schema-registry`` rule (never imported there). Every attribute
# a ``*_save*`` path writes must be registered here, and every load path
# must handle a registered field's ABSENCE exactly as declared — so a
# new index-state field (the upsert/delete/tombstone state ROADMAP
# item 5 adds) cannot ship without its forward/backward-compat story.
#
# Shape: kind -> {"version": <current writer version>,
#                 "fields": {name: (category, dtype_class, since, absent)}}
#
#   category     "array" (container payload) | "meta" (header JSON) |
#                "runtime" (never serialized: derived state a load
#                re-creates at its default — documented here so the
#                legacy-load goldens can pin the default)
#   dtype_class  coarse dtype family ("f32", "i32", "u8", "bool",
#                "str", "int", "json", None for runtime) — documentation
#                plus the chaos drill's corruption-target picker; loads
#                do not enforce it (the CRC already detects rot)
#   since        writer version that first emitted the field
#   absent       what a load does when the field is missing (or fails
#                CRC, for arrays):
#                  "refuse"  required: missing -> typed
#                            SerializationError, corrupt -> ChecksumError
#                  "default" optional: load falls back to the documented
#                            default (None / the meta .get default);
#                            corrupt -> dropped, load degrades
#                  "derive"  re-derivable: absence/corruption is healed
#                            or re-computed by shared machinery (mirror
#                            heal, size re-derivation, shape-derived
#                            quantizer state)
#
# "kind" and "version" themselves are consumed by the core gate
# (read_ckpt / check_ckpt_version), not by per-kind load code.
CKPT_SCHEMA = {
    "ivf_flat": {
        "version": 4,
        "fields": {
            "centers": ("array", "f32", 1, "refuse"),
            "list_data": ("array", "f32", 1, "refuse"),
            "slot_rows": ("array", "i32", 1, "refuse"),
            "list_sizes": ("array", "i32", 1, "refuse"),
            "source_ids": ("array", "i32", 1, "refuse"),
            "list_radii": ("array", "f32", 2, "default"),
            # live-mutation era (v3, neighbors/mutation): dead-row mask
            # (absent = all-live), applied-log cursor at the commit,
            # and the mutator's reserved per-list append slack
            "tombstones": ("array", "u8", 3, "default"),
            # integrity era (v4, raft_tpu/integrity): packed per-list
            # CRC-32C sidecar (rows = sorted list-granularity
            # DIGEST_FIELDS) + per-table digests in the header; absent
            # = no sidecar, the scrubber attaches one on first contact
            "list_digests": ("array", "u32", 4, "default"),
            "table_digests": ("meta", "json", 4, "default"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "metric": ("meta", "int", 1, "refuse"),
            "metric_arg": ("meta", "float", 1, "default"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "adaptive_centers": ("meta", "bool", 1, "default"),
            "mut_cursor": ("meta", "int", 3, "default"),
            "append_slack": ("meta", "int", 3, "default"),
            "fused_kb": ("runtime", None, 1, "default"),
        },
    },
    "ivf_pq": {
        "version": 3,
        "fields": {
            "rotation": ("array", "f32", 1, "refuse"),
            "centers": ("array", "f32", 1, "refuse"),
            "pq_centers": ("array", "f32", 1, "refuse"),
            "codes": ("array", "i32", 1, "refuse"),
            "slot_rows": ("array", "i32", 1, "refuse"),
            "list_sizes": ("array", "i32", 1, "refuse"),
            "source_ids": ("array", "i32", 1, "refuse"),
            "list_radii": ("array", "f32", 1, "default"),
            # live-mutation era (v2, neighbors/mutation)
            "tombstones": ("array", "u8", 2, "default"),
            # integrity era (v3, raft_tpu/integrity) — see ivf_flat
            "list_digests": ("array", "u32", 3, "default"),
            "table_digests": ("meta", "json", 3, "default"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "metric": ("meta", "int", 1, "refuse"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "pq_bits": ("meta", "int", 1, "refuse"),
            "codebook_kind": ("meta", "str", 1, "refuse"),
            "mut_cursor": ("meta", "int", 2, "default"),
            "append_slack": ("meta", "int", 2, "default"),
            "fused_kb": ("runtime", None, 1, "default"),
        },
    },
    "ivf_rabitq": {
        "version": 3,
        "fields": {
            "rotation": ("array", "f32", 1, "refuse"),
            "centers": ("array", "f32", 1, "refuse"),
            "codes": ("array", "u32", 1, "refuse"),
            "aux": ("array", "f32", 1, "refuse"),
            "slot_rows": ("array", "i32", 1, "refuse"),
            "list_sizes": ("array", "i32", 1, "refuse"),
            "source_ids": ("array", "i32", 1, "refuse"),
            # live-mutation era (v2, neighbors/mutation)
            "tombstones": ("array", "u8", 2, "default"),
            # integrity era (v3, raft_tpu/integrity) — see ivf_flat
            "list_digests": ("array", "u32", 3, "default"),
            "table_digests": ("meta", "json", 3, "default"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "metric": ("meta", "int", 1, "refuse"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "mut_cursor": ("meta", "int", 2, "default"),
            "append_slack": ("meta", "int", 2, "default"),
            # re-derived from the rotation's shape / process defaults
            "quantizer": ("meta", "str", 1, "derive"),
            "rot_dim": ("meta", "int", 1, "derive"),
            "query_bits": ("meta", "int", 1, "derive"),
        },
    },
    "mnmg_ivf_flat": {
        "version": 1,
        "fields": {
            "centers": ("array", "f32", 1, "refuse"),
            "list_data": ("array", "f32", 1, "refuse"),
            "host_gids": ("array", "i32", 1, "refuse"),
            "list_sizes": ("array", "i32", 1, "refuse"),
            "replica_store": ("array", "f32", 1, "derive"),
            "replica_gids": ("array", "i32", 1, "derive"),
            "replica_sizes": ("array", "i32", 1, "derive"),
            # written only when the index carries a correction-table
            # mirror (the shared _replica_arrays helper); registered for
            # every mnmg kind so the shared writer has one contract
            "replica_aux": ("array", "f32", 1, "derive"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "n": ("meta", "int", 1, "refuse"),
            "n_ranks": ("meta", "int", 1, "refuse"),
            "metric": ("meta", "int", 1, "refuse"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "bridged": ("meta", "bool", 1, "default"),
            "replication": ("meta", "int", 1, "default"),
        },
    },
    "mnmg_ivf_pq": {
        "version": 1,
        "fields": {
            "rotation": ("array", "f32", 1, "refuse"),
            "centers": ("array", "f32", 1, "refuse"),
            "pq_centers": ("array", "f32", 1, "refuse"),
            "codes": ("array", "i32", 1, "refuse"),
            "host_gids": ("array", "i32", 1, "refuse"),
            "list_sizes": ("array", "i32", 1, "refuse"),
            "replica_store": ("array", "i32", 1, "derive"),
            "replica_gids": ("array", "i32", 1, "derive"),
            "replica_sizes": ("array", "i32", 1, "derive"),
            "replica_aux": ("array", "f32", 1, "derive"),  # see mnmg_ivf_flat
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "n": ("meta", "int", 1, "refuse"),
            "n_ranks": ("meta", "int", 1, "refuse"),
            "metric": ("meta", "int", 1, "refuse"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "pq_dim": ("meta", "int", 1, "refuse"),
            "pq_bits": ("meta", "int", 1, "refuse"),
            "per_cluster": ("meta", "bool", 1, "default"),
            "extended": ("meta", "bool", 1, "default"),
            "bridged": ("meta", "bool", 1, "default"),
            "replication": ("meta", "int", 1, "default"),
        },
    },
    "mnmg_ivf_rabitq": {
        "version": 1,
        "fields": {
            "rotation": ("array", "f32", 1, "refuse"),
            "centers": ("array", "f32", 1, "refuse"),
            "codes": ("array", "u32", 1, "refuse"),
            "aux": ("array", "f32", 1, "refuse"),
            "host_gids": ("array", "i32", 1, "refuse"),
            "list_sizes": ("array", "i32", 1, "refuse"),
            "replica_store": ("array", "u32", 1, "derive"),
            "replica_gids": ("array", "i32", 1, "derive"),
            "replica_sizes": ("array", "i32", 1, "derive"),
            "replica_aux": ("array", "f32", 1, "derive"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "n": ("meta", "int", 1, "refuse"),
            "n_ranks": ("meta", "int", 1, "refuse"),
            "metric": ("meta", "int", 1, "refuse"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "bridged": ("meta", "bool", 1, "default"),
            "replication": ("meta", "int", 1, "default"),
        },
    },
    "mnmg_ivf_flat_sharded": {
        "version": 1,
        "fields": {
            "centers": ("array", "f32", 1, "refuse"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "n": ("meta", "int", 1, "refuse"),
            "n_ranks": ("meta", "int", 1, "refuse"),
            "n_parts": ("meta", "int", 1, "derive"),
            "parts": ("meta", "json", 1, "refuse"),
            "metric": ("meta", "int", 1, "refuse"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "replication": ("meta", "int", 1, "default"),
        },
    },
    "mnmg_ivf_pq_sharded": {
        "version": 1,
        "fields": {
            "rotation": ("array", "f32", 1, "refuse"),
            "centers": ("array", "f32", 1, "refuse"),
            "pq_centers": ("array", "f32", 1, "refuse"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "n": ("meta", "int", 1, "refuse"),
            "n_ranks": ("meta", "int", 1, "refuse"),
            "n_parts": ("meta", "int", 1, "derive"),
            "parts": ("meta", "json", 1, "refuse"),
            "metric": ("meta", "int", 1, "refuse"),
            "n_lists": ("meta", "int", 1, "refuse"),
            "pq_dim": ("meta", "int", 1, "refuse"),
            "pq_bits": ("meta", "int", 1, "refuse"),
            "per_cluster": ("meta", "bool", 1, "default"),
            "extended": ("meta", "bool", 1, "default"),
            "replication": ("meta", "int", 1, "default"),
        },
    },
    # one shared schema for every `{kind}_part` per-process part file
    # (the lint rule resolves `kind + "_part"` writes here); reads are
    # the shared `_load_local_tables` assembly, not per-kind load code
    "mnmg_sharded_part": {
        "version": 1,
        "fields": {
            "store": ("array", "f32", 1, "refuse"),
            "gids": ("array", "i32", 1, "refuse"),
            "sizes": ("array", "i32", 1, "derive"),
            "mirror_store": ("array", "f32", 1, "derive"),
            "mirror_gids": ("array", "i32", 1, "derive"),
            "kind": ("meta", "str", 1, "refuse"),
            "ranks": ("meta", "json", 1, "refuse"),
        },
    },
    # one mutation batch's payload container (neighbors/mutation): the
    # CRC'd sidecar a mutlog.jsonl line points at — written atomically
    # BEFORE its line is appended, swept once a checkpoint commit
    # supersedes it
    "mutation_batch": {
        "version": 1,
        "fields": {
            "ids": ("array", "i32", 1, "refuse"),
            # deletes and rebalances carry no vectors
            "vectors": ("array", "f32", 1, "default"),
            "kind": ("meta", "str", 1, "refuse"),
            "version": ("meta", "int", 1, "default"),
            "op": ("meta", "str", 1, "refuse"),
            "seq": ("meta", "int", 1, "refuse"),
        },
    },
}


class SerializationError(ValueError):
    """A container could not be decoded: truncated/empty file, bad magic,
    torn header. Subclasses ValueError so pre-existing `except ValueError`
    dispatch still catches it."""


class ChecksumError(SerializationError):
    """One or more field buffers failed CRC-32C verification. `path` names
    the container, `fields` the corrupt field names — the heal paths use
    them to decide which shards to re-materialize from a peer mirror."""

    def __init__(self, path: str, fields: List[str]):
        super().__init__(
            f"checksum mismatch in {path!r}: corrupt fields {fields}"
        )
        self.path = path
        self.fields = list(fields)


def _align(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def _data_start(hlen: int) -> int:
    """Byte offset of the data region: magic (8) + version/len fields
    (12) + JSON header, aligned — the ONE derivation (readers, the
    chaos offset probe and the field-range helper all call this)."""
    return _align(8 + 12 + hlen)


# -- CRC-32C (Castagnoli) ----------------------------------------------
#
# Pure numpy, no dependencies: per-block zero-init CRCs are computed
# VECTORIZED across blocks (the table recurrence runs its _BLOCK steps on
# an (n_blocks,) uint32 register file), then folded left-to-right with the
# precomputed shift-by-one-block linear map (CRC is GF(2)-linear, so
# "append _BLOCK zero bytes" is a 32x32 bit matrix, stored as 4x256
# byte-lookup tables). ~10 ms/MB vs ~1 s/MB for a bytewise Python loop.

_CRC_POLY = np.uint32(0x82F63B78)
_BLOCK = 1024


def _crc_table() -> np.ndarray:
    idx = np.arange(256, dtype=np.uint32)
    crc = idx
    for _ in range(8):
        crc = np.where(crc & 1, (crc >> 1) ^ _CRC_POLY, crc >> 1)
    return crc.astype(np.uint32)


_TBL = _crc_table()
_SHIFT_TBLS: Optional[np.ndarray] = None  # (4, 256) lazy


def _zero_steps(reg: np.ndarray, n: int) -> np.ndarray:
    """Advance CRC registers by n zero bytes (vectorized over registers)."""
    for _ in range(n):
        reg = _TBL[reg & 0xFF] ^ (reg >> np.uint32(8))
    return reg


def _shift_tables() -> np.ndarray:
    """4x256 lookup applying the "append _BLOCK zero bytes" linear map:
    shift(x) = T0[x&FF] ^ T1[(x>>8)&FF] ^ T2[(x>>16)&FF] ^ T3[x>>24]."""
    global _SHIFT_TBLS
    if _SHIFT_TBLS is None:
        basis = _zero_steps(np.uint32(1) << np.arange(32, dtype=np.uint32),
                            _BLOCK)  # (32,) images of each bit
        tbls = np.zeros((4, 256), np.uint32)
        for k in range(4):
            bytes_ = np.arange(256, dtype=np.uint32)
            acc = np.zeros(256, np.uint32)
            for bit in range(8):
                acc ^= np.where(bytes_ & (1 << bit),
                                basis[8 * k + bit], np.uint32(0))
            tbls[k] = acc
        _SHIFT_TBLS = tbls
    return _SHIFT_TBLS


def _shift_block(x: np.ndarray) -> np.ndarray:
    t = _shift_tables()
    return (t[0][x & 0xFF] ^ t[1][(x >> np.uint32(8)) & 0xFF]
            ^ t[2][(x >> np.uint32(16)) & 0xFF] ^ t[3][x >> np.uint32(24)])


def crc32c(data, crc: int = 0) -> int:
    """CRC-32C (Castagnoli) of a bytes-like / numpy buffer. `crc` chains a
    previous call's result. Matches the RFC 3720 reference
    (crc32c(b"123456789") == 0xE3069283)."""
    buf = np.frombuffer(memoryview(data).cast("B"), np.uint8)
    reg = np.uint32(~np.uint32(crc) & np.uint32(0xFFFFFFFF))
    n_blocks = buf.size // _BLOCK
    group = 1 << 16  # ≤64 MiB of payload widened to uint32 at a time
    for g0 in range(0, n_blocks, group):
        gn = min(group, n_blocks - g0)
        data2d = (buf[g0 * _BLOCK:(g0 + gn) * _BLOCK]
                  .reshape(gn, _BLOCK).astype(np.uint32))
        regs = np.zeros(gn, np.uint32)
        for j in range(_BLOCK):
            regs = _TBL[(regs ^ data2d[:, j]) & 0xFF] ^ (regs >> np.uint32(8))
        # affine split: running = shift(running_prev) ^ raw_block; the
        # init register rides the same shifts (f_I(M) = f_0(M) + shift(I))
        for i in range(gn):
            reg = _shift_block(reg) ^ regs[i]
    for b in buf[n_blocks * _BLOCK:]:
        reg = _TBL[(reg ^ b) & 0xFF] ^ (reg >> np.uint32(8))
    return int(~reg & np.uint32(0xFFFFFFFF))


def crc32c_rows(arr) -> np.ndarray:
    """(n_rows,) uint32: `crc32c` of each row of `arr` (rows along axis
    0), computed for all rows at once — the same block recurrence run
    on an (n_rows * n_blocks,) register file, then folded per row. Each
    table step is one numpy op over every row, so the cost no longer
    grows with the row count's Python loop (4,096 lists' digests: one
    pass, not 4,096)."""
    a = np.ascontiguousarray(arr)
    n_rows = a.shape[0]
    buf = a.reshape(n_rows, -1).view(np.uint8).reshape(n_rows, -1)
    n_blocks = buf.shape[1] // _BLOCK
    reg = np.full(n_rows, 0xFFFFFFFF, np.uint32)
    if n_blocks:
        group = max(1, (1 << 16) // n_blocks)  # rows per ≤64 MiB pass
        for r0 in range(0, n_rows, group):
            data2d = (buf[r0:r0 + group, :n_blocks * _BLOCK]
                      .reshape(-1, _BLOCK).astype(np.uint32))
            regs = np.zeros(data2d.shape[0], np.uint32)
            for j in range(_BLOCK):
                regs = (_TBL[(regs ^ data2d[:, j]) & 0xFF]
                        ^ (regs >> np.uint32(8)))
            regs = regs.reshape(-1, n_blocks)
            part = reg[r0:r0 + group]
            for i in range(n_blocks):
                part = _shift_block(part) ^ regs[:, i]
            reg[r0:r0 + group] = part
    for b in range(n_blocks * _BLOCK, buf.shape[1]):
        reg = _TBL[(reg ^ buf[:, b]) & 0xFF] ^ (reg >> np.uint32(8))
    return ~reg


# -- atomic path writes ------------------------------------------------

@contextlib.contextmanager
def atomic_write(path: Union[str, os.PathLike]):
    """Write-to-temp-then-rename protocol for checkpoint files: yields the
    temp path to write, then atomically `os.replace`s it over `path` on
    success (and unlinks it on failure). A crash mid-write leaves the
    previous file intact; readers never observe a torn container. Every
    checkpoint write in the library MUST route through here (ci/
    check_style.sh gates bare `os.rename` / `open(..., "wb")`)."""
    path = os.fspath(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        yield tmp
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def serialize_arrays(
    f: Union[str, os.PathLike, io.IOBase],
    arrays: Mapping[str, Any],
    meta: Dict[str, Any] | None = None,
) -> None:
    """Write named arrays + JSON-able metadata to a file or stream. Path
    writes are atomic (write-to-temp-then-rename) and every field carries
    a CRC-32C checksum the read path verifies."""
    own = isinstance(f, (str, os.PathLike))
    bufs = []
    fields = []
    offset = 0
    for name, arr in arrays.items():
        a = np.ascontiguousarray(np.asarray(arr))
        if a.dtype.byteorder == ">":
            a = a.astype(a.dtype.newbyteorder("<"))
        offset = _align(offset)
        fields.append(
            {
                "name": name,
                "dtype": a.dtype.str,
                "shape": list(a.shape),
                "offset": offset,
                "nbytes": int(a.nbytes),
                "crc32c": crc32c(a.data) if a.nbytes else 0,
            }
        )
        bufs.append((offset, a))
        offset += a.nbytes
    header = json.dumps({"meta": meta or {}, "fields": fields}).encode()

    if own:
        with atomic_write(f) as tmp:
            _write_container(tmp, header, bufs, try_native=True)
        return
    _write_stream(f, header, bufs)


def _write_container(path: str, header: bytes, bufs, try_native: bool) -> None:
    if try_native:
        # native C++ codec path (cpp/raft_tpu_native.cc rt_write_container)
        from raft_tpu import native

        if native.write_container(
            path, header,
            [a for _, a in bufs],
            [a.nbytes for _, a in bufs],
            [off for off, _ in bufs],
        ):
            return
    with open(path, "wb") as fh:
        _write_stream(fh, header, bufs)


def _write_stream(fh, header: bytes, bufs) -> None:
    fh.write(MAGIC)
    fh.write(struct.pack("<IQ", CONTAINER_VERSION, len(header)))
    fh.write(header)
    data_start = _align(fh.tell())
    fh.write(b"\x00" * (data_start - fh.tell()))
    pos = 0
    for off, a in bufs:
        if off > pos:
            fh.write(b"\x00" * (off - pos))
            pos = off
        fh.write(a.tobytes())
        pos += a.nbytes


def _describe(f) -> str:
    if isinstance(f, (str, os.PathLike)):
        return os.fspath(f)
    return getattr(f, "name", "<stream>")


def _read_header(fh, name: str) -> Tuple[int, dict]:
    """Shared magic + version + JSON header decode; raises
    `SerializationError` naming the file on any truncated/torn read
    (instead of the raw struct.error / JSONDecodeError / KeyError a
    short or garbage file used to surface)."""
    magic = fh.read(8)
    if len(magic) < 8:
        raise SerializationError(
            f"truncated container {name!r}: {len(magic)} bytes, expected at "
            f"least the 8-byte magic {MAGIC!r}"
        )
    if magic != MAGIC:
        raise SerializationError(
            f"not a raft_tpu serialized container (bad magic) in {name!r}: "
            f"got {magic!r}, expected {MAGIC!r}"
        )
    lenbytes = fh.read(12)
    if len(lenbytes) < 12:
        raise SerializationError(
            f"truncated container {name!r}: header length fields missing "
            f"(got {8 + len(lenbytes)} bytes)"
        )
    version, hlen = struct.unpack("<IQ", lenbytes)
    if version > CONTAINER_VERSION:
        raise SerializationError(
            f"container version {version} newer than supported "
            f"{CONTAINER_VERSION}"
        )
    raw = fh.read(hlen)
    if len(raw) < hlen:
        raise SerializationError(
            f"truncated container {name!r}: header says {hlen} bytes, file "
            f"holds {len(raw)}"
        )
    try:
        header = json.loads(raw.decode())
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise SerializationError(
            f"torn container header in {name!r}: {e}"
        ) from e
    if not isinstance(header, dict) or "meta" not in header:
        raise SerializationError(
            f"container header in {name!r} lacks the 'meta' section"
        )
    return hlen, header


def peek_meta(f: Union[str, os.PathLike, io.IOBase]) -> Dict[str, Any]:
    """Read ONLY a container's meta dict (magic + header; the data blob
    is never touched) — the cheap dispatch probe for multi-GB
    checkpoints whose kind decides which loader to run."""
    own = isinstance(f, (str, os.PathLike))
    fh = open(f, "rb") if own else f
    try:
        return _read_header(fh, _describe(f))[1]["meta"]
    finally:
        if own:
            fh.close()


def container_data_start(f: Union[str, os.PathLike, io.IOBase]) -> int:
    """Byte offset where a container's data region begins (header
    excluded) — chaos hooks corrupt only past here so the header stays
    parseable and the per-array checksums do the detecting."""
    own = isinstance(f, (str, os.PathLike))
    fh = open(f, "rb") if own else f
    try:
        hlen, _ = _read_header(fh, _describe(f))
        return _data_start(hlen)
    finally:
        if own:
            fh.close()


def deserialize_arrays(
    f: Union[str, os.PathLike, io.IOBase],
    to_device: bool = True,
    verify: bool = True,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Read a container; returns (arrays, meta). Arrays are jax.Arrays when
    `to_device` else numpy. With `verify` (default) every field's CRC-32C
    is checked and a mismatch raises `ChecksumError` naming the corrupt
    fields; pass verify=False only for forensic reads."""
    arrays, meta, bad = deserialize_arrays_checked(f, to_device=to_device,
                                                   verify=verify)
    if bad:
        raise ChecksumError(_describe(f), bad)
    return arrays, meta


def check_ckpt_version(meta: Dict[str, Any], path: str = "<container>") -> None:
    """The schema version gate: a checkpoint whose kind is registered in
    `CKPT_SCHEMA` but whose declared version is NEWER than this library
    writes carries fields whose semantics this build cannot know — loading
    it by best effort would silently mis-read index state, so refuse,
    typed. Unregistered kinds pass (generic containers gate elsewhere)."""
    kind = meta.get("kind")
    spec = CKPT_SCHEMA.get(kind)
    if spec is None:
        return
    version = int(meta.get("version", 1))
    if version > int(spec["version"]):
        raise SerializationError(
            f"checkpoint {path!r} declares {kind!r} version {version}, "
            f"newer than the library's supported version "
            f"{spec['version']} — refusing to load fields whose "
            f"semantics this build cannot know (upgrade raft_tpu)"
        )


def read_ckpt(
    f: Union[str, os.PathLike, io.IOBase],
    kind: str,
    to_device: bool = True,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Schema-checked checkpoint read — the single-file load path of the
    `CKPT_SCHEMA` contract. Returns (arrays, meta) after enforcing, in
    order:

      1. the container's declared kind matches `kind` (typed mismatch);
      2. the version gate (`check_ckpt_version`: newer-than-library
         checkpoints refuse, typed);
      3. required ("refuse") array fields of the file's version are
         present — a truncated writer cannot produce a half-index that
         explodes three layers later;
      4. corrupt (CRC-failed) fields degrade per their declared
         absent-on-load behavior: "default"/"derive" fields are DROPPED
         (the load falls back exactly as if the writer had never emitted
         them, with an obs `ckpt.degrade` event) while a corrupt
         "refuse" field raises `ChecksumError` naming it.
    """
    spec = CKPT_SCHEMA.get(kind)
    if spec is None:
        raise SerializationError(f"no CKPT_SCHEMA entry for kind {kind!r}")
    name = _describe(f)
    arrays, meta, bad = deserialize_arrays_checked(f, to_device=to_device)
    got = meta.get("kind")
    if got != kind:
        raise SerializationError(
            f"not a {kind} container: {name!r} declares kind {got!r}"
        )
    check_ckpt_version(meta, name)
    version = int(meta.get("version", 1))
    fields = spec["fields"]
    missing = [
        fname for fname, (cat, _dt, since, absent) in sorted(fields.items())
        if absent == "refuse" and since <= version
        and fname not in (arrays if cat == "array"
                          else meta if cat == "meta" else (fname,))
    ]
    if missing:
        raise SerializationError(
            f"checkpoint {name!r} ({kind} v{version}) is missing required "
            f"field(s) {missing} — torn or foreign writer"
        )
    if bad:
        required_bad = []
        for fname in bad:
            cat_spec = fields.get(fname)
            if cat_spec is not None and cat_spec[3] in ("default", "derive"):
                # registered-optional: degrade exactly as the schema
                # declares for absence — drop the field, load falls back
                arrays.pop(fname, None)
                from raft_tpu import obs

                obs.event("ckpt.degrade", file=name, field=fname,
                          action="dropped", absent=cat_spec[3])
            else:
                required_bad.append(fname)
        if required_bad:
            raise ChecksumError(name, required_bad)
    return arrays, meta


def field_byte_range(
    f: Union[str, os.PathLike, io.IOBase], name: str
) -> Tuple[int, int]:
    """Absolute (start, end) byte range of one named field's buffer in a
    container file — the chaos drills' targeted-rot helper (rot exactly
    one registered field and prove the load degrades per its schema)."""
    own = isinstance(f, (str, os.PathLike))
    fh = open(f, "rb") if own else f
    try:
        hlen, header = _read_header(fh, _describe(f))
        data_start = _data_start(hlen)
        for field in header.get("fields", ()):
            if field["name"] == name:
                start = data_start + int(field["offset"])
                return start, start + int(field["nbytes"])
        raise SerializationError(
            f"container {_describe(f)!r} has no field {name!r}"
        )
    finally:
        if own:
            fh.close()


def deserialize_arrays_checked(
    f: Union[str, os.PathLike, io.IOBase],
    to_device: bool = True,
    verify: bool = True,
) -> Tuple[Dict[str, Any], Dict[str, Any], List[str]]:
    """Like `deserialize_arrays` but returns (arrays, meta, bad_fields)
    instead of raising on checksum mismatch — corrupt fields still decode
    (garbage bytes) so heal paths can keep the intact fields and
    re-materialize only the bad ones from a peer mirror."""
    own = isinstance(f, (str, os.PathLike))
    name = _describe(f)
    fh = open(f, "rb") if own else f
    try:
        hlen, header = _read_header(fh, name)
        if "fields" not in header:
            raise SerializationError(
                f"container header in {name!r} lacks the 'fields' section"
            )
        data_start = _data_start(hlen)
        fh.seek(data_start)
        blob = fh.read()
        arrays: Dict[str, Any] = {}
        bad: List[str] = []
        for field in header["fields"]:
            off, nb = field["offset"], field["nbytes"]
            raw = blob[off: off + nb]
            if len(raw) < nb:
                raise SerializationError(
                    f"truncated container {name!r}: field "
                    f"{field['name']!r} wants {nb} bytes at offset {off}, "
                    f"file holds {len(raw)}"
                )
            if verify and nb and field.get("crc32c") is not None:
                if crc32c(raw) != int(field["crc32c"]):
                    bad.append(field["name"])
            a = np.frombuffer(raw, dtype=np.dtype(field["dtype"]))
            a = a.reshape(field["shape"])
            arrays[field["name"]] = jax.device_put(a) if to_device else a
        return arrays, header["meta"], bad
    finally:
        if own:
            fh.close()

"""The CKPT_SCHEMA runtime contract (core/serialize.py): legacy golden
checkpoints load with exactly the registered absent-on-load behavior,
newer-than-library checkpoints refuse typed, required-field absence
refuses typed, and corrupt registered-optional fields degrade (drop)
instead of crashing. The lint half of the contract — save coverage,
guarded load fallbacks, symmetry — lives in
tests/test_raftlint_statecheck.py; the seeded chaos flavor of the
degrade drill lives with the other ckpt drills in
tests/test_replication.py.

Goldens (tests/goldens/legacy_*.ckpt, regenerate with
tests/goldens/make_legacy_ckpts.py) are byte-for-byte what the
pre-`list_radii` / pre-`fused_kb` era writers emitted — real old bytes,
not a mock of them.
"""

import os

import numpy as np
import pytest

from raft_tpu.core.serialize import (
    CKPT_SCHEMA,
    ChecksumError,
    SerializationError,
    check_ckpt_version,
    field_byte_range,
    serialize_arrays,
)
from raft_tpu.neighbors import ivf_flat, ivf_pq, ivf_rabitq

GOLDENS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "goldens")


def _golden(name):
    return os.path.join(GOLDENS, name)


def test_legacy_flat_golden_loads_budgets_only():
    # the schema DECLARES radii-less -> default(None); the golden proves
    # the load honors it on real pre-radii bytes
    assert CKPT_SCHEMA["ivf_flat"]["fields"]["list_radii"][3] == "default"
    index = ivf_flat.load(_golden("legacy_ivf_flat_v2_noradii.ckpt"))
    assert index.list_radii is None
    assert index.fused_kb is None  # runtime field re-defaults
    q = np.asarray(index.centers)[:3] + 0.01
    # budgets-only adaptive probing: without radii the early-term bounds
    # stay off but the per-query budget path must still serve
    p = ivf_flat.SearchParams(n_probes=4, recall_target=0.9)
    vals, ids = ivf_flat.search(p, index, q.astype(np.float32), 3)
    assert np.asarray(ids).shape == (3, 3)
    assert (np.asarray(ids) >= 0).all()


def test_legacy_pq_golden_loads_budgets_only():
    assert CKPT_SCHEMA["ivf_pq"]["fields"]["list_radii"][3] == "default"
    index = ivf_pq.load(_golden("legacy_ivf_pq_v1_noradii.ckpt"))
    assert index.list_radii is None
    assert index.fused_kb is None
    q = np.asarray(index.centers)[:3] + 0.01
    p = ivf_pq.SearchParams(n_probes=4, recall_target=0.9)
    vals, ids = ivf_pq.search(p, index, q.astype(np.float32), 3)
    assert np.asarray(ids).shape == (3, 3)
    assert (np.asarray(ids) >= 0).all()


def test_legacy_rabitq_golden_loads_runtime_defaults():
    fields = CKPT_SCHEMA["ivf_rabitq"]["fields"]
    for f in ("tombstones", "mut_cursor", "list_digests"):
        assert fields[f][3] == "default"
    index = ivf_rabitq.load(_golden("legacy_ivf_rabitq_v1.ckpt"))
    assert index.tombstones is None and index.mut_cursor == 0
    assert index.list_digests is None
    # rabitq centers live in the rotated space — query in data space
    q = np.random.default_rng(3).random((3, index.dim), dtype=np.float32)
    vals, ids = ivf_rabitq.search(
        ivf_rabitq.SearchParams(n_probes=4), index, q.astype(np.float32), 3)
    assert np.asarray(ids).shape == (3, 3)


@pytest.mark.parametrize("kind,mod,golden", [
    ("ivf_flat", ivf_flat, "legacy_ivf_flat_v2_radii.ckpt"),
    ("ivf_pq", ivf_pq, "legacy_ivf_pq_v1_radii.ckpt"),
    ("ivf_rabitq", ivf_rabitq, "legacy_ivf_rabitq_v1.ckpt"),
])
def test_premutation_goldens_load_all_live(kind, mod, golden):
    """The mutation-era fields (tombstones / mut_cursor / append_slack)
    are declared absent-on-load defaults, and real pre-mutation bytes
    load with exactly the pre-mutation semantics: every row live,
    cursor 0, no reserved slack — and the index both serves and accepts
    a first mutation."""
    from raft_tpu.neighbors import mutation

    spec = CKPT_SCHEMA[kind]
    assert spec["fields"]["tombstones"][3] == "default"
    # mutation-era fields arrived together, strictly after v1 and no
    # later than the current version (the integrity era bumped past it)
    assert 1 < spec["fields"]["tombstones"][2] <= spec["version"]
    assert spec["fields"]["tombstones"][2] == spec["fields"]["mut_cursor"][2]
    assert spec["fields"]["mut_cursor"][3] == "default"
    assert spec["fields"]["append_slack"][3] == "default"
    index = mod.load(_golden(golden))
    assert index.tombstones is None
    assert int(index.mut_cursor) == 0 and int(index.append_slack) == 0
    assert mutation.live_rows(index) == int(index.size)  # all live
    sid = np.asarray(index.source_ids)
    out = mutation.delete(index, sid[:2])  # a legacy index is mutable
    assert int(out.n_tombstones) == 2


def test_newer_version_refuses_typed(tmp_path):
    """The since-version refusal: a checkpoint declaring a version newer
    than the library refuses with a TYPED SerializationError instead of
    loading fields whose semantics this build cannot know."""
    path = str(tmp_path / "future.ckpt")
    serialize_arrays(
        path, {"centers": np.zeros((2, 2), np.float32)},
        {"kind": "ivf_flat", "version": 99, "metric": 0, "n_lists": 2},
    )
    with pytest.raises(SerializationError, match="newer than the library"):
        ivf_flat.load(path)
    # the mnmg loads route the same gate through _load_verified
    with pytest.raises(SerializationError, match="newer than the library"):
        check_ckpt_version({"kind": "mnmg_ivf_pq", "version": 12}, path)
    # unregistered kinds pass (generic containers gate elsewhere)
    check_ckpt_version({"kind": "not_an_index", "version": 7}, path)


def test_missing_required_field_refuses_typed(tmp_path):
    path = str(tmp_path / "torn.ckpt")
    serialize_arrays(
        path, {"centers": np.zeros((2, 2), np.float32)},
        {"kind": "ivf_flat", "version": 2, "metric": 0, "n_lists": 2},
    )
    with pytest.raises(SerializationError, match="missing required"):
        ivf_flat.load(path)


def test_missing_required_meta_refuses_typed(tmp_path):
    """Meta-category refuse fields gate too: a foreign writer dropping
    'pq_bits' surfaces as the typed refusal, not a KeyError three
    layers into IndexParams construction."""
    path = str(tmp_path / "nometa.ckpt")
    arrays = {
        name: np.zeros((2, 2), np.float32)
        for name in ("rotation", "centers", "pq_centers", "codes",
                     "slot_rows", "list_sizes", "source_ids")
    }
    serialize_arrays(path, arrays,
                     {"kind": "ivf_pq", "version": 1, "metric": 0,
                      "n_lists": 2, "codebook_kind": "per_subspace"})
    with pytest.raises(SerializationError,
                       match=r"missing required field\(s\) \['pq_bits'\]"):
        ivf_pq.load(path)


def _flip(path, start, end):
    with open(path, "r+b") as fh:
        fh.seek(start)
        blk = fh.read(end - start)
        fh.seek(start)
        fh.write(bytes(b ^ 0xFF for b in blk))


def test_corrupt_optional_field_degrades_not_crashes(tmp_path, rng):
    """Rot exactly the registered-optional list_radii bytes: the load
    drops the field (absent='default' declared behavior) and serves
    budgets-only — the same container with a rotted REQUIRED field
    still raises ChecksumError naming it."""
    data = rng.random((96, 16), dtype=np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=4), data)
    assert index.list_radii is not None
    path = str(tmp_path / "radii.ckpt")
    ivf_flat.save(path, index)
    _flip(path, *field_byte_range(path, "list_radii"))
    loaded = ivf_flat.load(path)
    assert loaded.list_radii is None  # dropped, not garbage, not a crash
    p = ivf_flat.SearchParams(n_probes=4, recall_target=0.9)
    _, ids = ivf_flat.search(p, loaded, data[:5], 3)
    assert (np.asarray(ids) >= 0).all()

    path2 = str(tmp_path / "centers.ckpt")
    ivf_flat.save(path2, index)
    _flip(path2, *field_byte_range(path2, "centers"))
    with pytest.raises(ChecksumError, match="centers"):
        ivf_flat.load(path2)

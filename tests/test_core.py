"""Core runtime tests: Resources, validation, serialization, device_ndarray."""

import io
import threading

import numpy as np
import pytest

from raft_tpu import Resources, device_ndarray
from raft_tpu.core import (
    auto_sync_resources,
    check_matrix,
    serialize_arrays,
    deserialize_arrays,
)
from raft_tpu.core.interruptible import (
    synchronize,
    cancel,
    InterruptedException,
    TimeoutException,
)


def test_resources_rng_keys_differ():
    r = Resources(seed=1)
    k1, k2 = r.new_key(), r.new_key()
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


def test_resources_registry():
    r = Resources()
    calls = []
    r.add_resource_factory("thing", lambda: calls.append(1) or {"x": 1})
    assert r.get_resource("thing")["x"] == 1
    r.get_resource("thing")
    assert len(calls) == 1  # lazily created once
    with pytest.raises(KeyError):
        r.get_resource("missing")


def test_resources_comms_roundtrip():
    r = Resources()
    assert not r.comms_initialized()
    with pytest.raises(RuntimeError):
        r.get_comms()
    r.set_comms("fake-comms")
    assert r.get_comms() == "fake-comms"
    r.set_sub_comms("tp", "sub")
    assert r.get_sub_comms("tp") == "sub"


def test_with_mesh_shares_registry():
    r = Resources()
    r.set_comms("c")
    r2 = r.with_mesh("mesh-placeholder")
    assert r2.get_comms() == "c"
    assert r2.mesh == "mesh-placeholder"


def test_auto_sync_decorator():
    seen = {}

    @auto_sync_resources
    def f(x, resources=None):
        seen["res"] = resources
        return x + 1

    assert f(1) == 2
    assert seen["res"] is not None


def test_validation():
    with pytest.raises(ValueError):
        check_matrix(np.zeros(3))
    with pytest.raises(ValueError):
        check_matrix(np.zeros((2, 2), np.int16), dtypes=[np.float32])
    out = check_matrix(np.zeros((2, 2), np.float32), dtypes=[np.float32])
    assert out.shape == (2, 2)


def test_device_ndarray_roundtrip(rng):
    x = rng.random((4, 5), dtype=np.float32)
    d = device_ndarray(x)
    assert d.shape == (4, 5) and d.dtype == np.float32
    np.testing.assert_array_equal(d.copy_to_host(), x)


def test_serialize_roundtrip(rng, tmp_path):
    arrays = {
        "a": rng.random((3, 4), dtype=np.float32),
        "b": rng.integers(0, 100, (7,), dtype=np.int64),
        "c": np.zeros((0, 5), np.float32),
    }
    meta = {"kind": "test-index", "version": 3}
    path = tmp_path / "container.bin"
    serialize_arrays(str(path), arrays, meta)
    got, got_meta = deserialize_arrays(str(path), to_device=False)
    assert got_meta == meta
    for k in arrays:
        np.testing.assert_array_equal(got[k], arrays[k])
        assert got[k].dtype == arrays[k].dtype


def test_serialize_stream(rng):
    buf = io.BytesIO()
    serialize_arrays(buf, {"x": np.arange(10)}, {"v": 1})
    buf.seek(0)
    got, meta = deserialize_arrays(buf, to_device=False)
    np.testing.assert_array_equal(got["x"], np.arange(10))


def test_serialize_bad_magic():
    buf = io.BytesIO(b"NOTMAGIC" + b"\x00" * 100)
    with pytest.raises(ValueError):
        deserialize_arrays(buf)


def test_crc32c_reference_vectors():
    from raft_tpu.core.serialize import crc32c

    # RFC 3720 / Castagnoli check values
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c(bytes(range(32))) == 0x46DD794E
    # chaining equals one pass
    assert crc32c(b" world", crc32c(b"hello")) == crc32c(b"hello world")
    # block-vectorized path (>= 1 block + ragged tail) matches a
    # bytewise reference
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, 5000, dtype=np.uint8).tobytes()
    tbl = []
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
        tbl.append(c)
    ref = 0xFFFFFFFF
    for b in data:
        ref = tbl[(ref ^ b) & 0xFF] ^ (ref >> 8)
    assert crc32c(data) == ref ^ 0xFFFFFFFF


@pytest.mark.parametrize("shape,dtype", [
    ((7, 9), np.uint8),            # rows shorter than a block
    ((5, 300, 24), np.uint32),     # whole blocks and a ragged tail
    ((4, 1024), np.uint8),         # exactly one block a row
    ((6, 77, 2), np.float32),
    ((3, 0), np.uint8),            # empty rows: crc32c(b"") == 0
])
def test_crc32c_rows_equals_crc32c_of_each_row(shape, dtype):
    from raft_tpu.core.serialize import crc32c, crc32c_rows

    a = np.random.default_rng(4).integers(0, 255, shape).astype(dtype)
    got = crc32c_rows(a)
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(
        got, [crc32c(np.ascontiguousarray(row)) for row in a])


def test_serialize_truncated_and_torn_raise_typed(tmp_path):
    """Truncated/empty/garbage containers raise SerializationError
    naming the file and expected magic — not a raw struct.error or
    KeyError (satellite: typed decode failures)."""
    from raft_tpu.core.serialize import SerializationError, peek_meta

    empty = tmp_path / "empty.bin"
    empty.write_bytes(b"")
    with pytest.raises(SerializationError, match="empty.bin"):
        peek_meta(str(empty))
    short = tmp_path / "short.bin"
    short.write_bytes(b"RAFT")
    with pytest.raises(SerializationError, match="RAFTTPU"):
        deserialize_arrays(str(short))
    # magic intact, header length fields cut off
    torn = tmp_path / "torn.bin"
    torn.write_bytes(b"RAFTTPU\x00\x01\x00")
    with pytest.raises(SerializationError, match="torn.bin"):
        peek_meta(str(torn))
    # header says more bytes than the file holds
    half = tmp_path / "half.bin"
    serialize_arrays(str(half), {"x": np.arange(100)}, {"v": 1})
    data = half.read_bytes()
    half.write_bytes(data[:40])
    with pytest.raises(SerializationError, match="half.bin"):
        peek_meta(str(half))
    # SerializationError subclasses ValueError (old except-clauses hold)
    assert issubclass(SerializationError, ValueError)


def test_serialize_checksum_roundtrip_and_detect(tmp_path):
    from raft_tpu.core.serialize import ChecksumError

    path = tmp_path / "c.bin"
    arrays = {"a": np.arange(300, dtype=np.float32), "b": np.arange(50)}
    serialize_arrays(str(path), arrays, {"k": 1})
    got, _ = deserialize_arrays(str(path), to_device=False)
    np.testing.assert_array_equal(got["a"], arrays["a"])
    # flip one payload byte: the checksum names the corrupt field
    raw = bytearray(path.read_bytes())
    raw[-10] ^= 0xFF
    path.write_bytes(bytes(raw))
    with pytest.raises(ChecksumError, match="'b'"):
        deserialize_arrays(str(path), to_device=False)
    # forensic read still possible
    got2, _ = deserialize_arrays(str(path), to_device=False, verify=False)
    assert got2["b"].shape == (50,)


def test_serialize_atomic_write_leaves_no_temp(tmp_path):
    """Path writes are write-to-temp-then-rename: success leaves no temp
    file; a failing writer leaves neither temp nor final file."""
    from raft_tpu.core.serialize import atomic_write

    path = tmp_path / "ok.bin"
    serialize_arrays(str(path), {"x": np.arange(10)}, {})
    assert [f for f in tmp_path.iterdir()] == [path]
    doomed = tmp_path / "doomed.bin"
    with pytest.raises(RuntimeError, match="mid-write"):
        with atomic_write(str(doomed)) as tmp:
            with open(tmp, "wb") as fh:
                fh.write(b"partial")
            raise RuntimeError("mid-write crash")
    assert not doomed.exists()
    assert [f.name for f in tmp_path.iterdir()] == ["ok.bin"]


def test_interruptible_cancel():
    tid = threading.get_ident()
    cancel(tid)
    with pytest.raises(InterruptedException):
        synchronize()
    # flag cleared after raise
    synchronize()


class _NeverReady:
    """A pending 'array': polls as not-ready forever (the hung-mesh
    stand-in for the timeout/cancel paths of the health-check barrier)."""

    def is_ready(self):
        return False

    def block_until_ready(self):
        raise AssertionError("synchronize must poll is_ready, not block")


def test_interruptible_timeout_raises_and_clears():
    import time

    t0 = time.monotonic()
    with pytest.raises(TimeoutException, match="timeout_s=0.1"):
        synchronize(_NeverReady(), timeout_s=0.1, poll_interval_s=0.005)
    assert time.monotonic() - t0 >= 0.1
    # the cancellation flag was never set: later waits work unscathed
    synchronize()
    # no timeout on a ready value, even a tiny deadline
    synchronize(np.zeros(1), timeout_s=0.001)


def test_interruptible_cancel_mid_wait():
    """Another thread cancels a wait in flight (the health barrier's
    escape hatch): InterruptedException, and the flag clears so the
    thread's next wait is clean."""
    tid = threading.get_ident()
    t = threading.Timer(0.05, cancel, args=(tid,))
    t.start()
    try:
        with pytest.raises(InterruptedException):
            synchronize(_NeverReady(), timeout_s=10, poll_interval_s=0.005)
    finally:
        t.join()
    synchronize()  # flag cleared


def test_interruptible_cancel_beats_timeout():
    """Cancel landing before the deadline wins over the timeout."""
    tid = threading.get_ident()
    t = threading.Timer(0.02, cancel, args=(tid,))
    t.start()
    try:
        with pytest.raises(InterruptedException):
            synchronize(_NeverReady(), timeout_s=5, poll_interval_s=0.005)
    finally:
        t.join()
    synchronize()


def test_operators_vocabulary():
    """core/operators.hpp parity: composable ops drive the generic reduce."""
    import jax.numpy as jnp
    from raft_tpu.core import operators as op, KeyValuePair
    from raft_tpu.linalg import reduce

    x = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    r = reduce(x, axis=1, main_op=op.sq_op, reduce_op="add", final_op=op.sqrt_op)
    np.testing.assert_allclose(
        np.asarray(r), np.linalg.norm(np.asarray(x), axis=1), rtol=1e-6
    )
    a = KeyValuePair(jnp.asarray(0), jnp.asarray(3.0))
    b = KeyValuePair(jnp.asarray(1), jnp.asarray(2.0))
    assert int(op.argmin_op(a, b).key) == 1
    assert int(op.argmax_op(a, b).key) == 0
    assert float(op.compose_op(op.sqrt_op, op.sq_op)(jnp.asarray(-4.0))) == 4.0
    assert op.cast_op(jnp.int32)(jnp.asarray(3.7)).dtype == jnp.int32
    assert op.const_op(7)(123) == 7
    assert float(op.nz_op(jnp.asarray([0.0, 2.0])).sum()) == 1.0


def test_logger_set_callback_flush():
    """Regression (callback_sink.hpp parity): the callback sink must
    deliver records to `cb`, propagate handler flushes to `flush_cb`,
    and uninstall cleanly on `set_callback(None)`."""
    import importlib

    logger_mod = importlib.import_module("raft_tpu.core.logger")
    records, flushes = [], []
    logger_mod.set_callback(lambda lvl, msg: records.append((lvl, msg)),
                            flush_cb=lambda: flushes.append(1))
    try:
        logger_mod.set_level(logger_mod.RAFT_LEVEL_INFO)
        logger_mod.logger.info("cb %s", "works")
        assert len(records) == 1 and records[0][1].endswith("cb works")
        sinks = [h for h in logger_mod.logger.handlers
                 if isinstance(h, logger_mod._CallbackHandler)]
        assert len(sinks) == 1
        sinks[0].flush()
        assert flushes == [1]
        # a flush-less sink must be a no-op, not an AttributeError
        logger_mod.set_callback(lambda lvl, msg: None)
        [h.flush() for h in logger_mod.logger.handlers]
        # installing replaced the first sink; None removes the last one
        logger_mod.set_callback(None)
        assert not any(isinstance(h, logger_mod._CallbackHandler)
                       for h in logger_mod.logger.handlers)
        logger_mod.logger.info("after removal")
        assert len(records) == 1
    finally:
        logger_mod.set_callback(None)
        logger_mod.set_level(logger_mod.RAFT_LEVEL_WARN)


def test_output_type_config():
    """pylibraft set_output_as parity: numpy/torch/callable conversion."""
    from raft_tpu.core import set_output_as, convert_output
    import jax.numpy as jnp

    x = jnp.ones((2, 2), jnp.float32)
    try:
        set_output_as("numpy")
        out = convert_output((x, 5))
        assert isinstance(out[0], np.ndarray) and out[1] == 5
        set_output_as(lambda a: "custom")
        assert convert_output(x) == "custom"
        with pytest.raises(ValueError):
            set_output_as("cupy")
    finally:
        set_output_as("jax")


# -- persistent compile cache placement ---------------------------------


@pytest.mark.parametrize("env_dir", [None, "/somewhere/jax-cache"],
                         ids=["checkout", "env"])
def test_compilation_cache_placement(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and the helper sets nothing;
    without it the cache goes to the checkout's fixed .jax_cache."""
    import os

    import jax

    from raft_tpu.core import config

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = config.configure_compilation_cache()
        if env_dir is None:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_is_tpu_backend_is_the_default_backend():
    from raft_tpu.core import config

    assert config.is_tpu_backend() is False  # conftest pins the CPU mesh

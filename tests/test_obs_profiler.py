"""The program's instrumentation on the profiler's clock: `obs.span`
written into a `jax.profiler` trace, the garbage-collector hook, the
serve worker's per-batch spans, the IVF-PQ and IVF-RaBitQ builds' stage
spans, IVF-RaBitQ's scan counters, IVF-PQ's merge counter, and the stage
scopes inside the compiled search programs — and, with obs disabled, none
of it (no profiler annotation, no clock, no sync)."""

import gc
import glob
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs, serve
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.neighbors import brute_force, ivf_pq, ivf_rabitq
from raft_tpu.obs import spans

refine_mod = importlib.import_module("raft_tpu.neighbors.refine")

SERVE_STEPS = ("take", "merge", "batch", "dispatch", "fence", "scatter",
               "maintain")


@pytest.fixture
def obs_on():
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


def _host_events(logdir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(tmp_path))


@pytest.mark.parametrize("enabled", [True, False])
def test_span_writes_profiler_event_only_when_enabled(tmp_path, enabled):
    obs.reset()
    obs.enable(enabled)
    try:
        def work():
            with obs.span("drill.outer"):
                with obs.span("drill.inner"):
                    jax.block_until_ready(jnp.ones(4) + 1)
        names = {n for n, _ in _traced(tmp_path, work)}
    finally:
        obs.disable()
        obs.reset()
    mine = {n for n in names if n.startswith("raft_tpu.")}
    if enabled:
        assert {"raft_tpu.drill.outer", "raft_tpu.drill.inner"} <= mine
        assert obs.histogram("span.drill.outer").count == 0  # reset after
    else:
        assert not mine


def test_disabled_sites_touch_no_profiler_clock_or_sync(monkeypatch):
    """Disabled: `obs.span` (every new serve and build site), `settle`
    and the GC hook call no TraceAnnotation, read no clock, add no sync."""
    obs.disable()
    calls = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: calls.append("annotation"))
    monkeypatch.setattr(spans.time, "monotonic",
                        lambda: calls.append("clock") or 0.0)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append("sync") or x)
    with obs.span("serve.merge") as sp:
        assert sp.settle("value") == "value"
    assert obs._on_gc not in gc.callbacks
    gc.collect()
    assert calls == []


BUILDS = {
    "ivf_pq": (ivf_pq, ivf_pq.IndexParams(n_lists=4, pq_dim=4,
                                          kmeans_n_iters=2),
               ("ivf_pq.build.coarse", "ivf_pq.build.codebook"),
               ("rotation", "centers", "pq_centers", "codes", "slot_rows",
                "list_sizes", "source_ids")),
    "ivf_rabitq": (ivf_rabitq, ivf_rabitq.IndexParams(n_lists=4,
                                                      kmeans_n_iters=2),
                   ("ivf_rabitq.build.coarse", "ivf_rabitq.build.encode"),
                   ("rotation", "centers", "codes", "aux", "slot_rows",
                    "list_sizes", "source_ids")),
}


@pytest.mark.parametrize("kind", ["ivf_pq", "ivf_rabitq"])
def test_disabled_build_adds_no_sync(monkeypatch, kind):
    mod, params, _, _ = BUILDS[kind]
    obs.disable()
    x = np.random.default_rng(0).standard_normal((600, 8)).astype(np.float32)
    mod.build(params, x)  # compiles outside the count
    syncs = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: syncs.append(1) or real(v))
    mod.build(params, x)
    assert syncs == []
    obs.enable()
    try:
        mod.build(params, x)
    finally:
        obs.disable()
        obs.reset()
    assert len(syncs) == 3  # the two stages and the whole build


def test_gc_hook_records_collections_until_disabled(tmp_path, obs_on):
    assert obs._on_gc in gc.callbacks
    events = _traced(tmp_path / "on", lambda: gc.collect(2))
    gcs = [stats for name, stats in events if name == "raft_tpu.gc"]
    assert gcs and any(s.get("generation") == 2 for s in gcs)
    obs.disable()
    assert obs._on_gc not in gc.callbacks
    events = _traced(tmp_path / "off", lambda: gc.collect(2))
    assert not [name for name, _ in events if name == "raft_tpu.gc"]


def test_serve_step_spans_once_per_batch(obs_on):
    rng = np.random.default_rng(0)
    data = rng.standard_normal((256, 16)).astype(np.float32)
    server = serve.SearchServer(
        data, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    for _ in range(3):
        fut = server.submit(rng.standard_normal((2, 16)).astype(np.float32),
                            k=3)
        assert server.step() == 1
        fut.result(timeout=1.0)
    for step in SERVE_STEPS:
        assert obs.histogram(f"span.serve.{step}").count == 3, step
    evs = [e for e in obs.snapshot()["events"] if e["kind"] == "span"]
    parents = {(e["name"], e["parent"]) for e in evs}
    assert ("serve.dispatch", "serve.batch") in parents
    assert ("serve.fence", "serve.batch") in parents
    assert ("serve.take", None) in parents
    assert ("neighbors.brute_force.knn", "serve.dispatch") in parents


def test_served_batch_spans_in_profiler_trace(tmp_path, obs_on):
    rng = np.random.default_rng(0)
    data = rng.standard_normal((256, 16)).astype(np.float32)
    server = serve.SearchServer(
        data, serve.ServerConfig(buckets=(8,), max_wait_ms=0.0))
    server.warmup(3)

    def work():
        server.submit(rng.standard_normal((2, 16)).astype(np.float32), k=3)
        server.step()
    names = {n for n, _ in _traced(tmp_path, work)}
    assert {f"raft_tpu.serve.{s}" for s in SERVE_STEPS} <= names


@pytest.mark.parametrize("kind", sorted(BUILDS))
def test_build_stage_spans_and_bit_identical_outputs(obs_on, kind):
    mod, params, stages, fields = BUILDS[kind]
    x = np.random.default_rng(1).standard_normal((800, 8)).astype(np.float32)
    on = mod.build(params, x)
    for name in (f"neighbors.{kind}.build", f"neighbors.{kind}.extend",
                 *stages):
        assert obs.histogram(f"span.{name}").count == 1, name
    obs.disable()
    off = mod.build(params, x)
    for field in fields:
        np.testing.assert_array_equal(np.asarray(getattr(on, field)),
                                      np.asarray(getattr(off, field)))


def _stage_names(lowered):
    return {s for s in ("ivf_pq.coarse", "ivf_pq.invert", "ivf_pq.scan",
                        "ivf_pq.trim", "ivf_pq.merge", "ivf_rabitq.coarse",
                        "ivf_rabitq.scan", "ivf_rabitq.merge", "refine",
                        "brute_force.distance", "brute_force.select")
            if f"{s}/" in lowered.as_text(debug_info=True)}


@pytest.fixture(scope="module")
def small_index():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2000, 16)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    index = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8,
                                            kmeans_n_iters=2), x)
    ivf_pq.build_reconstruction(index)
    return x, q, index


@pytest.fixture(scope="module")
def small_rabitq():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2000, 16)), jnp.float32)
    index = ivf_rabitq.build(ivf_rabitq.IndexParams(
        n_lists=8, kmeans_n_iters=2, store_dataset=False), x)
    return index


def test_listmajor_program_names_its_stages(small_index):
    _, q, ix = small_index
    lowered = ivf_pq._search_impl_recon8_listmajor.lower(
        q, ix.rotation, ix.centers, ix.recon8, ix.recon_scale, ix.recon_norm,
        ix.slot_rows_pad, 10, 4, DistanceType.L2Expanded)
    assert _stage_names(lowered) == {"ivf_pq.coarse", "ivf_pq.invert",
                                     "ivf_pq.scan", "ivf_pq.trim",
                                     "ivf_pq.merge"}


@pytest.mark.parametrize("engine", ["recon8", "lut", "rabitq"])
def test_query_major_programs_name_their_stages(small_index, small_rabitq,
                                                engine):
    _, q, ix = small_index
    L2 = DistanceType.L2Expanded
    kind = "ivf_pq"
    if engine == "recon8":
        lowered = ivf_pq._search_impl_recon8.lower(
            q, ix.rotation, ix.centers, ix.recon8, ix.recon_scale,
            ix.recon_norm, ix.slot_rows_pad, 10, 4, L2)
    elif engine == "lut":
        lowered = ivf_pq._search_impl.lower(
            q, ix.rotation, ix.centers, ix.pq_centers, ix.codes,
            ix.slot_rows, 10, 4, L2, False)
    else:
        rb, kind = small_rabitq, "ivf_rabitq"
        lowered = ivf_rabitq._search_impl_rabitq.lower(
            q, rb.rotation, rb.centers, rb.codes, rb.aux, rb.slot_rows, 10,
            4, L2, query_bits=4)
    assert _stage_names(lowered) == {f"{kind}.coarse", f"{kind}.scan",
                                     f"{kind}.merge"}


@pytest.mark.parametrize("nq,rows", [(37, 3 * 16 * 4), (16, 16 * 4)])
def test_rabitq_scan_counters_from_static_shapes(small_rabitq, obs_on,
                                                 monkeypatch, nq, rows):
    """One increment a call, from shapes alone: nq queries x 4 probes
    live pairs; the rows scored for them, padding included — whole
    16-query blocks x 4 probes."""
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: pytest.fail("device sync"))
    q = np.random.default_rng(4).standard_normal((nq, 16)).astype(np.float32)
    ivf_rabitq.search(ivf_rabitq.SearchParams(
        n_probes=4, query_bits=4), small_rabitq, q, 10)
    assert obs.counter("ivf_rabitq.scan.pairs").value == nq * 4
    assert obs.counter("ivf_rabitq.scan.rows").value == rows


@pytest.mark.parametrize("enabled,nq,lookups", [
    (True, 32, 256 * 10), (True, 300, 512 * 10), (False, 32, 0)])
def test_ivf_pq_merge_id_lookups_from_static_shapes(small_index, monkeypatch,
                                                    enabled, nq, lookups):
    """One increment a list-major call, from shapes alone: the padded
    slice's rows x k row ids the merge looks up; nothing with obs off."""
    _, _, index = small_index
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: pytest.fail("device sync"))
    q = np.random.default_rng(5).standard_normal((nq, 16)).astype(np.float32)
    obs.reset()
    obs.enable(enabled)
    try:
        ivf_pq.search(ivf_pq.SearchParams(n_probes=4,
                                          score_mode="recon8_list"),
                      index, q, 10)
        assert obs.counter("ivf_pq.merge.id_lookups").value == lookups
    finally:
        obs.disable()
        obs.reset()


@pytest.mark.parametrize("enabled,nq,n_probes,rows", [
    (True, 32, 4, 16 * 128),  # 256-row slice, dup 128: 8 + 8 chunks of 128
    (True, 32, 1, 16 * 32),   # dup 32: 8 + 8 chunks of 32
    (True, 300, 4, 24 * 128),  # 512-row slice: 16 + 8 chunks of 128
    (False, 32, 4, 0)])
def test_ivf_pq_trim_rows_from_static_shapes(small_index, monkeypatch,
                                             enabled, nq, n_probes, rows):
    """One increment a list-major call, from shapes alone: the rows the
    XLA trim selects over at the rule's chunk (one superblock here);
    nothing with obs off."""
    _, _, index = small_index
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: pytest.fail("device sync"))
    q = np.random.default_rng(6).standard_normal((nq, 16)).astype(np.float32)
    obs.reset()
    obs.enable(enabled)
    try:
        ivf_pq.search(ivf_pq.SearchParams(n_probes=n_probes,
                                          score_mode="recon8_list"),
                      index, q, 10)
        assert obs.counter("ivf_pq.trim.rows").value == rows
    finally:
        obs.disable()
        obs.reset()


def test_refine_program_names_its_stage(small_index):
    x, q, _ = small_index
    lowered = refine_mod._refine_impl.lower(
        x, q, jnp.zeros((32, 40), jnp.int32), 10, DistanceType.L2Expanded)
    assert _stage_names(lowered) == {"refine"}


@pytest.mark.parametrize("tile", [256, 1 << 15])  # tiled scan, one block
def test_brute_force_program_names_its_stages(small_index, tile):
    x, q, _ = small_index
    lowered = brute_force._bf_knn_impl.lower(
        x, q, 10, DistanceType.L2Expanded, tile=tile)
    assert _stage_names(lowered) == {"brute_force.distance",
                                     "brute_force.select"}

"""Worker process for the multi-process distributed tests (the raft-dask
LocalCUDACluster analogue, test_comms.py:45): N controller processes x 4
virtual CPU devices each form one global mesh; collectives cross the
process boundary over the distributed runtime.

Run: python tests/_mp_worker.py <process_id> <num_processes> <port>
Prints one PASS line per check; exits non-zero on any failure.
"""

import os
import sys

PID = int(sys.argv[1])
NPROC = int(sys.argv[2])
PORT = sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from raft_tpu.comms import Comms, bootstrap_multihost
from raft_tpu.comms.comms import op_t

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def check(name, ok):
    if not ok:
        print(f"FAIL {name}", flush=True)
        sys.exit(1)
    print(f"PASS {name}", flush=True)


def main():
    first = bootstrap_multihost(f"127.0.0.1:{PORT}", num_processes=NPROC, process_id=PID)
    check("bootstrap", first and jax.process_count() == NPROC)
    n_dev = len(jax.devices())
    check("global_devices", n_dev == 4 * NPROC and len(jax.local_devices()) == 4)

    mesh = Mesh(np.array(jax.devices()), ("data",))
    comms = Comms(mesh=mesh)
    check("spans_processes", comms.spans_processes())
    R = comms.get_size()

    # shard_from_local: each process contributes its own rows
    local = np.full((8, 3), PID, np.float32)
    g = comms.shard_from_local(local)
    check("shard_from_local_shape", g.shape == (8 * NPROC, 3))

    # collectives across the process boundary. Fetching a process-spanning
    # array needs the multihost gather (device_get only sees local shards).
    from jax.experimental import multihost_utils

    def fetch(a):
        return np.asarray(multihost_utils.process_allgather(a, tiled=True))

    def allreduce_fn(c, xs):
        return c.allreduce(xs, op=op_t.SUM)

    out = comms.run(allreduce_fn, g)
    # rank r's shard is constant PID-of-r; elementwise SUM over the 8 ranks
    # (4 per process) = 4 * (0 + 1) everywhere
    ranks_per_proc = R // NPROC
    want = ranks_per_proc * sum(range(NPROC))
    check("allreduce_sum", np.allclose(fetch(out), want))

    def allgather_fn(c, xs):
        return c.allgather(xs)

    # P() out_specs: the gathered result is identical on every rank
    ag = comms.run(allgather_fn, g, out_specs=P())
    got_ag = fetch(ag)
    check(
        "allgather_content",
        got_ag.size == 8 * NPROC * 3
        and np.allclose(np.sort(got_ag.ravel()), np.sort(fetch(g).ravel())),
    )

    def shift_fn(c, xs):
        return c.shift(xs, 1)

    pp = comms.run(shift_fn, g)
    got_pp, got_g = fetch(pp), fetch(g)
    check(
        "ppermute_shift",
        got_pp.shape == got_g.shape and not np.array_equal(got_pp, got_g),
    )

    # replicate: same value on every controller; the local shard of a
    # replicated array is the full value
    rep = comms.replicate(np.arange(6, dtype=np.float32))
    local_rep = np.asarray(rep.addressable_shards[0].data)
    check("replicate", np.allclose(local_rep, np.arange(6)))

    # a real pipeline: distributed row-block top-k merge (the knn merge
    # topology) — local scores per rank, allgather + exact final merge
    def topk_merge(c, scores):
        from raft_tpu.comms.mnmg import _merge_local_topk
        import jax.numpy as jnp

        v = jnp.sort(scores, axis=-1)[:, :4]
        i = jnp.argsort(scores, axis=-1)[:, :4].astype(jnp.int32)
        mv, mi = _merge_local_topk(c, v, i, 4, True)
        return mv

    rng = np.random.default_rng(7)
    local_scores = rng.random((ranks_per_proc * 4, 32), dtype=np.float32)
    gs = comms.shard_from_local(local_scores, axis=0)
    try:
        mv = comms.run(topk_merge, gs, out_specs=P("data"))
        check("topk_merge", fetch(mv).ndim >= 2)
    except Exception as e:  # surface which pipeline broke, keep rc non-zero
        print(f"FAIL topk_merge: {type(e).__name__}: {e}", flush=True)
        sys.exit(1)

    # distributed k-means from per-process partitions: the full dataset is
    # generated identically on both controllers; each contributes its half
    from raft_tpu.comms import mnmg
    from raft_tpu.cluster import kmeans as local_kmeans

    rngk = np.random.default_rng(5)
    cents = rngk.uniform(-4, 4, (4, 8)).astype(np.float32)
    full = (
        cents[rngk.integers(0, 4, 128)]
        + 0.3 * rngk.standard_normal((128, 8)).astype(np.float32)
    )
    per_proc = 128 // NPROC
    local_part = full[PID * per_proc : (PID + 1) * per_proc]
    centers, inertia, _ = mnmg.kmeans_fit_local(
        comms, local_part, 4, max_iter=25, seed=0, n_init=3
    )
    labels = mnmg.kmeans_predict_local(comms, local_part, centers)
    check("kmeans_local_shapes", labels.shape == (per_proc,) and np.asarray(
        centers.addressable_shards[0].data).shape == (4, 8))
    _, inertia_single, _ = local_kmeans.fit(full, n_clusters=4, seed=0)
    check(
        f"kmeans_local_quality (mp={inertia:.3f} single={float(inertia_single):.3f})",
        inertia <= float(inertia_single) * 1.3 + 1e-6,
    )
    # labels must be consistent with the returned centers
    host_centers = np.asarray(centers.addressable_shards[0].data)
    want_labels = np.argmin(
        ((local_part[:, None, :] - host_centers[None]) ** 2).sum(-1), axis=1
    )
    check("kmeans_local_labels", np.array_equal(np.asarray(labels), want_labels))

    # distributed IVF-Flat build from per-process partitions, searched
    # across the process boundary; recall vs a locally-computed oracle
    from raft_tpu.neighbors import ivf_flat, brute_force

    nrows = 4096
    fdata = (
        cents[rngk.integers(0, 4, nrows)][:, :8].repeat(2, axis=1)
        + 0.3 * rngk.standard_normal((nrows, 16)).astype(np.float32)
    ).astype(np.float32)
    per_proc_f = nrows // NPROC
    flocal = fdata[PID * per_proc_f : (PID + 1) * per_proc_f]
    params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=8)
    di = mnmg.ivf_flat_build_local(comms, params, flocal)
    dv, dids = mnmg.ivf_flat_search(di, fdata[:64], 10, n_probes=8)
    # slot gids ARE caller row ids (process-order concatenation of the
    # partitions == fdata's row order here) — directly comparable
    got_ids = np.asarray(dids.addressable_shards[0].data)
    _, truth_f = brute_force.knn(fdata, fdata[:64], 10, metric="sqeuclidean")
    tf = np.asarray(truth_f)
    rec_f = np.mean(
        [len(set(got_ids[i]) & set(tf[i])) / 10 for i in range(64)]
    )
    check(f"ivf_flat_build_local_recall ({rec_f:.3f})", rec_f > 0.85)
    # extend must reject mirror-less multi-controller indexes clearly
    try:
        mnmg.ivf_flat_extend(di, fdata[:8])
        check("ivf_flat_local_extend_guard", False)
    except ValueError:
        check("ivf_flat_local_extend_guard", True)

    # collective extend_local: each controller appends 32 of its own rows
    # (uneven: proc 1 appends 16); new ids continue the global id space
    extra = (cents[rngk.integers(0, 4, 48)][:, :8].repeat(2, axis=1)
             + 0.3 * rngk.standard_normal((48, 16))).astype(np.float32)
    my_extra = extra[:32] if PID == 0 else extra[32:]
    di2 = mnmg.ivf_flat_extend_local(di, my_extra)
    check("ivf_flat_extend_local_n", di2.n == nrows + 48)
    _, xi = mnmg.ivf_flat_search(di2, extra[:8], 1, n_probes=16)
    got_x = np.asarray(xi.addressable_shards[0].data).ravel()
    # each appended row is its own nearest neighbor at full probing
    check("ivf_flat_extend_local_ids",
          np.array_equal(got_x, np.arange(nrows, nrows + 8)))

    # file-backed collective ingestion with UNEVEN files: proc 0 streams
    # 30 rows (2 batches @ 16), proc 1 only 10 (1 batch) — the batch-
    # count consensus keeps proc 1 participating with an empty tail call
    from raft_tpu import io as rt_io
    import tempfile

    more = (cents[rngk.integers(0, 4, 40)][:, :8].repeat(2, axis=1)
            + 0.3 * rngk.standard_normal((40, 16))).astype(np.float32)
    my_more = more[:30] if PID == 0 else more[30:]
    fpath = os.path.join(tempfile.gettempdir(), f"_mp_stream_{PID}.npy")
    np.save(fpath, my_more)
    di3 = rt_io.extend_from_file_local(
        mnmg.ivf_flat_extend_local, di2, fpath, batch_rows=16)
    check("extend_from_file_local_n", di3.n == nrows + 48 + 40)
    os.unlink(fpath)

    # distributed exact kNN from per-process partitions: ids are caller
    # row ids, so they compare directly against the local oracle
    kd, kids = mnmg.knn_local(comms, flocal, fdata[:32], 5)
    got_k = np.asarray(kids.addressable_shards[0].data)
    _, tk = brute_force.knn(fdata, fdata[:32], 5, metric="sqeuclidean")
    tk = np.asarray(tk)
    rec_k = np.mean([len(set(got_k[i]) & set(tk[i])) / 5 for i in range(32)])
    check(f"knn_local_exact ({rec_k:.3f})", rec_k == 1.0)

    # distributed IVF-PQ build from per-process partitions
    from raft_tpu.neighbors import ivf_pq

    pparams = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=6)
    dpq = mnmg.ivf_pq_build_local(comms, pparams, flocal)
    _, pids = mnmg.ivf_pq_search(dpq, fdata[:64], 10, n_probes=8)
    got_p = np.asarray(pids.addressable_shards[0].data)
    rec_p = np.mean([len(set(got_p[i]) & set(tf[i])) / 10 for i in range(64)])
    check(f"ivf_pq_build_local_recall ({rec_p:.3f})", rec_p > 0.5)
    # the high-recall pipeline: per-rank exact refine of each rank's own
    # candidates, merged — every controller passes its partition
    _, rids = mnmg.ivf_pq_search(
        dpq, fdata[:64], 10, n_probes=8, refine_dataset=flocal
    )
    got_r = np.asarray(rids.addressable_shards[0].data)
    rec_r = np.mean([len(set(got_r[i]) & set(tf[i])) / 10 for i in range(64)])
    check(f"ivf_pq_local_refined_recall ({rec_r:.3f})", rec_r >= rec_p and rec_r > 0.9)
    try:
        mnmg.ivf_pq_extend(dpq, fdata[:8])
        check("ivf_pq_local_extend_guard", False)
    except ValueError:
        check("ivf_pq_local_extend_guard", True)
    dpq2 = mnmg.ivf_pq_extend_local(dpq, my_extra)
    check("ivf_pq_extend_local_n", dpq2.n == nrows + 48 and dpq2.extended)
    _, pxi = mnmg.ivf_pq_search(dpq2, extra[:8], 1, n_probes=16)
    got_px = np.asarray(pxi.addressable_shards[0].data).ravel()
    check("ivf_pq_extend_local_ids",
          np.all((got_px >= 0) & (got_px < nrows + 48)))

    # fused-Pallas engines across the process boundary (interpret mode):
    # per-rank kernel + cross-process merge, overlap vs the exact engines
    _, zfi = mnmg.ivf_flat_search(di, fdata[:16], 5, n_probes=16,
                                  engine="pallas")
    _, zli = mnmg.ivf_flat_search(di, fdata[:16], 5, n_probes=16,
                                  engine="list")
    zf_, zl_ = fetch(zfi)[:16], fetch(zli)[:16]
    hits_f = sum(len(set(a.tolist()) & set(b.tolist()))
                 for a, b in zip(zf_, zl_))
    check(f"mp_flat_pallas_engine ({hits_f / zl_.size:.2f})",
          hits_f / zl_.size >= 0.85)
    _, ids_fused_trim = mnmg.ivf_pq_search(
        dpq, fdata[:16], 5, n_probes=16,
        engine="recon8_list", trim_engine="fused")
    _, ids_approx_trim = mnmg.ivf_pq_search(dpq, fdata[:16], 5, n_probes=16,
                                            engine="recon8_list")
    fus_, apx_ = fetch(ids_fused_trim)[:16], fetch(ids_approx_trim)[:16]
    hits_t = sum(len(set(a.tolist()) & set(b.tolist()))
                 for a, b in zip(fus_, apx_))
    check(f"mp_pq_fused_trim ({hits_t / apx_.size:.2f})",
          hits_t / apx_.size >= 0.8)
    try:
        mnmg.ivf_pq_save("/tmp/should_not_exist.rtpq", dpq)
        check("ivf_pq_local_save_guard", False)
    except ValueError:
        check("ivf_pq_local_save_guard", True)

    # single-chip -> distributed serving bridge on the spanning mesh:
    # both controllers build the identical single-chip index (same data,
    # same seed), then distribute_index block-splits its lists
    sidx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4), fdata
    )
    dsrv = mnmg.distribute_index(comms, sidx)
    _, bids = mnmg.ivf_pq_search(dsrv, fdata[:32], 5, n_probes=8)
    got_b = np.asarray(bids.addressable_shards[0].data)
    _, tb = brute_force.knn(fdata, fdata[:32], 5, metric="sqeuclidean")
    tb = np.asarray(tb)
    rec_b = np.mean([len(set(got_b[i]) & set(tb[i])) / 5 for i in range(32)])
    check(f"distribute_index_bridge ({rec_b:.3f})", rec_b > 0.6)

    print("WORKER_OK", flush=True)


if __name__ == "__main__":
    main()

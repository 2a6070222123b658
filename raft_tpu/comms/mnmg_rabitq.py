"""Distributed IVF-RaBitQ: driver build, SPMD binary-code search with
degraded mode + lossless replica failover, and the refine pipeline.

The index shards exactly like DistributedIvfPq — rank-major per-list
tables over the row shards — but the payload is the RaBitQ pair
(packed uint32 sign codes + the 2-scalar correction table) and there is
NO codebook stage: the build is the distributed coarse k-means plus one
SPMD encode pass, which is the whole fast-build story at pod scale.

Production surfaces from day one (ISSUE 6):
  - `health=` masks dead ranks pre-merge and returns
    `DegradedSearchResult(coverage)`; on a `replication=` build,
    surviving ring holders fail over BIT-IDENTICALLY at coverage 1.0
    through r-1 failures (comms/replication.py — the codes/aux/slot
    tables are all mirrored).
  - `refine_dataset` runs the exact per-rank rerank
    (mnmg_ivf_search._refine_local): every candidate a rank reports came
    from its own rows, so the rerank needs no cross-rank gathers.
  - chaos site "mnmg.ivf_rabitq.scores" poisons a shard's reported
    scores pre-merge (drilled in tests/test_resilience.py).
  - CRC-checked checkpoints with mirror healing live in mnmg_ckpt
    (`ivf_rabitq_save` / `ivf_rabitq_load`).
"""


import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from raft_tpu import obs
from raft_tpu.core import faults
from raft_tpu.comms.comms import Comms
from raft_tpu.comms.mnmg_common import (
    _cached_wrapper, _distributed_id_bound, _mask_dead_rank,
    _pack_result, _pad_queries, _replicated_filter_bits, _resolve_health,
    _shard_filtered, _shard_rows, rank_captured, wrapper_key,
)
from raft_tpu.comms.mnmg_merge import (
    _merge_local_topk, _merge_local_topk_scatter, _resolve_query_mode,
)
from raft_tpu.comms.mnmg_ivf_build import (
    _maybe_replicate, _pack_rank_tables, _spmd_pack_rows,
)

SCORES_SITE = "mnmg.ivf_rabitq.scores"


class DistributedIvfRabitq:
    """Data-parallel IVF-RaBitQ: replicated rotation/centers, per-rank
    packed-code + correction tables over the local shard.

    codes (R, n_lists, max_list, W) uint32 and aux (R, n_lists,
    max_list, 2) f32 are sharded on axis 0; slot_gids holds GLOBAL row
    ids (-1 pad) so shard-local results merge without translation. Host
    mirrors (`host_gids`, `list_sizes`) serve the checkpoint writer."""

    def __init__(self, comms, params, rotation, centers, codes, aux,
                 slot_gids, n, host_gids=None, list_sizes=None,
                 bridged: bool = False):
        self.comms = comms
        self.params = params
        self.rotation = rotation
        self.centers = centers
        self.codes = codes
        self.aux = aux
        self.slot_gids = slot_gids
        self.n = n
        self.host_gids = host_gids
        self.list_sizes = list_sizes
        self.bridged = bridged
        self.extended = False  # no distributed extend yet (ROADMAP 5c)
        self.replicas = None  # see DistributedIvfFlat.replicas
        self._refine_cache = None
        self._id_bound = None

    @property
    def id_bound(self) -> int:
        """One past the largest global id a search can return — the id
        space a `prefilter` must cover."""
        if self._id_bound is None:
            self._id_bound = _distributed_id_bound(self)
        return self._id_bound

    def clear_refine_cache(self) -> None:
        """Release the device-sharded dataset copy a refined search
        pinned (one entry, keyed by dataset identity)."""
        self._refine_cache = None


def _spmd_label_encode_rabitq(comms: Comms, xs, rotation, centers, metric):
    """Label + RaBitQ-encode the sharded rows inside shard_map (the
    O(n*d) encode never leaves the devices). Returns sharded
    (labels (n,), codes (n, W) uint32, aux (n, 2) f32)."""
    from raft_tpu.neighbors.ivf_rabitq import label_and_encode

    def build():
        @jax.jit
        def run(xs, rotation, centers):
            def body(xs, rotation, centers):
                return label_and_encode(xs, rotation, centers, metric)

            return jax.shard_map(
                body, mesh=comms.mesh,
                in_specs=(P(comms.axis, None), P(None, None), P(None, None)),
                out_specs=(P(comms.axis), P(comms.axis, None),
                           P(comms.axis, None)),
                check_vma=False,
            )(xs, rotation, centers)

        return run

    run = _cached_wrapper(
        wrapper_key("spmd_label_encode_rabitq", comms, metric),
        build,
    )
    return run(xs, rotation, centers)


@obs.spanned("mnmg.ivf_rabitq_build")
def ivf_rabitq_build(comms: Comms, params, dataset, seed: int = 0,
                     replication: int = 1) -> DistributedIvfRabitq:
    """Distributed IVF-RaBitQ build: coarse centers via distributed
    Lloyd EM over the rotated trainset fraction, then one SPMD
    label+encode pass — no codebook stage at all, so the build is
    coarse-kmeans-bound (the pod-scale fast-build claim, measured in
    bench/bench_ivf_rabitq.py). `replication` > 1 mirrors each rank's
    code/correction/slot tables onto its ring holders at build time so
    searches fail over losslessly through r-1 failures."""
    from raft_tpu.neighbors import ivf_pq as ivf_pq_mod
    from raft_tpu.neighbors.ivf_rabitq import rabitq_rot_dim

    x = np.asarray(dataset, np.float32)
    n, d = x.shape
    if params.n_lists > n:
        raise ValueError(f"n_lists={params.n_lists} > dataset rows {n}")
    r = comms.get_size()
    per = -(-n // r)
    n_lists = params.n_lists

    rot_dim = rabitq_rot_dim(d)
    key = jax.random.PRNGKey(seed)
    key, rk = jax.random.split(key)
    rotation = ivf_pq_mod._make_rotation(rk, rot_dim, d, True)
    rot_rep = comms.replicate(rotation)

    # coarse centers: the ONE distributed coarse-fit scaffolding shared
    # with ivf_pq_build (minus its codebook stage — nothing follows)
    from raft_tpu.comms.mnmg_ivf_build import _coarse_fit_rotated

    rng = np.random.default_rng(seed)
    centers, _, _ = _coarse_fit_rotated(
        comms, params, x, rotation, rot_rep, rng, seed
    )

    # SPMD label + encode the full dataset (codes stay on device). The
    # encode chaos site fires HERE on the host — inside the traced body
    # it would only fire at trace time and a warm wrapper cache would
    # silently disarm the drill
    from raft_tpu.neighbors.ivf_rabitq import ENCODE_SITE

    faults.fault_point(ENCODE_SITE, rank=jax.process_index())
    xs, _, _ = _shard_rows(comms, x)
    cen_rep = comms.replicate(centers)
    labels_sh, codes_sh, aux_sh = _spmd_label_encode_rabitq(
        comms, xs, rot_rep, cen_rep, params.metric
    )
    labels_np = np.asarray(labels_sh)  # (r*per,) — pad rows ignored below

    local_tbl, gids, sizes, _max_list = _pack_rank_tables(
        labels_np, n, per, r, n_lists
    )
    tbl_sh = comms.shard(jnp.asarray(local_tbl), axis=0)
    packed_codes = _spmd_pack_rows(comms, codes_sh, tbl_sh, per, jnp.uint32)
    packed_aux = _spmd_pack_rows(comms, aux_sh, tbl_sh, per, jnp.float32)

    return _maybe_replicate(DistributedIvfRabitq(
        comms,
        params,
        rot_rep,
        cen_rep,
        packed_codes,
        packed_aux,
        comms.shard(jnp.asarray(gids), axis=0),
        n,
        host_gids=gids,
        list_sizes=sizes,
    ), replication)


@rank_captured("mnmg.ivf_rabitq_search")
@obs.spanned("mnmg.ivf_rabitq_search")
def ivf_rabitq_search(index: DistributedIvfRabitq, queries, k: int,
                      n_probes: int = 20, refine_dataset=None,
                      refine_mult: int = 4, prefilter=None,
                      query_mode: str = "auto", query_bits: int = 0,
                      health=None,
                      adaptive: bool = False, recall_target=None,
                      budget_tau=None, min_probes: int = 1,
                      quantization: str = "auto"):
    """SPMD binary-code search: every rank scans its local packed codes
    for the same global probes and the estimator-ranked local top-k
    merge on all ranks ("replicated") or route to per-rank query blocks
    ("sharded"). `refine_dataset` (full dataset, insertion order)
    enables the exact per-rank rerank of a `refine_mult * k` shortlist —
    each rank re-ranks its OWN candidates against its dataset shard, so
    the merged distances are exact. `prefilter`, `health`, replica
    failover and `DegradedSearchResult` behave exactly as in
    `ivf_pq_search` (shared plumbing). Every rank runs the single-chip
    bit-plane scan (`ivf_rabitq._search_impl_rabitq`) on its shard."""
    from raft_tpu.neighbors.ivf_rabitq import (
        _search_impl_rabitq, rerank_depth, resolve_query_bits,
    )
    from raft_tpu.neighbors.ivf_pq import _coarse_select  # noqa: F401 (doc)
    from raft_tpu.comms.mnmg_ivf_search import _refine_layout, _refine_local
    from raft_tpu.comms.replication import failover_view
    from raft_tpu.distance.distance_types import DistanceType

    # lossless failover first (see ivf_pq_search): with surviving
    # holders the patched view + effective mask make the rest of this
    # function see repaired ranks as healthy
    index, health, repaired = failover_view(index, health)

    comms = index.comms
    ac = comms.comms
    from raft_tpu.comms import quantized

    qcfg = quantized.resolve(quantization)
    q = jnp.asarray(queries, jnp.float32)
    metric = index.params.metric
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf
    n_probes = int(min(n_probes, index.params.n_lists))
    qbits = resolve_query_bits(query_bits)

    kk_depth = (rerank_depth(int(k), max(refine_mult, 1))
                if refine_dataset is not None else int(k))

    # adaptive per-rank probe budgets (see ivf_flat_search: replicated
    # rotation/centers make one host-side plan the every-rank plan;
    # bounds off distributed)
    from raft_tpu.neighbors import probe_budget

    ap = probe_budget.resolve(
        n_probes, adaptive=adaptive, recall_target=recall_target,
        budget_tau=budget_tau, min_probes=min_probes, early_term=False)
    keep = None
    scanned_mean = None
    if ap is not None:
        keep, scanned = probe_budget.probe_plan(
            q, index.centers, n_probes=n_probes,
            min_probes=ap.min_probes, k=int(kk_depth), metric=metric,
            tau=ap.tau, rotation=index.rotation)
        scanned_mean = probe_budget.account(
            "mnmg.ivf_rabitq", scanned, int(q.shape[0]), n_probes)
    if obs.enabled():
        # n_rows = total padded slots of the (R, n_lists, max_list)
        # code tables — every rank scans its probed lists' pad slots too
        obs.span_cost(**obs.perf.cost_for(
            "mnmg.ivf_rabitq_search", nq=int(q.shape[0]),
            n_probes=(scanned_mean if scanned_mean is not None
                      else n_probes),
            n_lists=int(index.params.n_lists),
            n_rows=int(index.codes.shape[0] * index.codes.shape[1]
                       * index.codes.shape[2]),
            dim=int(index.centers.shape[-1]), k=int(k),
            query_bits=int(qbits),
            rerank_mult=int(refine_mult) if refine_dataset is not None else 0))
    mode = _resolve_query_mode(query_mode, comms, q.shape[0], k)
    live_rep, mode, coverage = _resolve_health(comms, health, query_mode, mode)
    nq = q.shape[0]
    if mode == "sharded":
        q, nq = _pad_queries(q, comms.get_size())
    merge = _merge_local_topk if mode == "replicated" else _merge_local_topk_scatter
    out_spec = P(None, None) if mode == "replicated" else P(comms.axis, None)

    qr = comms.replicate(q)
    adaptive_on = ap is not None
    if keep is not None and keep.shape[0] != q.shape[0]:
        # sharded-mode query padding: pad rows scan nothing
        keep = jnp.pad(keep, ((0, q.shape[0] - keep.shape[0]), (0, 0)),
                       constant_values=False)
    pv_rep = comms.replicate(
        keep if keep is not None else jnp.zeros((1, 1), bool))
    pf_bits, pf_n = _replicated_filter_bits(comms, prefilter, index.id_bound)
    refine = refine_dataset is not None
    if refine:
        xs_r, base_r, valid_r = _refine_layout(index, refine_dataset)
        base_rep = comms.replicate(np.asarray(base_r, np.int32))
        valid_rep = comms.replicate(np.asarray(valid_r, np.int32))
        kk = rerank_depth(int(k), max(refine_mult, 1))
    else:
        from raft_tpu.comms.mnmg_common import _ranks_by_proc

        xs_r = comms.shard(
            jnp.zeros((comms.get_size(), 1), jnp.float32), axis=0
        ) if not comms.spans_processes() else comms.shard_from_local(
            np.zeros((len(_ranks_by_proc(comms.mesh).get(
                jax.process_index(), [])), 1), np.float32), axis=0
        )
        base_rep = comms.replicate(np.zeros(comms.get_size(), np.int32))
        valid_rep = comms.replicate(np.zeros(comms.get_size(), np.int32))
        kk = int(k)

    def finish_body(v, gid, q, xs, base, valid, live):
        rank = ac.get_rank()
        if refine:
            v, gid = _refine_local(q, gid, xs, base, valid, rank,
                                   metric, worst)
        else:
            v = jnp.where(gid >= 0, v, worst)
        # corrupt AFTER the local refine (site models the shard's
        # REPORTED scores — same placement rationale as
        # mnmg.ivf_pq.scores)
        v = faults.corrupt_in_trace(SCORES_SITE, v, rank)
        v, gid = _mask_dead_rank(v, gid, live, rank, worst)
        return merge(ac, v, gid, k, select_min, quant=qcfg)

    def build_run():
        @functools.partial(jax.jit, static_argnames=("k", "use_pf"))
        def run(rotation, centers, codes, aux, gid_tbl, q, xs, base, valid,
                bits, live, pv, k: int, use_pf: bool):
            def body(rotation, centers, codes, aux, gid_tbl, q, xs, base,
                     valid, bits, live, pv):
                srows = _shard_filtered(gid_tbl[0], bits, pf_n, use_pf)
                # slot table holds global ids, so the impl's ids are
                # global
                v, gid = _search_impl_rabitq(
                    q, rotation, centers, codes[0], aux[0], srows,
                    kk, n_probes, metric, query_bits=qbits,
                    pvalid=pv if adaptive_on else None,
                )
                return finish_body(v, gid, q, xs, base, valid, live)

            return jax.shard_map(
                body, mesh=comms.mesh,
                in_specs=(P(None, None), P(None, None),
                          P(comms.axis, None, None, None),
                          P(comms.axis, None, None, None),
                          P(comms.axis, None, None),
                          P(None, None), P(comms.axis, None), P(None),
                          P(None), P(None), P(None), P(None, None)),
                out_specs=(out_spec, out_spec), check_vma=False,
            )(rotation, centers, codes, aux, gid_tbl, q, xs, base, valid,
              bits, live, pv)

        return run

    run = _cached_wrapper(
        wrapper_key(
            "rabitq", comms, mode, metric, int(k), kk,
            n_probes, refine, pf_n, qbits, adaptive_on, qcfg),
        build_run,
    )
    v, gid = run(
        index.rotation, index.centers, index.codes, index.aux,
        index.slot_gids, qr, xs_r, base_rep, valid_rep, pf_bits, live_rep,
        pv_rep, int(k), prefilter is not None,
    )
    return _pack_result(v, gid, nq, coverage, repaired)

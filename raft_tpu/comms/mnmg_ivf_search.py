"""Distributed IVF-Flat / IVF-PQ searches: per-rank engines under
shard_map, refine, prefilters, and the replicated/sharded merges."""


import functools
import warnings

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from raft_tpu import obs
from raft_tpu.core import faults
from raft_tpu.comms.comms import op_t
from raft_tpu.matrix.select_k import _select_k_impl
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.comms.mnmg_common import (
    _cached_wrapper, _local_layout, _mask_dead_rank, _pack_local,
    _pack_result, _pad_queries, _rank_layout, _ranks_by_proc,
    _replicated_filter_bits, _resolve_health, _shard_filtered, _shard_rows,
    rank_captured, wrapper_key,
)
from raft_tpu.comms.mnmg_merge import (
    _merge_local_topk, _merge_local_topk_scatter, _resolve_query_mode,
)
from raft_tpu.comms.mnmg_ivf_build import (
    DistributedIvfFlat, DistributedIvfPq,
)


def _build_distributed_recon(index: DistributedIvfPq,
                             pad_to_lanes: bool = False) -> None:
    """Per-rank int8 reconstruction stores for the list-major engine,
    decoded from the packed codes inside shard_map (lazily, idempotent —
    the distributed build_reconstruction). With `pad_to_lanes` the slot
    axis pads to the fused list scan's lane contract
    (recon_norm +inf, slot gids -1 on pad slots — masked exactly like
    in-list padding); once padded, the store stays padded (monotone,
    same contract as the single-chip build_reconstruction)."""
    base = int(index.codes.shape[2])
    have = int(index.recon8.shape[2]) if index.recon8 is not None else -1
    if have >= base:
        if pad_to_lanes:
            _pad_distributed_recon(index, base)
        return
    from raft_tpu.neighbors.ivf_pq import _decode_quantize

    comms = index.comms
    per_cluster = index.params.codebook_kind == _per_cluster_kind()

    @jax.jit
    def run(codes, pq_centers):
        def body(codes, pq_centers):
            r8, scale, rnorm = _decode_quantize(codes[0], pq_centers, per_cluster)
            return r8[None], scale, rnorm[None]

        return jax.shard_map(
            body, mesh=comms.mesh,
            in_specs=(P(comms.axis, None, None, None), P(None, None, None)),
            out_specs=(P(comms.axis, None, None, None), P(None),
                       P(comms.axis, None, None)), check_vma=False,
        )(codes, pq_centers)

    index.recon8, index.recon_scale, index.recon_norm = run(
        index.codes, index.pq_centers
    )
    index.slot_gids_pad = index.slot_gids
    if pad_to_lanes:
        _pad_distributed_recon(index, base)


def _pad_distributed_recon(index: DistributedIvfPq, base: int) -> None:
    """Pad the (sharded) recon store's slot axis to the fused list
    scan's lane contract; no-op when already wide enough."""
    from raft_tpu.ops.fused_scan import lane_padded

    lpad = lane_padded(base)
    extra = lpad - int(index.recon8.shape[2])
    if extra <= 0:
        return
    if index.slot_gids_pad is None:
        index.slot_gids_pad = index.slot_gids
    index.recon8 = jnp.pad(index.recon8, ((0, 0), (0, 0), (0, extra), (0, 0)))
    index.recon_norm = jnp.pad(index.recon_norm,
                               ((0, 0), (0, 0), (0, extra)),
                               constant_values=jnp.inf)
    index.slot_gids_pad = jnp.pad(index.slot_gids_pad,
                                  ((0, 0), (0, 0), (0, extra)),
                                  constant_values=-1)


def _per_cluster_kind():
    from raft_tpu.neighbors.ivf_pq import PER_CLUSTER

    return PER_CLUSTER


def _refine_layout(index, refine_dataset, allow_extended: bool = False):
    """Sharded original rows + per-rank (base, valid) for the distributed
    refine: rank j owns caller ids [base_j, base_j + valid_j), and its
    dataset shard row l holds caller id base_j + l — true for both the
    driver layout (contiguous global rows) and the *_local layout.

    The layout (including the device-sharded copy of the dataset) is
    cached on the index keyed by the dataset object's identity, so a
    serving loop passing the same array re-ships nothing. SINGLE-
    controller only: on a spanning mesh a per-process identity hit would
    let one process skip the layout collectives another still enters —
    a silent deadlock — so multi-controller calls always recompute
    (symmetric collectives every call). Release the pinned copy with
    index.clear_refine_cache()."""
    comms = index.comms
    cacheable = not comms.spans_processes()
    cache = getattr(index, "_refine_cache", None)
    if cacheable and cache is not None and cache[0] is refine_dataset:
        return cache[1], cache[2], cache[3]
    if getattr(index, "bridged", False):
        raise ValueError(
            "refine_dataset needs gids that index the dataset rows: "
            "bridged (distribute_index) layouts may carry arbitrary "
            "caller ids — refine on the single-chip index instead"
        )
    if getattr(index, "extended", False):
        # allow_extended = the post-merge refine topology, whose
        # ownership follows this layout's contiguous sharding rather
        # than the index's (now non-contiguous) list placement. It needs
        # the full-dataset layout: a *_local-extended partition's ids
        # are split between the original and extended id blocks, which
        # the per-partition layout cannot express.
        if not allow_extended or index.host_gids is None:
            raise ValueError(
                "refine on an extended index runs post-merge over the "
                "FULL dataset layout (driver-built indexes do this "
                "automatically); *_local-extended layouts are "
                "unsupported — rebuild to refine"
            )
    if index.host_gids is not None:  # driver build: the FULL host array
        x = np.asarray(refine_dataset, np.float32)
        if x.shape[0] != index.n:
            raise ValueError(
                f"refine_dataset has {x.shape[0]} rows, index holds {index.n}"
            )
        xs, n, per = _shard_rows(comms, x)
        r = comms.get_size()
        base = per * np.arange(r, dtype=np.int64)
        valid = np.clip(n - base, 0, per)
        if cacheable:
            index._refine_cache = (refine_dataset, xs, base, valid)
        return xs, base, valid
    # *_local build: THIS process's partition (collective)
    local = np.asarray(refine_dataset, np.float32)
    counts, per, lranks = _local_layout(comms, local.shape[0])
    if int(counts.sum()) != index.n:
        raise ValueError(
            f"refine_dataset partitions sum to {int(counts.sum())} rows, "
            f"index holds {index.n}"
        )
    xp, _ = _pack_local(local, per, lranks)
    xs = comms.shard_from_local(xp, axis=0)
    base, valid = _rank_layout(comms, counts, per)
    if cacheable:
        index._refine_cache = (refine_dataset, xs, base, valid)
    return xs, base, valid


def _exact_scores(q, rows, metric):
    """Exact (nq, kk) scores of gathered candidate rows."""
    if metric == DistanceType.InnerProduct:
        return jnp.einsum("qd,qkd->qk", q, rows)
    diff = q[:, None, :] - rows
    exact = jnp.sum(diff * diff, axis=2)
    if metric == DistanceType.L2SqrtExpanded:
        exact = jnp.sqrt(jnp.maximum(exact, 0.0))
    return exact


def _refine_local(q, gid, xs, base, valid, rank, metric, worst):
    """Exact per-rank re-rank: every candidate a rank reports came from
    its own lists, so its original row is in the rank's dataset shard —
    the distributed form of neighbors/refine.cuh with no cross-rank
    gathers. PQ scores are discarded; gids alone drive the gather."""
    local = gid - base[rank]
    own = (gid >= 0) & (local >= 0) & (local < valid[rank])
    rows = xs[jnp.clip(local, 0, xs.shape[0] - 1)]  # (nq, kk, d)
    exact = _exact_scores(q, rows, metric)
    return jnp.where(own, exact, worst), jnp.where(own, gid, -1)


def _refine_merged(ac, q, mgid, xs, base, valid, rank, metric, worst, k,
                   select_min):
    """Post-merge exact re-rank (inside shard_map): candidate ownership
    follows the refine dataset's CONTIGUOUS sharding, not the index's
    list placement — so it refines layouts whose per-rank gid ownership
    is non-contiguous (extended indexes), which the pre-merge
    `_refine_local` cannot. Each gid has exactly one owner in the
    contiguous layout; owners contribute exact scores, everyone else the
    worst value, and one MIN/MAX allreduce of the (nq, kk) shortlist
    assembles the exact scores on every rank. -1 merge pads have no
    owner, stay at worst, and sort last with id -1."""
    local = mgid - base[rank]
    own = (mgid >= 0) & (local >= 0) & (local < valid[rank])
    rows = xs[jnp.clip(local, 0, xs.shape[0] - 1)]  # (nq, kk, d)
    exact = _exact_scores(q, rows, metric)
    contrib = jnp.where(own, exact, worst)
    combined = ac.allreduce(contrib, op_t.MIN if select_min else op_t.MAX)
    fv, fp = _select_k_impl(combined, min(k, combined.shape[1]), select_min)
    return fv, jnp.take_along_axis(mgid, fp, axis=1)

@rank_captured("mnmg.ivf_pq_search")
@obs.spanned("mnmg.ivf_pq_search")
def ivf_pq_search(index: DistributedIvfPq, queries, k: int, n_probes: int = 20,
                  engine: str = "auto", refine_dataset=None,
                  refine_mult: int = 4, prefilter=None,
                  query_mode: str = "auto", trim_engine: str = "approx",
                  score_dtype: str = "bf16", health=None,
                  adaptive: bool = False, recall_target=None,
                  budget_tau=None, min_probes: int = 1,
                  quantization: str = "auto"):
    """SPMD search: every rank scores its local lists for the same global
    probes; local top-k are merged on all ranks ("replicated") or routed
    to per-rank query blocks ("sharded" — R× less merge traffic for
    serving; see `_resolve_query_mode` for "auto"). Both modes return the
    full (nq, k) result as a global jax.Array; sharded output is laid out
    query-sharded across the mesh instead of replicated.

    `engine`: "recon8_list" (the list-major int8-reconstruction engine the
    single-chip flagship uses — each rank streams each probed list once),
    "lut" (query-major, for tiny batches), or "auto" (same duplication
    heuristic as the single-chip `search`). With engine="recon8_list",
    `trim_engine="fused"` runs the EXACT fused scan+select trim
    (matrix/select_k list-scan dispatch; with score_dtype="int8" it is
    the dispatch layer's "fused_int8" int8-MXU strategy — ISSUE 11), and
    `score_dtype="int8"` scores with symmetric int8 queries — all
    mirroring the single-chip SearchParams options.

    `refine_dataset` enables the high-recall pipeline (neighbors/
    refine.cuh distributed): each rank takes a `refine_mult * k`
    shortlist from its PQ scores, re-ranks its OWN candidates exactly
    against the original vectors (a rank's candidates all come from its
    own rows — no cross-rank gathers), and the exact scores merge.
    Pass the full dataset for driver-built indexes, or this process's
    partition for *_local-built ones. EXTENDED driver-built indexes
    refine post-merge instead (`_refine_merged`: the global shortlist
    merges first, then owners in the dataset's contiguous sharding
    contribute exact scores through one MIN/MAX allreduce) — pass the
    full dataset including the extended rows; *_local-extended layouts
    cannot refine. This topology reduces across ranks per query, so an
    extended+refined search always returns the REPLICATED output layout
    — an explicit query_mode="sharded" request degrades to replicated
    with a warning.

    `prefilter` (core.Bitset or boolean mask over the GLOBAL id space,
    `index.id_bound` ids; identical on every controller) excludes
    samples before trim/selection on every rank — the slot tables hold
    global ids, so one replicated bitset serves all shards.

    `health` (resilience.RankHealth) enables degraded mode: unhealthy
    ranks' candidates are masked out of the merge (survivors' results
    are bit-identical to prefiltering the dead shard's rows away) and
    the return becomes a `DegradedSearchResult(values, ids, coverage)`
    with coverage = served shards / total. On an index with r-way
    replicas (`mnmg.replicate_index` / build `replication=`), unhealthy
    ranks with a surviving replica holder FAIL OVER instead: the
    holder's copy re-materializes the shard, results stay bit-identical
    to the all-healthy run at coverage 1.0, and the ranks appear in
    `DegradedSearchResult.repaired_ranks` — only failures past r-1
    degrade. Degraded masks are incompatible with the post-merge refine
    of extended indexes (exact scores there come from the refine
    dataset's contiguous owners, who may be dead).

    `quantization` selects the replicated merge's wire transport
    (comms/quantized): "off" is bit-identical to the exact merge,
    "int8"/"bf16" ship block-quantized candidate scores and re-rank
    survivors on exact psum-resolved values; the default "auto" stays
    exact until a chip bench banks a `comms_quant_mode` winner for this
    backend."""
    from raft_tpu.neighbors.ivf_pq import (
        _search_impl, _search_impl_recon8_listmajor, PER_CLUSTER,
    )
    from raft_tpu.comms.replication import failover_view

    # lossless failover first: with surviving replica holders the
    # patched view + effective mask make the rest of this function (and
    # its refine/extended checks) see repaired ranks as healthy
    index, health, repaired = failover_view(index, health)

    comms = index.comms
    ac = comms.comms
    from raft_tpu.comms import quantized

    # resolved before the wrapper caches below: the hashable config is
    # part of every cache key, so a tuned comms_quant_mode flip rebuilds
    # the traced program (cache-key completeness)
    qcfg = quantized.resolve(quantization)
    q = jnp.asarray(queries, jnp.float32)
    metric = index.params.metric
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf
    n_probes = int(min(n_probes, index.params.n_lists))
    per_cluster = index.params.codebook_kind == PER_CLUSTER
    # adaptive per-rank probe budgets: rotation/centers are replicated,
    # so ONE host-side plan is the every-rank plan (see ivf_flat_search;
    # bounds off distributed — radii are per-rank local state). Computed
    # on the UNPADDED queries so the accounting counts real rows only.
    from raft_tpu.neighbors import probe_budget

    ap = probe_budget.resolve(
        n_probes, adaptive=adaptive, recall_target=recall_target,
        budget_tau=budget_tau, min_probes=min_probes, early_term=False)
    keep = None
    scanned_mean = None
    if ap is not None:
        keep, scanned = probe_budget.probe_plan(
            q, index.centers, n_probes=n_probes,
            min_probes=ap.min_probes, k=int(k), metric=metric, tau=ap.tau,
            rotation=index.rotation)
        scanned_mean = probe_budget.account(
            "mnmg.ivf_pq", scanned, int(q.shape[0]), n_probes)
    # extended indexes refine POST-merge (ownership by the refine
    # dataset's contiguous sharding, see _refine_merged); that topology
    # reduces across ranks per query, so it needs replicated queries
    refine_merged = (refine_dataset is not None
                     and bool(getattr(index, "extended", False)))
    mode = _resolve_query_mode(query_mode, comms, q.shape[0], k)
    if refine_merged:
        if query_mode == "sharded":
            # an EXPLICIT sharded request changes the returned layout the
            # caller asked for — surface the degrade (silent fallback is
            # reserved for "auto"; ADVICE r3)
            warnings.warn(
                "query_mode='sharded' is incompatible with refined search "
                "on an extended index (post-merge refine reduces across "
                "ranks per query); returning the REPLICATED layout",
                stacklevel=2,
            )
        mode = "replicated"
    # health is controller-uniform by protocol: every controller raises
    # together (or none does) — no rank diverges past this point
    if refine_merged and health is not None and health.degraded:  # raftlint: disable=collective-divergence
        raise ValueError(
            "degraded-mode refine on an extended index is unsupported: "
            "post-merge exact scores come from the refine dataset's "
            "contiguous owners, and a dead owner cannot score its rows — "
            "search without refine_dataset, or rehydrate first"
        )
    live_rep, mode, coverage = _resolve_health(comms, health, query_mode, mode)
    nq = q.shape[0]
    if mode == "sharded":
        q, nq = _pad_queries(q, comms.get_size())
    merge = _merge_local_topk if mode == "replicated" else _merge_local_topk_scatter
    out_spec = P(None, None) if mode == "replicated" else P(comms.axis, None)

    if engine == "auto":
        if score_dtype == "int8" or trim_engine == "fused":
            # an explicit int8 / fused-trim request pins the engine that
            # honors it (same rule as the single-chip search: numerics
            # must not depend on batch size)
            engine = "recon8_list"
        else:
            # ivf_pq._resolve_score_mode's duplication heuristic,
            # restricted to the two distributed engines: on TPU the
            # resolution NEVER lands on lut (its gather kernel-faults
            # the device — docs/perf.md device-fault section)
            dup = q.shape[0] * n_probes / max(1, index.params.n_lists)
            engine = ("recon8_list"
                      if dup >= 4.0 or jax.default_backend() == "tpu"
                      else "lut")
    if engine not in ("recon8_list", "lut"):
        raise ValueError(f"unknown engine {engine!r}")
    if obs.enabled():
        # charged AFTER engine resolution: the list-major engine streams
        # every padded slot on every rank; lut touches the probed lists.
        # n_rows = total padded slots across the (R, n_lists, max_list)
        # code tables — pad slots are scored too.
        obs.span_cost(**obs.perf.cost_for(
            "mnmg.ivf_pq_search", nq=int(q.shape[0]), n_probes=n_probes,
            n_lists=int(index.params.n_lists),
            n_rows=int(index.codes.shape[0] * index.codes.shape[1]
                       * index.codes.shape[2]),
            dim=int(index.centers.shape[-1]),
            pq_dim=int(index.codes.shape[-1]), k=int(k), dtype=score_dtype,
            scanned_lists=(int(index.params.n_lists)
                           if engine == "recon8_list" and trim_engine != "fused"
                           else (scanned_mean if scanned_mean is not None
                                 else n_probes))))
    if engine == "lut":
        from raft_tpu.neighbors.ivf_pq import _check_lut_allowed

        _check_lut_allowed()  # explicit lut on TPU: same fence as single-chip

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
        xs_r, base_r, valid_r = _refine_layout(
            index, refine_dataset, allow_extended=refine_merged)
        base_rep = comms.replicate(np.asarray(base_r, np.int32))
        valid_rep = comms.replicate(np.asarray(valid_r, np.int32))
        # shortlist never narrower than k (a cap below k would shrink the
        # merged output width); inflation capped at 256 gathered rows
        kk = int(max(k, min(max(refine_mult, 1) * k, 256)))
    else:
        # zero-size placeholders keep one jitted signature per engine
        xs_r = comms.shard(
            jnp.zeros((comms.get_size(), 1), jnp.float32), axis=0
        ) if not comms.spans_processes() else comms.shard_from_local(
            np.zeros((len(_ranks_by_proc(comms.mesh).get(jax.process_index(), [])), 1),
                     np.float32), axis=0
        )
        base_rep = comms.replicate(np.zeros(comms.get_size(), np.int32))
        valid_rep = comms.replicate(np.zeros(comms.get_size(), np.int32))
        kk = int(k)

    def finish(v, gid, q, xs, base, valid, live):
        rank = ac.get_rank()
        if refine_merged:
            v = faults.corrupt_in_trace("mnmg.ivf_pq.scores", v, rank)
            v = jnp.where(gid >= 0, v, worst)
            # global shortlist kept as wide as the pre-merge path's total
            # exact re-rank depth (r ranks x kk each, under the same
            # 256-row gather cap) — merging down to kk first would drop
            # true neighbors PQ ranks 21st+ before exact scoring. Never
            # narrower than kk itself: kk >= k, and a sub-k shortlist
            # would shrink the (nq, k) output width.
            kk_merged = min(comms.get_size() * kk, max(256, kk))
            _, mgid = merge(ac, v, gid, kk_merged, select_min, quant=qcfg)
            return _refine_merged(ac, q, mgid, xs, base, valid,
                                  rank, metric, worst, k, select_min)
        if refine:
            v, gid = _refine_local(q, gid, xs, base, valid, rank, metric, worst)
        else:
            v = jnp.where(gid >= 0, v, worst)
        # corrupt AFTER the local refine: the site models the shard's
        # REPORTED scores, and the refine path discards the PQ scores
        # (gids alone drive its exact re-rank) — injecting earlier would
        # make the drill silently inert on refined searches
        v = faults.corrupt_in_trace("mnmg.ivf_pq.scores", v, rank)
        # degraded mode: an unhealthy rank's shard stops contributing
        v, gid = _mask_dead_rank(v, gid, live, rank, worst)
        return merge(ac, v, gid, k, select_min, quant=qcfg)

    def trim(out):
        return _pack_result(out[0], out[1], nq, coverage, repaired)

    if trim_engine not in ("approx", "fused"):
        raise ValueError(f"unknown trim_engine {trim_engine!r}")
    if trim_engine == "fused" and engine != "recon8_list":
        raise ValueError("trim_engine='fused' requires engine='recon8_list'")
    if score_dtype not in ("bf16", "int8"):
        raise ValueError(f"unknown score_dtype {score_dtype!r}")
    if score_dtype == "int8" and engine != "recon8_list":
        raise ValueError("score_dtype='int8' requires engine='recon8_list'")
    int8_q = score_dtype == "int8"
    if engine == "recon8_list":
        use_fused_trim = trim_engine == "fused"
        fused_kb = None
        if use_fused_trim:
            # the EXACT fused trim per rank (ISSUE 11): bf16 or — with
            # score_dtype="int8" — the int8 MXU path, both through the
            # matrix/select_k list-scan dispatch. Same envelope/kbuf
            # contract as the single-chip engine (the ONE shared
            # validation), checked per rank
            from raft_tpu.matrix.select_k import check_fused_list_request
            from raft_tpu.ops.fused_scan import lane_padded

            fused_kb = check_fused_list_request(
                "trim_engine='fused'",
                lane_padded(int(index.codes.shape[2])),
                int(index.rotation.shape[0]), int(kk), 1,
                getattr(index, "fused_kb", None), "trim_engine='approx'",
            )
            from raft_tpu.neighbors.ivf_pq import (
                _search_impl_recon8_listmajor_fused,
            )

            # monotonic candidate-buffer bookkeeping, like the flat
            # engine's _build_distributed_resid
            index.fused_kb = fused_kb
        _build_distributed_recon(index, pad_to_lanes=use_fused_trim)
        # ALWAYS the padded view: _build_distributed_recon keeps
        # slot_gids_pad width-matched to recon8 (== slot_gids until a
        # fused search pads the store in place — after which the approx
        # engine must see the same padded width or its score/slot
        # broadcast shapes diverge)
        gid_source = index.slot_gids_pad
        interp = jax.default_backend() == "cpu"

        def build_list():
            @functools.partial(jax.jit, static_argnames=("k", "use_pf"))
            def run_list(rotation, centers, recon8, scale, rnorm, gid_tbl,
                         q, xs, base, valid, bits, live, pv,
                         k: int, use_pf: bool):
                def body(rotation, centers, recon8, scale, rnorm, gid_tbl,
                         q, xs, base, valid, bits, live, pv):
                    srows = _shard_filtered(gid_tbl[0], bits, pf_n, use_pf)
                    pvk = pv if adaptive_on else None
                    if use_fused_trim:
                        v, gid = _search_impl_recon8_listmajor_fused(
                            q, rotation, centers, recon8[0], scale,
                            rnorm[0], srows, kk, n_probes, metric,
                            interpret=interp, int8_queries=int8_q,
                            kb=fused_kb, pvalid=pvk,
                        )
                    else:
                        v, gid = _search_impl_recon8_listmajor(
                            q, rotation, centers, recon8[0], scale,
                            rnorm[0], srows, kk, n_probes, metric,
                            int8_queries=int8_q, pvalid=pvk,
                        )
                    return finish(v, gid, q, xs, base, valid, live)

                return jax.shard_map(
                    body, mesh=comms.mesh,
                    in_specs=(P(None, None), P(None, None),
                              P(comms.axis, None, None, None), P(None),
                              P(comms.axis, None, None),
                              P(comms.axis, None, None),
                              P(None, None), P(comms.axis, None), P(None),
                              P(None), P(None), P(None), P(None, None)),
                    out_specs=(out_spec, out_spec), check_vma=False,
                )(rotation, centers, recon8, scale, rnorm, gid_tbl, q, xs,
                  base, valid, bits, live, pv)

            return run_list

        run_list = _cached_wrapper(
            wrapper_key(
                "pq_recon8_list", comms, mode, metric,
                int(k), kk, n_probes, refine, refine_merged, pf_n, int8_q,
                use_fused_trim, fused_kb, interp, adaptive_on, qcfg),
            build_list,
        )
        return trim(run_list(
            index.rotation, index.centers, index.recon8, index.recon_scale,
            index.recon_norm, gid_source, qr, xs_r, base_rep, valid_rep,
            pf_bits, live_rep, pv_rep, int(k), prefilter is not None,
        ))

    def build_lut():
        @functools.partial(jax.jit, static_argnames=("k", "use_pf"))
        def run(rotation, centers, pq_centers, codes, gid_tbl, q,
                xs, base, valid, bits, live, pv, k: int, use_pf: bool):
            def body(rotation, centers, pq_centers, codes, gid_tbl, q,
                     xs, base, valid, bits, live, pv):
                # slot table holds global ids, so _search_impl's ids are
                # global
                v, gid = _search_impl(
                    q, rotation, centers, pq_centers, codes[0],
                    _shard_filtered(gid_tbl[0], bits, pf_n, use_pf),
                    kk, n_probes, metric, per_cluster,
                    pvalid=pv if adaptive_on else None,
                )
                return finish(v, gid, q, xs, base, valid, live)

            return jax.shard_map(
                body, mesh=comms.mesh,
                in_specs=(P(None, None), P(None, None),
                          P(None, None, None),
                          P(comms.axis, None, None, None),
                          P(comms.axis, None, None),
                          P(None, None), P(comms.axis, None), P(None),
                          P(None), P(None), P(None), P(None, None)),
                out_specs=(out_spec, out_spec), check_vma=False,
            )(rotation, centers, pq_centers, codes, gid_tbl, q, xs, base,
              valid, bits, live, pv)

        return run

    run = _cached_wrapper(
        wrapper_key(
            "pq_lut", comms, mode, metric, int(k), kk,
            n_probes, refine, refine_merged, pf_n, per_cluster, adaptive_on,
            qcfg),
        build_lut,
    )
    return trim(run(
        index.rotation, index.centers, index.pq_centers, index.codes,
        index.slot_gids, qr, xs_r, base_rep, valid_rep, pf_bits, live_rep,
        pv_rep, int(k), prefilter is not None,
    ))


def _build_distributed_resid(index: DistributedIvfFlat, k: int) -> None:
    """Lazy per-rank derived store for the distributed fused Pallas scan
    (the IVF-Flat analogue of _build_distributed_recon): lane-padded
    bf16 per-slot RESIDUALS v - center_l plus f32 norms, with pad slots
    exact-zero / gid -1 — same derivation as the single-chip
    _pad_store_to_lanes, computed on the sharded arrays (centers are
    replicated, so XLA keeps everything rank-local). Mirrors the
    single-chip candidate-buffer bookkeeping: `index.fused_kb` records
    the compiled width and grows monotonically when `k` outruns it
    (never a silent per-list truncation)."""
    from raft_tpu.ops.fused_scan import fused_kbuf, lane_padded

    base = int(index.list_data.shape[2])
    lpad = lane_padded(base)
    if index.resid_bf16 is None or int(index.resid_bf16.shape[2]) != lpad:
        ld = jnp.pad(index.list_data, ((0, 0), (0, 0), (0, lpad - base), (0, 0)))
        sg = jnp.pad(index.slot_gids, ((0, 0), (0, 0), (0, lpad - base)),
                     constant_values=-1)
        resid = ld.astype(jnp.float32) - jnp.asarray(index.centers)[None, :, None, :]
        resid = jnp.where((sg >= 0)[..., None], resid, 0.0)
        index.resid_bf16 = resid.astype(jnp.bfloat16)
        index.resid_norm = jnp.sum(resid ** 2, axis=3)
        index.slot_gids_pad = sg
    kb = fused_kbuf(int(k))
    if getattr(index, "fused_kb", None) is None or kb > index.fused_kb:
        index.fused_kb = kb


@rank_captured("mnmg.ivf_flat_search")
@obs.spanned("mnmg.ivf_flat_search")
def ivf_flat_search(index: DistributedIvfFlat, queries, k: int, n_probes: int = 20,
                    prefilter=None, query_mode: str = "auto",
                    engine: str = "auto", health=None,
                    adaptive: bool = False, recall_target=None,
                    budget_tau=None, min_probes: int = 1,
                    quantization: str = "auto"):
    """SPMD search: every rank scans its local lists for the same global
    probes; local top-k are merged on all ranks ("replicated") or routed
    to per-rank query blocks ("sharded"; see `_resolve_query_mode`).
    `engine`: "query" (query-major, tiny batches), "list" (list-major
    — each rank streams each probed list once; the serving engine), or
    "pallas" (the fused distance+select-k scan per rank over
    lane-padded bf16 residual stores — exact-within-probed-lists modulo
    bf16 rounding, like the single-chip fused engine); "auto" uses the
    tuned/duplication heuristic the
    single-chip search uses (a tuned "pallas" winner maps to "list" —
    explicit opt-in for the distributed fused engine until it is
    chip-measured distributed). `prefilter` (core.Bitset or boolean mask
    over the GLOBAL id space, `index.id_bound` ids; identical on every
    controller) excludes samples before selection on every rank.

    `health` (resilience.RankHealth) enables degraded mode: unhealthy
    ranks' candidates are masked out of the merge and the return becomes
    a `DegradedSearchResult(values, ids, coverage)`; on a replicated
    index surviving holders fail over losslessly (coverage stays 1.0,
    `repaired_ranks` reports them) — see `ivf_pq_search`, including the
    `quantization` merge-transport knob."""
    from raft_tpu.neighbors.ivf_flat import (
        _search_impl, _search_impl_listmajor, _search_impl_listmajor_pallas,
    )
    from raft_tpu.comms.replication import failover_view
    from raft_tpu.comms import quantized

    # lossless failover before anything reads the mask (see ivf_pq_search)
    index, health, repaired = failover_view(index, health)

    comms = index.comms
    ac = comms.comms
    qcfg = quantized.resolve(quantization)
    qh = jnp.asarray(queries, jnp.float32)
    metric = index.params.metric
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.inf if select_min else -jnp.inf
    n_probes = int(min(n_probes, index.params.n_lists))
    pf_bits, pf_n = _replicated_filter_bits(comms, prefilter, index.id_bound)
    if engine == "auto":
        from raft_tpu.neighbors.ivf_flat import resolve_auto_engine

        engine = resolve_auto_engine(qh.shape[0], n_probes,
                                     index.params.n_lists, pallas_ok=None)
    if engine not in ("query", "list", "pallas"):
        raise ValueError(f"unknown engine {engine!r} (distributed ivf_flat "
                         "supports 'query', 'list', 'pallas', 'auto')")
    # adaptive per-rank probe budgets (ROADMAP item 2): centers and
    # queries are REPLICATED, so the coarse geometry — and therefore the
    # keep mask — is identical on every rank; one host-side plan serves
    # the whole mesh as a replicated operand and the merge is unchanged.
    # Bounds stay off distributed (radii are per-rank local state).
    from raft_tpu.neighbors import probe_budget

    ap = probe_budget.resolve(
        n_probes, adaptive=adaptive, recall_target=recall_target,
        budget_tau=budget_tau, min_probes=min_probes, early_term=False)
    keep = None
    scanned_mean = None
    if ap is not None:
        keep, scanned = probe_budget.probe_plan(
            qh, index.centers, n_probes=n_probes,
            min_probes=ap.min_probes, k=int(k), metric=metric, tau=ap.tau)
        scanned_mean = probe_budget.account(
            "mnmg.ivf_flat", scanned, int(qh.shape[0]), n_probes)
    if obs.enabled():
        # charged AFTER engine resolution (list-major streams every
        # padded slot on every rank); n_rows = total padded slots of the
        # (R, n_lists, max_list) store. Adaptive budgets charge the
        # ACTUAL scanned mean on the probed-list engines.
        obs.span_cost(**obs.perf.cost_for(
            "mnmg.ivf_flat_search", nq=int(qh.shape[0]), n_probes=n_probes,
            n_lists=int(index.params.n_lists),
            n_rows=int(index.list_data.shape[0] * index.list_data.shape[1]
                       * index.list_data.shape[2]),
            dim=int(index.list_data.shape[-1]), k=int(k),
            scanned_lists=(int(index.params.n_lists) if engine == "list"
                           else (scanned_mean if scanned_mean is not None
                                 else n_probes))))
    mode = _resolve_query_mode(query_mode, comms, qh.shape[0], int(k))
    live_rep, mode, coverage = _resolve_health(comms, health, query_mode, mode)
    nq = qh.shape[0]
    if mode == "sharded":
        qh, nq = _pad_queries(qh, comms.get_size())
    merge = _merge_local_topk if mode == "replicated" else _merge_local_topk_scatter
    out_spec = P(None, None) if mode == "replicated" else P(comms.axis, None)
    q = comms.replicate(qh)
    adaptive_on = ap is not None
    if keep is not None and keep.shape[0] != qh.shape[0]:
        # sharded-mode query padding: pad rows scan nothing
        keep = jnp.pad(keep, ((0, qh.shape[0] - keep.shape[0]), (0, 0)),
                       constant_values=False)
    # the keep-mask operand is ALWAYS passed (a (1, 1) dummy on the
    # fixed path, unused and DCE'd) so each engine keeps one body/spec
    pv_rep = comms.replicate(
        keep if keep is not None else jnp.zeros((1, 1), bool))

    def pack(v, gid):
        return _pack_result(v, gid, nq, coverage, repaired)

    if engine == "pallas":
        from raft_tpu.ops.fused_scan import (
            FUSED_MAX_K, fits_fused_list, fused_kbuf, lane_padded,
        )

        if int(k) > FUSED_MAX_K:
            raise ValueError(
                f"engine='pallas' caps per-list candidates at "
                f"{FUSED_MAX_K}; k={k}"
            )
        d = int(index.list_data.shape[-1])
        lpad = lane_padded(int(index.list_data.shape[2]))
        # store_itemsize=2: the scanned store is the bf16 residual copy;
        # gated at the width the kernel will RUN with — the recorded
        # fused_kb when a previous larger-k search already grew it (same
        # rule as the single-chip _pallas_fits)
        kb_run = max(fused_kbuf(int(k)),
                     getattr(index, "fused_kb", None) or 0)
        if not fits_fused_list(128, lpad, d, int(k), store_itemsize=2,
                               kbuf=kb_run):
            raise ValueError(
                f"engine='pallas': padded list length {lpad} x dim {d} "
                "exceeds the kernel's VMEM envelope; use engine='list'"
            )
        _build_distributed_resid(index, int(k))
        interp = jax.default_backend() == "cpu"
        kb = int(index.fused_kb)

        def build_pallas():
            @functools.partial(jax.jit, static_argnames=("k", "use_pf"))
            def run_pallas(resid, rnorm, gid_tbl, centers, q, bits, live,
                           pv, k: int, use_pf: bool):
                def body(resid, rnorm, gid_tbl, centers, q, bits, live, pv):
                    v, gid = _search_impl_listmajor_pallas(
                        q, centers, resid[0], rnorm[0],
                        _shard_filtered(gid_tbl[0], bits, pf_n, use_pf),
                        k, n_probes, metric, kb=kb, interpret=interp,
                        fault_key=faults.trace_key(),
                        pvalid=pv if adaptive_on else None,
                    )
                    rank = ac.get_rank()
                    v = faults.corrupt_in_trace("mnmg.ivf_flat.scores", v, rank)
                    v = jnp.where(gid >= 0, v, worst)
                    v, gid = _mask_dead_rank(v, gid, live, rank, worst)
                    return merge(ac, v, gid, k, select_min, quant=qcfg)

                return jax.shard_map(
                    body, mesh=comms.mesh,
                    in_specs=(P(comms.axis, None, None, None),
                              P(comms.axis, None, None),
                              P(comms.axis, None, None),
                              P(None, None), P(None, None), P(None),
                              P(None), P(None, None)),
                    out_specs=(out_spec, out_spec), check_vma=False,
                )(resid, rnorm, gid_tbl, centers, q, bits, live, pv)

            return run_pallas

        run_pallas = _cached_wrapper(
            wrapper_key(
                "flat_pallas", comms, mode, metric,
                n_probes, pf_n, interp, kb, adaptive_on, qcfg),
            build_pallas,
        )
        v, gid = run_pallas(index.resid_bf16, index.resid_norm,
                            index.slot_gids_pad, index.centers, q, pf_bits,
                            live_rep, pv_rep, int(k), prefilter is not None)
        return pack(v, gid)

    impl = _search_impl if engine == "query" else _search_impl_listmajor

    def build_flat():
        @functools.partial(jax.jit, static_argnames=("k", "use_pf"))
        def run(ld, gid_tbl, centers, q, bits, live, pv, k: int, use_pf: bool):
            def body(ld, gid_tbl, centers, q, bits, live, pv):
                # slot table holds global ids, so the impl's ids are
                # global
                v, gid = impl(
                    q, centers, ld[0],
                    _shard_filtered(gid_tbl[0], bits, pf_n, use_pf),
                    k, n_probes, metric,
                    pvalid=pv if adaptive_on else None,
                )
                rank = ac.get_rank()
                v = faults.corrupt_in_trace("mnmg.ivf_flat.scores", v, rank)
                v = jnp.where(gid >= 0, v, worst)
                v, gid = _mask_dead_rank(v, gid, live, rank, worst)
                return merge(ac, v, gid, k, select_min, quant=qcfg)

            return jax.shard_map(
                body, mesh=comms.mesh,
                in_specs=(P(comms.axis, None, None, None),
                          P(comms.axis, None, None),
                          P(None, None), P(None, None), P(None), P(None),
                          P(None, None)),
                out_specs=(out_spec, out_spec), check_vma=False,
            )(ld, gid_tbl, centers, q, bits, live, pv)

        return run

    run = _cached_wrapper(
        wrapper_key(
            "flat", comms, mode, metric, n_probes, pf_n,
            engine, adaptive_on, qcfg),
        build_flat,
    )
    v, gid = run(index.list_data, index.slot_gids, index.centers, q, pf_bits,
                 live_rep, pv_rep, int(k), prefilter is not None)
    return pack(v, gid)

"""Short on-chip diagnostics: where does search wall-time actually go?

Three questions the 2026-08-01 nine-minute chip window left open, each
answerable in seconds of chip time:

1. **Dispatch floor.** Every small stage measured ~80 ms regardless of
   FLOPs, suggesting a fixed per-dispatch round-trip. Times a trivial
   jit'd op and a chained-10x variant; the gap between (10 x single) and
   (1 x chained) IS the per-dispatch overhead. If it is ~80 ms, engine
   QPS at nq=4096 is dispatch-bound, not compute-bound, and every
   cross-engine delta under ~2x is suspect.

2. **sqeuclidean anomaly.** pairwise L2Expanded measured 825 ms vs
   cosine's 80 ms at the SAME (8192, 768) gemm shape (same `_dot`, same
   bf16 single-pass precision) — a 10x gap with no structural
   explanation. A/Bs L2Expanded / CosineExpanded / InnerProduct /
   raw jnp.matmul, then L2 with the norm terms dropped, isolating
   whether the epilogue (xn + yn - 2d + maximum) is the cost.

3. **Device time vs wall time per engine.** One search per engine under
   jax.profiler.trace; the trace directory size/presence is recorded and
   wall time re-measured, so even without opening TensorBoard the
   numbers bound how much of the 0.62 s approx-trim iteration is device
   compute.

Results bank incrementally to DIAG_RESULTS.json (same Banker discipline
as every chip suite)."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import common  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np

R = {}
_OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "DIAG_RESULTS.json"
)
# a CPU rehearsal must never clobber chip-banked rows (same rule as
# common.Banker)
import jax as _jax_cfg  # noqa: E402

if _jax_cfg.default_backend() == "cpu":
    _OUT = _OUT + ".cpu"


# merge-preload: any prior banked rows (e.g. the queue's fast-mode run
# earlier in the same window) survive this run — a later run must only
# ADD rows, never clobber chip-banked attribution (Banker discipline)
try:
    with open(_OUT) as _f:
        _prior = json.load(_f)
    if isinstance(_prior, dict):
        _prior.pop("aborted", None)  # a prior bail must not label this run
        R.update(_prior)
except (OSError, ValueError):
    pass


def _bank():
    print(json.dumps(R), flush=True)
    try:
        with open(_OUT, "w") as f:
            json.dump(R, f, indent=1)
    except OSError:
        pass



def timeit(fn, iters=10):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters


def main():
    from raft_tpu.core.config import configure_compilation_cache

    configure_compilation_cache()
    smoke = os.environ.get("RAFT_TPU_DIAG_SMOKE") == "1"
    # fast mode (the on-chip queue sets it): skip part 2 (the sqeuclidean
    # anomaly was RESOLVED in the 2026-08-01 window-2 ladder — both 1.48
    # TF/s) and part 3's mini-build + profiler trace (part 4's synthetic
    # stage decomposition answers the attribution question directly).
    fast = os.environ.get("RAFT_TPU_DIAG_FAST") == "1"
    # tail mode: ONLY the parts fast mode skipped (pairwise A/B +
    # mini-build trace) — the queue runs it after the headline banks, so
    # chip minutes aren't re-spent on the already-banked stage rows
    if os.environ.get("RAFT_TPU_DIAG_TAIL") == "1":
        _run_pairwise_ab(smoke)
        _run_engine_profile(smoke)
        return

    # ---- 1. dispatch floor ----
    x = jnp.ones((128, 128), jnp.float32)
    f1 = jax.jit(lambda a: a + 1.0)

    @jax.jit
    def f10(a):
        for _ in range(10):
            a = a + 1.0
        return a

    t_single = timeit(lambda: f1(x))
    t_chain = timeit(lambda: f10(x))
    # 10 dispatches of f1 vs 1 dispatch doing 10x the work:
    per_dispatch = max(0.0, (10 * t_single - t_chain) / 9)
    R["dispatch_single_ms"] = round(t_single * 1e3, 3)
    R["dispatch_chain10_ms"] = round(t_chain * 1e3, 3)
    R["per_dispatch_overhead_ms"] = round(per_dispatch * 1e3, 3)
    _bank()

    if fast:
        R["fast_mode_skipped"] = "pairwise_ab + engine_profile"
        _bank()
    else:
        _run_pairwise_ab(smoke)
        _run_engine_profile(smoke)
    _run_stage_decomposition(smoke)
    _run_refine_isolation(smoke)


def _run_pairwise_ab(smoke):
    # ---- 2. sqeuclidean anomaly ----
    from raft_tpu.distance import pairwise_distance
    from raft_tpu.distance.distance_types import DistanceType as D
    from raft_tpu.distance.pairwise import _dot, _row_norms_sq

    m = n = 512 if smoke else 8192
    d = 128 if smoke else 768
    kx, ky = jax.random.split(jax.random.PRNGKey(7))
    xb = jax.random.normal(kx, (m, d), jnp.bfloat16)
    yb = jax.random.normal(ky, (n, d), jnp.bfloat16)
    jax.block_until_ready((xb, yb))
    flops = 2.0 * m * n * d

    cases = {
        "matmul": jax.jit(lambda a, b: a @ b.T),
        "dot_f32acc": jax.jit(lambda a, b: _dot(a, b)),
        "inner_product": jax.jit(
            lambda a, b: pairwise_distance(a, b, metric=D.InnerProduct)
        ),
        "cosine": jax.jit(
            lambda a, b: pairwise_distance(a, b, metric=D.CosineExpanded)
        ),
        "l2_expanded": jax.jit(
            lambda a, b: pairwise_distance(a, b, metric=D.L2Expanded)
        ),
        # epilogue isolation: the L2 shape WITHOUT the norm broadcasts
        "l2_no_norms": jax.jit(
            lambda a, b: jnp.maximum(-2.0 * _dot(a, b), 0.0)
        ),
        # and the norm broadcasts WITHOUT the clamp
        "l2_no_clamp": jax.jit(
            lambda a, b: _row_norms_sq(a)[:, None]
            + _row_norms_sq(b)[None, :]
            - 2.0 * _dot(a, b)
        ),
    }
    for name, fn in cases.items():
        try:
            dt = timeit(lambda fn=fn: fn(xb, yb), iters=5)
            R[f"pw_{name}"] = {
                "ms": round(dt * 1e3, 2),
                "tflops": round(flops / dt / 1e12, 2),
            }
            print(f"pw_{name}: {dt*1e3:.1f} ms {flops/dt/1e12:.2f} TF/s", flush=True)
        except Exception as e:
            R[f"pw_{name}"] = {"error": str(e)[:160]}
            from raft_tpu.core.config import is_device_fault

            if is_device_fault(e):
                R["aborted"] = f"device fault during pw_{name}"
                _bank()
                sys.exit(4)
        _bank()


def _run_engine_profile(smoke):
    # ---- 3. device-time share of one engine iteration ----
    # Build a small-but-representative index (256k rows: ~35 s, vs the
    # ladder's 1M) and profile one approx-trim search. The profile trace
    # gives exact device time; wall time alongside bounds dispatch overhead.
    from raft_tpu.neighbors import ivf_pq

    if smoke:
        nrows, dim, nq, k, nl = 20_000, 32, 256, 10, 64
    else:
        nrows, dim, nq, k, nl = 256_000, 96, 4096, 10, 512
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    dataset = jax.random.normal(k1, (nrows, dim), jnp.float32)
    queries = jax.random.normal(k2, (nq, dim), jnp.float32)
    t0 = time.perf_counter()
    index = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=nl, pq_dim=dim // 2, kmeans_n_iters=4),
        dataset,
    )
    jax.block_until_ready(index.codes)
    R["mini_build_s"] = round(time.perf_counter() - t0, 1)
    p = ivf_pq.SearchParams(n_probes=32, score_mode="recon8_list")
    run = lambda: ivf_pq.search(p, index, queries, k)
    wall = timeit(run, iters=5)
    R["mini_search_wall_ms"] = round(wall * 1e3, 2)
    trace_dir = "/tmp/diag_trace"
    try:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(run())
        sz = sum(
            os.path.getsize(os.path.join(dp, f))
            for dp, _, fs in os.walk(trace_dir)
            for f in fs
        )
        R["trace_bytes"] = sz
        R["trace_dir"] = trace_dir
    except Exception as e:
        R["trace_error"] = str(e)[:160]
    _bank()


def _run_stage_decomposition(smoke):
    # ---- 4. stage-decomposed list-major pipeline at EXACT bench shape ----
    # Synthetic arrays (no index build): which stage owns the ~60x gap
    # between the measured 620 ms/batch and the ~10 ms roofline —
    # the qs/store gathers, the scoring matmuls, the approx trim, or the
    # regroup/merge. Stage timings are each one jit'd program, pipelined
    # 3 iters like every other measurement here.
    from raft_tpu.neighbors.probe_invert import invert_probes
    from raft_tpu.matrix.select_k import _select_k_impl

    if smoke:
        n_lists, L, rot, chunk, npb, nq4 = 16, 384, 32, 16, 4, 128
    else:
        n_lists, L, rot, chunk, npb, nq4 = 1024, 4992, 96, 128, 32, 4096
    kk = 10
    try:
        kA, kB, kC = jax.random.split(jax.random.PRNGKey(1), 3)
        recon8 = jax.random.randint(kA, (n_lists, L, rot), -127, 128, jnp.int8)
        rnorm = jnp.abs(jax.random.normal(kB, (n_lists, L), jnp.float32))
        q_rot = jax.random.normal(kC, (nq4, rot), jnp.float32)
        probes = jax.random.randint(
            jax.random.PRNGKey(2), (nq4, npb), 0, n_lists, jnp.int32
        )
        jax.block_until_ready((recon8, rnorm, q_rot, probes))

        q_pad = jnp.concatenate([q_rot, jnp.zeros((1, rot), jnp.float32)])

        # jit once; reused for both setup execution and the timed stages
        st_inv = jax.jit(lambda p: invert_probes(p, n_lists, chunk))
        st_qs = jax.jit(lambda qid_tbl: q_pad[qid_tbl])  # (ncb, chunk, rot)

        tables = st_inv(probes)
        jax.block_until_ready(tables)
        ncb = int(tables.lof.shape[0])
        qs = st_qs(tables.qid_tbl)
        jax.block_until_ready(qs)
    except Exception as e:
        R["st_setup"] = {"error": str(e)[:160]}
        from raft_tpu.core.config import is_device_fault

        if is_device_fault(e):
            R["aborted"] = "device fault during stage_decomposition setup"
            _bank()
            sys.exit(4)
        _bank()
        return

    def stage_store_gather(lof):
        # the approx engine's r8[lofb] stream, CB=8 blocks like block_fn
        def blk(lo):
            return jnp.sum(recon8[lo].astype(jnp.int32), axis=(1, 2))
        return jax.lax.map(blk, lof.reshape(-1, 8))

    def stage_score(lof, qs):
        def blk(inp):
            lo, q = inp
            rb = recon8[lo]  # (8, L, rot)
            dots = jnp.einsum(
                "cqd,csd->cqs", q.astype(jnp.bfloat16),
                rb.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
            )
            return jnp.sum(dots, axis=2)  # collapse so scores never hit HBM
        return jax.lax.map(
            blk, (lof.reshape(-1, 8), qs.reshape(-1, 8, chunk, rot))
        )

    def stage_score_trim(lof, qs):
        def blk(inp):
            lo, q = inp
            rb = recon8[lo]
            dots = jnp.einsum(
                "cqd,csd->cqs", q.astype(jnp.bfloat16),
                rb.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
            )
            scores = rnorm[lo][:, None, :] - 2.0 * dots
            return jax.lax.approx_min_k(scores, kk, recall_target=0.99)
        return jax.lax.map(
            blk, (lof.reshape(-1, 8), qs.reshape(-1, 8, chunk, rot))
        )

    def stage_score_trim_super(lof, qs):
        # round-5 structure: whole superblocks scored with ONE batched
        # einsum each — same math as stage_score_trim but
        # ~nsuper outer iterations instead of ncb/8 serialized inner scan
        # steps; the delta between the two rows IS the scan overhead the
        # 60x gap hypothesis blames
        budget = 1 << 27
        sb = min(max(1, budget // max(1, chunk * L)), int(lof.shape[0]))
        n_s = -(-int(lof.shape[0]) // sb)
        pad_b = n_s * sb - int(lof.shape[0])
        lofp = jnp.pad(lof, (0, pad_b)) if pad_b else lof
        qsp = jnp.pad(qs, ((0, pad_b), (0, 0), (0, 0))) if pad_b else qs

        def blk(inp):
            lo, q = inp  # (sb,), (sb, chunk, rot)
            rb = recon8[lo]
            dots = jnp.einsum(
                "cqd,csd->cqs", q.astype(jnp.bfloat16),
                rb.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
            )
            scores = rnorm[lo][:, None, :] - 2.0 * dots
            return jax.lax.approx_min_k(scores, kk, recall_target=0.99)
        return jax.lax.map(
            blk, (lofp.reshape(n_s, sb), qsp.reshape(n_s, sb, chunk, rot))
        )

    try:
        vals0 = jax.random.normal(jax.random.PRNGKey(3), (ncb, chunk, kk))
        slots0 = jax.random.randint(
            jax.random.PRNGKey(4), (ncb, chunk, kk), 0, L, jnp.int32
        )
        slot_rows0 = jax.random.randint(
            jax.random.PRNGKey(5), (n_lists, L), 0, 1 << 20, jnp.int32
        )
        jax.block_until_ready((vals0, slots0, slot_rows0))
    except Exception as e:
        R["st_setup"] = {"error": str(e)[:160]}
        _bank()
        return

    def stage_regroup(vals, slots, slot_rows):
        from raft_tpu.neighbors.probe_invert import regroup_merge

        return regroup_merge(
            tables, vals, slots, slot_rows, _select_k_impl, nq4, npb, kk, True
        )

    stages = {
        "st_invert": (st_inv, (probes,)),
        "st_qs_gather": (st_qs, (tables.qid_tbl,)),
        "st_store_gather": (jax.jit(stage_store_gather), (tables.lof,)),
        "st_score_nohbm": (jax.jit(stage_score), (tables.lof, qs)),
        "st_score_trim": (jax.jit(stage_score_trim), (tables.lof, qs)),
        "st_score_trim_super": (
            jax.jit(stage_score_trim_super), (tables.lof, qs)
        ),
        "st_regroup_merge": (
            jax.jit(stage_regroup), (vals0, slots0, slot_rows0)
        ),
    }
    for name, (fn, args) in stages.items():
        try:
            dt = timeit(lambda: fn(*args), iters=3)
            R[name] = {"ms": round(dt * 1e3, 2)}
            print(f"{name}: {dt*1e3:.1f} ms", flush=True)
        except Exception as e:
            R[name] = {"error": str(e)[:160]}
            from raft_tpu.core.config import is_device_fault

            if is_device_fault(e):
                R["aborted"] = f"device fault during {name}"
                _bank()
                sys.exit(4)
        _bank()
    R["st_shape"] = {"ncb": ncb, "chunk": chunk, "L": L, "rot": rot,
                     "nq": nq4, "n_probes": npb}
    _bank()


def _run_refine_isolation(smoke):
    # ---- 5. refine isolation at EXACT headline shape ----
    # The headline config is np8 REFINED: the stage decomposition above
    # covers only the PQ scan, but the 4k-shortlist exact rerank
    # (gather 4096x40 rows from the 1M dataset + distances + top-10) is
    # the other half of the 750 ms/batch. Synthetic arrays again — the
    # gather cost does not care about index contents.
    try:
        from raft_tpu.neighbors import refine as refine_fn

        key = jax.random.PRNGKey(3)
        k1, k2, k3 = jax.random.split(key, 3)
        n_full, dim_h, nq_h, k_h = 1_000_000, 96, 4096, 10
        if smoke:
            n_full, nq_h = 50_000, 256
        ds_h = jax.random.normal(k1, (n_full, dim_h), jnp.float32)
        qs_h = jax.random.normal(k2, (nq_h, dim_h), jnp.float32)
        cand_h = jax.random.randint(k3, (nq_h, 4 * k_h), 0, n_full)
        jax.block_until_ready((ds_h, qs_h, cand_h))
        # arrays as ARGUMENTS: closed-over they become compile-time
        # constants and XLA folds the whole rerank away (measured 0 ms)
        run = jax.jit(lambda a, b, c: refine_fn(a, b, c, k_h))
        jax.block_until_ready(run(ds_h, qs_h, cand_h))
        dt = timeit(lambda: run(ds_h, qs_h, cand_h), iters=3)
        R["st_refine_4k_shortlist"] = {"ms": round(dt * 1e3, 2),
                                       "n": n_full, "nq": nq_h,
                                       "cand": 4 * k_h}
        if smoke:
            # a rehearsal value must never read as the headline-shape
            # refine cost (same rule as bench.py's smoke tagging)
            R["st_refine_4k_shortlist"]["smoke"] = True
        print(f"st_refine_4k_shortlist: {dt*1e3:.1f} ms", flush=True)
    except Exception as e:
        R["st_refine_4k_shortlist"] = {"error": str(e)[:160]}
        from raft_tpu.core.config import is_device_fault

        if is_device_fault(e):
            R["aborted"] = "device fault during refine_isolation"
            _bank()
            sys.exit(4)
    _bank()


if __name__ == "__main__":
    main()

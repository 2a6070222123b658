"""Analytic cost model: FLOP/byte formulas per span, peaks, MFU.

The TPU-KNN paper (arxiv 2206.14286) frames every kernel decision in
FLOP/s-vs-peak roofline terms; ROADMAP open item 1 ("10x+ on the
pairwise-L2 hot path") is *judged* in those terms. This module is the
accounting half of that judgement: closed-form flops/bytes formulas for
the library's hot paths, registered per span name, so a span can charge
its analytic cost (`obs.span_cost(**perf.cost_for(name, ...))`) and the
report/bench layers can derive FLOP/s, B/s, and MFU against a
per-platform peak table.

Honesty rules, in order:
  - Peaks are *datasheet* numbers for real accelerators (v5e bf16/int8)
    and *nominal placeholders* for the CPU fallback — every CPU entry is
    tagged ``nominal`` and every derived MFU carries that tag through to
    the report, so a CPU rehearsal can never read as a chip roofline
    claim.
  - Formulas are models, not measurements. `xla_cost_analysis()` pulls
    XLA's own per-executable cost analysis so tests can pin the analytic
    formulas against what the compiler actually counted
    (tests/test_perf.py).
  - f32 flops are counted against the bf16 MXU peak (the achievable-rate
    configuration; f32-precision matmuls run *slower*, so the reported
    MFU is a lower bound, never an overclaim).

Pure host-side math: nothing here touches jax at module scope;
`platform_info()` reads the initialized default device.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

# -- peak table ---------------------------------------------------------

#: peaks keyed by the `device_kind` JAX reports ("cpu" for the host).
#: flops are per-chip dense peaks by compute dtype;
#: hbm_Bps is peak HBM bandwidth. "nominal" entries are bookkeeping
#: placeholders (an unknown host CPU has no datasheet) — MFU derived
#: from them is tagged and must never be read as a hardware claim.
PEAK_TABLE: Dict[str, dict] = {
    # TPU v5e (device_kind "TPU v5 lite"), Google Cloud "TPU v5e"
    # datasheet: 197 bf16 TFLOP/s, 394 int8 TOPS, 819 GB/s HBM.
    # f32 deliberately shares the bf16 peak (see module docstring).
    # "int" is the VPU integer-op ceiling (popcount/AND/shift-add — the
    # RaBitQ bit-plane scan's op class, which never touches the MXU):
    # no datasheet number exists, so it is an ARCHITECTURAL estimate —
    # 8x128 vector lanes x 4 ALU issue x ~0.94 GHz ≈ 3.9 Tops — kept
    # deliberately on the high side so int-op MFU under-reports rather
    # than flatters (the same honesty direction as f32-at-bf16-peak).
    "TPU v5 lite": {
        "peak_flops": {"bf16": 197e12, "f32": 197e12, "int8": 394e12,
                       "int": 3.9e12},
        "hbm_Bps": 819e9,
        "nominal": False,
    },
    # CPU fallback: nominal 200 GFLOP/s / 50 GB/s placeholders (a modern
    # vectorized server core's ballpark) so the arithmetic stays
    # runnable off-chip; honestly tagged. The "int" row is the same
    # NOMINAL class (vectorized popcount ballpark).
    "cpu": {
        "peak_flops": {"bf16": 200e9, "f32": 200e9, "int8": 400e9,
                       "int": 200e9},
        "hbm_Bps": 50e9,
        "nominal": True,
    },
}

_DTYPE_CANON = {
    "float32": "f32", "f32": "f32", "fp32": "f32",
    "bfloat16": "bf16", "bf16": "bf16",
    "float16": "bf16", "f16": "bf16",  # same MXU rate class
    "int8": "int8", "uint8": "int8",
    # 32-bit integer/logical VPU ops (popcount, AND, shift-add): their
    # own peak row — before this entry existed, uint32 popcount spans
    # fell to the f32 fallback and bit-plane MFU was charged against a
    # matmul peak it can never use (ISSUE 11 satellite)
    "int32": "int", "uint32": "int", "int": "int",
}

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "int8": 1, "int": 4}


def canon_dtype(dtype) -> str:
    """Normalize a dtype spelling (str, numpy/jax dtype, or scalar type
    like jnp.bfloat16) onto the peak table's keys; unknown dtypes count
    as f32 (the conservative rate)."""
    name = getattr(dtype, "name", None)
    if name is None and not isinstance(dtype, str):
        try:
            import numpy as _np

            name = _np.dtype(dtype).name
        except Exception:
            pass
    if name is None:
        name = str(dtype)
    return _DTYPE_CANON.get(name.lower(), "f32")


def dtype_bytes(dtype) -> int:
    return _DTYPE_BYTES[canon_dtype(dtype)]


def platform_info() -> dict:
    """Resolve the default device onto the peak table by the
    `device_kind` it reports. Returns a self-contained dict (platform /
    device_kind / peak_flops / hbm_Bps / nominal) that `obs.snapshot()`
    embeds, so a saved snapshot records which peaks its MFU numbers were
    computed against. An accelerator whose kind has no row raises: a
    wrong peak is worse than none."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return {"platform": "cpu", "device_kind": "cpu", **PEAK_TABLE["cpu"]}
    kind = str(dev.device_kind)
    if kind not in PEAK_TABLE:
        raise KeyError(f"no peak row for device kind {kind!r} "
                       f"({dev.platform}); add it to obs.perf.PEAK_TABLE")
    return {"platform": dev.platform, "device_kind": kind, **PEAK_TABLE[kind]}


def mfu(flops_by_dtype: Dict[str, float], seconds: float,
        info: Optional[dict] = None) -> Optional[float]:
    """Model FLOP utilization: sum over dtypes of flops_d / peak_d,
    divided by wall seconds. None when no peak covers the dtypes or the
    interval is empty — an unknown platform yields no MFU, not 0%."""
    if seconds <= 0.0 or not flops_by_dtype:
        return None
    info = info if info is not None else platform_info()
    peaks = info.get("peak_flops") or {}
    peak_seconds = 0.0
    for dt, fl in flops_by_dtype.items():
        peak = peaks.get(canon_dtype(dt))
        if not peak:
            return None
        peak_seconds += float(fl) / float(peak)
    return peak_seconds / float(seconds)


# -- analytic formulas --------------------------------------------------
#
# Every formula returns {"flops": int, "bytes": int, "dtype": str} — the
# kwargs shape `obs.span_cost(**...)` takes. flops count multiply+add as
# 2; bytes count the model's unavoidable HBM traffic (operands read once
# per use, outputs written once), not cache behavior.
#
# Composite formulas built with `_add` additionally carry
# "flops_by_dtype": each stage's flops stay attributed to the dtype/peak
# of the unit that executes them (the coarse f32 matmul, the int8 MXU
# scan, the uint32 popcount fold), so a mixed-dtype span's MFU weighs
# every component against ITS OWN peak instead of collapsing onto one —
# the two-peak weighting the integer fused engines need (an int8 scan's
# flops against the bf16 peak would double-report, and popcount ops
# against any matmul peak would be meaningless).


def _cost(flops: float, nbytes: float, dtype) -> dict:
    return {"flops": int(flops), "bytes": int(nbytes),
            "dtype": canon_dtype(dtype)}


def pairwise_l2(n: int, m: int, d: int, dtype="f32") -> dict:
    """Expanded pairwise L2: ||x||^2 + ||y||^2 - 2<x,y> over (n, d) x
    (m, d). Dominant term is the 2nmd matmul; the norm/broadcast adds
    are kept so small shapes cross-check tightly against XLA."""
    b = dtype_bytes(dtype)
    flops = 2.0 * n * m * d          # the -2 x @ y.T matmul
    flops += 2.0 * (n + m) * d       # row norms (mul + add per element)
    flops += 3.0 * n * m             # scale + two broadcast adds
    nbytes = (n * d + m * d) * b + n * m * 4.0  # f32 score matrix out
    return _cost(flops, nbytes, dtype)


def select_k(rows: int, cols: int, k: int, fused: bool = False) -> dict:
    """Top-k selection over a (rows, cols) score matrix: one compare per
    candidate (model of a single-pass partial selection) plus the
    per-row heap/sort tail. `fused=True` models the in-kernel partial
    select (ops/fused_scan.py): the candidates are consumed where they
    are produced, so the (rows, cols) score read never hits HBM — only
    the (rows, k) result does. The flops stay (the compares still
    happen on the VPU); the bytes are what fusion deletes."""
    flops = float(rows) * cols + float(rows) * k * max(_log2(cols), 1.0)
    if fused:
        nbytes = float(rows) * k * 8.0
    else:
        nbytes = float(rows) * cols * 4.0 + float(rows) * k * 8.0
    return _cost(flops, nbytes, "f32")


def knn(n: int, nq: int, d: int, k: int, dtype="f32",
        fused: bool = False) -> dict:
    """Exact brute-force kNN = full pairwise L2 + select-k. With
    `fused=True` (the fused Pallas scan) neither the score-matrix write
    of the pairwise stage nor the score-matrix read of the select stage
    is charged — the fused geometry the banked MFU must reflect."""
    pw = pairwise_l2(n, nq, d, dtype)
    if fused:
        b = dtype_bytes(dtype)
        pw = _cost(pw["flops"], (n * d + nq * d) * b, dtype)
    return _add(pw, select_k(nq, n, k, fused=fused), dtype=dtype)


def ivf_flat_scan(nq: int, n_probes: int, n_lists: int, n_rows: int,
                  dim: int, k: int, dtype="f32",
                  scanned_lists: Optional[int] = None,
                  fused: bool = False) -> dict:
    """Coarse quantizer + list scan + select. `scanned_lists` is the
    number of lists each query's scores actually stream through: the
    query-major engines touch `n_probes` lists (the default), the
    LIST-MAJOR engines stream every list and mask non-probed scores —
    pass `scanned_lists=n_lists` there, or the model undercounts the
    real work by n_lists/n_probes. `n_rows` should be the PADDED slot
    count (n_lists * max_list) when known — pad slots are scored too.
    `fused=True` (the fused Pallas engine) drops the score-matrix
    bytes: the per-chunk scores fold to the candidate buffer in VMEM
    (the scan's own operand-stream bytes stay — they are the store
    read fusion cannot delete)."""
    rows = _probed_rows(n_rows, n_lists,
                        n_probes if scanned_lists is None else scanned_lists)
    coarse = pairwise_l2(nq, n_lists, dim, dtype)
    scan = _cost(2.0 * nq * rows * dim,
                 nq * rows * dim * dtype_bytes(dtype), dtype)
    return _add(coarse, scan, select_k(nq, rows, k, fused=fused),
                dtype=dtype)


def ivf_pq_scan(nq: int, n_probes: int, n_lists: int, n_rows: int,
                dim: int, pq_dim: int, k: int, dtype="bf16",
                scanned_lists: Optional[int] = None,
                fused: bool = False) -> dict:
    """Coarse quantizer + PQ code scoring (reconstruct-and-dot model of
    the recon engines: one fused multiply-add per reconstructed
    dimension) + select. `scanned_lists`/`n_rows` follow the
    `ivf_flat_scan` convention (list-major engines stream EVERY padded
    list). Bytes are dominated by the per-(query, list) code reads —
    1 byte per pq_dim — which is exactly the wire the quantization
    exists to shrink. `fused=True` (the pallas/fused trims) drops the
    score-matrix bytes from the select stage, like `ivf_flat_scan`."""
    rows = _probed_rows(n_rows, n_lists,
                        n_probes if scanned_lists is None else scanned_lists)
    coarse = pairwise_l2(nq, n_lists, dim, "f32")
    scan = _cost(2.0 * nq * rows * dim, nq * rows * float(pq_dim), dtype)
    return _add(coarse, scan, select_k(nq, rows, k, fused=fused),
                dtype=dtype)


def rabitq_scan(nq: int, n_probes: int, n_lists: int, n_rows: int,
                dim: int, k: int, query_bits: int = 8,
                rerank_mult: int = 0) -> dict:
    """Binary-code integer scan: per (query, candidate) one AND+popcount
    per 32-bit word per query bit plane — charged as "int" ops (uint32
    VPU popcount/logical class, its own peak row: these ops never touch
    the MXU, so weighing them against a matmul peak would be
    meaningless), plus the exact rerank of rerank_mult*k candidates when
    enabled."""
    rows = _probed_rows(n_rows, n_lists, n_probes)
    words = (int(dim) + 31) // 32
    bits = max(1, int(query_bits))
    coarse = pairwise_l2(nq, n_lists, dim, "f32")
    # AND + popcount + shift-add per (pair, word, plane): 2 ops modeled,
    # the multiply+add convention applied to the integer unit
    # the packed codes, and the (nq, probes, rows, bits, W) intersection
    # tensor the scan materializes in blocks — its dominant write-out
    scan_bytes = nq * rows * words * 4.0 + nq * rows * bits * words * 4.0
    scan = _cost(2.0 * nq * rows * words * bits, scan_bytes, "int")
    parts = [coarse, scan, select_k(nq, rows, max(k, rerank_mult * k or k))]
    if rerank_mult:
        # exact rerank: EVERY query gathers its own distinct
        # rerank_mult*k-row shortlist from the dataset, so the bytes
        # term scales with nq (operands read once per use)
        cand = float(rerank_mult) * k
        parts.append(_cost(2.0 * nq * cand * dim + 3.0 * nq * cand,
                           nq * cand * dim * 4.0 + nq * dim * 4.0, "f32"))
    return _add(*parts, dtype="int")


def refine_rerank(nq: int, n_cand: int, dim: int, k: int, dtype="f32",
                  fused: bool = False) -> dict:
    """Exact re-rank of per-query candidate sets (neighbors/refine):
    every query gathers its own n_cand-row shortlist, one batched
    matvec scores it, select keeps k. `fused=True` (the fused rerank
    kernel) drops the (nq, n_cand) score round-trip from the select
    stage — the gathered candidate stream stays."""
    b = dtype_bytes(dtype)
    flops = 2.0 * nq * n_cand * dim + 3.0 * nq * n_cand
    nbytes = nq * n_cand * dim * b + nq * dim * b
    return _add(_cost(flops, nbytes, dtype),
                select_k(nq, n_cand, k, fused=fused), dtype=dtype)


def kmeans_step(n: int, d: int, n_clusters: int, iters: int = 1,
                dtype="f32") -> dict:
    """One Lloyd iteration: assignment (pairwise L2 vs centers) plus the
    weighted center update (2nd flops)."""
    one = _add(pairwise_l2(n, n_clusters, d, dtype),
               _cost(2.0 * n * d, n * d * dtype_bytes(dtype), dtype),
               dtype=dtype)
    return _cost(one["flops"] * max(1, int(iters)),
                 one["bytes"] * max(1, int(iters)), dtype)


#: per-rank wire-traffic factor by collective op (ring algorithms),
#: RELATIVE TO THE PAYLOAD obs.collective counts for that op — which is
#: the op's per-rank INPUT: the full buffer for allreduce/reducescatter/
#: bcast/barrier, but only the local SHARD for allgather (a ring
#: allgather forwards every other rank's shard through each rank, so
#: its factor is (w-1), not (w-1)/w). The EQuARX-style savings claim
#: (ROADMAP item 3) will be judged against exactly these counters.
WIRE_FACTORS: Dict[str, Callable[[int], float]] = {
    "allreduce": lambda w: 2.0 * (w - 1) / w,
    "allgather": lambda w: float(w - 1),
    "reducescatter": lambda w: float(w - 1) / w,
    "bcast": lambda w: float(w - 1) / w,
    "barrier": lambda w: 2.0 * (w - 1) / w,
    "device_sendrecv": lambda w: 1.0,
    "shift": lambda w: 1.0,
    "device_multicast_sendrecv": lambda w: 1.0,
}


def collective_wire_bytes(op: str, nbytes: int, world: int) -> int:
    """Modeled per-rank bytes on the wire for one collective of per-rank
    payload `nbytes` over `world` ranks (0 for world < 2 — a
    single-rank collective moves nothing)."""
    if world is None or world < 2:
        return 0
    factor = WIRE_FACTORS.get(op, lambda w: float(w - 1) / w)
    return int(float(nbytes) * factor(int(world)))


def _probed_rows(n_rows: int, n_lists: int, n_probes) -> float:
    # n_probes may be FRACTIONAL: adaptive probing charges the actual
    # per-query scanned-list mean, not the worst-case integer knob
    per_list = (float(n_rows) / max(1, int(n_lists)))
    return per_list * min(float(n_probes), float(int(n_lists)))


def _log2(x: float) -> float:
    import math

    return math.log2(max(2.0, float(x)))


def _add(*costs: dict, dtype=None) -> dict:
    flops = sum(c["flops"] for c in costs)
    nbytes = sum(c["bytes"] for c in costs)
    by: Dict[str, int] = {}
    for c in costs:
        sub = c.get("flops_by_dtype") or {c["dtype"]: c["flops"]}
        for dt, fl in sub.items():
            if fl:
                by[dt] = by.get(dt, 0) + int(fl)
    out = _cost(flops, nbytes, dtype if dtype is not None
                else costs[0]["dtype"])
    out["flops_by_dtype"] = by
    return out


# -- the per-span registry ---------------------------------------------

#: span name -> formula. Instrumented entry points resolve their span's
#: formula through here (`cost_for`), so "which spans have a cost
#: model" is one reviewable table, and the report can distinguish
#: "span with no model" from "model says zero".
SPAN_COST_MODEL: Dict[str, Callable[..., dict]] = {
    "neighbors.brute_force.knn": knn,
    "neighbors.ivf_flat.search": ivf_flat_scan,
    "neighbors.ivf_pq.search": ivf_pq_scan,
    "neighbors.refine": refine_rerank,
    "neighbors.ivf_rabitq.search": rabitq_scan,
    "mnmg.knn": knn,
    "mnmg.kmeans_fit": kmeans_step,
    "mnmg.ivf_flat_search": ivf_flat_scan,
    "mnmg.ivf_pq_search": ivf_pq_scan,
    "mnmg.ivf_rabitq_search": rabitq_scan,
}


def register(span_name: str, fn: Callable[..., dict]) -> None:
    """Register (or override) the cost formula for a span name."""
    SPAN_COST_MODEL[str(span_name)] = fn


def cost_for(span_name: str, **shape) -> dict:
    """Evaluate the registered formula for `span_name` with the given
    shape kwargs. KeyError for unregistered spans — a typo'd span name
    must fail loudly in the instrumented code path's tests, not
    silently charge nothing."""
    return SPAN_COST_MODEL[span_name](**shape)


# -- XLA cross-check ----------------------------------------------------

def xla_cost_analysis(fn, *args, **kwargs) -> Optional[dict]:
    """Compile `fn(*args, **kwargs)` and return XLA's own
    {"flops", "bytes"} for the executable, or None when the backend
    doesn't expose cost analysis. This is the ground truth the analytic
    formulas are pinned against (tests/test_perf.py)."""
    import jax

    try:
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes"] = float(ca["bytes accessed"])
    return out or None

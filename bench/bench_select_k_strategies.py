"""select_k strategy race: lax.top_k vs two-phase vs approx_max_k vs
the Pallas counting-select engine — plus the OPERAND-level race of the
fused distance+select-k kernel vs the materializing two-phase scan
(`matrix.scan_select_k`), the measurement behind the tuned
`select_k_strategy` key, and (ISSUE 11) the INTEGER-scan races behind
`select_k_strategy_int8` (exact fused int8 PQ-recon trim vs the XLA
int8 trim it would replace).

Reference parity: matrix/detail/select_k.cuh:67-88 picks warpsort vs radix
from an empirically-derived (batch, len, k) heuristic measured with
cpp/bench/matrix/select_k.cu. This is the TPU-side measurement that sets
`_select_k_impl`'s dispatch thresholds (matrix/select_k.py): run on the
chip, read the per-shape winners, and encode them with a citation to the
recorded numbers.

Grid: the reference bench's (batch, len, k) ladder plus the IVF shapes
this library actually funnels through select_k (coarse probe selection,
per-chunk trims, final merges). approx entries are flagged: approx_max_k
at recall_target=0.99 is not exact, so it can only back the engines that
already budget for an approximate trim (the list-major chunk trim), never
the public matrix.select_k contract. The scan race is exact on both
sides: the fused kernel's only deviation is ranking the bf16-rounded
operands (the compute_dtype=bfloat16 class), so `--apply` may promote
it as the auto strategy on chip data alone.
"""

import json
import sys, os

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from common import run_case
from raft_tpu.matrix.select_k import _two_phase_largest


def main(smoke: bool = False):
    # cache enablement rides run_case() in common.py
    from common import Banker

    bank = Banker(
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "SELECT_K_RACE_RESULTS.json"),
        {"smoke": smoke},
    )
    rng = np.random.default_rng(0)
    shapes = [
        # reference select_k.cu ladder
        (64, 1 << 14, 64),
        (64, 1 << 17, 128),
        (128, 1 << 20, 256),
        (1024, 1 << 14, 64),
        # IVF funnel shapes: coarse (nq x n_lists, small k), chunk trim
        # (chunk x max_list), final merge (nq x n_probes*k)
        (4096, 1024, 32),
        (128, 4096, 10),
        (4096, 320, 10),
        # brute-force per-tile select at headline geometry (the BF scan
        # calls _select_k_impl once per 32768-row tile; after the bf16
        # matmul flip this select is the scan's probable bottleneck)
        (4096, 1 << 15, 10),
    ]
    if smoke:  # CPU correctness pass: tiny grid, the chip run uses the full one
        shapes = [(16, 1 << 15, 32), (64, 512, 10)]
    from raft_tpu.matrix import select_k as select_k_public
    from raft_tpu.ops.select_counting import fits_counting

    interp = jax.default_backend() == "cpu"  # interpret too slow at scale
    strategies = {
        "topk": lambda v, k: lax.top_k(v, k),
        "twophase": lambda v, k: _two_phase_largest(v, k),
        "approx99": lambda v, k: lax.approx_max_k(v, k, recall_target=0.99),
        # the real public counting path (select_k owns negation/interp/dtype
        # handling — racing a private reimplementation would drift)
        "counting": lambda v, k: select_k_public(
            v, k, select_min=False, strategy="counting"
        ),
    }
    winners = {}
    for batch, length, k in shapes:
        vals = jnp.asarray(rng.random((batch, length), dtype=np.float32))
        best = None
        raced = []
        timings = {}
        for name, fn in strategies.items():
            if name == "twophase" and length < 2 * (1 << 14):
                continue  # needs >1 chunk to differ from topk
            # the wrapper pads rows to a lane multiple itself, so the fit
            # check must see the padded length or non-x128 shapes (the IVF
            # final-merge entry) silently lose their counting measurement
            padded_len = length + (-length) % 128
            if name == "counting" and not fits_counting(batch, padded_len, k):
                continue  # row exceeds the kernel's VMEM envelope
            if name == "counting" and interp and length > 1 << 15:
                continue  # interpret mode is too slow at large L
            if name == "counting":
                # select_k jits internally and validates in python; time it
                # as users call it rather than through an outer jit
                jfn = lambda v, fn=fn, k=k: fn(v, k)
            else:
                jfn = jax.jit(lambda v, fn=fn, k=k: fn(v, k))
            rec = run_case(
                "select_k_strategy",
                f"{name}_{batch}x{length}_k{k}",
                lambda v=vals, jfn=jfn: jfn(v),
                items=float(batch * length),
                unit="elems/s",
            )
            bank.record["rows"].append(rec)
            bank.flush()
            raced.append(name)
            timings[name] = rec["value"]
            if best is None or rec["value"] > best[1]:
                best = (name, rec["value"])
        bank.add({
            "suite": "select_k_strategy",
            "case": f"winner_{batch}x{length}_k{k}",
            "winner": best[0],
            "value": best[1],
            "unit": "elems/s",
        })
        winners[(batch, length, k)] = (best[0], tuple(raced), timings)

    # -- operand-level race: fused scan+select vs two-phase ------------
    # (nq, n, d, k): the brute-force headline geometry shrunk to the
    # backend, plus a rerank-shaped small-n entry. Exact on both sides;
    # the fused kernel scores bf16 operands (documented rounding class).
    scan_shapes = [(4096, 1 << 15, 96, 10), (1024, 4096, 96, 100)]
    if smoke:
        scan_shapes = [(128, 4096, 32, 10)]
    from raft_tpu.matrix import scan_select_k
    from raft_tpu.ops.fused_scan import fits_fused

    scan_winners = {}
    for nq, n, d, k in scan_shapes:
        if interp and n * nq > 1 << 20:
            continue  # interpret-mode kernel too slow at scale
        qv = jnp.asarray(rng.random((nq, d), dtype=np.float32))
        dv = jnp.asarray(rng.random((n, d), dtype=np.float32))
        best = None
        timings = {}
        for name in ("two_phase", "fused"):
            if name == "fused" and not fits_fused(nq, n, d, k):
                continue
            rec = run_case(
                "select_k_strategy",
                f"scan_{name}_{nq}x{n}x{d}_k{k}",
                lambda name=name: scan_select_k(qv, dv, k, strategy=name),
                items=float(nq),
                unit="qps",
            )
            bank.record["rows"].append(rec)
            bank.flush()
            timings[name] = rec["value"]
            if best is None or rec["value"] > best[1]:
                best = (name, rec["value"])
        if best is None:
            continue
        bank.add({
            "suite": "select_k_strategy",
            "case": f"scan_winner_{nq}x{n}x{d}_k{k}",
            "winner": best[0],
            "value": best[1],
            "unit": "qps",
        })
        scan_winners[(nq, n, d, k)] = (best[0], timings)

    # -- integer-scan races (ISSUE 11) ---------------------------------
    # (a) int8 PQ-recon trim: the exact fused int8 scan (dispatch
    #     strategy "fused_int8") vs the XLA int8 trim that
    #     trim_engine="auto" runs otherwise — both score on the int8 MXU
    #     path with identical quantization and both trims are exact, so
    #     the race is purely the trim geometry; a fused win flips the
    #     tuned `select_k_strategy_int8` key.
    from raft_tpu.neighbors import ivf_pq

    int_winners = {}
    if smoke or interp:
        nq_i, n_i, d_i, nl_i, probes_i, k_i = 64, 4000, 32, 16, 8, 10
    else:
        # bench geometry: the 1Mx96 headline shrunk to a race-friendly
        # 100K (build cost, not scan cost, is the bound here)
        nq_i, n_i, d_i, nl_i, probes_i, k_i = 4096, 100_000, 96, 1024, 32, 10
    data_i = jnp.asarray(rng.random((n_i, d_i), dtype=np.float32))
    q_i = jnp.asarray(rng.random((nq_i, d_i), dtype=np.float32))

    pq_idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=nl_i, kmeans_n_iters=4, pq_dim=d_i // 2),
        data_i,
    )
    pq_entries = {
        "int8_fused": ivf_pq.SearchParams(
            n_probes=probes_i, trim_engine="fused", score_dtype="int8"),
        "int8_xla": ivf_pq.SearchParams(
            n_probes=probes_i, score_mode="recon8_list",
            trim_engine="approx", score_dtype="int8"),
    }
    best = None
    timings = {}
    for name, sp in pq_entries.items():
        rec = run_case(
            "select_k_strategy",
            f"pqint8_{name}_{nq_i}x{n_i}x{d_i}_k{k_i}",
            lambda sp=sp: ivf_pq.search(sp, pq_idx, q_i, k_i),
            items=float(nq_i),
            unit="qps",
        )
        bank.record["rows"].append(rec)
        bank.flush()
        timings[name] = rec["value"]
        if best is None or rec["value"] > best[1]:
            best = (name, rec["value"])
    bank.add({
        "suite": "select_k_strategy",
        "case": f"pqint8_winner_{nq_i}x{n_i}x{d_i}_k{k_i}",
        "winner": best[0], "value": best[1], "unit": "qps",
    })
    int_winners["pq_int8"] = (best[0], timings)

    return winners, scan_winners, int_winners


def apply_winners(winners: dict, scan_winners: dict = None,
                  int_winners: dict = None, smoke: bool = False) -> None:
    """Turn the per-shape race results into tuned defaults (merge
    semantics). The chunked-dispatch threshold comes from the DIRECT
    topk-vs-twophase timings — the overall shape winner can be a third
    strategy, which would otherwise mask where the crossover sits: the
    smallest length where twophase beat topk head-to-head at every shape
    of that length, provided topk did not beat twophase at any longer
    length (a non-monotone grid means there is no clean crossover to
    encode). Counting winning EVERY shape it actually raced in promotes
    it as the auto strategy (it is exact, so the flip is purely
    performance). Refused for smoke/CPU runs: those measurements reflect
    interpret-mode/host behavior, not the chip the defaults serve."""
    from raft_tpu.core import tuned

    if smoke or jax.default_backend() == "cpu":
        print(json.dumps({"applied": None,
                          "detail": "smoke/CPU run; tuned file left untouched"}))
        return
    updates = {"hints": {
        f"select_k_{b}x{l}_k{k}": w for (b, l, k), (w, _, _) in winners.items()
    }}
    pair_verdicts = {}  # length -> [twophase beat topk, per shape]
    for (b, l, k), (_, _, timings) in winners.items():
        if "topk" in timings and "twophase" in timings:
            pair_verdicts.setdefault(l, []).append(
                timings["twophase"] > timings["topk"]
            )
    twophase_lens = sorted(l for l, f in pair_verdicts.items() if all(f))
    topk_lens = [l for l, f in pair_verdicts.items() if not all(f)]
    if twophase_lens and not any(l > twophase_lens[0] for l in topk_lens):
        updates["select_k_chunk_threshold"] = max(1024, twophase_lens[0] - 1)
    entered = {(b, l, k): w for (b, l, k), (w, raced, _) in winners.items()
               if "counting" in raced}
    if entered and all(w == "counting" for w in entered.values()):
        updates["select_k_auto_strategy"] = "counting"
    # the fused scan winning EVERY operand-level shape it entered
    # promotes it as the tuned select_k_strategy (matrix.scan_select_k
    # auto + knn/refine/ivf auto engines all consult this one key); it
    # ranks bf16-rounded operands, the same measured-acceptable class as
    # the bf16 matmul flip, so a clean sweep on chip data flips it
    if scan_winners:
        updates["hints"] = {**updates.get("hints", {}), **{
            f"scan_select_k_{nq}x{n}x{d}_k{k}": w
            for (nq, n, d, k), (w, _) in scan_winners.items()
        }}
        if all(w == "fused" for w, _ in scan_winners.values()):
            updates["select_k_strategy"] = "fused"
    # the int8 trim's key flips on its own race. Chip data only (the
    # smoke/CPU refusal above).
    if int_winners:
        updates["hints"] = {**updates.get("hints", {}), **{
            f"int_scan_{kind}": w for kind, (w, _) in int_winners.items()
        }}
        if int_winners.get("pq_int8", (None,))[0] == "int8_fused":
            updates["select_k_strategy_int8"] = "fused_int8"
    tuned.merge(updates)
    print(json.dumps({"applied": tuned.path(),
                      "keys": [k for k in updates if k != "hints"]}))


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    w, sw, iw = main(smoke=smoke)
    if "--apply" in sys.argv:
        apply_winners(w or {}, sw or {}, iw or {}, smoke=smoke)

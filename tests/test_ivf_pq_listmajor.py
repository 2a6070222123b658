"""IVF-PQ list-major search: the engine every batch cell runs
(`score_mode="recon8_list"`), its exact trim, its numerics rule and the
score-mode choice. Kept out of the slow tier so every run guards them."""

import numpy as np
import pytest

from raft_tpu.neighbors import ivf_pq
from raft_tpu.random import make_blobs

from _ivf_pq_data import dataset, index16, recall, truth10  # noqa: F401


def test_recon8_listmajor(dataset, truth10, index16):
    """List-major engine scores the same int8 reconstructions as the
    query-major recon8 engine — results must agree (modulo top-k ties) and
    pass the same recall floor."""
    data, queries = dataset
    index = index16
    i_qm = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8"), index, queries, 10
    )[1]
    d_lm, i_lm = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )
    i_qm, i_lm = np.asarray(i_qm), np.asarray(i_lm)
    overlap = np.mean(
        [len(set(i_qm[r]) & set(i_lm[r])) / 10 for r in range(len(i_qm))]
    )
    assert overlap >= 0.95, f"engine disagreement: overlap {overlap}"
    assert recall(i_lm, truth10) >= recall(i_qm, truth10) - 0.02
    assert np.all(np.diff(np.asarray(d_lm), axis=1) >= -1e-4)


def test_recon8_listmajor_int8_queries(dataset, truth10, index16):
    """score_dtype="int8" (symmetric int8 x int8 scoring) must track the
    bf16 list-major engine: the extra query-side quantization may shift
    near-tie candidates but not the recalled set materially."""
    data, queries = dataset
    index = index16
    i_bf = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )[1]
    d_i8, i_i8 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list", score_dtype="int8"),
        index, queries, 10,
    )
    i_bf, i_i8 = np.asarray(i_bf), np.asarray(i_i8)
    overlap = np.mean(
        [len(set(i_bf[r]) & set(i_i8[r])) / 10 for r in range(len(i_bf))]
    )
    assert overlap >= 0.9, f"int8 engine diverged: overlap {overlap}"
    assert recall(i_i8, truth10) >= recall(i_bf, truth10) - 0.03
    assert np.all(np.diff(np.asarray(d_i8), axis=1) >= -1e-4)


def test_recon8_listmajor_bf16_trim(dataset, truth10, index16):
    """internal_distance_dtype="bfloat16" trims the list-major engine in
    bf16 — near-tie ranking noise only; the recalled set must track f32."""
    data, queries = dataset
    index = index16
    i_f32 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"), index, queries, 10
    )[1]
    d_bf, i_bf = ivf_pq.search(
        ivf_pq.SearchParams(
            n_probes=16, score_mode="recon8_list", internal_distance_dtype="bfloat16"
        ),
        index, queries, 10,
    )
    assert np.asarray(d_bf).dtype == np.float32  # returned distances stay f32
    i_f32, i_bf = np.asarray(i_f32), np.asarray(i_bf)
    overlap = np.mean(
        [len(set(i_f32[r]) & set(i_bf[r])) / 10 for r in range(len(i_f32))]
    )
    assert overlap >= 0.9, f"bf16 trim diverged: overlap {overlap}"
    assert recall(i_bf, truth10) >= recall(i_f32, truth10) - 0.03


def test_internal_distance_dtype_auto_resolves_f32_off_tpu(
        dataset, index16, monkeypatch, tmp_path):
    """The "auto" default resolves by a rule in code: exact f32 trim on
    non-TPU backends, bf16 trim scores on the TPU — with no tuned file
    present — so the default-params result is bit-identical to the
    explicit dtype the rule names."""
    import jax

    from raft_tpu.core import tuned

    data, queries = dataset
    d_auto, i_auto = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"),
        index16, queries, 10,
    )
    d_f32, i_f32 = ivf_pq.search(
        ivf_pq.SearchParams(
            n_probes=16, score_mode="recon8_list",
            internal_distance_dtype="float32",
        ),
        index16, queries, 10,
    )
    np.testing.assert_array_equal(np.asarray(i_auto), np.asarray(i_f32))
    np.testing.assert_array_equal(np.asarray(d_auto), np.asarray(d_f32))
    with pytest.raises(ValueError, match="internal_distance_dtype"):
        ivf_pq.search(
            ivf_pq.SearchParams(n_probes=16, internal_distance_dtype="fp8"),
            index16, queries, 10,
        )
    # the chip's rule, off the chip: the backend reads "tpu" and no tuned
    # file exists; the engine still runs on this process's CPU devices
    monkeypatch.setattr(tuned, "_PATH", str(tmp_path / "absent.json"))
    tuned.reload()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        d_tpu, i_tpu = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list"),
            index16, queries, 10,
        )
        d_bf, i_bf = ivf_pq.search(
            ivf_pq.SearchParams(
                n_probes=16, score_mode="recon8_list",
                internal_distance_dtype="bfloat16",
            ),
            index16, queries, 10,
        )
    finally:
        monkeypatch.undo()
        tuned.reload()
    np.testing.assert_array_equal(np.asarray(i_tpu), np.asarray(i_bf))
    np.testing.assert_array_equal(np.asarray(d_tpu), np.asarray(d_bf))
    assert not np.array_equal(np.asarray(d_tpu), np.asarray(d_f32))


def test_auto_score_mode(dataset, truth10, index16):
    """auto picks an engine by batch duplication factor; both regimes work."""
    data, queries = dataset
    index = index16
    # 80 queries * 16 probes / 32 lists = 40x duplication -> list-major
    i_auto = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="auto"), index, queries, 10
    )[1]
    i_lut = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="lut"), index, queries, 10
    )[1]
    assert recall(i_auto, truth10) >= recall(i_lut, truth10) - 0.03
    # single query -> query-major lut
    d, i = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, score_mode="auto"), index, queries[:1], 10
    )
    assert np.asarray(i).shape == (1, 10)


def test_trim_engine_names(dataset, index16):
    """trim_engine takes "auto", "approx", "exact" and "fused"; any other
    name, the retired "pallas" bin trim included, raises."""
    _, queries = dataset
    for trim in ("pallas", "radix"):
        with pytest.raises(ValueError, match="trim_engine"):
            ivf_pq.search(
                ivf_pq.SearchParams(n_probes=16, score_mode="recon8_list",
                                    trim_engine=trim),
                index16, queries, 10,
            )


def test_default_params_route_to_measured_engine(monkeypatch):
    """VERDICT r4 #5: a default-constructed SearchParams must land on the
    measured winner, never the device-faulting lut engine, on TPU."""
    p = ivf_pq.SearchParams()
    assert p.score_mode == "auto"
    # TPU resolution: small-dup batches fall back to the gather-free
    # recon8 engine, large-dup to recon8_list; NEVER lut
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ivf_pq._resolve_score_mode(p, nq=1, n_probes=4, n_lists=64) == "recon8"
    assert (
        ivf_pq._resolve_score_mode(p, nq=4096, n_probes=32, n_lists=64)
        == "recon8_list"
    )
    # an int8, exact or fused request pins the only engine honoring it,
    # whatever the batch
    for kw in ({"score_dtype": "int8"}, {"trim_engine": "exact"},
               {"trim_engine": "fused"}):
        assert ivf_pq._resolve_score_mode(
            ivf_pq.SearchParams(**kw), nq=1, n_probes=4, n_lists=64
        ) == "recon8_list"
    # CPU keeps the classic small-batch lut (no fault class there)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ivf_pq._resolve_score_mode(p, nq=1, n_probes=4, n_lists=64) == "lut"


@pytest.fixture(scope="module")
def trim_data():
    """3,000 rows in 64 lists (about 47 rows a list), so a probed list
    can hold fewer rows than k = 40."""
    data, _ = make_blobs(3000, 32, n_clusters=24, cluster_std=2.0, seed=41)
    q, _ = make_blobs(50, 32, n_clusters=24, cluster_std=2.0, seed=42)
    keep = np.random.default_rng(43).random(3000) < 0.15
    return np.asarray(data), np.asarray(q), keep


@pytest.fixture(scope="module")
def trim_pq(trim_data):
    return ivf_pq.build(ivf_pq.IndexParams(n_lists=64, pq_dim=16),
                        trim_data[0])


@pytest.fixture(scope="module")
def trim_flat(trim_data):
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.build(ivf_flat.IndexParams(n_lists=64), trim_data[0])


def _spy_trim(monkeypatch, jitted):
    """Record, on the host, what the list-major trim sees: the pair
    tables, the slot table and every scored block. `jitted` is cleared so
    the next call traces through the spy."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors import probe_invert

    rec = {"blocks": []}
    orig = probe_invert.score_and_select

    def spy(tables, block_fn, slot_rows, *args, **kw):
        def block(inp):
            s = block_fn(inp)
            jax.debug.callback(
                lambda *a: rec["blocks"].append([np.asarray(x) for x in a]),
                inp[0], inp[1], s)
            return s

        pv = (tables.pair_valid if tables.pair_valid is not None
              else jnp.ones(tables.g0.shape, bool))
        jax.debug.callback(
            lambda *a: rec.update(zip(("g0", "s0", "lof", "qid", "rows",
                                       "pv"),
                                      [np.asarray(x) for x in a])),
            tables.g0, tables.s0, tables.lof, tables.qid_tbl, slot_rows, pv)
        return orig(tables, block, slot_rows, *args, **kw)

    monkeypatch.setattr(probe_invert, "score_and_select", spy)
    jitted.clear_cache()
    return rec


def _trim_reference(rec, nq, n_probes, k, source_ids):
    """Each (query, probed list)'s exact top-kk by (score, slot), merged
    per query by (score, probe rank): the list-major contract in NumPy.
    A budget-masked pair gives kk candidates of score inf and id -1.
    Also returns how many live pairs had fewer than kk real candidates."""
    n_pad = len(rec["g0"]) // n_probes  # the pad id: the slice's size
    rows_of = {}
    for lofs, qids, s in rec["blocks"]:
        for c, r in zip(*np.nonzero(qids != n_pad)):
            rows_of[(int(lofs[c]), int(qids[c, r]))] = s[c, r].astype(
                np.float32)
    max_list = rec["rows"].shape[1]
    kk = min(k, max_list)
    slots = np.arange(max_list)
    vals, ids, short = [], [], 0
    for q in range(nq):
        cv, cr = [], []
        for p in range(n_probes):
            if not rec["pv"][q * n_probes + p]:
                cv.append(np.full(kk, np.inf, np.float32))
                cr.append(np.full(kk, -1))
                continue
            c, r = rec["g0"][q * n_probes + p], rec["s0"][q * n_probes + p]
            lst = int(rec["lof"][c])
            assert rec["qid"][c, r] == q
            sc = rows_of[(lst, q)]
            short += int(np.isfinite(sc).sum() < kk)
            top = np.lexsort((slots, sc))[:kk]
            cv.append(sc[top])
            cr.append(rec["rows"][lst][top])
        cv, cr = np.concatenate(cv), np.concatenate(cr)
        best = np.argsort(cv, kind="stable")[:k]
        vals.append(cv[best])
        ids.append(np.where(cr[best] >= 0,
                            source_ids[np.maximum(cr[best], 0)], -1))
    return np.stack(vals), np.stack(ids), short


#: the IVF-PQ trim scores: bf16 or f32 trim over bf16 MXU dots, or int8
#: query rows against the int8 codes (an f32 trim)
SCORES = {
    "bf16": {"internal_distance_dtype": "bfloat16"},
    "f32": {"internal_distance_dtype": "float32"},
    "int8": {"internal_distance_dtype": "float32", "score_dtype": "int8"},
}

TRIM_CASES = {
    # name: (index, trim scores, k, prefilter or adaptive probe
    # budgets); "pq16" is the shared 20,000-row index, on which recall
    # is checked besides
    "pq-bf16-k40": ("pq", "bf16", 40, None),
    "pq-bf16-k10-prefilter": ("pq", "bf16", 10, "prefilter"),
    "pq-f32-k40": ("pq", "f32", 40, None),
    "pq-f32-k10-prefilter": ("pq", "f32", 10, "prefilter"),
    "pq16-recall": ("pq16", "f32", 10, None),
    "flat-k40": ("flat", "f32", 40, None),
    "flat-k10-prefilter": ("flat", "f32", 10, "prefilter"),
    "pq-f32-k40-budget": ("pq", "f32", 40, "budget"),
    "flat-k40-budget": ("flat", "f32", 40, "budget"),
    "pq-int8-k40": ("pq", "int8", 40, None),
    "pq-int8-k10-prefilter": ("pq", "int8", 10, "prefilter"),
    "pq-int8-k40-budget": ("pq", "int8", 40, "budget"),
}


@pytest.mark.parametrize("case", list(TRIM_CASES))
def test_listmajor_trim_is_exact(case, trim_data, trim_pq, trim_flat,
                                 dataset, truth10, index16, monkeypatch):
    """The list-major trim keeps each (query, probed list)'s exact top-kk,
    ties to the lower slot, and the merge then takes the best k: the
    answers equal a NumPy reference built from the engine's own scores,
    including lists with fewer than kk live rows and pairs dropped by
    adaptive probe budgets. Every IVF-PQ trim_engine name that runs the
    XLA trim returns the same bits, and so does a fixed 128-pair chunk
    in place of the engine's `list_chunk` width."""
    from raft_tpu.neighbors import ivf_flat, probe_invert

    which, scores, k, extra = TRIM_CASES[case]
    _, queries, keep = trim_data
    filtered = extra == "prefilter"
    prefilter = keep if filtered else None
    budget = ({"adaptive": True, "budget_tau": 0.5} if extra == "budget"
              else {})
    index = {"pq": trim_pq, "pq16": index16, "flat": trim_flat}[which]
    n_probes = 8
    if which == "pq16":
        queries, n_probes = dataset[1], 16
    if which == "flat":
        jitted = ivf_flat._search_impl_listmajor

        def run(trim):
            return ivf_flat.search(
                ivf_flat.SearchParams(n_probes=n_probes, engine="list",
                                      **budget),
                index, queries, k, prefilter=prefilter)
    else:
        jitted = ivf_pq._search_impl_recon8_listmajor

        def run(trim):
            return ivf_pq.search(
                ivf_pq.SearchParams(n_probes=n_probes,
                                    score_mode="recon8_list",
                                    trim_engine=trim, **SCORES[scores],
                                    **budget),
                index, queries, k, prefilter=prefilter)

        d0, i0 = run("approx")
        for trim in ("auto", "exact"):
            d, i = run(trim)
            np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
            np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))
        monkeypatch.setattr(probe_invert, "list_chunk", lambda *a: 128)
        jitted.clear_cache()
        try:
            d, i = run("approx")
        finally:
            monkeypatch.undo()
            jitted.clear_cache()
        np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))

    rec = _spy_trim(monkeypatch, jitted)
    try:
        d, i = (np.asarray(x) for x in run("auto"))
    finally:
        monkeypatch.undo()
        jitted.clear_cache()
    # IVF-PQ's XLA trim takes the rule's chunk (the 256-query slice:
    # 32 pairs at 8 probes over 64 lists, 128 for "pq16"); IVF-Flat 128
    n_slice, n_lists = len(rec["g0"]) // n_probes, int(index.n_lists)
    assert rec["qid"].shape[1] == (
        128 if which == "flat"
        else probe_invert.list_chunk(n_slice, n_probes, n_lists))
    ref_d, ref_i, short = _trim_reference(rec, len(queries), n_probes, k,
                                          np.asarray(index.source_ids))
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_array_equal(d, ref_d)
    if filtered:
        assert np.all(keep[i[i >= 0]])
    assert rec["pv"].all() != bool(budget), "budgets masked no pair"
    if which != "pq16":
        assert short > 0, "no probed list shorter than kk"
        return
    assert recall(i, truth10) >= 0.45  # measured 0.505
    assert np.all(np.diff(d, axis=1) >= -1e-5)
    assert i.min() >= 0
    # "exact" still names the list-major engine's trim
    with pytest.raises(ValueError, match="exact"):
        ivf_pq.search(
            ivf_pq.SearchParams(score_mode="recon8", trim_engine="exact"),
            index, queries, 10,
        )


def test_distributed_list_path_chunk_parity(trim_data, monkeypatch):
    """The distributed XLA list path (`mnmg.ivf_pq_search` on a 4-device
    mesh) runs the engine at the rule's chunk (its floor of 32 pairs for
    50 queries x 8 probes over 64 lists) and returns the bits a fixed 128
    gives."""
    from raft_tpu.comms import Comms, mnmg, mnmg_common
    from raft_tpu.neighbors import probe_invert

    data, queries, _ = trim_data
    index = mnmg.ivf_pq_build(Comms(n_devices=4),
                              ivf_pq.IndexParams(n_lists=64, pq_dim=16), data)
    rule = probe_invert.list_chunk
    engine = ivf_pq._search_impl_recon8_listmajor

    def run(chunk_fn):
        seen = []
        monkeypatch.setattr(probe_invert, "list_chunk",
                            lambda *a: seen.append(chunk_fn(*a)) or seen[-1])
        monkeypatch.setattr(mnmg_common, "_JIT_WRAPPER_CACHE", {})
        engine.clear_cache()
        try:
            d, i = mnmg.ivf_pq_search(index, queries, 40, n_probes=8,
                                      engine="recon8_list")
            return np.asarray(d), np.asarray(i), seen
        finally:
            monkeypatch.undo()
            engine.clear_cache()

    d0, i0, seen = run(rule)
    assert seen == [32]
    d, i, seen = run(lambda *a: 128)
    assert seen == [128]
    np.testing.assert_array_equal(d, d0)
    np.testing.assert_array_equal(i, i0)
    assert i0.min() >= 0

"""The IVF-RaBitQ kind of the benchmark: its work count against
hand-worked shapes, and its harness pieces at a CPU-sized cell (the
program's answers correct, its control's not, and the served searcher
answering as the batch call does)."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness, roofline, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: wiki1m-rabitq's shapes at 32 probes and a 40-row shortlist
WIKI = {"rows": 1_000_000, "dim": 768, "k": 10,
        "index": {"n_lists": 4096}, "search": {"n_probes": 32,
                                               "rerank_mult": 4}}


def _counts():
    return spec.module(REPO, "counts", "ivf_rabitq")


def test_least_work_of_a_10k_call():
    w = _counts().least(WIKI, 10_000)
    # every list probed: 1M rows x (24 code words + 2 float32
    # corrections); a 40-row float32 shortlist per query; the queries;
    # 4,096 rotated float32 centers; the 768 x 768 rotation
    assert w["bytes"] == (104_000_000 + 1_228_800_000 + 30_720_000
                          + 12_582_912 + 2_359_296)
    # rotation, coarse and re-rank dot products
    assert w["ops"] == (2 * 10_000 * 768 * 768 + 2 * 10_000 * 4096 * 768
                        + 2 * 10_000 * 40 * 768)
    peak = spec.peaks(REPO, "TPU v5 lite")
    assert roofline.least_s(w, peak) == pytest.approx(1_378_462_208 / 819e9)


def test_least_work_of_one_row():
    w = _counts().least(WIKI, 1)
    # 32 probed lists of 244.140625 rows x 104 bytes, 40 x 768 x 4, the
    # query, the centers, the rotation
    assert w["bytes"] == 812_500 + 122_880 + 3072 + 12_582_912 + 2_359_296


def test_roofline_share_of_the_config_file():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "wiki1m-rabitq.json")) as f:
        cfg = json.load(f)
    run = SimpleNamespace(cfg=cfg, calls=[10_000] * 3, trace={"busy_s": 2.0},
                          counts=_counts(), peak=spec.peaks(REPO, "TPU v5 lite"))
    one = roofline.least_s(_counts().least(cfg, 10_000), run.peak)
    assert roofline.share(run) == pytest.approx(100 * 3 * one / 2.0)


def test_least_work_rounds_the_width_up_to_whole_words():
    # 100 dimensions rotate into 128 and pack into 4 words
    w = _counts().least(dict(WIKI, dim=100), 1)
    assert w["bytes"] == (32 * 244.140625 * (16 + 8) + 40 * 400 + 400
                          + 4096 * 128 * 4 + 128 * 100 * 4)


def _tiny_rabitq():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "wiki1m-rabitq.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-rabitq", rows=20_000, dim=96, queries=2000)
    cfg["data"] = dict(cfg["data"], blobs=20)  # about 1,000 rows a blob
    cfg["index"] = dict(cfg["index"], n_lists=64, kmeans_n_iters=4)
    cfg["search"] = dict(cfg["search"], n_probes=16, rerank_mult=16)
    return cfg


def test_program_correct_and_control_not(tiny_root):
    root = tiny_root(
        [{"name": "t.rq", "config": "tiny-rabitq", "traffic": "tiny-batch"}],
        extra_files={"benchmark/configs/tiny-rabitq.json": _tiny_rabitq()})
    cell = harness.Cell(root, "t.rq", 2**31 + 5, 0.0)
    cell.setup()
    assert cell.state.index.dataset is None  # the rows are held once
    program = cell.compare(cell.window(1.0))
    control = cell.compare(cell.window(1.0, variant="control"))
    limits = cell.cfg["limits"]
    assert all(c["ok"] for c in
               harness.reference.judge(program, limits).values())
    judged = harness.reference.judge(control, limits)
    assert not all(c["ok"] for c in judged.values())
    assert control["dist_err"] > 100 * program["dist_err"]

    # the served searcher answers as the batch call does, row for row
    q = cell.queries[:5]
    vals, ids = cell.kind.search_fn(cell.state, cell.cfg, "program")(q, 10)
    served = cell.kind.searcher(cell.state, cell.cfg, "program", (8,))
    v2, i2, coverage = served.search(q, 10)
    assert coverage == 1.0
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(ids))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(vals))

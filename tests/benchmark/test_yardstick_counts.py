"""The benchmark's work counts and peak table against hand-worked
shapes."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import roofline, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(REPO, "benchmark", "configs", name)) as f:
        return json.load(f)


def test_ivf_pq_least_work_of_a_10k_call():
    counts = spec.module(REPO, "counts", "ivf_pq")
    w = counts.least(_cfg("deep1m-ivfpq.json"), 10_000)
    # every list probed: 1M rows x 48 code bytes; a 40-row float32
    # shortlist per query; the queries; 1,024 float32 centers
    assert w["bytes"] == 48_000_000 + 153_600_000 + 3_840_000 + 393_216
    assert w["ops"] == 2 * 10_000 * 1024 * 96 + 2 * 10_000 * 40 * 96
    peak = spec.peaks(REPO, "TPU v5 lite")
    assert roofline.least_s(w, peak) == pytest.approx(205_833_216 / 819e9)


def test_ivf_pq_least_work_of_one_row():
    counts = spec.module(REPO, "counts", "ivf_pq")
    w = counts.least(_cfg("deep1m-ivfpq.json"), 1)
    # 8 probed lists of 976.5625 rows x 48 bytes, 40 x 96 x 4, the
    # query, the centers
    assert w["bytes"] == 375_000 + 15_360 + 384 + 393_216


def test_brute_force_least_work_is_one_corpus_read():
    counts = spec.module(REPO, "counts", "brute_force")
    w = counts.least(_cfg("deep10m-flat.json"), 8)
    assert w["bytes"] == (10_000_000 + 8) * 96 * 4
    assert w["ops"] == 2 * 8 * 10_000_000 * 96
    peak = spec.peaks(REPO, "TPU v5 lite")
    assert roofline.least_s(w, peak) == pytest.approx(w["bytes"] / 819e9)


def test_roofline_share_sums_calls_over_busy_time():
    counts = spec.module(REPO, "counts", "ivf_pq")
    run = SimpleNamespace(cfg=_cfg("deep1m-ivfpq.json"), calls=[10_000] * 2,
                          trace={"busy_s": 1.0}, counts=counts,
                          peak=spec.peaks(REPO, "TPU v5 lite"))
    assert roofline.share(run) == pytest.approx(
        100 * 2 * 205_833_216 / 819e9)
    assert roofline.share(SimpleNamespace(**dict(vars(run), trace=None))) is None


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks(REPO, "cpu")

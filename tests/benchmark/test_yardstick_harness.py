"""The harness end to end at a CPU-sized cell, with the look for a chip
skipped: pieces found by name, the control and the planted faults come
out not correct, and the command refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FLAT_SERVE = {"name": "t.flat.serve", "config": "tiny-flat",
              "traffic": "tiny-open"}
FLAT_BATCH = {"name": "t.flat.batch", "config": "tiny-flat",
              "traffic": "tiny-batch"}

# index kinds that exist only as files of the test tree: the real
# brute-force kind with its timed path broken underneath
_WRAP = '''
import os
import numpy as np
from raft_tpu import serve
from benchmark import spec

_bf = spec.module(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "indexes", "brute_force")
build = _bf.build


def search_fn(state, cfg, variant):
    fn = _bf.search_fn(state, cfg, variant)
    return lambda q, k: _break(*fn(q, k), None)[:2]


class _Broken(serve.Searcher):
    def __init__(self, inner):
        self.inner, self.dim = inner, inner.dim

    def search(self, queries, k, probe_scale=1.0, recall_target=None):
        return _break(*self.inner.search(queries, k))


def searcher(state, cfg, variant, buckets):
    return _Broken(_bf.searcher(state, cfg, variant, buckets))
'''
FAULTS = {
    # a answer altered where it is produced: every id one off
    "altered": "def _break(v, i, c):\n    return v, i + 1, c\n",
    # half of the batch left out: the later rows come back empty
    "half_batch": ("def _break(v, i, c):\n"
                   "    v, i = np.array(v), np.array(i)\n"
                   "    h = len(i) // 2\n"
                   "    v[h:], i[h:] = np.inf, -1\n"
                   "    return v, i, c\n"),
    # answers that never come: the device call fails on real traffic
    # (warm-up's all-zero queries, 1 from every unit-norm row, pass)
    "never": ("def _break(v, i, c):\n"
              "    if np.allclose(np.asarray(v), 1.0, atol=1e-3):\n"
              "        return v, i, c\n"
              "    raise RuntimeError('planted fault')\n"),
}


def _run(root, name, **kw):
    return harness.run_cell(root, name, 2**31 + 3, 1.0, **kw)


def test_pieces_found_by_name_and_result_line(tiny_root, capsys):
    # a configuration, two traffic mixes and a per-layer metric that
    # exist only as files of this test's tree
    layer = {"name": "test.device_calls", "unit": "calls",
             "better": "higher", "source": "host_clock", "layer": "test",
             "moves": "qps", "workloads": ["t.flat.batch"]}
    root = tiny_root(
        [FLAT_BATCH, FLAT_SERVE], per_layer=[layer],
        extra_files={"benchmark/layers/test.device_calls.py":
                     "def read(run):\n    return len(run.calls)\n"})
    out = _run(root, "t.flat.batch")
    assert out["correct"] and out["failed"] == 0
    assert sorted(out["metrics"]) == ["qps", "setup_s"]
    assert list(out)[-1] == "checks"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    traced = _run(root, "t.flat.batch", trace=True)
    assert traced["metrics"]["test.device_calls"]["value"] >= 1
    served = _run(root, "t.flat.serve")
    assert served["correct"] and sorted(served["metrics"]) == [
        "p99_ms", "setup_s"]
    assert served["attempted"] == 100
    window = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if '"phase": "window"' in line]
    assert all("late_p99_ms" in w for w in window if w["attempted"] == 100)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tiny_root, fault):
    kind = f"flat_{fault}"
    cfg_path = os.path.join(REPO, "benchmark", "configs", "deep10m-flat.json")
    with open(cfg_path) as f:
        cfg = json.load(f)
    root = tiny_root(
        [{"name": "t.fault", "config": "tiny-fault", "traffic": "tiny-open"}],
        extra_files={f"benchmark/indexes/{kind}.py": _WRAP + FAULTS[fault]})
    tiny = os.path.join(root, "benchmark", "configs", "tiny-flat.json")
    with open(tiny) as f:
        cfg = dict(json.load(f), name="tiny-fault", kind=kind)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-fault.json"), "w") as f:
        json.dump(cfg, f)
    out = _run(root, "t.fault")
    assert out["correct"] is False
    failing = [n for n, c in out["checks"].items()
               if c["value"] is None
               or not (c["value"] >= c["limit"] if c["rule"] == ">="
                       else c["value"] <= c["limit"])]
    assert failing


@pytest.mark.parametrize("config,traffic", [("tiny-flat", "tiny-open"),
                                            ("tiny-ivfpq", "tiny-batch")])
def test_control_is_not_correct(tiny_root, config, traffic):
    root = tiny_root([{"name": "t.c", "config": config, "traffic": traffic}])
    cell = harness.Cell(root, "t.c", 11, 0.0)
    cell.setup()
    program = cell.compare(cell.window(1.0))
    control = cell.compare(cell.window(1.0, variant="control"))
    limits = cell.cfg["limits"]
    assert harness.reference.judge(program, limits)["dist_err"]["ok"]
    judged = harness.reference.judge(control, limits)
    assert not all(c["ok"] for c in judged.values())
    assert control["dist_err"] > 100 * program["dist_err"]


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "deep1m-ivfpq.batch", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cli_refuses_without_a_tpu():
    res = _cli(REPO)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not any(line.startswith("{") for line in res.stdout.splitlines())

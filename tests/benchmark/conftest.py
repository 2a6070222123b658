"""A checkout-shaped tree at a size the CPU holds: the benchmark package
copied beside a BENCHMARK.json of tiny cells, so the harness finds
every piece by name exactly as it does in a real checkout."""

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"rows": 20000, "dim": 16, "queries": 2000}


def _config(source: str, **changes) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", source)) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["data"] = dict(cfg["data"], blobs=64)
    for key, val in changes.items():
        cfg[key] = dict(cfg[key], **val) if isinstance(val, dict) else val
    return cfg


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def make_root(tmp, workloads, extra_files=None, per_layer=None) -> str:
    """A tree with the benchmark package, tiny configs ("tiny-ivfpq",
    "tiny-flat"), tiny traffic ("tiny-batch", "tiny-open"), the given
    workloads and per-layer entries, and any extra files."""
    root = str(tmp)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")
    _write(os.path.join(b, "configs", "tiny-ivfpq.json"), _config(
        "deep1m-ivfpq.json", name="tiny-ivfpq",
        index={"n_lists": 32, "pq_dim": 8, "kmeans_n_iters": 4}))
    _write(os.path.join(b, "configs", "tiny-flat.json"),
           _config("deep10m-flat.json", name="tiny-flat"))
    _write(os.path.join(b, "traffic", "tiny-batch.json"), {
        "loop": "closed", "rows_per_call": 2000, "k": 10,
        "query_pool": 2000, "check_rows": 300})
    _write(os.path.join(b, "traffic", "tiny-open.json"), {
        "loop": "open", "rate_per_s": 100,
        "one_row_share": 0.8, "rows_lo": 2, "rows_hi": 32, "k": 10,
        "query_pool": 2000, "queue_rows": 100000, "check_requests": 40})
    for rel, body in (extra_files or {}).items():
        _write(os.path.join(root, rel), body)
    configs = sorted({w["config"] for w in workloads})
    bench = {
        "command": ["python3", "-m", "benchmark.run"],
        "paths": ["benchmark"],
        "run_seconds": 1,
        "configs": [{"name": c, "source": "test",
                     "file": f"benchmark/configs/{c}.json", "reduced": [],
                     "why": "test"} for c in configs],
        "workloads": [dict(w, chips=1, why="test") for w in workloads],
        "end_to_end": [
            {"name": "qps", "unit": "queries/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": [w["name"] for w in workloads
                           if "batch" in w["traffic"]]},
            {"name": "p99_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock",
             "workloads": [w["name"] for w in workloads
                           if "open" in w["traffic"]]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer or [],
    }
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return lambda *a, **kw: make_root(tmp_path, *a, **kw)

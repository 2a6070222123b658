"""Finds a run's pieces by the names that BENCHMARK.json gives them.

Everything that belongs to one configuration, traffic mix, index kind or
per-layer metric lives in a file of its own under `<root>/benchmark/`:

    configs/<config>.json     named by BENCHMARK.json's `configs[].file`
    traffic/<mix>.json        a cell's `traffic`
    indexes/<kind>.py         a configuration's `kind`
    counts/<kind>.py          the least work of that kind's search
    layers/<metric>.py        a per-layer metric's reader

so a later PR adds a piece by adding a file and an entry, never by
editing a file that is already here.
"""

from __future__ import annotations

import importlib.util
import json
import os

PKG = "benchmark"


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_bench(root: str) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(root: str, bench: dict, name: str) -> dict:
    return _read_json(os.path.join(root, _named(bench["configs"], name,
                                                "config")["file"]))


def traffic(root: str, name: str) -> dict:
    return _read_json(os.path.join(root, PKG, "traffic", name + ".json"))


def module(root: str, sub: str, name: str):
    """Import `<root>/benchmark/<sub>/<name>.py` by path (names may hold
    dots and dashes, which an import statement cannot spell)."""
    path = os.path.join(root, PKG, sub, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"_bench_{sub}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics(bench: dict, section: str, cell: str) -> list:
    """The `end_to_end` or `per_layer` entries that `cell` reports: those
    that list it under `workloads`, and those with no such key."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def peaks(root: str, device_kind: str) -> dict:
    """The chip's peaks; a device kind that is not in the table is an
    error, never a default."""
    table = _read_json(os.path.join(root, PKG, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PKG}/peaks.json")
    return table[device_kind]

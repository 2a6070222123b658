"""raftlint suite: per-rule fixture snippets (positive, negative,
pragma-suppressed, baseline-matched), engine mechanics (deterministic
output, baseline lifecycle, CLI), the end-to-end contract that the repo
itself lints clean, and the fault-site drift test tying
``core.faults.FAULT_SITES`` to the chaos drills.

Fixture trees are written under tmp_path mirroring the repo layout
(rules scope on repo-relative paths like ``raft_tpu/...``), with
``repo_root=tmp_path`` so the real repo never leaks into a fixture run.
"""

import ast
import fnmatch
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.raftlint import Finding, lint_paths
from tools.raftlint.engine import write_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MINI_REGISTRY = """
FAULT_SITES = {
    "good.site": "a registered site",
    "other.site": "another registered site",
}
"""


def run_lint(tmp_path, files, rules=None, baseline=None, registry=True):
    """Write `files` ({relpath: source}) under tmp_path and lint them."""
    if registry and "raft_tpu/core/faults.py" not in files:
        files = dict(files)
        files["raft_tpu/core/faults.py"] = MINI_REGISTRY
        # the unused-site check only runs on whole-package scans,
        # detected by the package root being in the scan set
        files.setdefault("raft_tpu/__init__.py", "")
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    res = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                     baseline=baseline, rules=rules)
    return res


def rules_at(res, relpath=None):
    return [(f.rule, f.line) for f in res.findings
            if relpath is None or f.path == relpath]


# -- trace safety -------------------------------------------------------

def test_trace_host_effect_fires_with_location(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/distance/mod.py": """
        import time
        import jax

        @jax.jit
        def traced(x):
            t = time.monotonic()
            print("hello")
            return x + t

        def host():
            print(time.monotonic())
    """}, rules=["trace-host-effect"])
    assert rules_at(res) == [("trace-host-effect", 7),
                             ("trace-host-effect", 8)]
    f = res.findings[0]
    assert f.path == "raft_tpu/distance/mod.py" and f.col > 0


def test_trace_rules_exempt_tests_and_host_code(tmp_path):
    res = run_lint(tmp_path, {"tests/test_mod.py": """
        import time
        import jax

        @jax.jit
        def hostile(x):
            return x + time.monotonic()
    """}, rules=["trace-host-effect"])
    assert res.findings == []


def test_trace_detects_name_passing_and_pallas(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/ops/kern.py": """
        import time
        import jax
        from jax.experimental import pallas as pl

        def kernel(ref, out):
            time.sleep(1)

        def body(x):
            time.sleep(2)
            return x

        def launch(x):
            out = pl.pallas_call(kernel, out_shape=None)(x)
            return jax.shard_map(body, mesh=None)(out)
    """}, rules=["trace-host-effect"])
    assert rules_at(res) == [("trace-host-effect", 7),
                             ("trace-host-effect", 10)]


def test_trace_nested_defs_inherit_traced_context(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/ops/nested.py": """
        import time
        import jax

        @jax.jit
        def outer(x):
            def inner(y):
                return y + time.monotonic()
            return inner(x)
    """}, rules=["trace-host-effect"])
    assert rules_at(res) == [("trace-host-effect", 8)]


def test_trace_nondeterminism_flags_module_rng_not_jax_random(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/random/mod.py": """
        import random
        import numpy as np
        import jax

        @jax.jit
        def traced(x, key):
            a = random.random()
            b = np.random.default_rng(0).normal()
            c = jax.random.uniform(key, (2,))
            return x + a + b + c
    """}, rules=["trace-nondeterminism"])
    assert rules_at(res) == [("trace-nondeterminism", 8),
                             ("trace-nondeterminism", 9)]


def test_trace_host_sync_item_and_builtins_on_traced_args(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/matrix/mod.py": """
        import jax

        @jax.jit
        def traced(x, k):
            n = int(x.shape[0])   # shapes are static: int() on an
            v = float(x)          # attribute chain is not flagged
            flag = bool(k)
            return v + x.item() + flag + n
    """}, rules=["trace-host-sync"])
    assert rules_at(res) == [("trace-host-sync", 7),
                             ("trace-host-sync", 8),
                             ("trace-host-sync", 9)]


def test_trace_static_argnames_exempt_from_host_sync(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/matrix/mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def traced(x, k):
            return x[: int(k)] + float(x)
    """}, rules=["trace-host-sync"])
    # int(k) exempt (static), float(x) still flagged
    assert rules_at(res) == [("trace-host-sync", 7)]
    assert "float(x)" in res.findings[0].message


def test_trace_try_except_around_lax_only(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/linalg/mod.py": """
        import jax
        from jax import lax

        @jax.jit
        def traced(x):
            try:
                y = lax.add(x, x)
            except ValueError:
                y = x
            try:
                z = {}["missing"]
            except KeyError:
                z = 0
            return y + z
    """}, rules=["trace-try-except"])
    assert rules_at(res) == [("trace-try-except", 7)]


# -- lock discipline ----------------------------------------------------

LOCKY = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0          # __init__ is exempt (pre-publication)
            self._free = 0

        def bump(self):
            with self._lock:
                self._n += 1

        def read_locked_ok(self):
            with self._lock:
                return self._n

        def racy_read(self):
            return self._n

        def suppressed(self):
            return self._n       # raftlint: disable=lock-discipline

        def _peek_locked(self):
            return self._n       # *_locked naming convention

        def untracked(self):
            return self._free    # never written under the lock
"""


def test_lock_discipline_positive_negative_pragma_convention(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/serve/mod.py": LOCKY},
                   rules=["lock-discipline"])
    assert rules_at(res) == [("lock-discipline", 19)]
    assert "_n" in res.findings[0].message
    assert res.pragma_suppressed == 1


def test_lock_discipline_nested_callbacks_are_lock_free(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/serve/mod.py": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()

            def set(self, v):
                with self._lock:
                    self._v = v
                    return lambda: self._v
    """}, rules=["lock-discipline"])
    assert rules_at(res) == [("lock-discipline", 11)]


# -- fault-site drift ---------------------------------------------------

def test_fault_site_unknown_literal_glob_and_const(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        from raft_tpu.core import faults

        BAD_SITE = "not.registered"
        GOOD_SITE = "good.site"

        def f(plan):
            faults.fault_point("bogus.site")
            faults.fault_point(GOOD_SITE)
            plan.matching("good.*", "slow_rank")
            plan.matching("zzz.*", "slow_rank")
    """}, rules=["fault-site-unknown"])
    assert rules_at(res) == [("fault-site-unknown", 4),
                             ("fault-site-unknown", 8),
                             ("fault-site-unknown", 11)]


def test_fault_site_unused_reported_at_registry_entry(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        from raft_tpu.core import faults

        def f():
            faults.fault_point("good.site")
    """}, rules=["fault-site-unused"])
    assert [(f.rule, f.path) for f in res.findings] == [
        ("fault-site-unused", "raft_tpu/core/faults.py")]
    assert "'other.site'" in res.findings[0].message


def test_fault_site_unused_skipped_on_partial_scans(tmp_path):
    """Linting a subdirectory (no package root in the scan) must not
    declare every registered site unused — the hooks live elsewhere."""
    for rel, src in {
        "raft_tpu/__init__.py": "",
        "raft_tpu/core/faults.py": MINI_REGISTRY,
        "raft_tpu/serve/mod.py": "x = 1\n",
    }.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    res = lint_paths([str(tmp_path / "raft_tpu/serve")],
                     repo_root=str(tmp_path), baseline=None,
                     rules=["fault-site-unused"])
    assert res.findings == []


def test_nonexistent_path_fails_loudly(tmp_path):
    """A typo'd path must never turn the gate green while linting
    nothing."""
    with pytest.raises(ValueError, match="does not exist"):
        lint_paths([str(tmp_path / "renamed_away")],
                   repo_root=str(tmp_path), baseline=None)
    r = _cli(["--root", str(tmp_path), str(tmp_path / "renamed_away")])
    assert r.returncode == 2 and "does not exist" in r.stderr
    # same for an explicit non-Python file: exit 0 having linted
    # nothing is the failure mode, not a convenience
    notpy = tmp_path / "data.json"
    notpy.write_text("{}")
    with pytest.raises(ValueError, match="not a Python file"):
        lint_paths([str(notpy)], repo_root=str(tmp_path), baseline=None)


def test_write_baseline_preserves_unscanned_paths_and_stale_scoping(tmp_path):
    """Path-subset runs see only a slice of the repo: --write-baseline
    must preserve other paths' grandfathered entries, and live entries
    for unscanned files must not be reported stale."""
    for rel in ("raft_tpu/util/a.py", "raft_tpu/serve/b.py"):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text("import time\nx = time.time()\n")
    base = tmp_path / "base.json"
    # baseline the whole tree (2 entries), then re-write from a subset
    full = ["--baseline", str(base), "--root", str(tmp_path)]
    assert _cli(full + ["--write-baseline", str(tmp_path)]).returncode == 0
    assert _cli(full + ["--write-baseline",
                        str(tmp_path / "raft_tpu/serve")]).returncode == 0
    entries = json.load(open(base))["findings"]
    assert sorted(e["path"] for e in entries) == [
        "raft_tpu/serve/b.py", "raft_tpu/util/a.py"]
    # subset lint run: suppressed by baseline, and the util entry
    # (unscanned) is NOT advertised as stale
    r = _cli(full + [str(tmp_path / "raft_tpu/serve")])
    assert r.returncode == 0
    assert "stale" not in r.stderr


def test_fault_site_gate_fails_closed_on_unparseable_registry(tmp_path):
    """A refactor that makes FAULT_SITES non-literal (dict(...), merge
    expressions) must fail the gate, not silently disable it."""
    res = run_lint(tmp_path, {
        "raft_tpu/core/faults.py": "FAULT_SITES = dict(a='x')\n",
        "raft_tpu/__init__.py": "",
        "raft_tpu/comms/mod.py": """
            from raft_tpu.core import faults

            def f():
                faults.fault_point("totally.bogus.site")
        """,
    }, rules=["fault-site-unknown"], registry=False)
    assert [(f.rule, f.path) for f in res.findings] == [
        ("fault-site-unknown", "raft_tpu/core/faults.py")]
    assert "literal dict" in res.findings[0].message


def test_fault_site_fixture_tree_clean_when_all_used(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        from raft_tpu.core import faults

        def f(plan):
            faults.fault_point("good.site")
            faults.corrupt_host("other.site", None)
    """}, rules=["fault-site-unknown", "fault-site-unused"])
    assert res.findings == []


# -- layer purity -------------------------------------------------------

def test_layer_purity_dag_and_lazy_escape(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/core/mod.py": """
        from raft_tpu.obs import registry   # core must import no sibling

        def lazy():
            from raft_tpu import obs        # sanctioned escape hatch
            return obs
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res) == [("layer-purity", 2)]


def test_layer_purity_sealed_packages(tmp_path):
    res = run_lint(tmp_path, {
        "raft_tpu/comms/mod.py": """
            def lazy():
                from raft_tpu.serve import engine   # apex: banned even lazily
        """,
        "bench/bench_mod.py": """
            import tests.conftest                    # nothing imports tests
        """,
    }, rules=["layer-purity"], registry=False)
    assert [(f.path, f.rule) for f in res.findings] == [
        ("bench/bench_mod.py", "layer-purity"),
        ("raft_tpu/comms/mod.py", "layer-purity"),
    ]


def test_layer_purity_relative_imports_resolve(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        from ..neighbors import ivf_flat   # comms may not import neighbors
        from ..matrix import select_k      # allowed by the layer map
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res) == [("layer-purity", 2)]


def test_layer_purity_quantizer_cycle_ban(tmp_path):
    """The shared quantizer layer must never import an index module back
    at module scope (ivf_pq/ivf_rabitq import IT — the cycle would close
    on first import). Absolute, from-import and relative forms all
    fire; the sanctioned function-level lazy import does not; and the
    same imports are fine from any OTHER neighbors module."""
    res = run_lint(tmp_path, {"raft_tpu/neighbors/quantizer.py": """
        from raft_tpu.neighbors import ivf_pq          # banned: cycle
        from raft_tpu.neighbors.ivf_rabitq import search  # banned: cycle
        from .ivf_flat import _pack_lists              # banned: cycle
        from raft_tpu.neighbors import refine          # fine: not an index
        from raft_tpu.cluster import kmeans_balanced   # fine: MODULE_ALLOWED

        def lazy():
            from raft_tpu.neighbors.ivf_pq import SearchParams  # sanctioned
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res) == [("layer-purity", 2), ("layer-purity", 3),
                             ("layer-purity", 4)]
    # the same import is fine from any OTHER neighbors module (scoped by
    # path: the quantizer fixture file from above is still on disk)
    ok = run_lint(tmp_path, {"raft_tpu/neighbors/other.py": """
        from raft_tpu.neighbors import ivf_pq  # any other module may
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(ok, "raft_tpu/neighbors/other.py") == []


def test_layer_purity_probe_budget_cycle_ban(tmp_path):
    """The adaptive-probing budget layer (ISSUE 12) is held to the
    quantizer's contract: every index engine imports IT at module
    scope, so a module-scope import of any index module (or of
    probe_invert, which the engines also wire it through) closes a
    cycle and fires; the sanctioned lazy form does not."""
    res = run_lint(tmp_path, {"raft_tpu/neighbors/probe_budget.py": """
        from raft_tpu.neighbors import ivf_flat        # banned: cycle
        from .probe_invert import chunk_validity       # banned: cycle
        from raft_tpu.matrix.select_k import _select_k_impl  # MODULE_ALLOWED
        from raft_tpu.distance.distance_types import DistanceType  # fine

        def lazy():
            from raft_tpu.neighbors.ivf_pq import SearchParams  # sanctioned
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res) == [("layer-purity", 2), ("layer-purity", 3)]


def test_layer_purity_probe_budget_module_allowed_is_stricter(tmp_path):
    """MODULE_ALLOWED narrows probe_budget below the neighbors
    allowance: notably ops (which full neighbors may import) is sealed
    for it — the budget layer steers kernels only through the
    matrix/select_k dispatch door, never directly."""
    res = run_lint(tmp_path, {"raft_tpu/neighbors/probe_budget.py": """
        from raft_tpu.ops import fused_scan    # banned: below its allowance
        from raft_tpu.cluster import kmeans_balanced  # banned: not allowed
        from raft_tpu import obs               # fine: MODULE_ALLOWED
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res, "raft_tpu/neighbors/probe_budget.py") == [
        ("layer-purity", 2), ("layer-purity", 3)]


def test_probe_budget_importable_by_all_engines_without_cycle():
    """The real modules: probe_budget imports cleanly on its own, all
    three engines import it, and its own module scope contains no
    neighbors-sibling import (the cycle ban's real-world pin)."""
    import ast as _ast

    src = open(os.path.join(REPO, "raft_tpu", "neighbors",
                            "probe_budget.py")).read()
    tree = _ast.parse(src)
    for node in _ast.walk(tree):
        if isinstance(node, _ast.ImportFrom) and node.col_offset == 0:
            mod = node.module or ""
            assert not mod.startswith("raft_tpu.neighbors"), mod
            assert not mod.startswith("raft_tpu.ops"), mod
    from raft_tpu.neighbors import ivf_flat, ivf_pq, ivf_rabitq  # noqa: F401
    from raft_tpu.neighbors import probe_budget  # noqa: F401


def test_layer_purity_mutation_cycle_ban(tmp_path):
    """The live-mutation layer (ISSUE 16) orchestrates ABOVE the index
    modules — it calls extend/save/load on all three kinds at call time
    — so a module-scope import of any engine is banned (the lazy
    `_index_module` dispatch is the sanctioned form, the jobs-runner
    posture one layer down); non-index siblings stay fine."""
    res = run_lint(tmp_path, {"raft_tpu/neighbors/mutation.py": """
        from raft_tpu.neighbors import ivf_flat        # banned: cycle
        from .ivf_pq import _grow_and_scatter_multi    # banned: cycle
        from raft_tpu.neighbors.ivf_rabitq import search  # banned: cycle
        from raft_tpu.core import serialize            # fine: MODULE_ALLOWED

        def lazy():
            from raft_tpu.neighbors import ivf_flat as mod  # sanctioned
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res) == [("layer-purity", 2), ("layer-purity", 3),
                             ("layer-purity", 4)]


def test_layer_purity_mutation_module_allowed_is_stricter(tmp_path):
    """MODULE_ALLOWED seals mutation.py to core+obs — strictly below
    the full neighbors allowance: distance/matrix/cluster are all fine
    for neighbors at large but banned here. The mutation layer moves
    rows and writes logs; it never computes."""
    res = run_lint(tmp_path, {"raft_tpu/neighbors/mutation.py": """
        from raft_tpu.distance import pairwise_distance  # banned
        from raft_tpu.matrix.select_k import select_k    # banned
        from raft_tpu import obs                  # fine: MODULE_ALLOWED
        from raft_tpu.core import faults          # fine: MODULE_ALLOWED
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res, "raft_tpu/neighbors/mutation.py") == [
        ("layer-purity", 2), ("layer-purity", 3)]


def test_mutation_importable_without_cycle():
    """The real module: mutation.py imports cleanly on its own, and its
    module scope contains no neighbors-sibling (or compute-layer)
    import — the cycle ban's real-world pin."""
    import ast as _ast

    src = open(os.path.join(REPO, "raft_tpu", "neighbors",
                            "mutation.py")).read()
    tree = _ast.parse(src)
    for node in _ast.walk(tree):
        if isinstance(node, _ast.ImportFrom) and node.col_offset == 0:
            mod = node.module or ""
            assert not mod.startswith("raft_tpu.neighbors"), mod
            assert not mod.startswith("raft_tpu.distance"), mod
            assert not mod.startswith("raft_tpu.matrix"), mod
    from raft_tpu.neighbors import mutation  # noqa: F401


def test_layer_purity_ops_never_imports_dispatch_back(tmp_path):
    """ANY_LEVEL_BAN (ISSUE 10): `ops` is the kernel layer matrix and
    neighbors dispatch INTO (select_k's fused strategy, every fused
    engine) — an ops -> matrix/neighbors import closes a dispatch cycle
    and is banned at any level, lazy function-level included. Reaching
    DOWN (core/distance) stays fine, and matrix/neighbors importing ops
    stays the sanctioned direction."""
    res = run_lint(tmp_path, {"raft_tpu/ops/fused_scan.py": """
        from raft_tpu.matrix.select_k import select_k   # banned: cycle
        from raft_tpu.distance import pairwise          # fine: reaches down

        def lazy():
            from raft_tpu.neighbors import brute_force  # banned EVEN lazily
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res) == [("layer-purity", 2), ("layer-purity", 6)]
    # the INTEGER kernels (ISSUE 11) are held to the same contract: a
    # kernel module in ops that reaches for the quantizer's estimator
    # helpers (neighbors) fires
    res_int = run_lint(tmp_path, {"raft_tpu/ops/bitplane_kernel.py": """
        def kernel_wrapper():
            from raft_tpu.neighbors.quantizer import estimate_dot  # banned
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res_int, "raft_tpu/ops/bitplane_kernel.py") == [
        ("layer-purity", 3)]
    ok = run_lint(tmp_path, {
        "raft_tpu/matrix/fine.py": """
            from raft_tpu.ops.fused_scan import fused_topk  # dispatch -> ops
        """,
        "raft_tpu/neighbors/fine.py": """
            from raft_tpu.ops import fused_scan             # engines -> ops
        """,
    }, rules=["layer-purity"], registry=False)
    assert rules_at(ok, "raft_tpu/matrix/fine.py") == []
    assert rules_at(ok, "raft_tpu/neighbors/fine.py") == []


def test_integer_kernels_live_under_ops():
    """ISSUE 11 location pin: the integer fused kernels are defined in
    the ops layer (where every Pallas kernel lives) and the engine
    layers reach them only through the matrix/select_k dispatch — no
    pallas_call outside ops/ in the neighbors engines."""
    import ast as _ast

    from raft_tpu.ops import fused_scan

    assert hasattr(fused_scan, "fused_list_topk_int8")
    for mod in ("raft_tpu/neighbors/ivf_pq.py",
                "raft_tpu/neighbors/ivf_rabitq.py"):
        src = open(os.path.join(REPO, mod)).read()
        assert "pallas_call" not in src, f"{mod} must dispatch, not own kernels"
        tree = _ast.parse(src)
        for node in _ast.walk(tree):
            if isinstance(node, _ast.ImportFrom) and node.module:
                assert not node.module.startswith("jax.experimental.pallas"), (
                    f"{mod} imports pallas directly"
                )


def test_layer_purity_library_never_imports_bench(tmp_path):
    """LIB_SEALED (ISSUE 7): the measurement layer reads raft_tpu, never
    the reverse — an `import bench` anywhere in the library (obs
    especially: the ledger/cost model live there precisely to keep this
    edge out) fires at any level, even lazily; bench/ files themselves
    are exempt (they import each other freely)."""
    res = run_lint(tmp_path, {
        "raft_tpu/obs/evil.py": """
            import bench

            def lazy():
                from bench.common import Banker   # banned even lazily
        """,
        "bench/fine.py": """
            import bench                           # bench may see itself
        """,
    }, rules=["layer-purity"], registry=False)
    assert [(f.path, f.line) for f in res.findings] == [
        ("raft_tpu/obs/evil.py", 2), ("raft_tpu/obs/evil.py", 5)]


def test_layer_purity_jobs_layer(tmp_path):
    """ISSUE 8: the job runner sits beside serve at the apex — it may
    import core/io/comms/obs at module scope, reaches index modules only
    through the lazy escape hatch, and serve/bench stay sealed against
    it even lazily (a runner importing the apex could never supervise
    it from outside; the library never imports the measurement layer)."""
    res = run_lint(tmp_path, {"raft_tpu/jobs/mod.py": """
        from raft_tpu.core import faults            # fine: layer map
        from raft_tpu import io, comms, obs         # fine: layer map
        from raft_tpu.neighbors import ivf_flat     # module-scope: fires

        def lazy():
            from raft_tpu.neighbors import ivf_pq   # sanctioned escape
            from raft_tpu.serve import engine       # sealed even lazily
            from bench.common import Banker         # LIB_SEALED: fires
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res) == [("layer-purity", 4), ("layer-purity", 8),
                             ("layer-purity", 9)]


def test_layer_purity_new_perf_modules_lint_clean(tmp_path):
    """The ISSUE-7 shapes stay legal: obs modules importing core +
    stdlib, comms importing obs, bench importing raft_tpu.obs.ledger."""
    res = run_lint(tmp_path, {
        "raft_tpu/obs/perf2.py": """
            import subprocess
            from raft_tpu.core import config
        """,
        "raft_tpu/comms/mnmg_extra.py": """
            from raft_tpu import obs
        """,
        "bench/common2.py": """
            from raft_tpu.obs import ledger
        """,
    }, rules=["layer-purity"], registry=False)
    assert rules_at(res) == []


def test_layer_purity_quantizer_module_allowed_is_stricter(tmp_path):
    """MODULE_ALLOWED narrows the quantizer below the neighbors
    subpackage map: `random` is allowed for neighbors at large but NOT
    for the quantizer module."""
    res = run_lint(tmp_path, {"raft_tpu/neighbors/quantizer.py": """
        from raft_tpu.random import rng     # outside the module map
        from raft_tpu.matrix import select_k  # inside it
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(res, "raft_tpu/neighbors/quantizer.py") == [
        ("layer-purity", 2)]
    ok = run_lint(tmp_path, {"raft_tpu/neighbors/other.py": """
        from raft_tpu.random import rng     # neighbors at large: fine
    """}, rules=["layer-purity"], registry=False)
    assert rules_at(ok, "raft_tpu/neighbors/other.py") == []


def test_quantizer_importable_by_both_indexes_without_cycle():
    """The real modules: quantizer imports cleanly on its own, both
    index modules import it, and the quantizer's own module-scope
    imports touch neither — the import graph the cycle ban freezes."""
    import ast as _ast

    src = open(os.path.join(REPO, "raft_tpu", "neighbors",
                            "quantizer.py")).read()
    tree = _ast.parse(src)
    top_imports = []
    for node in tree.body:
        if isinstance(node, _ast.Import):
            top_imports += [a.name for a in node.names]
        elif isinstance(node, _ast.ImportFrom):
            top_imports.append(node.module or "")
    banned = ("ivf_pq", "ivf_rabitq", "ivf_flat")
    assert not [m for m in top_imports
                if any(b in (m or "") for b in banned)], top_imports
    # and the live import graph works both ways
    from raft_tpu.neighbors import ivf_pq, ivf_rabitq, quantizer

    assert ivf_pq._encode is quantizer._encode
    assert ivf_rabitq.packed_words is quantizer.packed_words


# -- hygiene ------------------------------------------------------------

def test_hygiene_bare_except_and_untyped_raise(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/util/mod.py": """
        def f():
            try:
                g()
            except:
                raise RuntimeError("boom")
            try:
                g()
            except ValueError:
                raise TimeoutError("typed is fine")
    """}, rules=["hygiene-bare-except", "hygiene-untyped-raise"])
    assert rules_at(res) == [("hygiene-bare-except", 5),
                             ("hygiene-untyped-raise", 6)]


def test_hygiene_wallclock_scoped_out_of_tests(tmp_path):
    files = {
        "raft_tpu/util/mod.py": "import time\nt = time.time()\n",
        "bench/bench_mod.py": "import time\nt = time.time()\n",
        "tests/test_mod.py": "import time\nt = time.time()\n",
    }
    res = run_lint(tmp_path, files, rules=["hygiene-wallclock"])
    assert sorted(f.path for f in res.findings) == [
        "bench/bench_mod.py", "raft_tpu/util/mod.py"]


def test_hygiene_raw_write_with_serialize_exemption(tmp_path):
    files = {
        "raft_tpu/io/mod.py": """
            import os

            import gzip

            def f(a, b):
                os.rename(a, b)
                os.replace(a, b)
                with open(a, "wb") as fh:
                    fh.write(b"x")
                with gzip.open(a, "wb") as fh:   # attribute opens too
                    fh.write(b"x")
                with a.open("wb") as fh:    # Path.open: mode is arg 0
                    fh.write(b"x")
                with open(a, "rb") as fh:   # reads are fine
                    fh.read()
                open("file.wb.bin")         # filename is not a mode
        """,
        "raft_tpu/core/serialize.py": """
            import os

            def atomic_write(a, b):
                os.replace(a, b)
        """,
    }
    res = run_lint(tmp_path, files, rules=["hygiene-raw-write"])
    assert rules_at(res, "raft_tpu/io/mod.py") == [
        ("hygiene-raw-write", 7), ("hygiene-raw-write", 8),
        ("hygiene-raw-write", 9), ("hygiene-raw-write", 11),
        ("hygiene-raw-write", 13)]
    assert rules_at(res, "raft_tpu/core/serialize.py") == []


def test_hygiene_float64_only_when_reaching_jax(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/stats/mod.py": """
        import numpy as np
        import jax.numpy as jnp

        host = np.zeros(4, np.float64)          # host-side numpy: fine
        dev = jnp.zeros(4, dtype="float64")     # reaches jax: flagged
        also = jnp.asarray(host, dtype=np.float64)
        alias = jnp.float64
        once = jnp.zeros(3, dtype=jnp.float64)  # exactly ONE finding
    """}, rules=["hygiene-float64"])
    assert rules_at(res) == [("hygiene-float64", 6),
                             ("hygiene-float64", 7),
                             ("hygiene-float64", 8),
                             ("hygiene-float64", 9)]


# -- collective divergence / order (raftlint 2.0, CFG-based) ------------

RANKY = """
    import jax

    def get_rank():
        return jax.process_index()
"""


def test_collective_divergence_direct_rank_guard(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": RANKY + """
    def diverge(comms):
        if get_rank() == 0:
            comms.allreduce(1)

    def uniform(comms, n_probes):
        if n_probes > 4:          # static config: every rank agrees
            comms.allreduce(1)
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 8)]
    assert "allreduce" in res.findings[0].message
    assert "rank-dependent" in res.findings[0].message


def test_collective_divergence_health_and_filesystem_taint(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        import os

        def on_health(comms, health):
            if health.degraded:
                comms.barrier()

        def on_fs(comms, path):
            if os.path.exists(path):     # per-host fs probe
                comms.allgather(1)
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 5),
                             ("collective-divergence", 9)]
    assert "health-dependent" in res.findings[0].message
    assert "filesystem-dependent" in res.findings[1].message


def test_collective_divergence_interprocedural_two_calls_away(tmp_path):
    """`if health.degraded: repair(...)` fires even though the ppermute
    lives two resolved calls away — the project-summary half of the
    engine."""
    res = run_lint(tmp_path, {
        "raft_tpu/comms/top.py": RANKY + """
    from raft_tpu.comms.mid import repair

    def heal(comms, health):
        if health.degraded:
            repair(comms)
    """,
        "raft_tpu/comms/mid.py": """
            from raft_tpu.comms.leaf import mirror

            def repair(comms):
                return mirror(comms)
        """,
        "raft_tpu/comms/leaf.py": """
            from jax import lax

            def mirror(comms):
                return lax.ppermute(1, "ranks", [(0, 1)])
        """,
    }, rules=["collective-divergence"])
    assert rules_at(res, "raft_tpu/comms/top.py") == [
        ("collective-divergence", 10)]
    assert "repair" in res.findings[0].message


def test_collective_divergence_multi_level_rank_wrapper(tmp_path):
    """Rank-sourceness must survive wrapper CHAINS (review finding): a
    branch on rank_of() — two resolved calls from process_index — is as
    divergent as a branch on get_rank()."""
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": RANKY + """
    def rank_of():
        return get_rank()

    def f(comms):
        if rank_of() == 0:
            comms.allreduce(1)
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 11)]


def test_collective_divergence_ternary_in_nested_def_reported_once(tmp_path):
    """A ternary inside a nested def must be reported exactly once (by
    the nested def's own analysis), not again by every enclosing
    function's walk (review finding: duplicate findings double baseline
    entries and pragma counts)."""
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        def outer(comms, health):
            def body(x):
                return comms.allreduce(x) if health.degraded else x
            return body
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 4)]


def test_collective_divergence_early_return_guard(tmp_path):
    """`if rank != 0: return` guards the collective after it without
    lexically enclosing it — control dependence, not indentation."""
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": RANKY + """
    def driver_only(comms):
        if get_rank() != 0:
            return None
        return comms.gather(1)
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 8)]


def test_collective_divergence_rank_dependent_loop_trip_count(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": RANKY + """
    def uneven(comms):
        for _ in range(get_rank()):
            comms.barrier()

    def even(comms, n):
        for _ in range(n):
            comms.barrier()
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 8)]
    assert "trip count" in res.findings[0].message


def test_collective_divergence_ternary_arm(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": RANKY + """
    def pick(comms, health):
        x = comms.allreduce(1) if health.degraded else 0
        return x
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 8)]
    assert "conditional expression" in res.findings[0].message


def test_collective_divergence_nested_def_reference_counts(tmp_path):
    """A rank-guarded *reference* to a collective-emitting nested def
    (the shard_map/retry callback shape) is the emission point."""
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": RANKY + """
    def launch(comms, shard_map):
        def body(x):
            return comms.allreduce(x)

        if get_rank() == 0:
            return shard_map(body)
        return None
    """}, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 11)]
    assert "body()" in res.findings[0].message


def test_collective_divergence_both_sides_emitting_is_clean(tmp_path):
    """Rank-dependent branch where BOTH sides emit the same sequence:
    no divergence (and no order drift) — the mesh stays in lockstep."""
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": RANKY + """
    def symmetric(comms, payload):
        if get_rank() == 0:
            out = comms.allreduce(payload)
        else:
            out = comms.allreduce(0)
        return out
    """}, rules=["collective-divergence", "collective-order"])
    assert res.findings == []


def test_collective_divergence_pragma_and_baseline(tmp_path):
    files = {"raft_tpu/comms/mod.py": RANKY + """
    def driver_work(comms):
        if get_rank() == 0:  # raftlint: disable=collective-divergence
            comms.gather(1)

    def unjustified(comms):
        if get_rank() == 0:
            comms.gather(1)
    """}
    res = run_lint(tmp_path, files, rules=["collective-divergence"])
    assert rules_at(res) == [("collective-divergence", 12)]
    assert res.pragma_suppressed == 1
    base = tmp_path / "base.json"
    write_baseline(str(base), res.findings)
    again = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                       baseline=str(base), rules=["collective-divergence"])
    assert again.ok and again.baseline_suppressed == 1


def test_collective_order_drift_and_pragma(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        def drift(comms, health):
            if health.degraded:
                comms.allreduce(1)
                comms.allgather(2)
            else:
                comms.allgather(2)
                comms.allreduce(1)

        def same_order(comms, health):
            if health.degraded:
                comms.allreduce(1)
                comms.allgather(2)
            else:
                comms.allreduce(0)
                comms.allgather(0)

        def justified(comms, health):
            if health.degraded:  # raftlint: disable=collective-order
                comms.allreduce(1)
                comms.allgather(2)
            else:
                comms.allgather(2)
                comms.allreduce(1)
    """}, rules=["collective-order"])
    assert rules_at(res) == [("collective-order", 3)]
    assert "different orders" in res.findings[0].message
    assert res.pragma_suppressed == 1


def test_collective_rules_scope_out_of_tools_and_tests(tmp_path):
    """Divergence analysis runs on raft_tpu/ only — bench drivers and
    tests branch on rank freely (single-process harnesses)."""
    src = RANKY + """
    def diverge(comms):
        if get_rank() == 0:
            comms.allreduce(1)
    """
    res = run_lint(tmp_path, {"bench/mod.py": src, "tests/test_x.py": src,
                              "tools/mod.py": src},
                   rules=["collective-divergence"])
    assert res.findings == []


def test_divergence_rule_catches_what_the_13_syntactic_rules_miss(tmp_path):
    """The acceptance drill: a rank-guarded collective that every PR-5
    rule walks straight past (it is well-typed, lock-free, fault-site-
    clean, layer-pure, hygienic and untraced) is caught only by the
    flow-sensitive divergence rule."""
    files = {"raft_tpu/comms/mod.py": RANKY + """
    from raft_tpu.core import faults

    def checkpoint_then_sync(comms, path):
        faults.fault_point("good.site")
        if get_rank() == 0:
            comms.barrier()
        faults.fault_point("other.site")
        return path
    """}
    pr5_rules = ["trace-host-effect", "trace-nondeterminism",
                 "trace-host-sync", "trace-try-except", "lock-discipline",
                 "fault-site-unknown", "fault-site-unused", "layer-purity",
                 "hygiene-bare-except", "hygiene-wallclock",
                 "hygiene-raw-write", "hygiene-untyped-raise",
                 "hygiene-float64"]
    blind = run_lint(tmp_path, files, rules=pr5_rules)
    assert blind.findings == []
    caught = run_lint(tmp_path, files, rules=["collective-divergence"])
    assert rules_at(caught) == [("collective-divergence", 11)]


# -- lock-order deadlock (raftlint 2.0, interprocedural) ----------------

DEADLOCKY = """
    import threading

    class A:
        def __init__(self):
            self._la = threading.Lock()

        def one(self, b):
            with self._la:
                b.grab()

    class B:
        def __init__(self):
            self._lb = threading.Lock()

        def grab(self):
            with self._lb:
                pass

        def two(self, a):
            with self._lb:
                a.one(self)
"""


def test_lock_order_cycle_across_classes(tmp_path):
    """A holds la and (via the by-name-resolved b.grab()) takes lb; B
    holds lb and takes la through a.one(): both edges of the cycle are
    reported, at the acquisition sites."""
    res = run_lint(tmp_path, {"raft_tpu/serve/mod.py": DEADLOCKY},
                   rules=["lock-order-deadlock"])
    assert rules_at(res) == [("lock-order-deadlock", 10),
                             ("lock-order-deadlock", 22)]
    assert "cycle" in res.findings[0].message
    assert "A._la" in res.findings[0].message
    assert "B._lb" in res.findings[0].message


def test_lock_order_consistent_order_is_clean(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/serve/mod.py": """
        import threading

        class A:
            def __init__(self):
                self._la = threading.Lock()

            def one(self, b):
                with self._la:
                    b.grab()

        class B:
            def __init__(self):
                self._lb = threading.Lock()

            def grab(self):
                with self._lb:
                    pass

            def standalone(self):
                with self._lb:      # never takes la while holding lb
                    return 1
    """}, rules=["lock-order-deadlock"])
    assert res.findings == []


def test_lock_order_self_reacquire_lock_vs_rlock(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/serve/mod.py": """
        import threading

        class Plain:
            def __init__(self):
                self._l = threading.Lock()

            def boom(self):
                with self._l:
                    with self._l:
                        pass

        class Reentrant:
            def __init__(self):
                self._l = threading.RLock()

            def fine(self):
                with self._l:
                    with self._l:
                        pass
    """}, rules=["lock-order-deadlock"])
    assert rules_at(res) == [("lock-order-deadlock", 10)]
    assert "re-acquiring" in res.findings[0].message


def test_lock_order_self_reacquire_through_self_call(tmp_path):
    """Interprocedural self-edge: m() holds the lock and calls a sibling
    method that takes it again — resolved through `self`, no by-name
    guessing."""
    res = run_lint(tmp_path, {"raft_tpu/obs/mod.py": """
        import threading

        class Box:
            def __init__(self):
                self._l = threading.Lock()

            def outer(self):
                with self._l:
                    self.inner()

            def inner(self):
                with self._l:
                    pass
    """}, rules=["lock-order-deadlock"])
    assert rules_at(res) == [("lock-order-deadlock", 10)]


def test_lock_order_by_name_fallback_requires_unique_name(tmp_path):
    """obj.clear() where several classes define clear(): the by-name
    fallback must NOT union all candidates into fabricated cycles."""
    res = run_lint(tmp_path, {"raft_tpu/serve/mod.py": """
        import threading

        class A:
            def __init__(self):
                self._la = threading.Lock()

            def run(self, other):
                with self._la:
                    other.clear()

            def clear(self):
                with self._la:
                    pass

        class B:
            def __init__(self):
                self._lb = threading.Lock()

            def clear(self):
                with self._lb:
                    pass
    """}, rules=["lock-order-deadlock"])
    assert res.findings == []


def test_lock_order_pragma_and_locked_convention(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/serve/mod.py": DEADLOCKY.replace(
        "b.grab()", "b.grab()  # raftlint: disable=lock-order-deadlock")},
        rules=["lock-order-deadlock"])
    # A-side edge suppressed in place; the B-side edge still reported
    assert rules_at(res) == [("lock-order-deadlock", 22)]
    assert res.pragma_suppressed == 1
    # *_locked methods run with "the" class lock already held (single-
    # lock classes): re-taking it inside one is a self-deadlock the seed
    # makes visible
    res2 = run_lint(tmp_path, {"raft_tpu/serve/mod2.py": """
        import threading

        class Box:
            def __init__(self):
                self._l = threading.Lock()

            def peek_locked(self):
                with self._l:      # caller already holds it: deadlock
                    return 1
    """}, rules=["lock-order-deadlock"])
    assert rules_at(res2, "raft_tpu/serve/mod2.py") == [
        ("lock-order-deadlock", 9)]
    assert "re-acquiring" in res2.findings[-1].message


# -- commit ordering (raftlint 2.0, dominance-based) --------------------

def test_commit_ordering_cursor_first_fires(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/jobs/mod.py": """
        from raft_tpu.core.serialize import atomic_write

        def bad(jd, path, payload):
            jd.write_json(path / "cursor.json", {"n": 1})
            atomic_write(path / "data.bin", payload)
    """}, rules=["commit-ordering"])
    assert rules_at(res) == [("commit-ordering", 5)]
    assert "cursor-written-LAST" in res.findings[0].message


def test_commit_ordering_branch_only_artifact_does_not_dominate(tmp_path):
    """An artifact write inside one branch does not protect a cursor
    write after the join — dominance, not lexical order."""
    res = run_lint(tmp_path, {"raft_tpu/jobs/mod.py": """
        from raft_tpu.core.serialize import atomic_write

        def racy(jd, path, payload, fresh):
            if fresh:
                atomic_write(path / "data.bin", payload)
            jd.write_json(path / "cursor.json", {"n": 1})
    """}, rules=["commit-ordering"])
    assert rules_at(res) == [("commit-ordering", 7)]


def test_commit_ordering_dominating_artifact_is_clean(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/jobs/mod.py": """
        from raft_tpu.core.serialize import atomic_write

        def good(jd, path, payload, index):
            index.save(str(path / "ckpt"))
            jd.write_json(path / "cursor.json", {"n": 1})

        def also_good(jd, path, payload, fresh):
            if fresh:
                atomic_write(path / "a.bin", payload)
            else:
                atomic_write(path / "b.bin", payload)
            jd.write_json(path / "progress.json", {"n": 1})
    """}, rules=["commit-ordering"])
    assert res.findings == []


def test_commit_ordering_skips_pure_sidecar_helpers(tmp_path):
    """Functions with no artifact write (JobDir.write_json itself, pure
    config writers) have no intra-function protocol to check."""
    res = run_lint(tmp_path, {"raft_tpu/jobs/mod.py": """
        def write_json(path, obj):
            tmp = str(path) + ".tmp"
            _dump(tmp, obj)

        def sidecar_only(jd, path):
            jd.write_json(path / "cursor.json", {"n": 1})
    """}, rules=["commit-ordering"])
    assert res.findings == []


def test_commit_ordering_pragma_and_baseline(tmp_path):
    files = {"raft_tpu/jobs/mod.py": """
        from raft_tpu.core.serialize import atomic_write

        def known(jd, path, payload):
            jd.write_json(path / "cursor.json", {})  # raftlint: disable=commit-ordering
            atomic_write(path / "data.bin", payload)

        def fresh(jd, path, payload):
            jd.write_json(path / "marker.json", {})
            atomic_write(path / "data.bin", payload)
    """}
    res = run_lint(tmp_path, files, rules=["commit-ordering"])
    assert rules_at(res) == [("commit-ordering", 9)]
    assert res.pragma_suppressed == 1
    base = tmp_path / "base.json"
    write_baseline(str(base), res.findings)
    again = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                       baseline=str(base), rules=["commit-ordering"])
    assert again.ok and again.baseline_suppressed == 1


# -- engine mechanics ---------------------------------------------------

def test_pragma_multi_rule_and_all(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/util/mod.py": """
        import time
        a = time.time()  # raftlint: disable=hygiene-wallclock
        b = time.time()  # raftlint: disable=all
        c = time.time()  # raftlint: disable=hygiene-bare-except
    """}, rules=["hygiene-wallclock"])
    assert rules_at(res) == [("hygiene-wallclock", 5)]
    assert res.pragma_suppressed == 2


def test_baseline_suppresses_and_reports_stale(tmp_path):
    files = {"raft_tpu/util/mod.py": "import time\nt = time.time()\n"}
    first = run_lint(tmp_path, files, rules=["hygiene-wallclock"])
    assert len(first.findings) == 1
    base = tmp_path / "baseline.json"
    write_baseline(str(base), first.findings
                   + [Finding("raft_tpu/gone.py", 1, 1,
                              "hygiene-wallclock", "already fixed")])
    res = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                     baseline=str(base), rules=["hygiene-wallclock"])
    assert res.findings == [] and res.ok
    assert res.baseline_suppressed == 1
    assert res.stale_baseline == [
        ("raft_tpu/gone.py", "hygiene-wallclock", "already fixed")]
    # a --rules subset must not report other rules' live entries stale
    other = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                       baseline=str(base), rules=["hygiene-bare-except"])
    assert other.stale_baseline == []


def test_lint_paths_deterministic(tmp_path):
    files = {
        "raft_tpu/util/a.py": "import time\nx = time.time()\n",
        "raft_tpu/util/b.py": "import time\nx = time.time()\ny = time.time()\n",
    }
    a = run_lint(tmp_path, files, rules=["hygiene-wallclock"]).findings
    b = run_lint(tmp_path, files, rules=["hygiene-wallclock"]).findings
    assert a == b == sorted(a)


# -- CLI ----------------------------------------------------------------

@pytest.fixture()
def cli_tree(tmp_path):
    (tmp_path / "raft_tpu/util").mkdir(parents=True)
    (tmp_path / "raft_tpu/util/mod.py").write_text(
        "import time\nt = time.time()\n")
    return tmp_path


def _cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.raftlint"] + args,
        capture_output=True, text=True, cwd=cwd)


def test_cli_json_stable_and_exit_codes(cli_tree):
    args = ["--json", "--no-baseline", "--root", str(cli_tree),
            "--rules", "hygiene-wallclock", str(cli_tree / "raft_tpu")]
    r1, r2 = _cli(args), _cli(args)
    assert r1.returncode == 1 and r2.returncode == 1
    assert r1.stdout == r2.stdout  # byte-stable across runs
    payload = json.loads(r1.stdout)
    assert [f["rule"] for f in payload["findings"]] == ["hygiene-wallclock"]
    f = payload["findings"][0]
    assert f["path"] == "raft_tpu/util/mod.py" and f["line"] == 2
    # sorted output contract
    keys = [(f["path"], f["line"], f["col"]) for f in payload["findings"]]
    assert keys == sorted(keys)
    # clean tree exits 0
    (cli_tree / "raft_tpu/util/mod.py").write_text("x = 1\n")
    assert _cli(args).returncode == 0


def test_cli_write_baseline_refuses_rule_filter(cli_tree):
    """--write-baseline over a rule-filtered run would clobber every
    other rule's grandfathered entries; the CLI refuses."""
    r = _cli(["--write-baseline", "--rules", "hygiene-wallclock",
              "--baseline", str(cli_tree / "b.json"),
              "--root", str(cli_tree), str(cli_tree / "raft_tpu")])
    assert r.returncode == 2
    assert "clobber" in r.stderr
    assert not (cli_tree / "b.json").exists()


def _git(tree, *args):
    return subprocess.run(
        ["git", "-C", str(tree), "-c", "user.email=t@t", "-c",
         "user.name=t", *args], capture_output=True, text=True)


def test_cli_changed_lints_only_the_diff(tmp_path):
    """--changed = merge-base drift + working tree + untracked, scoped
    to the given paths: a dirty file and a fresh file are linted, an
    untouched committed file with a live finding is NOT."""
    tree = tmp_path
    (tree / "raft_tpu/util").mkdir(parents=True)
    dirty = tree / "raft_tpu/util/dirty.py"
    stale = tree / "raft_tpu/util/stale.py"
    dirty.write_text("x = 1\n")
    stale.write_text("import time\nt = time.time()\n")  # pre-existing
    assert _git(tree, "init", "-q").returncode == 0
    _git(tree, "add", "-A")
    assert _git(tree, "commit", "-qm", "seed").returncode == 0
    dirty.write_text("import time\nt = time.time()\n")          # modified
    (tree / "raft_tpu/util/fresh.py").write_text(
        "import time\nt = time.time()\n")                        # untracked
    args = ["--changed", "--no-baseline", "--root", str(tree),
            "--rules", "hygiene-wallclock", str(tree / "raft_tpu")]
    r = _cli(args)
    assert r.returncode == 1, r.stderr
    assert "dirty.py" in r.stdout and "fresh.py" in r.stdout
    assert "stale.py" not in r.stdout
    # committed drift against an explicit base ref is picked up too
    _git(tree, "add", "-A")
    _git(tree, "commit", "-qm", "drift")
    r2 = _cli(["--changed", "HEAD~1"] + args[1:])
    assert r2.returncode == 1
    assert "dirty.py" in r2.stdout and "fresh.py" in r2.stdout
    assert "stale.py" not in r2.stdout
    # a fully clean diff is a no-op success, not a usage error
    r3 = _cli(["--changed", "HEAD", "--no-baseline", "--root", str(tree),
               str(tree / "raft_tpu")])
    assert r3.returncode == 0
    assert "nothing to lint" in r3.stderr


def test_cli_changed_bad_base_ref_is_usage_error(tmp_path):
    """A typo'd BASE (or a path operand swallowed into BASE position)
    must fail loudly, not silently anchor at HEAD and skip every
    committed drift (review finding)."""
    (tmp_path / "raft_tpu").mkdir(parents=True)
    (tmp_path / "raft_tpu/mod.py").write_text("x = 1\n")
    assert _git(tmp_path, "init", "-q").returncode == 0
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    r = _cli(["--changed", "no-such-ref", "--root", str(tmp_path),
              str(tmp_path / "raft_tpu")])
    assert r.returncode == 2
    assert "does not resolve" in r.stderr


def test_cli_changed_outside_git_is_usage_error(tmp_path):
    (tmp_path / "raft_tpu").mkdir(parents=True)
    (tmp_path / "raft_tpu/mod.py").write_text("x = 1\n")
    r = _cli(["--changed", "--root", str(tmp_path),
              str(tmp_path / "raft_tpu")])
    assert r.returncode == 2
    assert "git repository" in r.stderr


def test_cli_unknown_rule_is_usage_error(cli_tree):
    r = _cli(["--rules", "no-such-rule", "--root", str(cli_tree),
              str(cli_tree / "raft_tpu")])
    assert r.returncode == 2
    assert "unknown rule" in r.stderr


def test_cli_list_rules_names_every_family():
    r = _cli(["--list-rules"])
    assert r.returncode == 0
    for fam in ("trace-host-effect", "trace-nondeterminism",
                "trace-host-sync", "trace-try-except", "lock-discipline",
                "fault-site-unknown", "fault-site-unused", "layer-purity",
                "hygiene-bare-except", "hygiene-wallclock",
                "hygiene-raw-write", "hygiene-untyped-raise",
                "hygiene-float64",
                # raftlint 2.0 CFG/interprocedural families
                "collective-divergence", "collective-order",
                "lock-order-deadlock", "commit-ordering",
                # raftlint 3.0 kernelcheck + tuned registry families
                "kernel-vmem-envelope", "kernel-blockspec-consistency",
                "kernel-dtype-flow", "dispatch-envelope-guard",
                "tuned-key-registry",
                # raftlint 4.0 statecheck families
                "cache-key-completeness", "ckpt-schema-registry"):
        assert fam in r.stdout, fam


def test_parse_error_is_a_finding(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/util/broken.py": "def f(:\n"},
                   registry=False)
    assert [f.rule for f in res.findings] == ["parse-error"]


# -- end-to-end contracts ----------------------------------------------

def test_repo_lints_clean_end_to_end():
    """The acceptance bar: the linter exits 0 over the whole repo (after
    fixes/pragmas/baseline). A regression anywhere in the library fails
    here with the precise finding in the assert message."""
    res = lint_paths(["raft_tpu", "bench", "tests", "tools"], repo_root=REPO)
    assert res.ok, "\n" + "\n".join(f.format() for f in res.findings)


def test_check_style_delegates_greps_to_raftlint():
    """The four grep gates must live in raftlint now: reintroducing them
    as greps (or dropping the raftlint invocation) fails here."""
    sh = open(os.path.join(REPO, "ci", "check_style.sh")).read()
    assert "tools.raftlint" in sh
    for gone in ("except[[:space:]]*:", "time\\.time", "os\\.rename",
                 "'wb'"):
        assert gone not in sh, f"grep gate {gone!r} should live in raftlint"


# -- FAULT_SITES drift --------------------------------------------------

def _drill_sites(path):
    """Site literals exercised by Fault(...) constructions in a test
    file (site= keyword or second positional)."""
    tree = ast.parse(open(path).read())
    sites = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and getattr(node.func, "attr", getattr(node.func, "id", None))
                == "Fault"):
            continue
        expr = None
        for kw in node.keywords:
            if kw.arg == "site":
                expr = kw.value
        if expr is None and len(node.args) > 1:
            expr = node.args[1]
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            sites.add(expr.value)
    return sites


def test_fault_sites_match_chaos_drills_exactly():
    """Drift test: FAULT_SITES == the union of sites the chaos drills
    actually install faults at (test_resilience + test_replication, plus
    test_serve for the serving sites). A site registered but never
    drilled — or drilled but unregistered — fails here."""
    from raft_tpu.core import faults

    exercised = set()
    for name in ("test_resilience.py", "test_replication.py",
                 "test_serve.py", "test_jobs.py", "test_mutation.py",
                 "test_trace.py", "test_integrity.py"):
        exercised |= _drill_sites(os.path.join(REPO, "tests", name))
    known = set(faults.known_sites())
    expanded = set()
    for s in exercised:
        if any(c in s for c in "*?["):
            expanded |= set(fnmatch.filter(sorted(known), s))
        else:
            expanded.add(s)
    assert expanded == known, (
        f"undrilled registry sites: {sorted(known - expanded)}; "
        f"unregistered drill sites: {sorted(expanded - known)}")


def test_fault_sites_registry_renders_docstring():
    from raft_tpu.core import faults

    assert faults.known_sites() == tuple(sorted(faults.FAULT_SITES))
    for site in faults.known_sites():
        assert site in faults.__doc__


# -- kernelcheck (raftlint 3.0) -----------------------------------------

MINI_TUNED_REGISTRY = """
TUNED_KEYS = {
    "good_key": {"kind": "choice", "choices": ("a", "b"),
                 "bench": "bench/bench_mini.py"},
    "num_key": {"kind": "int", "choices": None, "bench": None},
    "hints": {"kind": "hints", "choices": None, "bench": None},
}
"""

MINI_KERNEL_MODULE = """
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128

KERNEL_ENVELOPES = {
    "scan": ("fits_scan", {}),
}


def fits_scan(chunk, L, k):
    step = (
        4 * chunk * L        # score tile
        + 4 * chunk * L      # slot plane
        + 4 * chunk * 128    # query rows
        + 8 * chunk * 128    # output buffers
    )
    return L % _LANES == 0 and step <= 10 * 1024 * 1024


def _make_kernel(k):
    def kernel(q_ref, store_ref, vals_ref, idx_ref):
        dots = lax.dot_general(
            q_ref[:].astype(jnp.bfloat16),
            store_ref[:].astype(jnp.bfloat16),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        slot = lax.broadcasted_iota(jnp.int32, dots.shape, 1)
        vals_ref[:] = dots
        idx_ref[:] = slot
    return kernel


def scan(q, store, k, chunk=128):
    nq, rot = q.shape
    L = store.shape[0]
    if q.dtype != jnp.float32 or store.dtype != jnp.float32:
        raise ValueError("f32 operands")
    vals, idx = pl.pallas_call(
        _make_kernel(int(k)),
        grid=(nq // chunk, 1),
        in_specs=[
            pl.BlockSpec((chunk, 128), lambda i, j: (i, 0)),
            pl.BlockSpec((L, 128), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((chunk, 128), lambda i, j: (i, 0)),
            pl.BlockSpec((chunk, 128), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nq, 128), jnp.float32),
            jax.ShapeDtypeStruct((nq, 128), jnp.int32),
        ),
    )(q, store)
    return vals, idx
"""


def test_kernel_vmem_envelope_clean_on_matching_pair(tmp_path):
    src = MINI_KERNEL_MODULE.replace(
        "+ 4 * chunk * 128    # query rows",
        "+ 4 * chunk * 128 + 4 * L * 128",
    )
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": src},
                   rules=["kernel-vmem-envelope"])
    assert res.findings == []


def test_kernel_vmem_envelope_under_charge_fires_at_envelope(tmp_path):
    """The acceptance fixture: the envelope under-charges its kernel by
    ONE buffer (the store block, L x 128 f32, is never charged)."""
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": MINI_KERNEL_MODULE},
                   rules=["kernel-vmem-envelope"])
    assert res.findings, "missing-buffer envelope must fire"
    f = res.findings[0]
    assert f.rule == "kernel-vmem-envelope"
    assert "under-charges" in f.message and "fits_scan" in f.message
    # anchored at the envelope def (the formula is what needs fixing)
    assert "def fits_scan" in (tmp_path / "raft_tpu/ops/mini.py") \
        .read_text().splitlines()[f.line - 1]


def test_kernel_vmem_envelope_fails_closed_on_unanalyzable_body(tmp_path):
    # a kernel the interpreter cannot resolve (functools.partial) must
    # not turn the gate green unverified: no dot/store was checked
    src = MINI_KERNEL_MODULE.replace(
        "_make_kernel(int(k)),", "functools.partial(_make_kernel, int(k)),"
    ).replace("import jax\n", "import functools\nimport jax\n")
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": src},
                   rules=["kernel-vmem-envelope"])
    assert any("kernel body not analyzable" in f.message
               for f in res.findings), [f.message for f in res.findings]


def test_kernel_vmem_envelope_fails_closed_and_coverage(tmp_path):
    # a registered wrapper that does not exist, and a pallas wrapper
    # that is not registered, both fire
    src = MINI_KERNEL_MODULE.replace(
        '"scan": ("fits_scan", {}),', '"ghost": ("fits_scan", {}),')
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": src},
                   rules=["kernel-vmem-envelope"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "no such function" in msgs  # ghost pairing
    assert "not paired with an envelope" in msgs  # scan uncovered


def test_kernel_vmem_envelope_pragma_and_baseline(tmp_path):
    src = MINI_KERNEL_MODULE.replace(
        "def fits_scan(chunk, L, k):",
        "def fits_scan(chunk, L, k):  # raftlint: disable=kernel-vmem-envelope",
    )
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": src},
                   rules=["kernel-vmem-envelope"])
    assert res.findings == [] and res.pragma_suppressed >= 1
    # baseline: grandfather the raw finding
    raw = run_lint(tmp_path / "b", {"raft_tpu/ops/mini.py": MINI_KERNEL_MODULE},
                   rules=["kernel-vmem-envelope"])
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), raw.findings)
    res2 = run_lint(tmp_path / "b", {"raft_tpu/ops/mini.py": MINI_KERNEL_MODULE},
                    rules=["kernel-vmem-envelope"], baseline=str(bl))
    assert res2.findings == [] and res2.baseline_suppressed >= 1


def test_blockspec_consistency_arity_rank_and_out_dtype(tmp_path):
    bad = MINI_KERNEL_MODULE.replace(
        "pl.BlockSpec((L, 128), lambda i, j: (0, 0)),",
        "pl.BlockSpec((L, 128), lambda i: (0, 0)),",
    ).replace(
        "jax.ShapeDtypeStruct((nq, 128), jnp.int32),",
        "jax.ShapeDtypeStruct((nq, 128), jnp.bfloat16),",
    )
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": bad},
                   rules=["kernel-blockspec-consistency"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "index_map takes" in msgs and "calls it with 2" in msgs
    assert "declares bfloat16 but the kernel body finally stores int32" \
        in msgs


def test_blockspec_consistency_index_map_result_rank(tmp_path):
    bad = MINI_KERNEL_MODULE.replace(
        "pl.BlockSpec((chunk, 128), lambda i, j: (i, 0)),\n"
        "            pl.BlockSpec((L, 128), lambda i, j: (0, 0)),",
        "pl.BlockSpec((chunk, 128), lambda i, j: (i, 0, 0)),\n"
        "            pl.BlockSpec((L, 128), lambda i, j: (0, 0)),",
    )
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": bad},
                   rules=["kernel-blockspec-consistency"])
    assert any("returns 3 coordinates for a rank-2 block" in f.message
               for f in res.findings)


def test_blockspec_consistency_negative_and_pragma(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": MINI_KERNEL_MODULE},
                   rules=["kernel-blockspec-consistency"])
    assert res.findings == []
    bad = MINI_KERNEL_MODULE.replace(
        "pl.BlockSpec((L, 128), lambda i, j: (0, 0)),",
        "pl.BlockSpec((L, 128), lambda i: (0, 0)),  "
        "# raftlint: disable=kernel-blockspec-consistency",
    )
    res2 = run_lint(tmp_path / "p", {"raft_tpu/ops/mini.py": bad},
                    rules=["kernel-blockspec-consistency"])
    assert res2.findings == [] and res2.pragma_suppressed >= 1


def test_kernel_dtype_flow_f32_dot_and_preferred(tmp_path):
    bad = MINI_KERNEL_MODULE.replace(
        "q_ref[:].astype(jnp.bfloat16),", "q_ref[:],")
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": bad},
                   rules=["kernel-dtype-flow"])
    assert res.findings and all(f.rule == "kernel-dtype-flow"
                                for f in res.findings)
    assert "(float32, bfloat16)" in res.findings[0].message
    # wrong accumulator dtype also fires
    bad2 = MINI_KERNEL_MODULE.replace(
        "preferred_element_type=jnp.float32,",
        "preferred_element_type=jnp.bfloat16,")
    res2 = run_lint(tmp_path / "b", {"raft_tpu/ops/mini.py": bad2},
                    rules=["kernel-dtype-flow"])
    assert any("must accumulate to float32" in f.message
               for f in res2.findings)


def test_kernel_dtype_flow_popcount_and_unregistered_exempt(tmp_path):
    bad = MINI_KERNEL_MODULE.replace(
        "slot = lax.broadcasted_iota(jnp.int32, dots.shape, 1)",
        "slot = lax.population_count("
        "lax.broadcasted_iota(jnp.int32, dots.shape, 1))",
    )
    res = run_lint(tmp_path, {"raft_tpu/ops/mini.py": bad},
                   rules=["kernel-dtype-flow"])
    assert any("population_count over int32" in f.message
               for f in res.findings)
    # the same f32 dot in an UNREGISTERED module stays silent: the
    # full-precision kernels (pairwise_pallas, fused_l2_argmin) are f32
    # by design
    unreg = MINI_KERNEL_MODULE.replace("KERNEL_ENVELOPES = {", "IGNORED = {") \
        .replace("q_ref[:].astype(jnp.bfloat16),", "q_ref[:],")
    res2 = run_lint(tmp_path / "u", {"raft_tpu/ops/unreg.py": unreg},
                    rules=["kernel-dtype-flow"])
    assert res2.findings == []


# -- dispatch-envelope-guard --------------------------------------------

GUARDED_ENGINE = """
from raft_tpu.ops.fused_scan import fits_fused_list, fused_list_topk


def search(store, qres, k):
    if not fits_fused_list(128, store.shape[1], store.shape[2], k):
        raise ValueError("past the envelope")
    return fused_list_topk(None, qres, store, None, k)
"""

MINI_SELECT_K = """
from raft_tpu.ops.fused_scan import fits_fused_list, fused_list_topk


def resolve_int8_trim_strategy(L, rot, k):
    if fits_fused_list(128, L, rot, k):
        return "fused_int8"
    return None


def list_scan_select_k(lof, qres, store, base, k):
    return fused_list_topk(lof, qres, store, base, k)
"""

STRATEGY_ENGINE = """
from raft_tpu.matrix.select_k import (
    list_scan_select_k, resolve_int8_trim_strategy,
)


def search(store, qres, k, engine="auto"):
    if engine == "fused_int8":
        checked = resolve_int8_trim_strategy(128, 96, k)
        strat = "fused_int8"
    elif engine == "auto":
        strat = resolve_int8_trim_strategy(128, 96, k)
    else:
        strat = "xla"
    if strat == "fused_int8":
        return list_scan_select_k(None, qres, store, None, k)
    return None
"""


def test_dispatch_guard_unguarded_public_call_fires(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/neighbors/eng.py": """
        from raft_tpu.ops.fused_scan import fused_list_topk

        def search(store, qres, k):
            return fused_list_topk(None, qres, store, None, k)
    """}, rules=["dispatch-envelope-guard"])
    assert [f.rule for f in res.findings] == ["dispatch-envelope-guard"]
    assert "fused_list_topk" in res.findings[0].message


def test_dispatch_guard_dominating_fits_raise_is_clean(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/neighbors/eng.py": GUARDED_ENGINE},
                   rules=["dispatch-envelope-guard"])
    assert res.findings == []


def test_dispatch_guard_strategy_literal_reaching_defs(tmp_path):
    # every reaching assignment of `strat` is benign or validated
    res = run_lint(tmp_path, {"raft_tpu/neighbors/eng.py": STRATEGY_ENGINE,
                              "raft_tpu/matrix/select_k.py": MINI_SELECT_K},
                   rules=["dispatch-envelope-guard"])
    assert res.findings == []
    # ... but a fused literal assigned with NO guard poisons the branch
    bad = STRATEGY_ENGINE.replace(
        "        checked = resolve_int8_trim_strategy(128, 96, k)\n"
        '        strat = "fused_int8"\n',
        '        strat = "fused_int8"\n',
    )
    res2 = run_lint(tmp_path / "b",
                    {"raft_tpu/neighbors/eng.py": bad,
                     "raft_tpu/matrix/select_k.py": MINI_SELECT_K},
                    rules=["dispatch-envelope-guard"])
    assert [f.rule for f in res2.findings] == ["dispatch-envelope-guard"]


def test_dispatch_guard_private_impl_propagates_to_callers(tmp_path):
    files = {"raft_tpu/neighbors/eng.py": """
        from raft_tpu.ops.fused_scan import fits_fused_list, fused_list_topk

        def _impl(store, qres, k):
            return fused_list_topk(None, qres, store, None, k)

        def search(store, qres, k):
            if not fits_fused_list(128, 1024, 96, k):
                raise ValueError("past the envelope")
            return _impl(store, qres, k)
    """}
    res = run_lint(tmp_path, dict(files), rules=["dispatch-envelope-guard"])
    assert res.findings == []
    # a second, unguarded caller breaks the proof — the finding anchors
    # at the routing call inside the impl
    files["raft_tpu/neighbors/eng.py"] += (
        "\n\n        def fast_path(store, qres, k):\n"
        "            return _impl(store, qres, k)\n")
    res2 = run_lint(tmp_path / "b", files,
                    rules=["dispatch-envelope-guard"])
    assert [f.rule for f in res2.findings] == ["dispatch-envelope-guard"]
    assert "fused_list_topk" in res2.findings[0].message


def test_dispatch_guard_scope_and_pragma(tmp_path):
    # ops/ is the kernel layer itself: exempt
    res = run_lint(tmp_path, {"raft_tpu/ops/inner.py": """
        from raft_tpu.ops.fused_scan import fused_list_topk

        def helper(store, qres, k):
            return fused_list_topk(None, qres, store, None, k)
    """}, rules=["dispatch-envelope-guard"])
    assert res.findings == []
    res2 = run_lint(tmp_path / "p", {"raft_tpu/neighbors/eng.py": """
        from raft_tpu.ops.fused_scan import fused_list_topk

        def search(store, qres, k):
            return fused_list_topk(None, qres, store, None, k)  # raftlint: disable=dispatch-envelope-guard
    """}, rules=["dispatch-envelope-guard"])
    assert res2.findings == [] and res2.pragma_suppressed == 1


# -- tuned-key-registry --------------------------------------------------

def run_tuned_lint(tmp_path, files, **kw):
    files = dict(files)
    files.setdefault("raft_tpu/core/tuned.py", MINI_TUNED_REGISTRY)
    # a reader of every registered fixture key, so the unused-entry
    # check stays quiet unless a test removes a read on purpose
    files.setdefault("raft_tpu/matrix/_readers.py", """
        from raft_tpu.core import tuned

        def consult():
            return (tuned.get("good_key"), tuned.get("num_key"),
                    tuned.hints())
    """)
    return run_lint(tmp_path, files, rules=["tuned-key-registry"], **kw)


def test_tuned_key_unknown_read_fires(tmp_path):
    res = run_tuned_lint(tmp_path, {"raft_tpu/matrix/mod.py": """
        from raft_tpu.core import tuned

        def resolve():
            a = tuned.get("good_key")
            b = tuned.get_choice("good_kye", ("a", "b"), "a")
            return a, b
    """})
    assert len(res.findings) == 1
    assert "good_kye" in res.findings[0].message


def test_tuned_key_const_resolution_and_bad_const(tmp_path):
    res = run_tuned_lint(tmp_path, {"raft_tpu/neighbors/mod.py": """
        from raft_tpu.core import tuned

        POLICY_KEY = "good_key"
        BAD_KEY = "not_registered"

        def resolve():
            return tuned.get(POLICY_KEY)
    """})
    # the read through POLICY_KEY resolves and is registered; the BAD
    # constant itself fires (the dedupe contract)
    assert len(res.findings) == 1
    assert "BAD_KEY" in res.findings[0].message


def test_tuned_key_hints_idiom_enforced(tmp_path):
    res = run_tuned_lint(tmp_path, {"raft_tpu/comms/mod.py": """
        from raft_tpu.core import tuned

        def resolve():
            return tuned.get("hints") or {}
    """})
    assert len(res.findings) == 1
    assert "tuned.hints()" in res.findings[0].message


def test_tuned_key_writer_typo_and_bad_choice(tmp_path):
    """The acceptance fixture: an --apply writer writes a typo'd key
    (and, separately, a value outside the registered choice set)."""
    res = run_tuned_lint(tmp_path, {"bench/bench_mini.py": """
        from raft_tpu.core import tuned

        def apply_winners(w):
            updates = {"good_kye": "a", "num_key": 7}
            updates["good_key"] = "z"
            tuned.merge(dict(updates, hints={"measured_on": "cpu"}))
    """})
    msgs = sorted(f.message for f in res.findings)
    assert len(res.findings) == 2
    assert any("unregistered tuned key 'good_kye'" in m for m in msgs)
    assert any("writes 'z' to 'good_key'" in m for m in msgs)


def test_tuned_key_unused_fires_on_whole_scan_only(tmp_path):
    # the default fixture reader is overridden with one that skips
    # num_key: the registry entry goes dead
    reader = {"raft_tpu/matrix/_readers.py": """
        from raft_tpu.core import tuned

        def resolve():
            return tuned.get("good_key"), tuned.hints()
    """}
    res = run_tuned_lint(tmp_path, dict(reader))
    assert [f.message for f in res.findings] == [
        "registered tuned key 'num_key' is never read by any dispatch "
        "path or written by any bench — dead registry entry"]
    # partial scan (no raft_tpu/__init__.py): no basis to call keys dead
    files = dict(reader)
    files["raft_tpu/core/tuned.py"] = MINI_TUNED_REGISTRY
    for rel, src in files.items():
        p = tmp_path / "partial" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    res2 = lint_paths([str(tmp_path / "partial" / "raft_tpu/matrix")],
                      repo_root=str(tmp_path / "partial"), baseline=None,
                      rules=["tuned-key-registry"])
    assert res2.findings == []


def test_tuned_key_registry_fails_closed_when_missing(tmp_path):
    res = run_lint(tmp_path, {"raft_tpu/matrix/mod.py": """
        from raft_tpu.core import tuned

        def resolve():
            return tuned.get("anything")
    """}, rules=["tuned-key-registry"])
    assert len(res.findings) == 1
    assert "TUNED_KEYS registry missing" in res.findings[0].message


def test_tuned_key_pragma_and_baseline(tmp_path):
    src = {"raft_tpu/matrix/mod.py": """
        from raft_tpu.core import tuned

        def resolve():
            return tuned.get("experimental_key")  # raftlint: disable=tuned-key-registry
    """}
    res = run_tuned_lint(tmp_path, src)
    assert res.findings == [] and res.pragma_suppressed == 1
    raw_src = {"raft_tpu/matrix/mod.py": src["raft_tpu/matrix/mod.py"]
               .replace("  # raftlint: disable=tuned-key-registry", "")}
    raw = run_tuned_lint(tmp_path / "b", raw_src)
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), raw.findings)
    res2 = run_tuned_lint(tmp_path / "b", raw_src, baseline=str(bl))
    assert res2.findings == [] and res2.baseline_suppressed == 1


# -- the mutation smoke test over the REAL modules ----------------------
#
# The acceptance contract: perturbing one BlockSpec width, one envelope
# constant, one dot operand dtype, one dispatch guard, and one tuned-key
# literal in the real sources each yields exactly the expected finding —
# and the unmutated copies lint clean under the same rule. This is the
# proof the abstract interpreter actually covers the production kernels,
# not just fixtures.

_MUTATIONS = [
    ("blockspec-width",
     ["raft_tpu/ops/fused_scan.py"],
     "raft_tpu/ops/fused_scan.py",
     "pl.BlockSpec((bq, d_pad), lambda i, j: (i, 0),",
     "pl.BlockSpec((2 * bq, d_pad), lambda i, j: (i, 0),",
     "kernel-vmem-envelope", "under-charges"),
    ("envelope-constant",
     ["raft_tpu/ops/fused_scan.py"],
     "raft_tpu/ops/fused_scan.py",
     "+ 2 * (bq + bn) * d_pad",
     "+ 1 * (bq + bn) * d_pad",
     "kernel-vmem-envelope", "under-charges"),
    ("dot-operand-dtype",
     ["raft_tpu/ops/fused_scan.py"],
     "raft_tpu/ops/fused_scan.py",
     "q.astype(jnp.bfloat16),",
     "q.astype(jnp.float32),",
     "kernel-dtype-flow", "(float32, bfloat16)"),
    ("dispatch-guard",
     ["raft_tpu/ops/fused_scan.py", "raft_tpu/matrix/select_k.py",
      "raft_tpu/neighbors/ivf_flat.py"],
     "raft_tpu/neighbors/ivf_flat.py",
     "if not _pallas_fits(index, k):",
     "if False:",
     "dispatch-envelope-guard", "list_scan_select_k"),
    ("tuned-key-literal",
     ["raft_tpu/core/tuned.py", "bench/bench_ivf_rabitq.py"],
     "bench/bench_ivf_rabitq.py",
     'tuned.merge({"rabitq_rerank_mult": int(rb_best[1]),',
     'tuned.merge({"rabitq_rerank_mlt": int(rb_best[1]),',
     "tuned-key-registry", "rabitq_rerank_mlt"),
    # raftlint 4.0 statecheck: delete one field from a real
    # _cached_wrapper key tuple -> the PR-1/4/12 stale-program class
    ("cache-key-field-deleted",
     ["raft_tpu/comms/mnmg_ivf_search.py", "raft_tpu/comms/mnmg_common.py"],
     "raft_tpu/comms/mnmg_ivf_search.py",
     "            n_probes, refine, refine_merged, pf_n, per_cluster, "
     "adaptive_on,\n            qcfg),",
     "            n_probes, refine, refine_merged, pf_n, per_cluster, "
     "qcfg),",
     "cache-key-completeness", "'adaptive_on'"),
    # save an index attribute the registry has never heard of
    ("ckpt-unregistered-save-field",
     ["raft_tpu/core/serialize.py", "raft_tpu/neighbors/ivf_flat.py"],
     "raft_tpu/neighbors/ivf_flat.py",
     '"source_ids": index.source_ids,',
     '"source_ids": index.source_ids, "magnet": index.centers,',
     "ckpt-schema-registry", "'magnet'"),
    # drop a registered-optional field's legacy-load fallback
    ("ckpt-load-fallback-dropped",
     ["raft_tpu/core/serialize.py", "raft_tpu/neighbors/ivf_flat.py"],
     "raft_tpu/neighbors/ivf_flat.py",
     'index.list_radii = arrays.get("list_radii")',
     'index.list_radii = arrays["list_radii"]',
     "ckpt-schema-registry", "UNGUARDED"),
]


@pytest.mark.parametrize(
    "label,copies,target,old,new,rule_name,needle",
    _MUTATIONS, ids=[m[0] for m in _MUTATIONS])
def test_mutation_smoke_real_sources(tmp_path, label, copies, target, old,
                                     new, rule_name, needle):
    import shutil

    for rel in copies:
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    clean = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                       baseline=None, rules=[rule_name])
    assert clean.findings == [], \
        "unmutated copies must lint clean:\n" + "\n".join(
            f.format() for f in clean.findings)
    src = (tmp_path / target).read_text()
    assert old in src, f"mutation anchor drifted: {old!r}"
    (tmp_path / target).write_text(src.replace(old, new, 1))
    mutated = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                         baseline=None, rules=[rule_name])
    assert mutated.findings, f"{label}: mutation must fire {rule_name}"
    assert all(f.rule == rule_name for f in mutated.findings)
    assert any(needle in f.message for f in mutated.findings), \
        "\n".join(f.format() for f in mutated.findings)

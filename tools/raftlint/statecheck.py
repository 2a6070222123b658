"""Statecheck analysis core (raftlint 4.0): the two state surfaces the
live-serving roadmap churns hardest — compiled-program cache keys and
checkpoint schemas — reduced to machine-checkable dataflow questions.

Memoized-trace sites (``_cached_wrapper`` callers, module-level
``*_CACHE`` dict caches) build a jitted/shard_map'd program from the
names their build closure READS; the cache key must cover every one of
those reads or a stale compiled program silently serves after the input
changes (the PR-1 fault-plan, PR-4 probe-count, PR-12 adaptive-flag bug
class). This module answers, per site:

  - which enclosing-scope names the build closure (transitively, through
    sibling nested defs it references) actually reads — its **trace
    inputs**;
  - which of those **flow into the key**: the name appears in the key
    expression, or every reaching assignment derives it from key-covered
    names, module-level statics, and function-scope imports (a bounded
    derivation fixpoint). Derivations through a **tuned read**
    (``tuned.get``/``get_choice``/``hints``, directly or via a resolved
    callee's summary) never count as covered — tuned state is
    process-global but NOT process-stable, exactly why every site keys
    the *resolved* value.

Checkpoint sites (``serialize_arrays``/``_write_ckpt`` callers, the
``load``/``ivf_*_load`` dispatchers) are matched against the
machine-readable ``core/serialize.py::CKPT_SCHEMA`` registry — read by
AST here, never by import (raft_tpu would drag jax in). The extraction
helpers resolve dict-literal keys through local name chasing,
``**splat`` helper calls, and ONE level of save-helper parameterization
(``_save_local_impl(filename, index, store, kind, quant_arrays, meta)``
resolves at each caller), failing CLOSED on anything murkier.

Everything is stdlib ``ast``, deterministic (sorted iteration
throughout), and under-reports rather than guessing — except where a
registry entry exists, which must never turn the gate green unverified.
"""

from __future__ import annotations

import ast
import builtins
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.raftlint.engine import (
    Module,
    const_str,
    dotted_chain,
    load_module,
    terminal_name,
)
from tools.raftlint.project import ProjectIndex, is_tuned_read

_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)

_BUILTINS = frozenset(dir(builtins))

#: the memoized-trace entry point the MNMG serving layer routes through
CACHED_WRAPPER_NAMES = frozenset({"_cached_wrapper"})

#: the shared key constructor (mnmg_common.wrapper_key): its args ARE
#: the key parts, and the comms session argument covers the mesh/axis
WRAPPER_KEY_NAMES = frozenset({"wrapper_key"})

CKPT_REGISTRY_RELPATH = "raft_tpu/core/serialize.py"

#: the integrity sidecar's field registry (raft_tpu.integrity.digest.
#: DIGEST_FIELDS) — AST-read like CKPT_SCHEMA, pinned against it by the
#: integrity-digest-registry rule
DIGEST_REGISTRY_RELPATH = "raft_tpu/integrity/digest.py"

#: writers whose (arrays, meta) arguments define a checkpoint's on-disk
#: field set (positional layout ``writer(file, arrays, meta)``)
CKPT_WRITER_NAMES = frozenset({"serialize_arrays", "_write_ckpt"})

#: the schema-gated read entry points a load path must route through
CKPT_GATE_NAMES = frozenset({"read_ckpt", "check_ckpt_version"})

#: `<param> + "_part"` checkpoint kinds share one part-file schema
PART_SCHEMA_KIND = "mnmg_sharded_part"


# -- scope-aware free variables -----------------------------------------

def _bound_in(fn: ast.AST) -> Set[str]:
    """Names bound directly in `fn`'s scope: params, assignment/for/with
    targets, walrus targets, imports, nested def/class names —
    comprehension targets included (their leakage is a Python-2-ism we
    deliberately over-bind against). Does not descend into nested defs."""
    out: Set[str] = set()
    if isinstance(fn, _FUNCS + (ast.Lambda,)):
        a = fn.args
        for p in a.posonlyargs + a.args + a.kwonlyargs:
            out.add(p.arg)
        if a.vararg:
            out.add(a.vararg.arg)
        if a.kwarg:
            out.add(a.kwarg.arg)
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, _FUNCS):
            out.add(node.name)
            continue  # its body is its own scope
        if isinstance(node, ast.Lambda):
            continue
        if isinstance(node, ast.ClassDef):
            out.add(node.name)
            continue
        if isinstance(node, ast.Name) and isinstance(
                node.ctx, (ast.Store, ast.Del)):
            out.add(node.id)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                if alias.name != "*":
                    out.add(alias.asname
                            or alias.name.split(".")[0])
        elif isinstance(node, ast.ExceptHandler) and node.name:
            out.add(node.name)
        stack.extend(ast.iter_child_nodes(node))
    return out


def free_names(fn: ast.AST) -> Set[str]:
    """Names `fn` (or a scope nested inside it) reads from enclosing
    scopes — the closure's input surface. Scope-accurate per nesting
    level; over-binds comprehension targets (under-reporting, by
    design)."""
    bound = _bound_in(fn)
    free: Set[str] = set()
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, _FUNCS + (ast.Lambda,)):
            free |= free_names(node) - bound
            continue
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id not in bound and node.id not in _BUILTINS:
                free.add(node.id)
        stack.extend(ast.iter_child_nodes(node))
    return free


def _import_bound(fn: ast.AST) -> Set[str]:
    """Names bound by import statements anywhere inside `fn` (function-
    scope imports resolve to fixed module attributes — static, like
    module-level names)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                if alias.name != "*":
                    out.add(alias.asname or alias.name.split(".")[0])
    return out


def module_static_names(module: Module) -> Set[str]:
    """Module-level bindings: imports, top-level defs/classes, and
    module constants. Process-stable from a trace-cache perspective
    (the one mutable exception — the tuned registry — is handled by the
    tuned-read taint, not here)."""
    out: Set[str] = set()
    for node in module.tree.body:
        if isinstance(node, _FUNCS) or isinstance(node, ast.ClassDef):
            out.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                if alias.name != "*":
                    out.add(alias.asname or alias.name.split(".")[0])
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    for e in t.elts:
                        if isinstance(e, ast.Name):
                            out.add(e.id)
        elif isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name):
            out.add(node.target.id)
    return out


# -- key expressions and derivation coverage ----------------------------

def key_expr_names(key: ast.AST) -> Optional[Set[str]]:
    """Every Name read anywhere inside the key expression (attribute
    roots included: ``comms.mesh`` covers ``comms``); None when the
    expression is not an analyzable key shape (not a tuple literal or a
    ``wrapper_key(...)`` call)."""
    if isinstance(key, ast.Call) and terminal_name(
            key.func) in WRAPPER_KEY_NAMES:
        names: Set[str] = set()
        for a in list(key.args) + [kw.value for kw in key.keywords]:
            for n in ast.walk(a):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                    names.add(n.id)
        return names
    if isinstance(key, ast.Tuple):
        names = set()
        for n in ast.walk(key):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                names.add(n.id)
        return names
    return None


def key_tag(key: ast.AST) -> Optional[str]:
    """The site's const tag (first key element), for messages."""
    elts = ()
    if isinstance(key, ast.Call) and terminal_name(
            key.func) in WRAPPER_KEY_NAMES:
        elts = key.args
    elif isinstance(key, ast.Tuple):
        elts = key.elts
    return const_str(elts[0]) if elts else None


def _assignments_in(fns: Sequence[ast.AST]) -> Dict[str, List[ast.AST]]:
    """name -> RHS expressions assigned to it across the enclosing
    function chain (pairwise for same-length tuple-to-tuple assigns, the
    ``impl, cb = _search_impl, None`` idiom; whole-RHS otherwise).
    Nested defs are skipped — their assignments are their own scope."""
    out: Dict[str, List[ast.AST]] = {}

    def add(target: ast.AST, value: ast.AST) -> None:
        if isinstance(target, ast.Name):
            out.setdefault(target.id, []).append(value)
        elif isinstance(target, (ast.Tuple, ast.List)):
            if isinstance(value, (ast.Tuple, ast.List)) \
                    and len(value.elts) == len(target.elts):
                for t, v in zip(target.elts, value.elts):
                    add(t, v)
            else:
                for t in target.elts:
                    add(t, value)

    for fn in fns:
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, _FUNCS + (ast.Lambda,)):
                continue
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    add(t, node.value)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                if node.value is not None:
                    add(node.target, node.value)
            elif isinstance(node, ast.NamedExpr):
                add(node.target, node.value)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                add(node.target, node.iter)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        add(item.optional_vars, item.context_expr)
            stack.extend(ast.iter_child_nodes(node))
    return out


def _rhs_tuned(expr: ast.AST, index: Optional[ProjectIndex],
               module_path: str) -> bool:
    """Does this RHS (transitively, via resolved callee summaries) read
    the tuned registry? Tuned-tainted derivations are never 'covered' —
    a mid-process tuned flip must rebuild the wrapper."""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        if is_tuned_read(node):
            return True
        if index is not None:
            for q in index.resolve_call(module_path, node.func):
                s = index.summaries.get(q)
                if s is not None and s.tuned_read:
                    return True
    return False


@dataclasses.dataclass
class CoverageEnv:
    """The derivation context of one memoized site: the enclosing
    function chain's assignments, the static name sets, and the project
    index for tuned-read resolution."""

    assigns: Dict[str, List[ast.AST]]
    static: Set[str]  # module-level + function-scope-import names
    module_path: str
    index: Optional[ProjectIndex] = None

    def covered_closure(self, seed: Set[str], bound: int = 64) -> Set[str]:
        """Expand key-covered names through derivations: a name joins
        when EVERY reaching assignment's free reads are covered/static
        and tuned-free. Bounded fixpoint, deterministic order."""
        covered = set(seed)
        for _ in range(bound):
            grew = False
            for name in sorted(self.assigns):
                if name in covered:
                    continue
                rhss = self.assigns[name]
                if not rhss:
                    continue
                ok = True
                for rhs in rhss:
                    if _rhs_tuned(rhs, self.index, self.module_path):
                        ok = False
                        break
                    for n in ast.walk(rhs):
                        if isinstance(n, ast.Name) \
                                and isinstance(n.ctx, ast.Load) \
                                and n.id != name \
                                and n.id not in covered \
                                and n.id not in self.static \
                                and n.id not in _BUILTINS:
                            ok = False
                            break
                    if not ok:
                        break
                if ok:
                    covered.add(name)
                    grew = True
            if not grew:
                break
        return covered


# -- memoized-trace site extraction -------------------------------------

@dataclasses.dataclass
class CacheSite:
    """One ``_cached_wrapper(key, build)`` call: the key expression, the
    resolved build def (or None), and the enclosing function chain."""

    module: Module
    call: ast.Call
    key: ast.AST
    build: Optional[ast.AST]
    chain: List[ast.AST]  # enclosing functions, outermost first


def _function_chains(module: Module):
    """Yield (fn, chain) for every def at any depth; `chain` is the
    enclosing function list ending with fn itself."""

    def walk(node, chain):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FUNCS):
                yield child, chain + [child]
                yield from walk(child, chain + [child])
            elif not isinstance(child, ast.Lambda):
                yield from walk(child, chain)

    yield from walk(module.tree, [])


def collect_cache_sites(module: Module) -> List[CacheSite]:
    sites: List[CacheSite] = []
    for fn, chain in _function_chains(module):
        # only this function's OWN statements (a site inside a nested
        # def is found when that def is visited)
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, _FUNCS + (ast.Lambda,)):
                continue
            if isinstance(node, ast.Call) and terminal_name(
                    node.func) in CACHED_WRAPPER_NAMES and len(node.args) >= 2:
                key, build_ref = node.args[0], node.args[1]
                build = _resolve_build(build_ref, chain)
                sites.append(CacheSite(module, node, key, build, chain))
            stack.extend(ast.iter_child_nodes(node))
    # the wrapper's own definition passes its `key` param through — it
    # is the mechanism, not a site
    return [s for s in sites
            if not (s.chain and s.chain[-1].name in CACHED_WRAPPER_NAMES)]


def _resolve_build(ref: ast.AST, chain: Sequence[ast.AST]) -> Optional[ast.AST]:
    if isinstance(ref, ast.Lambda):
        return ref
    if not isinstance(ref, ast.Name):
        return None
    for fn in reversed(list(chain)):
        for node in ast.walk(fn):
            if isinstance(node, _FUNCS) and node.name == ref.id:
                return node
    return None


def local_fn_defs(chain: Sequence[ast.AST]) -> Dict[str, ast.AST]:
    """Function defs visible in the enclosing chain's scopes (sibling
    helpers like ``finish`` — a build referencing one inherits its free
    reads)."""
    out: Dict[str, ast.AST] = {}
    for fn in chain:
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, _FUNCS):
                out.setdefault(node.name, node)
                continue
            if not isinstance(node, ast.Lambda):
                stack.extend(ast.iter_child_nodes(node))
    return out


def trace_inputs(build: ast.AST, chain: Sequence[ast.AST],
                 static: Set[str]) -> Set[str]:
    """The build closure's enclosing-scope reads, expanded transitively
    through sibling nested defs it references (``finish`` et al.), minus
    statics — the names that must flow into the key."""
    helpers = local_fn_defs(chain)
    seen_fns: Set[int] = set()
    names: Set[str] = set()
    queue = [build]
    while queue:
        fn = queue.pop()
        if id(fn) in seen_fns:
            continue
        seen_fns.add(id(fn))
        for name in sorted(free_names(fn)):
            helper = helpers.get(name)
            if helper is not None and helper is not fn:
                queue.append(helper)
                continue
            names.add(name)
    own_bound: Set[str] = set()
    for fn in chain:
        own_bound |= _import_bound(fn)
    return {n for n in names if n not in static and n not in own_bound
            and n not in _BUILTINS}


def tuned_reads_inside(fn: ast.AST) -> List[ast.Call]:
    """Direct tuned-registry reads INSIDE a build closure: the traced
    program would bake one read of mutable global state without keying
    it."""
    return [node for node in ast.walk(fn)
            if isinstance(node, ast.Call) and is_tuned_read(node)]


# -- module-level *_CACHE dict sites ------------------------------------

@dataclasses.dataclass
class DictCacheSite:
    module: Module
    fn: ast.AST
    cache_name: str
    key: ast.AST           # resolved tuple expression
    key_node: ast.AST      # where to anchor findings
    value_exprs: List[ast.AST]  # RHS of `CACHE[key] = v` stores


def module_cache_names(module: Module) -> Set[str]:
    return {n for n in module_static_names(module) if n.endswith("_CACHE")}


def collect_dict_cache_sites(module: Module) -> List[DictCacheSite]:
    caches = module_cache_names(module)
    if not caches:
        return []
    sites: List[DictCacheSite] = []
    for fn, chain in _function_chains(module):
        params = set()
        if isinstance(fn, _FUNCS):
            a = fn.args
            params = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
        assigns = _assignments_in([fn])
        key_expr: Optional[ast.AST] = None
        key_node: Optional[ast.AST] = None
        cache_name = ""
        values: List[ast.AST] = []
        opaque = False
        for node in ast.walk(fn):
            k = None
            cname = ""
            if isinstance(node, ast.Subscript) and isinstance(
                    node.value, ast.Name) and node.value.id in caches:
                k = node.slice
                cname = node.value.id
            elif isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) and isinstance(
                    node.func.value, ast.Name) \
                    and node.func.value.id in caches \
                    and node.func.attr in ("get", "setdefault", "pop") \
                    and node.args:
                k = node.args[0]
                cname = node.func.value.id
            if k is None:
                continue
            expr = k
            if isinstance(k, ast.Name):
                if k.id in params:
                    opaque = True  # the wrapper mechanism: key is opaque
                    continue
                rhss = assigns.get(k.id, [])
                expr = rhss[0] if len(rhss) == 1 else None
            if isinstance(expr, ast.Tuple) and (
                    key_node is None
                    or (k.lineno, k.col_offset)
                    < (key_node.lineno, key_node.col_offset)):
                key_expr, key_node = expr, k  # earliest usage anchors
                cache_name = cname
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) and isinstance(
                            t.value, ast.Name) and t.value.id in caches:
                        values.append(node.value)
        if key_expr is not None and not opaque and values:
            sites.append(DictCacheSite(module, fn, cache_name, key_expr,
                                       key_node, values))
    return sites


# -- checkpoint schema registry (AST-read) ------------------------------

@dataclasses.dataclass(frozen=True)
class FieldSpec:
    category: str   # array | meta | runtime
    dtype: Optional[str]
    since: int
    absent: str     # refuse | default | derive
    line: int
    col: int


@dataclasses.dataclass
class KindSchema:
    version: int
    fields: Dict[str, FieldSpec]
    line: int
    col: int


def load_ckpt_schema(modules: Sequence[Module], repo_root: str
                     ) -> Tuple[Optional[Dict[str, KindSchema]], Optional[str]]:
    """Parse ``CKPT_SCHEMA`` from core/serialize.py (scanned set first,
    disk fallback). None when missing or not a literal — fail closed."""
    reg_mod = next((m for m in modules if m.path == CKPT_REGISTRY_RELPATH),
                   None)
    if reg_mod is None:
        import os

        abspath = os.path.join(repo_root, CKPT_REGISTRY_RELPATH)
        if os.path.exists(abspath):
            reg_mod, _err = load_module(abspath, repo_root)
    if reg_mod is None:
        return None, None
    for node in ast.walk(reg_mod.tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "CKPT_SCHEMA"
                for t in node.targets):
            schema = _parse_schema(node.value)
            return schema, reg_mod.path
    return None, reg_mod.path


def _parse_schema(node: ast.AST) -> Optional[Dict[str, KindSchema]]:
    if not isinstance(node, ast.Dict):
        return None
    out: Dict[str, KindSchema] = {}
    for k, v in zip(node.keys, node.values):
        kind = const_str(k)
        if kind is None or not isinstance(v, ast.Dict):
            return None
        version = None
        fields: Dict[str, FieldSpec] = {}
        for kk, vv in zip(v.keys, v.values):
            key = const_str(kk)
            if key == "version" and isinstance(vv, ast.Constant) \
                    and isinstance(vv.value, int):
                version = vv.value
            elif key == "fields" and isinstance(vv, ast.Dict):
                for fk, fv in zip(vv.keys, vv.values):
                    fname = const_str(fk)
                    spec = _parse_field(fv, fk)
                    if fname is None or spec is None:
                        return None
                    fields[fname] = spec
        if version is None:
            return None
        out[kind] = KindSchema(version, fields, k.lineno, k.col_offset + 1)
    return out


def _parse_field(node: ast.AST, key_node: ast.AST) -> Optional[FieldSpec]:
    if not isinstance(node, ast.Tuple) or len(node.elts) != 4:
        return None
    vals = []
    for e in node.elts:
        if isinstance(e, ast.Constant):
            vals.append(e.value)
        else:
            return None
    cat, dtype, since, absent = vals
    if cat not in ("array", "meta", "runtime") \
            or absent not in ("refuse", "default", "derive") \
            or not isinstance(since, int):
        return None
    return FieldSpec(cat, dtype, since, absent,
                     key_node.lineno, key_node.col_offset + 1)


@dataclasses.dataclass(frozen=True)
class DigestSpec:
    granularity: str  # list | table
    line: int
    col: int


def load_digest_fields(modules: Sequence[Module], repo_root: str
                       ) -> Tuple[Optional[Dict[str, Dict[str, DigestSpec]]],
                                  Optional[str]]:
    """Parse ``DIGEST_FIELDS`` from integrity/digest.py (scanned set
    first, disk fallback) into kind -> {field -> DigestSpec}. None when
    missing, not a literal, or a granularity is not list/table — the
    digest-registry rule fails closed on None exactly like the
    checkpoint-schema rule does."""
    reg_mod = next((m for m in modules if m.path == DIGEST_REGISTRY_RELPATH),
                   None)
    if reg_mod is None:
        import os

        abspath = os.path.join(repo_root, DIGEST_REGISTRY_RELPATH)
        if os.path.exists(abspath):
            reg_mod, _err = load_module(abspath, repo_root)
    if reg_mod is None:
        return None, None
    for node in ast.walk(reg_mod.tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "DIGEST_FIELDS"
                for t in node.targets):
            return _parse_digest_fields(node.value), reg_mod.path
    return None, reg_mod.path


def _parse_digest_fields(node: ast.AST
                         ) -> Optional[Dict[str, Dict[str, DigestSpec]]]:
    if not isinstance(node, ast.Dict):
        return None
    out: Dict[str, Dict[str, DigestSpec]] = {}
    for k, v in zip(node.keys, node.values):
        kind = const_str(k)
        if kind is None or not isinstance(v, ast.Dict):
            return None
        fields: Dict[str, DigestSpec] = {}
        for fk, fv in zip(v.keys, v.values):
            fname = const_str(fk)
            gran = const_str(fv)
            if fname is None or gran not in ("list", "table"):
                return None
            fields[fname] = DigestSpec(gran, fk.lineno, fk.col_offset + 1)
        out[kind] = fields
    return out


# -- checkpoint save-site extraction ------------------------------------

@dataclasses.dataclass
class SaveSite:
    module: Module
    node: ast.Call
    kind: Optional[str]               # resolved kind, or None
    array_keys: List[Tuple[str, ast.AST]]
    meta_keys: List[Tuple[str, ast.AST]]
    unresolved: List[Tuple[str, ast.AST]]  # human tag + anchor, fail closed


def _writer_args(call: ast.Call) -> Tuple[Optional[ast.AST], Optional[ast.AST]]:
    """(arrays, meta) of a ``writer(file, arrays, meta)`` call."""
    arrays = call.args[1] if len(call.args) > 1 else None
    meta = call.args[2] if len(call.args) > 2 else None
    for kw in call.keywords:
        if kw.arg == "arrays":
            arrays = kw.value
        elif kw.arg == "meta":
            meta = kw.value
    return arrays, meta


class _SaveResolver:
    """Resolves a writer call's arrays/meta expressions to const field
    keys within one function, chasing local names, ``**splat`` helpers,
    and (via `param_env`) one level of caller-supplied parameter
    values."""

    def __init__(self, module: Module, fn: ast.AST, index: ProjectIndex,
                 param_env: Optional[Dict[str, ast.AST]] = None,
                 caller: Optional["_SaveResolver"] = None):
        self.module = module
        self.fn = fn
        self.index = index
        self.assigns = _assignments_in([fn])
        self.params = set()
        if isinstance(fn, _FUNCS):
            a = fn.args
            self.params = {p.arg for p in
                           a.posonlyargs + a.args + a.kwonlyargs}
        self.param_env = param_env or {}
        self.caller = caller

    def dict_keys(self, expr: ast.AST, depth: int = 0
                  ) -> Tuple[List[Tuple[str, ast.AST]],
                             List[Tuple[str, ast.AST]]]:
        """(resolved const keys, unresolved tags) of a dict-valued
        expression."""
        keys: List[Tuple[str, ast.AST]] = []
        bad: List[Tuple[str, ast.AST]] = []
        if expr is None:
            return keys, bad
        if depth > 4:
            return keys, [("dict resolution too deep", expr)]
        if isinstance(expr, ast.Dict):
            for k, v in zip(expr.keys, expr.values):
                if k is None:  # **splat
                    sk, sb = self._splat_keys(v, depth)
                    keys += sk
                    bad += sb
                    continue
                s = const_str(k)
                if s is None:
                    bad.append(("non-const dict key", k))
                else:
                    keys.append((s, k))
            return keys, bad
        if isinstance(expr, ast.Name):
            if expr.id in self.params:
                bound = self.param_env.get(expr.id)
                if bound is not None and self.caller is not None:
                    return self.caller.dict_keys(bound, depth + 1)
                return [], [("parameterized dict "
                             f"{expr.id!r} with no caller binding", expr)]
            rhss = self.assigns.get(expr.id, [])
            if not rhss:
                return [], [(f"unresolvable name {expr.id!r}", expr)]
            for rhs in rhss:
                sk, sb = self.dict_keys(rhs, depth + 1)
                keys += sk
                bad += sb
            # plus `name["k"] = v` stores anywhere in the function
            for node in ast.walk(self.fn):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Subscript) and isinstance(
                                t.value, ast.Name) \
                                and t.value.id == expr.id:
                            s = const_str(t.slice)
                            if s is None:
                                bad.append(("non-const store key", t))
                            else:
                                keys.append((s, t))
            return keys, bad
        if isinstance(expr, ast.Call):
            return self._splat_keys(expr, depth)
        return [], [("unanalyzable dict expression", expr)]

    def _splat_keys(self, expr: ast.AST, depth: int
                    ) -> Tuple[List[Tuple[str, ast.AST]],
                               List[Tuple[str, ast.AST]]]:
        """Const keys contributed by ``**helper(...)`` /
        ``**obj.method()``: resolve the callee and collect the dict-
        literal keys + const subscript stores in its body."""
        if isinstance(expr, ast.Name):
            return self.dict_keys(expr, depth + 1)
        if not isinstance(expr, ast.Call):
            return [], [("unanalyzable **splat", expr)]
        target = self._resolve_callee(expr)
        if target is None:
            return [], [("unresolvable **splat callee", expr)]
        keys: List[Tuple[str, ast.AST]] = []
        bad: List[Tuple[str, ast.AST]] = []
        found_dict = False
        for node in ast.walk(target):
            if isinstance(node, ast.Dict):
                found_dict = True  # an empty literal is a resolved answer
                for k in node.keys:
                    if k is None:
                        continue
                    s = const_str(k)
                    if s is not None:
                        keys.append((s, expr))
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        s = const_str(t.slice)
                        if s is not None:
                            keys.append((s, expr))
        if not keys and not found_dict:
            bad.append(("**splat callee writes no const keys", expr))
        return keys, bad

    def _resolve_callee(self, call: ast.Call) -> Optional[ast.AST]:
        qnames = self.index.resolve_call(self.module.path, call.func)
        if len(qnames) == 1:
            return self.index.functions[qnames[0]].node
        # `obj.method()` where obj's local assignment names its class:
        # `quant = RabitqQuantizer(...)` -> RabitqQuantizer.state_arrays
        f = call.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            for rhs in self.assigns.get(f.value.id, []):
                if isinstance(rhs, ast.Call):
                    cls_name = terminal_name(rhs.func)
                    for cq, info in sorted(self.index.classes.items()):
                        if info.name == cls_name and f.attr in info.methods:
                            return info.methods[f.attr]
        hits = self.index.resolve_methods_by_name(
            terminal_name(f) or "")
        if len(hits) == 1:
            return self.index.functions[hits[0]].node
        return None

    def kind_of(self, meta_expr: ast.AST, depth: int = 0
                ) -> Tuple[Optional[str], Optional[str]]:
        """(kind, unresolved-reason) from a meta expression's "kind"
        entry. ``<param> + "_part"`` maps to the shared part schema."""
        if depth > 4:
            return None, "kind resolution too deep"
        expr = meta_expr
        if isinstance(expr, ast.Name) and expr.id not in self.params:
            rhss = self.assigns.get(expr.id, [])
            if len(rhss) == 1:
                return self.kind_of(rhss[0], depth + 1)
        if isinstance(expr, ast.Name) and expr.id in self.params:
            bound = self.param_env.get(expr.id)
            if bound is not None and self.caller is not None:
                return self.caller.kind_of(bound, depth + 1)
            return None, f"parameterized meta {expr.id!r}"
        if not isinstance(expr, ast.Dict):
            return None, "meta is not a dict literal"
        for k, v in zip(expr.keys, expr.values):
            if k is not None and const_str(k) == "kind":
                return self._kind_value(v)
        return None, None  # kind-less container: not a checkpoint

    def _kind_value(self, v: ast.AST) -> Tuple[Optional[str], Optional[str]]:
        s = const_str(v)
        if s is not None:
            return s, None
        if isinstance(v, ast.BinOp) and isinstance(v.op, ast.Add) \
                and const_str(v.right) == "_part":
            return PART_SCHEMA_KIND, None
        if isinstance(v, ast.Name):
            if v.id in self.params:
                bound = self.param_env.get(v.id)
                if bound is not None and self.caller is not None:
                    return self.caller._kind_value(bound)
                return None, f"parameterized kind {v.id!r}"
            rhss = self.assigns.get(v.id, [])
            if len(rhss) == 1:
                return self._kind_value(rhss[0])
        return None, "unresolvable kind value"


def _bind_call_params(callee: ast.AST, call: ast.Call) -> Dict[str, ast.AST]:
    """param name -> argument expression for one project call site."""
    env: Dict[str, ast.AST] = {}
    if not isinstance(callee, _FUNCS):
        return env
    a = callee.args
    names = [p.arg for p in a.posonlyargs + a.args]
    for i, arg in enumerate(call.args):
        if i < len(names):
            env[names[i]] = arg
    for kw in call.keywords:
        if kw.arg:
            env[kw.arg] = kw.value
    return env


def collect_save_sites(modules: Sequence[Module],
                       index: ProjectIndex) -> List[SaveSite]:
    """Every checkpoint write in raft_tpu/: direct writer calls resolved
    in place; parameterized helper writes (``_save_local_impl``)
    resolved once per project caller."""
    sites: List[SaveSite] = []
    by_path = {m.path: m for m in modules}
    for module in sorted(by_path.values(), key=lambda m: m.path):
        if not module.path.startswith("raft_tpu/"):
            continue
        for fn, chain in _function_chains(module):
            if isinstance(fn, _FUNCS) and fn.name in CKPT_WRITER_NAMES:
                continue  # the writers' own bodies are the mechanism
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or terminal_name(
                        node.func) not in CKPT_WRITER_NAMES:
                    continue
                arrays_e, meta_e = _writer_args(node)
                res = _SaveResolver(module, fn, index)
                kind, kind_bad = res.kind_of(meta_e) if meta_e is not None \
                    else (None, "no meta argument")
                if kind is None and kind_bad is None:
                    continue  # kind-less: a generic container, not a ckpt
                needs_caller = (kind_bad or "").startswith("parameterized")
                pdicts = [
                    e for e in (arrays_e, meta_e)
                    if isinstance(e, ast.Name) and e.id in res.params
                ]
                if needs_caller or pdicts:
                    sites += _resolve_via_callers(
                        module, fn, node, index, by_path)
                    continue
                a_keys, a_bad = res.dict_keys(arrays_e) \
                    if arrays_e is not None else ([], [])
                m_keys, m_bad = res.dict_keys(meta_e) \
                    if meta_e is not None else ([], [])
                unresolved = list(a_bad) + list(m_bad)
                if kind is None:
                    unresolved.append((kind_bad, node))
                sites.append(SaveSite(module, node, kind, a_keys, m_keys,
                                      unresolved))
    return sites


def _resolve_via_callers(module: Module, fn: ast.AST, writer_call: ast.Call,
                         index: ProjectIndex, by_path) -> List[SaveSite]:
    """One level of save-helper parameterization: re-resolve this
    writer call once per project caller of `fn`, with the caller's
    argument expressions bound to `fn`'s params."""
    qname = f"{module.path}::{fn.name}"
    out: List[SaveSite] = []
    found_caller = False
    for mpath in sorted(by_path):
        caller_mod = by_path[mpath]
        if not mpath.startswith("raft_tpu/"):
            continue
        for cfn, _chain in _function_chains(caller_mod):
            for node in ast.walk(cfn):
                if not isinstance(node, ast.Call):
                    continue
                if qname not in index.resolve_call(mpath, node.func):
                    continue
                found_caller = True
                caller_res = _SaveResolver(caller_mod, cfn, index)
                env = _bind_call_params(fn, node)
                res = _SaveResolver(module, fn, index, param_env=env,
                                    caller=caller_res)
                arrays_e, meta_e = _writer_args(writer_call)
                kind, kind_bad = res.kind_of(meta_e) \
                    if meta_e is not None else (None, "no meta argument")
                if kind is None and kind_bad is None:
                    continue
                a_keys, a_bad = res.dict_keys(arrays_e) \
                    if arrays_e is not None else ([], [])
                m_keys, m_bad = res.dict_keys(meta_e) \
                    if meta_e is not None else ([], [])
                unresolved = list(a_bad) + list(m_bad)
                if kind is None:
                    unresolved.append((kind_bad, writer_call))
                # anchor findings at the CALLER (the kind owner)
                out.append(SaveSite(caller_mod, node, kind, a_keys, m_keys,
                                    unresolved))
    if not found_caller:
        out.append(SaveSite(module, writer_call, None, [], [],
                            [("parameterized checkpoint write with no "
                              "resolvable caller", writer_call)]))
    return out


# -- checkpoint load-site extraction ------------------------------------

@dataclasses.dataclass
class FieldAccess:
    field: str
    guarded: bool   # .get(...) or an `in`-membership test
    node: ast.AST


@dataclasses.dataclass
class LoadSite:
    module: Module
    fn: ast.AST
    kinds: List[str]           # const kinds this load dispatches on
    accesses: List[FieldAccess]
    helper_accesses: List[FieldAccess]  # via resolved callees (1 level)
    calls_gate: bool           # transitively reaches read_ckpt/check_*


def _field_accesses(fn: ast.AST) -> List[FieldAccess]:
    out: List[FieldAccess] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load):
            s = const_str(node.slice)
            if s is not None:
                out.append(FieldAccess(s, False, node))
        elif isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and node.func.attr == "get" \
                and node.args:
            s = const_str(node.args[0])
            if s is not None:
                out.append(FieldAccess(s, True, node))
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.In, ast.NotIn)):
            s = const_str(node.left)
            if s is not None:
                out.append(FieldAccess(s, True, node))
    return out


def _load_kinds(fn: ast.AST) -> List[str]:
    """Const kinds a function dispatches on: ``meta.get("kind") ==
    "x"`` / ``!=`` comparisons, and ``read_ckpt(f, "x")`` calls."""
    kinds: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.Eq, ast.NotEq)):
            sides = [node.left, node.comparators[0]]
            consts = [const_str(s) for s in sides]
            for side, other in ((0, 1), (1, 0)):
                s = consts[other]
                probe = sides[side]
                if s is None:
                    continue
                if isinstance(probe, ast.Call) and isinstance(
                        probe.func, ast.Attribute) \
                        and probe.func.attr == "get" and probe.args \
                        and const_str(probe.args[0]) == "kind":
                    kinds.add(s)
                elif isinstance(probe, ast.Subscript) \
                        and const_str(probe.slice) == "kind":
                    kinds.add(s)
        elif isinstance(node, ast.Call) and terminal_name(
                node.func) == "read_ckpt" and len(node.args) >= 2:
            s = const_str(node.args[1])
            if s is not None:
                kinds.add(s)
    return sorted(kinds)


def collect_load_sites(modules: Sequence[Module],
                       index: ProjectIndex) -> List[LoadSite]:
    # which functions transitively reach a schema gate
    gated: Set[str] = set()
    callees: Dict[str, Set[str]] = {}
    for q, info in sorted(index.functions.items()):
        cs: Set[str] = set()
        hit = False
        for node in ast.walk(info.node):
            if isinstance(node, ast.Call):
                if terminal_name(node.func) in CKPT_GATE_NAMES:
                    hit = True
                cs.update(index.resolve_call(info.module, node.func,
                                             cls=info.cls))
        callees[q] = cs
        if hit:
            gated.add(q)
    for _ in range(10):
        grew = False
        for q, cs in sorted(callees.items()):
            if q not in gated and cs & gated:
                gated.add(q)
                grew = True
        if not grew:
            break

    sites: List[LoadSite] = []
    for module in sorted(modules, key=lambda m: m.path):
        if not module.path.startswith("raft_tpu/"):
            continue
        for fn, chain in _function_chains(module):
            kinds = _load_kinds(fn)
            if not kinds or not isinstance(fn, _FUNCS):
                continue
            if "load" not in fn.name and "Load" not in fn.name:
                continue
            qname = f"{module.path}::{fn.name}"
            helper_acc: List[FieldAccess] = []
            seen: Set[str] = {qname}
            frontier = sorted(callees.get(qname, ()))
            for _depth in range(3):
                nxt: List[str] = []
                for cq in frontier:
                    if cq in seen or cq not in index.functions:
                        continue
                    seen.add(cq)
                    helper_acc += _field_accesses(index.functions[cq].node)
                    nxt += sorted(callees.get(cq, ()))
                frontier = nxt
            sites.append(LoadSite(module, fn, kinds, _field_accesses(fn),
                                  helper_acc, qname in gated))
    return sites

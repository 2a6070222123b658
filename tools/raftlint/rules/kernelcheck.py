"""kernelcheck rules: the Pallas kernel/envelope/dispatch contracts,
machine-checked (raftlint 3.0; analysis core in tools/raftlint/kernels).

Why lint-time: every fused-kernel defect in this family surfaces ON
CHIP — a VMEM envelope that under-charges its kernel OOMs the first
real grid step, one that over-charges silently refuses workloads that
fit (the dispatch falls back and the queue slot measures the wrong
engine), a drifted index_map arity or operand dtype dies in Mosaic
compile, and an unguarded fused call site violates the PR-10/11
"explicit past-envelope requests raise" contract only when a too-large
index finally arrives. Chip sessions are the scarce resource (ROADMAP
item 1); these rules burn none of them.

``kernel-vmem-envelope``
    For every kernel registered in the module's ``KERNEL_ENVELOPES``
    pairing (the FAULT_SITES pattern: ``{"fused_topk": ("fits_fused",
    {binding overrides}), ...}``), the per-grid-step VMEM bytes the
    kernel actually allocates (in/out blocks, symbolic over the shared
    parameter names; revisited buffers once; scalar-prefetch operands
    are SMEM and uncharged) are compared monomial-by-monomial against
    the AST-evaluated envelope formula. Envelope coefficient below the
    kernel's on any monomial = under-charge (chip OOM). An envelope
    total exceeding 2x the kernel's blocks+intermediates at concrete
    probe geometries = over-charge (refused workloads that fit).
    Registered kernels the interpreter cannot analyze fail CLOSED.

``kernel-blockspec-consistency``
    Structural geometry checks on EVERY ``pl.pallas_call`` site in
    raft_tpu/: index_map arity == grid rank + num_scalar_prefetch
    (checked per optional-operand variant — the PR-12 ``chunk_valid``
    second prefetch operand is exactly where ``*s`` arity drifts),
    index_map result rank == block rank, out block rank == out_shape
    rank, operand count == in_spec count, and the out_shape dtype ==
    the dtype the kernel body finally stores.

``kernel-dtype-flow``
    Abstract dtype propagation through registered kernels' bodies: MXU
    ``dot``/``dot_general`` operands must be (bf16, bf16) -> f32 or
    (int8, int8) -> int32 (an f32 operand reaching the MXU runs at
    half rate silently — TPU-KNN's peak-FLOP/s claim is exactly about
    not doing that), and ``population_count`` operands must be
    unsigned. Unregistered kernels are exempt: the full-precision f32
    kernels (pairwise_pallas, fused_l2_argmin) are f32 by design.

``dispatch-envelope-guard``
    Every call site routing into the fused kernel family (the ops
    entry points and the ``matrix/select_k`` list/bitplane dispatch
    doors) must be guarded by the matching ``fits_*`` /
    ``check_*_request`` validation on every path: a lexically
    dominating guard, a branch on a strategy variable whose every
    reaching assignment is either a non-fused literal, a resolver that
    validates, or a fused literal assigned under a guard — or, for
    private impls, the same proof at every project call site.
    Intentional exceptions carry a justified pragma on the call line.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.raftlint.engine import (
    Finding,
    Module,
    project_rule,
    rule,
    terminal_name,
)
from tools.raftlint.kernels import (
    BlockSpecV,
    CannotEval,
    KernelSite,
    Poly,
    SDSV,
    analyze_module,
    envelope_info,
    probe_eval,
    PROBE_POINTS,
)

_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)

#: over-charge tolerance: the envelope may conservatively pad, but
#: charging more than every block AND every intermediate the body can
#: hold, twice over, refuses workloads that fit
OVERCHARGE_FACTOR = 2.0
OVERCHARGE_SLACK = 65536


def _in_scope(path: str) -> bool:
    return path.startswith("raft_tpu/")


def _registry_line(module: Module) -> Tuple[int, int]:
    for node in module.tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "KERNEL_ENVELOPES"
                for t in node.targets):
            return node.lineno, node.col_offset + 1
    return 1, 1


# -- kernel-vmem-envelope -------------------------------------------------


@rule(
    "kernel-vmem-envelope",
    "a registered Pallas kernel's per-grid-step block bytes and its "
    "fits_* envelope formula disagree (under-charge = chip OOM, "
    "over-charge = refused workloads that fit)",
    "raft_tpu/ modules declaring KERNEL_ENVELOPES",
)
def check_vmem_envelope(module: Module) -> Iterator[Finding]:
    if not _in_scope(module.path):
        return
    ana = analyze_module(module)
    if ana.registry is None:
        return
    reg_line, reg_col = _registry_line(module)
    interp = ana.interp
    seen_msgs: Set[str] = set()

    def emit(line, col, msg):
        if msg not in seen_msgs:
            seen_msgs.add(msg)
            yield Finding(module.path, line, col, "kernel-vmem-envelope", msg)

    # coverage: every pallas wrapper in a registered module must be
    # paired (a new kernel without an envelope is unguardable)
    for wrapper in ana.pallas_wrappers:
        if wrapper not in ana.registry:
            fn = interp.functions[wrapper]
            yield from emit(
                fn.lineno, fn.col_offset + 1,
                f"kernel {wrapper!r} contains a pallas_call but is not "
                f"paired with an envelope in KERNEL_ENVELOPES")

    for wrapper, (env_name, bindings) in sorted(ana.registry.items()):
        wfn = interp.functions.get(wrapper)
        if wfn is None:
            yield from emit(
                reg_line, reg_col,
                f"KERNEL_ENVELOPES pairs {wrapper!r} but no such function "
                f"exists in this module")
            continue
        efn = interp.functions.get(env_name)
        if efn is None:
            yield from emit(
                reg_line, reg_col,
                f"KERNEL_ENVELOPES pairs {wrapper!r} with {env_name!r} but "
                f"no such envelope function exists in this module")
            continue
        einfo = envelope_info(interp, efn, bindings)
        if einfo.bytes_poly is None:
            yield from emit(
                efn.lineno, efn.col_offset + 1,
                f"envelope {env_name!r} is not symbolically evaluable "
                f"({einfo.failed}) — the cross-check fails closed")
            continue
        sites = ana.sites.get(wrapper) or []
        if not sites:
            yield from emit(
                wfn.lineno, wfn.col_offset + 1,
                f"registered kernel {wrapper!r}: no analyzable pallas_call "
                f"site found — the cross-check fails closed")
            continue
        for site in sites:
            if site.body is not None and site.body.failed:
                # fail CLOSED: an unanalyzable body means the dtype-flow
                # and final-store checks saw nothing — the registry
                # entry must not turn the gate green unverified
                yield from emit(
                    wfn.lineno, wfn.col_offset + 1,
                    f"registered kernel {wrapper!r} [{site.variant}]: "
                    f"kernel body not analyzable ({site.body.failed}) — "
                    f"the cross-check fails closed")
                continue
            blocks, why = site.block_bytes()
            if why is not None:
                yield from emit(
                    wfn.lineno, wfn.col_offset + 1,
                    f"registered kernel {wrapper!r} [{site.variant}]: "
                    f"{why} — the cross-check fails closed")
                continue
            # under-charge: the envelope must cover every block term
            for mono, need, got in blocks.monomials_below(einfo.bytes_poly):
                yield from emit(
                    efn.lineno, efn.col_offset + 1,
                    f"envelope {env_name!r} under-charges kernel "
                    f"{wrapper!r} [{site.variant}]: block bytes term "
                    f"{mono} needs coefficient >= {need}, formula has "
                    f"{got} — a fitting verdict can VMEM-OOM on chip")
            # over-charge: probe-point totals
            inters = site.body.intermediates if site.body else Poly.const(0)
            for point in PROBE_POINTS:
                try:
                    ev = probe_eval(interp, einfo.bytes_poly, point,
                                    dict(_itemsize_probe(bindings)))
                    bv = probe_eval(interp, blocks, point,
                                    dict(_itemsize_probe(bindings)))
                    iv = probe_eval(interp, inters, point,
                                    dict(_itemsize_probe(bindings)))
                except (CannotEval, ZeroDivisionError, OverflowError):
                    continue
                bound = OVERCHARGE_FACTOR * (bv + iv) + OVERCHARGE_SLACK
                if ev > bound:
                    yield from emit(
                        efn.lineno, efn.col_offset + 1,
                        f"envelope {env_name!r} over-charges kernel "
                        f"{wrapper!r} [{site.variant}]: at a probe "
                        f"geometry it charges {int(ev)} bytes vs "
                        f"{int(bv + iv)} the kernel can allocate — the "
                        f"dispatch refuses workloads that fit")
                    break


def _itemsize_probe(bindings) -> Dict[str, int]:
    out = {}
    for k, v in bindings.items():
        if k.endswith("_itemsize") and isinstance(v, int):
            out[k[:-len("_itemsize")]] = v
    return out


# -- kernel-blockspec-consistency -----------------------------------------


@rule(
    "kernel-blockspec-consistency",
    "pallas_call BlockSpec geometry drift: index_map arity vs grid rank "
    "+ scalar prefetch, index_map/block/out_shape rank, operand count, "
    "out dtype vs the kernel body's final store",
    "raft_tpu/",
)
def check_blockspec_consistency(module: Module) -> Iterator[Finding]:
    if not _in_scope(module.path):
        return
    ana = analyze_module(module)
    seen: Set[Tuple] = set()
    for wrapper in sorted(ana.sites):
        for site in ana.sites[wrapper]:
            for f in _site_consistency(module, wrapper, site):
                key = (f.line, f.col, f.message)
                if key not in seen:
                    seen.add(key)
                    yield f


def _site_consistency(module: Module, wrapper: str,
                      site: KernelSite) -> Iterator[Finding]:
    grid_rank = len(site.grid) if site.grid is not None else None
    if grid_rank is not None:
        required = grid_rank + site.nsp
        specs = list(site.in_specs) + list(site.out_specs)
        for spec in specs:
            if not isinstance(spec, BlockSpecV) or spec.index_map is None:
                continue
            lam = spec.index_map.node
            if not isinstance(lam, ast.Lambda):
                continue
            npos = len(lam.args.posonlyargs) + len(lam.args.args)
            ndef = len(lam.args.defaults)
            has_var = lam.args.vararg is not None
            ok = (npos - ndef <= required and (required <= npos or has_var))
            if not ok:
                accepts = (f">= {npos - ndef}" if has_var
                           else f"{npos - ndef}..{npos}")
                yield Finding(
                    module.path, lam.lineno, lam.col_offset + 1,
                    "kernel-blockspec-consistency",
                    f"{wrapper} [{site.variant}]: index_map takes "
                    f"{accepts} args but the grid rank ({grid_rank}) + "
                    f"num_scalar_prefetch ({site.nsp}) calls it with "
                    f"{required} — Mosaic rejects this at compile time")
            if spec.shape is not None and isinstance(lam.body, ast.Tuple) \
                    and len(lam.body.elts) != len(spec.shape):
                yield Finding(
                    module.path, lam.lineno, lam.col_offset + 1,
                    "kernel-blockspec-consistency",
                    f"{wrapper} [{site.variant}]: index_map returns "
                    f"{len(lam.body.elts)} coordinates for a rank-"
                    f"{len(spec.shape)} block")
    if site.out_specs and site.out_shapes \
            and len(site.out_specs) != len(site.out_shapes):
        yield Finding(
            module.path, site.call_node.lineno,
            site.call_node.col_offset + 1, "kernel-blockspec-consistency",
            f"{wrapper} [{site.variant}]: {len(site.out_specs)} out_specs "
            f"vs {len(site.out_shapes)} out_shape entries")
    for i, (spec, osh) in enumerate(zip(site.out_specs, site.out_shapes)):
        if isinstance(spec, BlockSpecV) and spec.shape is not None \
                and isinstance(osh, SDSV) and osh.shape is not None \
                and len(spec.shape) != len(osh.shape):
            yield Finding(
                module.path, spec.node.lineno, spec.node.col_offset + 1,
                "kernel-blockspec-consistency",
                f"{wrapper} [{site.variant}]: out block {i} has rank "
                f"{len(spec.shape)} but out_shape[{i}] has rank "
                f"{len(osh.shape)}")
    if site.in_specs and site.operands \
            and len(site.operands) != len(site.in_specs) \
            and site.scalar_count is not None:
        yield Finding(
            module.path, site.node.lineno, site.node.col_offset + 1,
            "kernel-blockspec-consistency",
            f"{wrapper} [{site.variant}]: {len(site.operands)} array "
            f"operands passed for {len(site.in_specs)} in_specs")
    if site.body is not None:
        for i, osh in enumerate(site.out_shapes):
            if not isinstance(osh, SDSV) or osh.dtype is None:
                continue
            stored = site.body.out_store_dtype(site, i)
            if stored is not None and stored != osh.dtype:
                yield Finding(
                    module.path, site.call_node.lineno,
                    site.call_node.col_offset + 1,
                    "kernel-blockspec-consistency",
                    f"{wrapper} [{site.variant}]: out_shape[{i}] declares "
                    f"{osh.dtype} but the kernel body finally stores "
                    f"{stored}")


# -- kernel-dtype-flow ----------------------------------------------------

_MXU_OK = {("bfloat16", "bfloat16"): "float32", ("int8", "int8"): "int32"}


@rule(
    "kernel-dtype-flow",
    "registered fused kernels must score (bf16,bf16)->f32 or "
    "(int8,int8)->int32 on the MXU and popcount unsigned words — an f32 "
    "operand reaching a dot runs at silent half rate",
    "raft_tpu/ modules declaring KERNEL_ENVELOPES",
)
def check_dtype_flow(module: Module) -> Iterator[Finding]:
    if not _in_scope(module.path):
        return
    ana = analyze_module(module)
    if ana.registry is None:
        return
    seen: Set[Tuple] = set()
    for wrapper in sorted(ana.registry):
        for site in ana.sites.get(wrapper) or []:
            if site.body is None:
                continue
            for d in site.body.dots:
                if d.lhs is None or d.rhs is None:
                    continue
                pref = _MXU_OK.get((d.lhs, d.rhs))
                if pref is None:
                    msg = (f"{wrapper} [{site.variant}]: MXU dot scores "
                           f"({d.lhs}, {d.rhs}) operands — fused kernels "
                           f"must score (bfloat16, bfloat16)->float32 or "
                           f"(int8, int8)->int32; an implicit upcast also "
                           f"inflates real VMEM past the envelope's charge")
                elif d.preferred is not None and d.preferred != pref:
                    msg = (f"{wrapper} [{site.variant}]: ({d.lhs}, {d.rhs}) "
                           f"dot must accumulate to {pref}, not "
                           f"{d.preferred}")
                else:
                    continue
                key = (d.node.lineno, d.node.col_offset, msg)
                if key not in seen:
                    seen.add(key)
                    yield Finding(module.path, d.node.lineno,
                                  d.node.col_offset + 1,
                                  "kernel-dtype-flow", msg)
            for p in site.body.popcounts:
                if p.dtype is not None and not p.dtype.startswith("uint"):
                    msg = (f"{wrapper} [{site.variant}]: population_count "
                           f"over {p.dtype} — bit-plane scans popcount "
                           f"uint32 words")
                    key = (p.node.lineno, p.node.col_offset, msg)
                    if key not in seen:
                        seen.add(key)
                        yield Finding(module.path, p.node.lineno,
                                      p.node.col_offset + 1,
                                      "kernel-dtype-flow", msg)


# -- dispatch-envelope-guard ----------------------------------------------

#: direct entry points into the fused kernel family: the ops kernels and
#: the matrix/select_k dispatch doors
ROUTING_FUNCS = {"fused_topk", "fused_list_topk", "fused_list_topk_int8",
                 "list_scan_select_k"}

#: envelope validations (direct names; transitive callers found by
#: summary fixpoint over the project call graph)
CHECK_FUNCS = {"fits_fused", "fits_fused_list", "check_fused_list_request"}

#: strategy literals that name a fused engine in a dispatch branch
FUSED_LITERALS = {"fused", "fused_int8"}


def _guard_scope(path: str) -> bool:
    # ops/ is the kernel layer itself; matrix/neighbors/comms are where
    # routing decisions live
    return path.startswith("raft_tpu/") and not path.startswith(
        "raft_tpu/ops/")


Cond = Tuple[str, str]  # (ast.dump of the test, "then"|"else")


class _FnFacts:
    """Lexical facts of one top-level function: routing calls, envelope
    tokens, and name assignments — each with its branch-condition set."""

    def __init__(self):
        self.routing: List[Tuple[ast.Call, frozenset, int]] = []
        self.tokens: List[Tuple[frozenset, int]] = []
        self.assigns: Dict[str, List[Tuple[ast.AST, frozenset, int]]] = {}
        self.refs: Dict[str, List[Tuple[frozenset, int]]] = {}
        self.cond_nodes: Dict[str, ast.AST] = {}


def _collect(fn: ast.AST, module_path: str, is_check) -> _FnFacts:
    facts = _FnFacts()

    def walk(node, conds):
        if isinstance(node, ast.If):
            walk(node.test, conds)
            key = (ast.dump(node.test), "then")
            facts.cond_nodes[key[0]] = node.test
            for s in node.body:
                walk(s, conds | {key})
            for s in node.orelse:
                walk(s, conds | {(ast.dump(node.test), "else")})
            return
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    facts.assigns.setdefault(t.id, []).append(
                        (node.value, conds, node.lineno))
                elif isinstance(t, ast.Tuple) \
                        and isinstance(node.value, ast.Tuple) \
                        and len(t.elts) == len(node.value.elts):
                    # `fused_kb, strat = None, "xla"` — pairwise
                    for te, ve in zip(t.elts, node.value.elts):
                        if isinstance(te, ast.Name):
                            facts.assigns.setdefault(te.id, []).append(
                                (ve, conds, node.lineno))
            walk(node.value, conds)
            return
        if isinstance(node, ast.Call):
            name = terminal_name(node.func)
            if name in CHECK_FUNCS or is_check(node, module_path):
                facts.tokens.append((conds, node.lineno))
            if name in ROUTING_FUNCS:
                facts.routing.append((node, conds, node.lineno))
            elif name is not None:
                facts.refs.setdefault(name, []).append((conds, node.lineno))
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            facts.refs.setdefault(node.id, []).append(
                (conds, node.lineno))
        for child in ast.iter_child_nodes(node):
            walk(child, conds)

    for stmt in (fn.body if isinstance(fn, _FUNCS) else [fn]):
        walk(stmt, frozenset())
    return facts


def _token_covers(facts: _FnFacts, conds: frozenset, line: int) -> bool:
    """A token whose conditions all hold wherever `conds` hold, emitted
    no later in the source — the check-then-route idiom."""
    return any(tc <= conds and tl <= line for tc, tl in facts.tokens)


def _strategy_guarded(facts: _FnFacts, conds: frozenset, is_check,
                      module_path: str) -> bool:
    """A branch on `<name> == "<fused literal>"` (or `in (...)`) guards
    the call when every reaching assignment of <name> is benign: a
    non-fused literal, a resolver that validates the envelope, or a
    fused literal assigned under a token."""
    for dump, pol in conds:
        if pol != "then":
            continue
        test = facts.cond_nodes.get(dump)
        name = _strategy_test_name(facts, test)
        if name is None:
            continue
        assigns = facts.assigns.get(name)
        if not assigns:
            continue
        if all(_assign_ok(facts, v, c, ln, is_check, module_path)
               for v, c, ln in assigns):
            return True
    return False


def _strategy_test_name(facts: _FnFacts, test,
                        depth: int = 0) -> Optional[str]:
    """The strategy variable a branch tests: ``strat == "fused_..."``
    directly, or (one level) a boolean flag whose every assignment is
    such a comparison (``use_fused = strat == "fused_int8"``)."""
    if isinstance(test, ast.Name) and depth == 0:
        inner = {
            _strategy_test_name(facts, v, 1)
            for v, _c, _l in facts.assigns.get(test.id, ())
        }
        if len(inner) == 1 and None not in inner:
            return inner.pop()
        return None
    if not isinstance(test, ast.Compare) or len(test.ops) != 1 \
            or not isinstance(test.left, ast.Name):
        return None
    if not isinstance(test.ops[0], (ast.Eq, ast.In)):
        return None
    cmp = test.comparators[0]
    lits = set()
    if isinstance(cmp, ast.Constant) and isinstance(cmp.value, str):
        lits.add(cmp.value)
    elif isinstance(cmp, (ast.Tuple, ast.List, ast.Set)):
        for e in cmp.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                lits.add(e.value)
    return test.left.id if lits & FUSED_LITERALS else None


def _assign_ok(facts, value, conds, line, is_check, module_path) -> bool:
    if isinstance(value, ast.Constant):
        if isinstance(value.value, str) and value.value in FUSED_LITERALS:
            return _token_covers(facts, conds, line)
        return True  # a non-fused literal can't select the fused branch
    if isinstance(value, ast.Call):
        name = terminal_name(value.func)
        if name in CHECK_FUNCS or is_check(value, module_path):
            return True
    return False


@project_rule(
    "dispatch-envelope-guard",
    "a call routing into the fused kernel family is not covered by the "
    "matching fits_*/check_* envelope validation on every path",
    "raft_tpu/ (matrix dispatch, neighbors/, comms/mnmg_*)",
)
def check_dispatch_envelope_guard(modules, repo_root) -> Iterator[Finding]:
    from tools.raftlint.project import project_index

    index = project_index(modules)

    # summary fixpoint: which project functions transitively reach an
    # envelope check
    has_check: Set[str] = set()
    direct: Dict[str, Set[str]] = {}
    for q, info in index.functions.items():
        callees: Set[str] = set()
        hit = False
        for node in ast.walk(info.node):
            if isinstance(node, ast.Call):
                if terminal_name(node.func) in CHECK_FUNCS:
                    hit = True
                callees.update(index.resolve_call(info.module, node.func,
                                                  cls=info.cls))
        direct[q] = callees
        if hit:
            has_check.add(q)
    for _ in range(10):
        grew = False
        for q, callees in direct.items():
            if q not in has_check and callees & has_check:
                has_check.add(q)
                grew = True
        if not grew:
            break

    def is_check(call: ast.Call, module_path: str) -> bool:
        return any(q in has_check
                   for q in index.resolve_call(module_path, call.func))

    # per-function lexical facts, lazily
    facts_cache: Dict[int, _FnFacts] = {}

    def facts_of(fn: ast.AST, module_path: str) -> _FnFacts:
        f = facts_cache.get(id(fn))
        if f is None:
            f = _collect(fn, module_path, is_check)
            facts_cache[id(fn)] = f
        return f

    scope_mods = [m for m in modules if _guard_scope(m.path)]
    # top-level functions per module (methods included)
    fns_by_mod: Dict[str, List[ast.AST]] = {}
    for m in scope_mods:
        fns = []
        for node in m.tree.body:
            if isinstance(node, _FUNCS):
                fns.append(node)
            elif isinstance(node, ast.ClassDef):
                fns.extend(x for x in node.body if isinstance(x, _FUNCS))
        fns_by_mod[m.path] = fns

    def fn_guarded(fn: ast.AST, module_path: str, conds: frozenset,
                   line: int, depth: int, seen: Set[str]) -> bool:
        facts = facts_of(fn, module_path)
        if _token_covers(facts, conds, line):
            return True
        if _strategy_guarded(facts, conds, is_check, module_path):
            return True
        # propagate to the callers of a private impl: every reference
        # site must itself be guarded
        if not fn.name.startswith("_") or depth >= 3:
            return False
        qname = f"{module_path}::{fn.name}"
        if qname in seen:
            return False
        seen = seen | {qname}
        sites: List[Tuple[ast.AST, str, frozenset, int]] = []
        for m in scope_mods:
            for outer in fns_by_mod[m.path]:
                of = facts_of(outer, m.path)
                for conds2, line2 in of.refs.get(fn.name, ()):
                    # the name must actually resolve to this function
                    # from that module (same module or a followed import)
                    if m.path != module_path and not _imports_symbol(
                            index, m.path, fn.name, qname):
                        continue
                    sites.append((outer, m.path, conds2, line2))
        if not sites:
            return True  # no visible callers: silence, never a guess
        return all(fn_guarded(outer, mp, c2, l2, depth + 1, seen)
                   for outer, mp, c2, l2 in sites)

    for m in scope_mods:
        for fn in fns_by_mod[m.path]:
            if fn.name in ROUTING_FUNCS:
                continue  # the dispatch door itself: callers carry it
            facts = facts_of(fn, m.path)
            for call, conds, line in facts.routing:
                if not fn_guarded(fn, m.path, conds, line, 0, set()):
                    name = terminal_name(call.func)
                    yield Finding(
                        m.path, call.lineno, call.col_offset + 1,
                        "dispatch-envelope-guard",
                        f"call to {name} is not covered by its "
                        f"fits_*/check_* envelope validation on every "
                        f"path — explicit past-envelope requests must "
                        f"raise (PR-10/11 contract); add the guard or a "
                        f"justified pragma")


def _imports_symbol(index, module_path: str, name: str, qname: str) -> bool:
    imp = index.imports.get(module_path, {}).get(name)
    if imp is None or imp[0] != "symbol":
        return False
    return f"{imp[1].replace('.', '/')}.py::{imp[2]}" == qname

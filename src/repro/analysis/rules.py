"""latlint rules L001–L005 and L007 (AST checks; L006 lives in kernel_lint).

Each rule encodes a convention the repo's determinism or safety story
depends on; see the module docstring of :mod:`repro.analysis` for the
one-line summaries and ROADMAP "Conventions" for the rationale.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .latlint import LintContext, Rule, SourceFile, Violation

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def import_maps(tree: ast.AST) -> Tuple[Dict[str, str], Dict[str, Tuple[str, str]]]:
    """``(module_aliases, from_imports)``: ``import time as t`` yields
    ``{"t": "time"}``; ``from time import time as now`` yields
    ``{"now": ("time", "time")}``."""
    mod_alias: Dict[str, str] = {}
    from_imports: Dict[str, Tuple[str, str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                mod_alias[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                from_imports[a.asname or a.name] = (node.module or "", a.name)
    return mod_alias, from_imports


def terminal_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _own_body_walk(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body without descending into nested function scopes."""
    stack = list(getattr(fn, "body", []))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def is_generator_fn(fn: ast.AST) -> bool:
    return any(isinstance(n, (ast.Yield, ast.YieldFrom))
               for n in _own_body_walk(fn))


def enclosing_function(tree: ast.AST, target: ast.AST) -> Optional[ast.AST]:
    """Innermost FunctionDef whose subtree contains ``target``."""
    best: Optional[ast.AST] = None

    def visit(node: ast.AST, current: Optional[ast.AST]) -> bool:
        nonlocal best
        if node is target:
            best = current
            return True
        nxt = node if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)) else current
        return any(visit(child, nxt) for child in ast.iter_child_nodes(node))

    visit(tree, None)
    return best


# ---------------------------------------------------------------------------
# L001 — wall-clock / global random
# ---------------------------------------------------------------------------

_TIME_FNS = {"time", "monotonic", "monotonic_ns", "time_ns",
             "perf_counter", "perf_counter_ns", "process_time"}
_RANDOM_FNS = {"random", "randint", "uniform", "choice", "choices", "shuffle",
               "sample", "randrange", "getrandbits", "gauss", "expovariate",
               "betavariate", "normalvariate", "triangular", "seed",
               "randbytes", "vonmisesvariate", "paretovariate"}
_DATETIME_NOW = {"now", "utcnow", "today"}


class WallClockRule(Rule):
    """``core/tracing.py`` is exempt: it stamps the host's clock on spans
    for operators and benchmarks, and the program never reads a record
    back, so the host's time cannot reach the simulation."""

    id = "L001"
    title = "no wall-clock or module-global random in sim-executing code"

    def applies(self, rel: str) -> bool:
        return not rel.endswith("core/tracing.py")

    def check(self, sf: SourceFile, ctx: LintContext) -> Iterable[Violation]:
        mod_alias, from_imports = import_maps(sf.tree)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
                base = mod_alias.get(func.value.id)
                if base == "time" and func.attr in _TIME_FNS:
                    yield self.violation(
                        sf, node, f"wall-clock time.{func.attr}() — "
                        "sim-executing code must use sim.now")
                elif base == "random" and func.attr in _RANDOM_FNS:
                    yield self.violation(
                        sf, node, f"module-global random.{func.attr}() — "
                        "use the Sim's seeded Random (sim.rng)")
                elif (func.attr in _DATETIME_NOW and not node.args
                      and not node.keywords
                      and self._is_datetime(func.value, mod_alias,
                                            from_imports)):
                    yield self.violation(
                        sf, node, f"argless datetime.{func.attr}() reads the "
                        "wall clock — derive timestamps from sim.now")
            elif (isinstance(func, ast.Attribute)
                  and isinstance(func.value, ast.Attribute)
                  and isinstance(func.value.value, ast.Name)
                  and func.attr in _DATETIME_NOW
                  and not node.args and not node.keywords
                  and mod_alias.get(func.value.value.id) == "datetime"
                  and func.value.attr in ("datetime", "date")):
                yield self.violation(
                    sf, node, f"argless datetime.{func.attr}() reads the "
                    "wall clock — derive timestamps from sim.now")
            elif isinstance(func, ast.Name):
                origin = from_imports.get(func.id)
                if origin is None:
                    continue
                module, name = origin
                if module == "time" and name in _TIME_FNS:
                    yield self.violation(
                        sf, node, f"wall-clock {name}() (from time) — "
                        "sim-executing code must use sim.now")
                elif module == "random" and name in _RANDOM_FNS:
                    yield self.violation(
                        sf, node, f"module-global {name}() (from random) — "
                        "use the Sim's seeded Random (sim.rng)")

    @staticmethod
    def _is_datetime(value: ast.Name, mod_alias: Dict[str, str],
                     from_imports: Dict[str, Tuple[str, str]]) -> bool:
        if from_imports.get(value.id, ("", ""))[0] == "datetime":
            return True
        return mod_alias.get(value.id) == "datetime"


# ---------------------------------------------------------------------------
# L002 — raw RPC plane
# ---------------------------------------------------------------------------

_RAW_RPC = {"register_unary", "call_unary"}
_L002_EXEMPT = ("core/service.py", "core/rpc.py")


class RawRpcRule(Rule):
    id = "L002"
    title = "no raw register_unary/call_unary outside core/service.py"

    def applies(self, rel: str) -> bool:
        return not rel.endswith(_L002_EXEMPT)

    def check(self, sf: SourceFile, ctx: LintContext) -> Iterable[Violation]:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                name = terminal_name(node.func)
                if name in _RAW_RPC:
                    yield self.violation(
                        sf, node, f"raw {name}() bypasses the typed service "
                        "plane — declare a Service with @unary/@streaming "
                        "MethodSpecs instead")


# ---------------------------------------------------------------------------
# L003 — unsafe deserialization
# ---------------------------------------------------------------------------

_PICKLE_LOADERS = {"load", "loads", "Unpickler"}


class PickleRule(Rule):
    id = "L003"
    title = "no pickle.load(s) outside core/safepickle.py"

    def applies(self, rel: str) -> bool:
        return not rel.endswith("core/safepickle.py")

    def check(self, sf: SourceFile, ctx: LintContext) -> Iterable[Violation]:
        mod_alias, from_imports = import_maps(sf.tree)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name: Optional[str] = None
            if (isinstance(func, ast.Attribute)
                    and isinstance(func.value, ast.Name)
                    and mod_alias.get(func.value.id) == "pickle"
                    and func.attr in _PICKLE_LOADERS):
                name = f"pickle.{func.attr}"
            elif isinstance(func, ast.Name):
                origin = from_imports.get(func.id)
                if (origin is not None and origin[0] == "pickle"
                        and origin[1] in _PICKLE_LOADERS):
                    name = f"pickle.{origin[1]}"
            if name is not None:
                yield self.violation(
                    sf, node, f"{name} deserializes arbitrary objects — "
                    "peer-supplied bytes must go through "
                    "core/safepickle.restricted_loads")


# ---------------------------------------------------------------------------
# L004 — hedging requires idempotency (cross-file)
# ---------------------------------------------------------------------------

_SPEC_DECORATORS = {"unary", "streaming"}
_HEDGE_WRAPPERS = {"hedged_call"}


def index_method_specs(ctx: LintContext) -> None:
    """Record every ``@unary``/``@streaming`` declaration: both the python
    method name and the wire name map to the declared ``idempotent`` flag.
    Conflicting duplicate declarations collapse to False (conservative)."""
    for sf in ctx.files:
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                if not (isinstance(dec, ast.Call)
                        and terminal_name(dec.func) in _SPEC_DECORATORS):
                    continue
                idem = False
                for kw in dec.keywords:
                    if (kw.arg == "idempotent"
                            and isinstance(kw.value, ast.Constant)):
                        idem = bool(kw.value.value)
                names = [node.name]
                if dec.args and isinstance(dec.args[0], ast.Constant) \
                        and isinstance(dec.args[0].value, str):
                    names.append(dec.args[0].value)
                for n in names:
                    if n in ctx.method_idempotency:
                        ctx.method_idempotency[n] = (
                            ctx.method_idempotency[n] and idem)
                    else:
                        ctx.method_idempotency[n] = idem


class HedgedIdempotentRule(Rule):
    id = "L004"
    title = "hedged_call only over idempotent MethodSpecs"

    def check(self, sf: SourceFile, ctx: LintContext) -> Iterable[Violation]:
        hedge_sites = [n for n in ast.walk(sf.tree)
                       if isinstance(n, ast.Call)
                       and terminal_name(n.func) in _HEDGE_WRAPPERS]
        for site in hedge_sites:
            scope = enclosing_function(sf.tree, site) or sf.tree
            flagged: Set[str] = set()
            for node in ast.walk(scope):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    continue
                method = node.func.attr
                if method in flagged:
                    continue
                idem = ctx.method_idempotency.get(method)
                if idem is False:
                    flagged.add(method)
                    yield self.violation(
                        sf, site, f"hedged_call in a scope invoking "
                        f"'{method}', whose MethodSpec does not declare "
                        "idempotent=True — hedging can execute it twice")


# ---------------------------------------------------------------------------
# L005 — generator-process hygiene (cross-file)
# ---------------------------------------------------------------------------


def index_generators(ctx: LintContext) -> None:
    """Names that are *unambiguously* generator functions: every definition
    with that name in the scanned set contains a yield.  Ambiguous names
    (e.g. ``send`` — generator on RpcChannel, plain method on Stream) are
    excluded so the rule cannot misfire on plain calls."""
    defs: Dict[str, List[bool]] = {}
    for sf in ctx.files:
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(is_generator_fn(node))
    ctx.generator_only_names = {name for name, flags in defs.items()
                                if all(flags)}


class OrphanGeneratorRule(Rule):
    id = "L005"
    title = "bare call of a yield-protocol function is never driven"

    def check(self, sf: SourceFile, ctx: LintContext) -> Iterable[Violation]:
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Expr)
                    and isinstance(node.value, ast.Call)):
                continue
            name = terminal_name(node.value.func)
            if name in ctx.generator_only_names:
                yield self.violation(
                    sf, node, f"bare call of generator function '{name}' "
                    "creates a generator nothing will drive — use "
                    "`yield from {0}(...)` or `sim.process({0}(...))`"
                    .format(name))


# ---------------------------------------------------------------------------
# L007 — O(keys) flat summary construction outside the Merkle path
# ---------------------------------------------------------------------------

_L007_EXEMPT = ("core/crdt.py",)


class FlatSummaryRule(Rule):
    id = "L007"
    title = "no flat O(keys) key_digests() summary outside core/crdt.py"

    def applies(self, rel: str) -> bool:
        return not rel.endswith(_L007_EXEMPT)

    def check(self, sf: SourceFile, ctx: LintContext) -> Iterable[Violation]:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                name = terminal_name(node.func)
                if name == "key_digests":
                    yield self.violation(
                        sf, node, "key_digests() builds an O(keys) flat "
                        "summary every call — sync probes should walk "
                        "summary_forest()/summary_roots() (O(log n) MST "
                        "localization); waive only where the flat v2/v1 "
                        "wire surface for old peers is the point")

"""WARM001 — static warmup coverage of the jit dispatch key space.

The flight recorder proves ``compiles_after_warmup_total == 0`` *dynamically*
— but only for the key space a bench run happens to exercise. This rule is
the static twin: every ``record_exec("<kind>", <key>)`` dispatch site on the
serving paths of the warmup-scope files must have a matching registration
inside ``Scheduler.warmup()`` (or a helper it calls), with a compatible key
arity. A serving kind warmup never touches is a guaranteed mid-traffic
compile the moment that path first fires — exactly the regression class the
0-compile invariant exists to prevent.

Key arities are derived from the key expression: tuple literals count their
elements, ``+``-concatenation sums, conditional suffixes like
``+ ((flag,) if cond else ())`` produce arity *sets* ({4, 5}), and names
resolve through local tuple assignments. A serving site and its warmup twin
agree when their arity sets intersect (the recorder keys executables by
``(kind,) + tuple(key)``, so kind+arity is the static shape of the key
space; the element *values* are runtime rungs the bench still covers).

``static_warmup_report()`` exports the same enumeration for the tests, which
cross-check it against the recorder's dynamically observed executable keys
— the static and dynamic views of the 0-compile invariant must agree.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.dtlint.callgraph import project_graph, split_gid
from tools.dtlint.core import (
    Finding, LintConfig, ProjectIndex, dotted, enclosing_map, qualname_at,
    rule,
)


def _tuple_arities(expr: ast.AST, local_tuples: Dict[str, Set[int]]) -> Optional[Set[int]]:
    """Possible element counts of a tuple-valued key expression, or None
    when the shape is not statically evident."""
    if isinstance(expr, ast.Tuple):
        if any(isinstance(e, ast.Starred) for e in expr.elts):
            return None
        return {len(expr.elts)}
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        l = _tuple_arities(expr.left, local_tuples)
        r = _tuple_arities(expr.right, local_tuples)
        if l is None or r is None:
            return None
        return {a + b for a in l for b in r}
    if isinstance(expr, ast.IfExp):
        l = _tuple_arities(expr.body, local_tuples)
        r = _tuple_arities(expr.orelse, local_tuples)
        if l is None or r is None:
            return None
        return l | r
    if isinstance(expr, ast.Name):
        return local_tuples.get(expr.id)
    if isinstance(expr, ast.Call) and dotted(expr.func) == "tuple" and expr.args:
        return _tuple_arities(expr.args[0], local_tuples)
    return None


def _local_tuple_arities(fn: ast.AST) -> Dict[str, Set[int]]:
    """{var: arity set} for locals assigned tuple literals (handles the
    ``mixed_key = (a, b, c, d)`` then ``mixed_key + (...)`` pattern)."""
    out: Dict[str, Set[int]] = {}
    for _ in range(2):  # second pass resolves tuple-from-tuple chains
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                ar = _tuple_arities(node.value, out)
                if ar is not None:
                    out[node.targets[0].id] = ar
    return out


class DispatchSite:
    __slots__ = ("kind", "file", "line", "qualname", "arities")

    def __init__(self, kind: str, file: str, line: int, qualname: str,
                 arities: Optional[Set[int]]) -> None:
        self.kind = kind
        self.file = file
        self.line = line
        self.qualname = qualname
        self.arities = arities


def _collect_sites(index: ProjectIndex) -> List[DispatchSite]:
    cfg = index.config
    sites: List[DispatchSite] = []
    for mod in index.modules:
        if mod.relpath not in cfg.warmup_scopes and not any(
            mod.relpath.endswith("/" + s) for s in cfg.warmup_scopes
        ):
            continue
        line_map = enclosing_map(mod.tree)
        fn_arities: Dict[str, Dict[str, Set[int]]] = {}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            if not name or name.split(".")[-1] != "record_exec":
                continue
            if len(node.args) < 1:
                continue
            karg = node.args[0]
            if not (isinstance(karg, ast.Constant) and isinstance(karg.value, str)):
                continue
            q = qualname_at(line_map, node.lineno)
            if q not in fn_arities:
                fn = None
                for fq, f in _functions_cache(mod):
                    if fq == q:
                        fn = f
                        break
                fn_arities[q] = _local_tuple_arities(fn) if fn is not None else {}
            arities = (_tuple_arities(node.args[1], fn_arities[q])
                       if len(node.args) > 1 else None)
            sites.append(DispatchSite(karg.value, mod.relpath, node.lineno, q, arities))
    return sites


_FN_CACHE: Dict[int, List[Tuple[str, ast.AST]]] = {}


def _functions_cache(mod) -> List[Tuple[str, ast.AST]]:
    from tools.dtlint.core import iter_functions

    key = id(mod)
    if key not in _FN_CACHE:
        if len(_FN_CACHE) > 64:
            _FN_CACHE.clear()
        _FN_CACHE[key] = list(iter_functions(mod.tree))
    return _FN_CACHE[key]


def _warmup_closure(index: ProjectIndex) -> Set[Tuple[str, str]]:
    """(relpath, qualname) pairs reachable from the warmup entry point —
    registrations inside helpers warmup calls count as warmed."""
    cfg = index.config
    pg = project_graph(index)
    roots = []
    for g, info in pg.funcs.items():
        relpath, q = split_gid(g)
        if q == cfg.warmup_func and any(
            relpath == s or relpath.endswith("/" + s) for s in cfg.warmup_scopes
        ):
            roots.append(g)
    return {split_gid(g) for g in pg.reachable(roots)}


def enumerate_warmup(index: ProjectIndex):
    """(warmed {kind: arity set}, serving [DispatchSite]) over the
    warmup-scope files."""
    sites = _collect_sites(index)
    closure = _warmup_closure(index)
    warmed: Dict[str, Set[int]] = {}
    serving: List[DispatchSite] = []
    for s in sites:
        if (s.file, s.qualname) in closure:
            cur = warmed.setdefault(s.kind, set())
            if s.arities:
                cur |= s.arities
        else:
            serving.append(s)
    return warmed, serving


@rule("WARM001", "serving-path jit dispatch keys (record_exec kinds/arities) not pre-registered by Scheduler.warmup()")
def warm001(index: ProjectIndex) -> List[Finding]:
    warmed, serving = enumerate_warmup(index)
    if not warmed and not serving:
        return []
    findings: List[Finding] = []
    for s in serving:
        mod = index.module(s.file)
        if mod is not None and mod.suppressed("WARM001", s.line):
            continue
        if s.kind not in warmed:
            findings.append(Finding(
                "WARM001", s.file, s.line, s.qualname,
                f"dispatch kind '{s.kind}' is never registered by warmup() — "
                f"the first request on this path compiles mid-traffic "
                f"(breaks the 0-post-warmup-compiles invariant)",
                key=f"unwarmed:{s.kind}",
            ))
            continue
        warm_ar = warmed[s.kind]
        if s.arities and warm_ar and not (s.arities & warm_ar):
            findings.append(Finding(
                "WARM001", s.file, s.line, s.qualname,
                f"dispatch kind '{s.kind}' keys {sorted(s.arities)}-tuples "
                f"here but warmup() registers {sorted(warm_ar)}-tuples — "
                f"the serving key shape can never hit the warmed executable",
                key=f"arity:{s.kind}",
            ))
    return findings


def static_warmup_report(root: str) -> dict:
    """Test-facing export: the statically enumerated warmup key space.

    ``{"warmed": {kind: [arities]}, "serving": {kind: [arities]}}`` —
    ``tests/test_dtlint.py`` holds ``serving`` inside ``warmed``, and
    ``tests/test_decode_paths.py`` a warmed scheduler's compiled
    kinds/arities inside ``warmed``, closing the loop between this rule
    and the runtime 0-compile gate. Pure ast, no JAX import.
    """
    index = ProjectIndex(LintConfig(root=root))
    warmed, serving = enumerate_warmup(index)
    serving_k: Dict[str, Set[int]] = {}
    for s in serving:
        cur = serving_k.setdefault(s.kind, set())
        if s.arities:
            cur |= s.arities
    return {
        "warmed": {k: sorted(v) for k, v in sorted(warmed.items())},
        "serving": {k: sorted(v) for k, v in sorted(serving_k.items())},
    }

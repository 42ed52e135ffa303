"""SYNC001 — blocking device syncs inside scheduler/engine hot paths.

A steady-state decode dispatch performs exactly one blocking host↔device
sync (its sampled-token readback: one a step, or one a window). Every
extra ``np.asarray``/``float()``/``.item()``/``jax.device_get``/
``.block_until_ready()`` on a device value serializes the host against
the device once more — and the regression is invisible until a chip run
measures the idle share.

The rule scopes to the hot-path functions named in
``tools/dtlint/sync_allowlist.json`` and classifies every local name as
HOST / DEVICE / UNKNOWN with a small per-function taint pass:

- DEVICE: results of ``jnp.*``/``jax.*`` calls (except ``device_get``),
  calls through ``*_jit`` wrappers, params annotated ``jax.Array``.
- HOST: ``np.*`` results, literals/displays/comprehensions, ``len``,
  ``time.*``, ``jax.device_get`` results, params annotated with host
  types (int/float/bool/str/List/...).
- UNKNOWN: everything else (attribute reads, helper returns).

``block_until_ready``/``device_get`` always flag; ``np.asarray``/
``np.array`` flag on DEVICE **and UNKNOWN** arguments (guilty until
proven host — in these few functions a wrongly-accused host copy is a
one-line allowlist entry, a missed device sync is a perf regression);
``float``/``int``/``.item``/``.tolist`` flag on DEVICE only.

The allowlist file names each *sanctioned* sync — (file, func, call) with
a role and a reason. The ``role: "per_step"`` entries are the statically
declared 1-sync-per-step budget (``tests/test_dtlint.py`` holds each
path to at most one).
"""

from __future__ import annotations

import ast
import json
import os
from typing import Callable, Dict, List, Optional

from tools.dtlint.callgraph import project_graph
from tools.dtlint.core import Finding, ProjectIndex, dotted, iter_functions, rule

HOST = "host"
DEVICE = "device"
UNKNOWN = "unknown"

_ALWAYS_SYNC = {"block_until_ready"}
_DEVICE_GET = {"jax.device_get", "device_get"}
_COPYING = {"np.asarray", "np.array", "numpy.asarray", "numpy.array", "onp.asarray"}
_NARROWING = {"float", "int", "bool"}
_NARROWING_METHODS = {"item", "tolist"}

_HOST_ANN = {"int", "float", "bool", "str", "bytes", "list", "dict", "set",
             "tuple", "optional", "sequence", "iterable", "callable"}
_DEVICE_ANN_HINTS = ("jax.array", "jnp.ndarray", "jax.numpy", "array")


def load_sync_config(path: str) -> dict:
    if not os.path.exists(path):
        return {"hot_paths": {}, "allowed_syncs": []}
    with open(path) as f:
        return json.load(f)


def _classify_call(call: ast.Call) -> str:
    name = dotted(call.func)
    if not name:
        return UNKNOWN
    if name in _DEVICE_GET or name in _COPYING or name.startswith("np."):
        return HOST
    if name in ("len", "range", "sum", "min", "max", "sorted", "list", "tuple",
                "dict", "set", "zip", "enumerate", "round", "abs"):
        return HOST
    if name.startswith(("time.", "os.", "math.")):
        return HOST
    if name.startswith(("jnp.", "jax.", "lax.")):
        return DEVICE
    if name.split(".")[-1].endswith("_jit"):
        return DEVICE
    return UNKNOWN


def _classify_expr(expr: ast.AST, taint: Dict[str, str],
                   call_cls: Callable[[ast.Call], str] = _classify_call) -> str:
    if isinstance(expr, ast.Constant):
        return HOST
    if isinstance(expr, (ast.List, ast.Tuple, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp, ast.GeneratorExp, ast.JoinedStr)):
        return HOST
    if isinstance(expr, ast.Call):
        return call_cls(expr)
    if isinstance(expr, ast.Name):
        return taint.get(expr.id, UNKNOWN)
    if isinstance(expr, ast.Subscript):
        return _classify_expr(expr.value, taint, call_cls)
    if isinstance(expr, ast.BinOp):
        l = _classify_expr(expr.left, taint, call_cls)
        r = _classify_expr(expr.right, taint, call_cls)
        if DEVICE in (l, r):
            return DEVICE
        if UNKNOWN in (l, r):
            return UNKNOWN
        return HOST
    if isinstance(expr, ast.Compare) or isinstance(expr, ast.BoolOp):
        return HOST
    if isinstance(expr, ast.Attribute):
        # self.cache.k and friends: resident device buffers.
        base = dotted(expr)
        if ".cache." in f".{base}." or base.endswith((".k", ".v")):
            return DEVICE if base.startswith("self.") else UNKNOWN
        return UNKNOWN
    return UNKNOWN


def _ann_class(ann: Optional[ast.AST]) -> str:
    if ann is None:
        return UNKNOWN
    name = dotted(ann)
    if not name and isinstance(ann, ast.Subscript):
        name = dotted(ann.value)
    if not name and isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        name = ann.value
    low = (name or "").lower()
    if any(h in low for h in _DEVICE_ANN_HINTS):
        return DEVICE
    if low.split(".")[-1] in _HOST_ANN:
        return HOST
    return UNKNOWN


def _taint_function(fn: ast.AST,
                    call_cls: Callable[[ast.Call], str] = _classify_call) -> Dict[str, str]:
    taint: Dict[str, str] = {}
    a = fn.args
    for p in list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs):
        taint[p.arg] = _ann_class(p.annotation)
    # Two passes: later assignments may reference earlier names.
    for _ in range(2):
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                cls = _classify_expr(node.value, taint, call_cls)
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        taint[tgt.id] = cls
                    elif isinstance(tgt, ast.Tuple):
                        for el in tgt.elts:
                            if isinstance(el, ast.Name):
                                taint[el.id] = cls
            elif isinstance(node, ast.For) and isinstance(node.target, ast.Name):
                taint.setdefault(node.target.id, HOST)
    return taint


@rule("SYNC001", "blocking device syncs in hot-path functions outside the sanctioned allowlist")
def sync001(index: ProjectIndex) -> List[Finding]:
    allowlist_path = index.config.abspath(index.config.sync_allowlist_path)
    cfg = load_sync_config(allowlist_path)
    hot_paths: Dict[str, List[str]] = cfg.get("hot_paths", {})
    allowed = {
        (e["file"], e["func"], e["call"]): e
        for e in cfg.get("allowed_syncs", [])
    }

    pg = project_graph(index)
    ret_classes = pg.infer_return_classes()

    findings: List[Finding] = []
    # Allowlist entries can only shrink: every (file, func, call) must still
    # name an existing hot-path function containing that call, else the
    # entry is stale and fails the run (same semantics as a stale baseline).
    findings.extend(_validate_allowlist(index, cfg))

    for mod in index.modules:
        hot_funcs = None
        for file_key, funcs in hot_paths.items():
            if mod.relpath == file_key or mod.relpath.endswith("/" + file_key):
                hot_funcs = set(funcs)
                break
        if not hot_funcs:
            continue
        for q, fn in iter_functions(mod.tree):
            if q not in hot_funcs:
                continue

            def call_cls(call: ast.Call, _q=q, _rel=mod.relpath) -> str:
                cls = _classify_call(call)
                if cls != UNKNOWN:
                    return cls
                # Interprocedural: helper returns classified project-wide
                # (fixpoint over the v2 graph), so `rows = self._gather()`
                # taints `rows` with _gather's cross-module return class.
                callee = pg.resolve_call(_rel, _q, dotted(call.func))
                if callee is not None:
                    return ret_classes.get(callee, UNKNOWN)
                return UNKNOWN

            taint = _taint_function(fn, call_cls)

            def emit(line: int, call_name: str, detail: str) -> None:
                if (mod.relpath, q, call_name) in allowed:
                    return
                if mod.suppressed("SYNC001", line):
                    return
                findings.append(Finding(
                    "SYNC001", mod.relpath, line, q,
                    f"blocking sync {call_name}({detail}) in hot path — the decode "
                    f"step budget is 1 sync (sync_allowlist.json names it); "
                    f"allowlist with a reason or move off the step path",
                    key=f"sync:{call_name}",
                ))

            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted(node.func)
                tail = name.split(".")[-1] if name else ""
                if tail in _ALWAYS_SYNC:
                    emit(node.lineno, "block_until_ready", dotted(node.func.value) if isinstance(node.func, ast.Attribute) else "")
                elif name in _DEVICE_GET:
                    emit(node.lineno, "jax.device_get", "")
                elif name in _COPYING and node.args:
                    cls = _classify_expr(node.args[0], taint, call_cls)
                    if cls in (DEVICE, UNKNOWN):
                        canon = "np.array" if tail == "array" else "np.asarray"
                        emit(node.lineno, canon, f"{ast.unparse(node.args[0])}: {cls}")
                elif name in _NARROWING and node.args:
                    if _classify_expr(node.args[0], taint, call_cls) == DEVICE:
                        emit(node.lineno, name, ast.unparse(node.args[0]))
                elif tail in _NARROWING_METHODS and isinstance(node.func, ast.Attribute):
                    if _classify_expr(node.func.value, taint, call_cls) == DEVICE:
                        emit(node.lineno, f".{tail}", ast.unparse(node.func.value))
    return findings


def _sync_call_names(fn: ast.AST) -> set:
    """Canonical sync-call names present in a function body, matching the
    vocabulary ``allowed_syncs`` entries use in their ``call`` field."""
    out = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        tail = name.split(".")[-1] if name else ""
        if tail in _ALWAYS_SYNC:
            out.add("block_until_ready")
        elif name in _DEVICE_GET:
            out.add("jax.device_get")
        elif name in _COPYING:
            out.add("np.array" if tail == "array" else "np.asarray")
        elif name in _NARROWING:
            out.add(name)
        elif tail in _NARROWING_METHODS:
            out.add(f".{tail}")
    return out


def _validate_allowlist(index: ProjectIndex, cfg: dict) -> List[Finding]:
    """Stale-entry detection for sync_allowlist.json ("can only shrink"):
    every hot_paths function must still exist, and every allowed_syncs
    entry must still name an in-scope function that contains the call."""
    rel = index.config.sync_allowlist_path.replace(os.sep, "/")
    hot_paths: Dict[str, List[str]] = cfg.get("hot_paths", {})
    findings: List[Finding] = []

    def funcs_of(file_key: str) -> Optional[Dict[str, ast.AST]]:
        for mod in index.modules:
            if mod.relpath == file_key or mod.relpath.endswith("/" + file_key):
                return dict(iter_functions(mod.tree))
        return None

    func_maps: Dict[str, Optional[Dict[str, ast.AST]]] = {}
    for file_key, names in hot_paths.items():
        func_maps[file_key] = fm = funcs_of(file_key)
        if fm is None:
            continue  # file not under the scanned paths this run — skip
        for fname in names:
            if fname not in fm:
                findings.append(Finding(
                    "SYNC001", rel, 1, "<allowlist>",
                    f"hot_paths names {file_key}:{fname} but no such function "
                    f"exists — stale scope entry, remove it",
                    key=f"stale-allowlist:hot:{file_key}:{fname}",
                ))
    for e in cfg.get("allowed_syncs", []):
        file_key, fname, call = e.get("file", ""), e.get("func", ""), e.get("call", "")
        fm = func_maps.get(file_key)
        if fm is None and file_key not in func_maps:
            func_maps[file_key] = fm = funcs_of(file_key)
        if fm is None:
            continue
        where = f"{file_key}:{fname}"
        if fname not in hot_paths.get(file_key, []):
            findings.append(Finding(
                "SYNC001", rel, 1, "<allowlist>",
                f"allowed_syncs entry {where} ({call}) is not in SYNC001 "
                f"scope (hot_paths) — dead exemption, remove it",
                key=f"stale-allowlist:scope:{where}:{call}",
            ))
            continue
        if fname not in fm:
            findings.append(Finding(
                "SYNC001", rel, 1, "<allowlist>",
                f"allowed_syncs entry {where} ({call}) names a function that "
                f"no longer exists — stale exemption, remove it",
                key=f"stale-allowlist:func:{where}:{call}",
            ))
            continue
        if call not in _sync_call_names(fm[fname]):
            findings.append(Finding(
                "SYNC001", rel, 1, "<allowlist>",
                f"allowed_syncs entry {where} no longer matches: {fname} "
                f"contains no {call} sync — the sanctioned sync was removed, "
                f"shrink the allowlist",
                key=f"stale-allowlist:call:{where}:{call}",
            ))
    return findings

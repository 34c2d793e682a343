"""Rules for the serving hot path and Pallas kernel hygiene.

``host-sync-in-hot-path`` guards the engine's one-sync-per-step contract:
the only device->host transfer a steady-state step is allowed is the single
int32-per-row token readback (engine ``_step_*`` docstrings).  Everything
else — ``.item()`` in a loop, an ``np.asarray`` on an intermediate, a
``float()`` on a device scalar — serializes the dispatch pipeline and turns
a ~100us step into a blocking round-trip.

``pallas-kernel-hygiene`` enforces three kernel-authoring contracts:

  * no Python ``if``/``while`` on traced values inside a kernel body
    (ref loads and ``pl.program_id`` are traced — branch with ``pl.when``
    or ``jnp.where``);
  * a wrapper that launches ``pl.pallas_call`` must carry at least one
    divisibility ``assert`` (``x % b == 0``-shaped) tying its grid to its
    block shapes — Mosaic's errors for misaligned tiles are unreadable, the
    assert is the contract surface;
  * backend/interpret dispatch belongs to ``kernels.ops`` /
    ``kernels.dispatch``: a kernel module neither hardcodes
    ``interpret=True/False`` at the ``pallas_call``, omits it (Mosaic
    crash on CPU), nor consults ``jax.default_backend()`` itself.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set

from repro.analysis.core import (
    Finding,
    SourceFile,
    assigned_names,
    dotted_name,
    rule,
    stmt_scan_roots,
    walk_statements,
)

# ------------------------------------------------- host-sync-in-hot-path ----
#: per-step engine functions: between step() entry and return, device->host
#: sync is budgeted at exactly one token readback (inline-suppressed at the
#: sanctioned line)
HOT_FN_RE = re.compile(
    r"^(_step_\w+|_ragged_exec|_decode_batch|_prefill_request|_warm_ragged)$")

#: calls that force a device->host transfer when fed a device value
_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "jax.device_get"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_CAST_BUILTINS = {"float", "int", "bool"}

def _rhs_is_hostlike(node: ast.AST, host: Set[str]) -> bool:
    """Does this RHS produce a host value (literal, np constructor, clock,
    len/int/float of host things)?"""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.Tuple,
                         ast.ListComp, ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        name = dotted_name(node.func)
        if name.startswith(("np.", "numpy.", "time.")):
            return True
        if name in ("len", "int", "float", "bool", "range", "sorted",
                    "list", "dict", "set", "tuple", "sum", "min", "max",
                    "self.clock"):
            return True
        return False
    if isinstance(node, (ast.Name, ast.Attribute)):
        name = dotted_name(node)
        return name in host
    if isinstance(node, ast.Subscript):
        return _rhs_is_hostlike(node.value, host)
    if isinstance(node, ast.BinOp):
        return _rhs_is_hostlike(node.left, host) \
            and _rhs_is_hostlike(node.right, host)
    return False


@rule("host-sync-in-hot-path",
      "device->host transfer (np.asarray / .item() / float()) inside a "
      "per-step engine function outside the sanctioned token readback")
def check_host_sync(sf: SourceFile) -> Iterable[Finding]:
    tree = sf.tree
    assert tree is not None
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not HOT_FN_RE.match(fn.name):
            continue
        yield from _check_hot_fn(sf, fn)


def _check_hot_fn(sf: SourceFile, fn: ast.AST) -> Iterable[Finding]:
    host: Set[str] = set()          # names known to hold host values
    for stmt in walk_statements(getattr(fn, "body", [])):
        flagged_targets = False
        for root in stmt_scan_roots(stmt):
            for node in ast.walk(root):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                msg: Optional[str] = None
                if name in _SYNC_CALLS:
                    arg = node.args[0] if node.args else None
                    if arg is not None and not _rhs_is_hostlike(arg, host):
                        msg = (f"{name}() on a device value inside hot-path "
                               f"'{getattr(fn, 'name', '?')}' forces a "
                               f"blocking device->host transfer")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _SYNC_METHODS
                      and not _rhs_is_hostlike(node.func.value, host)):
                    msg = (f".{node.func.attr}() on a device value inside "
                           f"hot-path '{getattr(fn, 'name', '?')}' forces "
                           f"a blocking device->host transfer")
                elif name in _CAST_BUILTINS and node.args:
                    arg = node.args[0]
                    if isinstance(arg, (ast.Name, ast.Attribute,
                                        ast.Subscript)) \
                            and not _rhs_is_hostlike(arg, host):
                        msg = (f"{name}() on a device value inside "
                               f"hot-path '{getattr(fn, 'name', '?')}' "
                               f"is a hidden device->host sync")
                if msg:
                    yield Finding(rule="host-sync-in-hot-path", path=sf.rel,
                                  line=node.lineno, col=node.col_offset,
                                  message=msg)
                    flagged_targets = True
        # propagate hostness: a sync result IS host afterwards (so the
        # engine's sanctioned `nxt = np.asarray(nxt)` poisons nothing
        # downstream), and host producers stay host
        targets = assigned_names(stmt)
        if targets:
            value = getattr(stmt, "value", None)
            if flagged_targets or (
                    value is not None and _rhs_is_hostlike(value, host)):
                host.update(targets)
            elif isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, ast.Call) and dotted_name(
                        stmt.value.func) in _SYNC_CALLS:
                host.update(targets)
            else:
                host.difference_update(targets)


# ----------------------------------------------- pallas-kernel-hygiene ----
_PROGRAM_ID_CALLS = {"pl.program_id", "pl.num_programs"}


def _is_kernel_fn(fn: ast.AST) -> bool:
    args = getattr(fn, "args", None)
    if args is None:
        return False
    names = [a.arg for a in args.args + args.kwonlyargs]
    if args.vararg is not None:
        names.append(args.vararg.arg)
    return any(n.endswith("_ref") or n == "refs" for n in names)


def _kernel_file(sf: SourceFile) -> bool:
    # ops.py / dispatch.py ARE the sanctioned backend-dispatch homes; the
    # autotuner is legitimately backend-aware (cache keys, tune gating).
    parts = sf.rel.split("/")
    return ("kernels" in parts[:-1]
            and parts[-1] not in ("ops.py", "dispatch.py", "autotune.py",
                                  "__init__.py"))


def _tainted_in(node: ast.AST, tainted: Set[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) \
                and dotted_name(sub.func) in _PROGRAM_ID_CALLS:
            return True
        if isinstance(sub, ast.Subscript):
            base = dotted_name(sub.value)
            if base.endswith("_ref") or base in tainted:
                return True
        if isinstance(sub, ast.Name) and sub.id in tainted:
            return True
    return False


@rule("pallas-kernel-hygiene",
      "kernel-body Python branches on traced values, pallas_call wrappers "
      "without divisibility asserts, and interpret/backend dispatch "
      "decisions made outside kernels.ops/kernels.dispatch")
def check_pallas_hygiene(sf: SourceFile) -> Iterable[Finding]:
    tree = sf.tree
    assert tree is not None
    in_kernel_file = _kernel_file(sf)

    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _is_kernel_fn(fn):
            yield from _check_kernel_body(sf, fn)
        yield from _check_wrapper(sf, fn, in_kernel_file)

    if in_kernel_file:
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and dotted_name(node.func) in (
                    "jax.default_backend", "jax.devices"):
                yield Finding(
                    rule="pallas-kernel-hygiene", path=sf.rel,
                    line=node.lineno, col=node.col_offset,
                    message="backend dispatch decision inside a kernel "
                            "module: route interpret/backend selection "
                            "through kernels.dispatch (ops.py picks "
                            "Mosaic/interpret/XLA-twin in one place)")


def _check_kernel_body(sf: SourceFile, fn: ast.AST) -> Iterable[Finding]:
    tainted: Set[str] = set()
    for stmt in walk_statements(getattr(fn, "body", [])):
        if isinstance(stmt, (ast.If, ast.While)) \
                and _tainted_in(stmt.test, tainted):
            yield Finding(
                rule="pallas-kernel-hygiene", path=sf.rel,
                line=stmt.lineno, col=stmt.col_offset,
                message=f"Python {'if' if isinstance(stmt, ast.If) else 'while'} "
                        f"on a traced value inside kernel body "
                        f"'{getattr(fn, 'name', '?')}': ref loads and "
                        f"pl.program_id are traced — use pl.when or "
                        f"jnp.where")
        value = getattr(stmt, "value", None)
        if value is not None and _tainted_in(value, tainted):
            tainted.update(n for n in assigned_names(stmt)
                           if "." not in n)


def _check_wrapper(sf: SourceFile, fn: ast.AST,
                   in_kernel_file: bool) -> Iterable[Finding]:
    calls = [node for node in ast.walk(fn)
             if isinstance(node, ast.Call)
             and dotted_name(node.func).endswith("pallas_call")]
    # only direct pallas_call launches in *this* function body (not in
    # nested defs, which get their own visit)
    calls = [c for c in calls if _owns(fn, c)]
    if not calls:
        return
    has_mod_assert = any(
        isinstance(stmt, ast.Assert) and any(
            isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Mod)
            for sub in ast.walk(stmt.test))
        for stmt in ast.walk(fn) if isinstance(stmt, ast.Assert))
    for call in calls:
        if not has_mod_assert:
            yield Finding(
                rule="pallas-kernel-hygiene", path=sf.rel,
                line=call.lineno, col=call.col_offset,
                message=f"'{getattr(fn, 'name', '?')}' launches "
                        f"pl.pallas_call with no grid/block divisibility "
                        f"assert (x % block == 0): misaligned tiles fail "
                        f"deep inside Mosaic — assert the contract here")
        if not in_kernel_file:
            continue
        interp = next((kw for kw in call.keywords
                       if kw.arg == "interpret"), None)
        if interp is None:
            if not any(kw.arg is None for kw in call.keywords):  # **kwargs
                yield Finding(
                    rule="pallas-kernel-hygiene", path=sf.rel,
                    line=call.lineno, col=call.col_offset,
                    message="pallas_call without interpret=: defaults to "
                            "Mosaic compilation, which aborts off-TPU — "
                            "thread interpret through "
                            "kernels.dispatch.default_interpret")
        elif isinstance(interp.value, ast.Constant):
            yield Finding(
                rule="pallas-kernel-hygiene", path=sf.rel,
                line=interp.value.lineno, col=interp.value.col_offset,
                message=f"pallas_call hardcodes interpret="
                        f"{interp.value.value!r}: dispatch belongs to "
                        f"kernels.ops/kernels.dispatch so tests, CPU twins "
                        f"and TPU runs share one policy")


def _owns(fn: ast.AST, node: ast.AST) -> bool:
    """True when ``node`` is inside ``fn`` but not inside a nested def."""
    for stmt in ast.walk(fn):
        if stmt is fn:
            continue
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            if any(sub is node for sub in ast.walk(stmt)):
                return False
    return True

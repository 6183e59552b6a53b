#!/usr/bin/env python
"""Static check: no host-device synchronization inside compiled dispatch.

PR 7's contract is ONE fused launch per flush: the jitted entry points
(`kernels/*/ops.py`, anything under ``@jax.jit`` /
``@partial(jit, ...)``) must stay pure traced array code. A host sync
smuggled into a traced body — ``np.asarray(tracer)``,
``x.block_until_ready()``, ``.item()`` / ``.tolist()``, ``float(x)`` on
a tracer — either fails at trace time in surprising ways or, worse,
silently constant-folds a value that should have been dynamic. This
lint rejects the whole class before a benchmark has to find it.

Mechanics: AST-walk every module under --root. A function counts as
COMPILED when any decorator is jit-shaped: a bare ``jit`` name, a
dotted ``*.jit``, a call of either, or ``partial(<jit-ish>, ...)``.
Inside a compiled body, flag:

  * calls through the host numpy module (``np.*`` / ``numpy.*``) — the
    classic tracer->host round trip (jnp is the traced namespace);
  * ``.block_until_ready()`` / ``.item()`` / ``.tolist()`` calls —
    unconditional device syncs;
  * ``float(...)`` / ``int(...)`` / ``bool(...)`` on a non-constant —
    concretization, a trace error or a silent constant fold.

A second rule guards the ISSUE 9 contract from the other side: inside
the batch-wise dispatch run loops (functions named ``_run_*`` /
``_land_*`` / ``_dispatch*``, minus the ``*_scalar`` oracles), a
``.mr_array(...)`` call under a For/While/comprehension is a per-WR MR
fetch — the pattern the fused ``_fused_mr_rows`` gather replaced (one
``mr_array`` + one ``gather_records`` launch per same-MR segment).
Hoist the fetch out of the loop or route the run through the fused
extraction.

    python scripts/lint_hot_path.py [--root src/repro]

Exit 0 clean, 1 with a violation listing otherwise (wired into
scripts/tier1.sh next to lint_counters.py).
"""
from __future__ import annotations

import argparse
import ast
import os
import sys

SYNC_METHODS = {"block_until_ready", "item", "tolist"}
HOST_MODULES = {"np", "numpy"}
CONCRETIZERS = {"float", "int", "bool"}


def _is_jit_expr(node: ast.AST) -> bool:
    """``jit`` / ``jax.jit`` (any dotted .jit)."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    return isinstance(node, ast.Attribute) and node.attr == "jit"


def _is_jit_decorator(dec: ast.AST) -> bool:
    if _is_jit_expr(dec):
        return True
    if not isinstance(dec, ast.Call):
        return False
    if _is_jit_expr(dec.func):            # @jit(static_argnames=...)
        return True
    fn = dec.func                         # @partial(jit, ...)
    is_partial = (isinstance(fn, ast.Name) and fn.id == "partial") or \
        (isinstance(fn, ast.Attribute) and fn.attr == "partial")
    return is_partial and bool(dec.args) and _is_jit_expr(dec.args[0])


def _violations_in(fn: ast.FunctionDef, path: str) -> list[str]:
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            v = f.value
            if isinstance(v, ast.Name) and v.id in HOST_MODULES:
                out.append(
                    f"{path}:{node.lineno}: host numpy call "
                    f"`{v.id}.{f.attr}(...)` inside compiled "
                    f"`{fn.name}` — use jnp (traced) or hoist to the "
                    "caller")
            elif f.attr in SYNC_METHODS:
                out.append(
                    f"{path}:{node.lineno}: `.{f.attr}()` inside "
                    f"compiled `{fn.name}` — a device sync cannot live "
                    "in a traced body")
        elif isinstance(f, ast.Name) and f.id in CONCRETIZERS:
            if not all(isinstance(a, ast.Constant) for a in node.args):
                out.append(
                    f"{path}:{node.lineno}: `{f.id}(...)` on a "
                    f"non-constant inside compiled `{fn.name}` — "
                    "concretizes a tracer (trace error or silent "
                    "constant fold)")
    return out


_DISPATCH_PREFIXES = ("_run_", "_land_", "_dispatch")
_LOOP_NODES = (ast.For, ast.While, ast.ListComp, ast.SetComp,
               ast.DictComp, ast.GeneratorExp)


def _is_dispatch_fn(name: str) -> bool:
    """Hot dispatch run loops — the `*_scalar` oracles are exempt (the
    element-at-a-time path is the bit-exactness reference, per-WR by
    design)."""
    return name.startswith(_DISPATCH_PREFIXES) and \
        not name.endswith("_scalar")


def _mr_array_in_loops(fn: ast.FunctionDef, path: str) -> list[str]:
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, _LOOP_NODES):
            continue
        for call in ast.walk(node):
            if isinstance(call, ast.Call) and \
                    isinstance(call.func, ast.Attribute) and \
                    call.func.attr == "mr_array":
                out.append(
                    f"{path}:{call.lineno}: per-WR `.mr_array(...)` "
                    f"inside a loop in dispatch `{fn.name}` — fetch "
                    "once per same-MR segment and gather fused "
                    "(`_fused_mr_rows`), not per WR")
    return out


def scan_module(path: str) -> list[str]:
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    out: list[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if any(_is_jit_decorator(d) for d in node.decorator_list):
            out.extend(_violations_in(node, path))
        if _is_dispatch_fn(node.name):
            out.extend(_mr_array_in_loops(node, path))
    return out


def lint(root: str) -> list[str]:
    violations: list[str] = []
    for dirpath, _, files in sorted(os.walk(root)):
        for fn in sorted(files):
            if fn.endswith(".py"):
                violations.extend(scan_module(os.path.join(dirpath, fn)))
    return violations


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--root", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src", "repro"))
    args = p.parse_args()
    if not os.path.isdir(args.root):
        print(f"lint_hot_path: no such directory {args.root}",
              file=sys.stderr)
        raise SystemExit(2)
    violations = lint(args.root)
    if violations:
        print("lint_hot_path: hot-path violations (host syncs in "
              "compiled bodies / per-WR MR fetches in dispatch loops):")
        for v in violations:
            print(f"  {v}")
        raise SystemExit(1)
    print(f"lint_hot_path: clean ({args.root})")


if __name__ == "__main__":
    main()

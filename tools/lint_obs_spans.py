#!/usr/bin/env python
"""AST lint: every pallas-dispatching engine path runs under a span, and
every kernel has a stable name.

The observability contract (DESIGN.md Sec. 3l) is that no kernel launch
escapes the trace: any code path in the match runtime that can reach a
``pl.pallas_call`` dispatch must execute inside a tracer span, so a
``--trace`` run accounts for every launch.  This lint enforces that
statically, with no imports and no JAX:

1. **Kernel discovery.**  Parse every module under ``src/repro/kernels/``
   and compute, to a fixpoint, the set of functions that *transitively*
   contain a ``pallas_call`` (directly, or by calling -- by bare name --
   another kernel-package function that does).

2. **Dispatch sites.**  Parse the match runtime modules under
   ``src/repro/match/`` (excluding ``calibrate.py``, whose whole job is
   timing *raw* kernels for the cost model -- wrapping those would
   corrupt the calibration) and find every call whose callee resolves to
   a dispatching kernel function: ``alias.func(...)`` where ``alias``
   imports a kernel module, or a bare name imported from one.

3. **Coverage.**  A dispatch site is covered if it sits lexically inside
   a ``with`` statement over a ``*.span(...)`` context, or -- to a
   fixpoint -- if it sits inside a function every one of whose call
   sites (found across the same runtime modules) is covered.  This lets
   helpers like ``_chunk_scores`` stay span-free as long as each caller
   wraps them.

4. **Kernel names.**  Every ``pallas_call`` under ``src/repro/kernels/``
   passes ``name=`` as a string literal (or a conditional between
   literals).  The device trace names a kernel's instruction after it,
   and the benchmark's roofline readers match those names, so a
   refactor must not rename a kernel by accident.

Exit status 1 with ``file:line`` diagnostics on any uncovered dispatch
or unnamed kernel.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

REPO = Path(__file__).resolve().parent.parent
# calibrate.py times raw kernel dispatches on purpose (autotune must
# measure the kernel, not the kernel plus tracing overhead).
EXCLUDE = {"calibrate.py"}


def _parse(path: Path) -> ast.AST:
    return ast.parse(path.read_text(), filename=str(path))


# -- step 1: which kernel functions transitively reach pallas_call? ----------

def _contains_pallas_call(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "pallas_call":
                return True
            if isinstance(f, ast.Name) and f.id == "pallas_call":
                return True
    return False


def _literal_name(expr: ast.AST) -> bool:
    """A string constant, or a conditional whose branches all are."""
    if isinstance(expr, ast.Constant):
        return isinstance(expr.value, str) and bool(expr.value)
    if isinstance(expr, ast.IfExp):
        return _literal_name(expr.body) and _literal_name(expr.orelse)
    return False


def unnamed_pallas_calls(kernels_dir: Path, root: Path) -> List[str]:
    """``file:line`` of each ``pallas_call`` without a literal ``name=``."""
    out: List[str] = []
    for path in sorted(kernels_dir.glob("*.py")):
        for node in ast.walk(_parse(path)):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not ((isinstance(f, ast.Attribute) and f.attr == "pallas_call")
                    or (isinstance(f, ast.Name) and f.id == "pallas_call")):
                continue
            if not any(kw.arg == "name" and _literal_name(kw.value)
                       for kw in node.keywords):
                out.append(f"{path.relative_to(root)}:{node.lineno}")
    return out


def _called_names(fn: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name):
                out.add(f.id)
            elif isinstance(f, ast.Attribute):
                out.add(f.attr)
    return out


def dispatching_kernel_functions(kernels_dir: Path) -> Set[str]:
    """Bare names of kernel-package functions that reach pallas_call."""
    fns: Dict[str, ast.AST] = {}
    for path in sorted(kernels_dir.glob("*.py")):
        tree = _parse(path)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, node)
    dispatching = {n for n, fn in fns.items() if _contains_pallas_call(fn)}
    changed = True
    while changed:
        changed = False
        for name, fn in fns.items():
            if name in dispatching:
                continue
            if _called_names(fn) & dispatching:
                dispatching.add(name)
                changed = True
    return dispatching


# -- step 2 + 3: dispatch sites and span coverage in the runtime -------------

class _Site:
    __slots__ = ("path", "line", "callee", "func_stack", "in_span")

    def __init__(self, path: str, line: int, callee: str,
                 func_stack: Tuple[str, ...], in_span: bool):
        self.path = path
        self.line = line
        self.callee = callee
        self.func_stack = func_stack     # enclosing defs, outermost first
        self.in_span = in_span


def _is_span_with(node: ast.With) -> bool:
    for item in node.items:
        expr = item.context_expr
        if (isinstance(expr, ast.Call)
                and isinstance(expr.func, ast.Attribute)
                and expr.func.attr == "span"):
            return True
    return False


class _Visitor(ast.NodeVisitor):
    """Collect kernel-dispatch sites + every call site of local defs."""

    def __init__(self, path: str, kernel_aliases: Set[str],
                 kernel_names: Set[str], dispatching: Set[str]):
        self.path = path
        self.kernel_aliases = kernel_aliases    # `_fq`, `_swar`, ...
        self.kernel_names = kernel_names        # bare imported names
        self.dispatching = dispatching
        self.sites: List[_Site] = []
        # bare callee name -> list of (func_stack, in_span) call sites
        self.calls: Dict[str, List[Tuple[Tuple[str, ...], bool]]] = {}
        self._funcs: List[str] = []
        self._spans = 0

    def visit_With(self, node: ast.With) -> None:
        if _is_span_with(node):
            self._spans += 1
            self.generic_visit(node)
            self._spans -= 1
        else:
            self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._funcs.append(node.name)
        # Span state does not flow into a nested def: the def's *body*
        # runs when called, not where the `with` is open.
        spans, self._spans = self._spans, 0
        self.generic_visit(node)
        self._spans = spans
        self._funcs.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _callee(self, call: ast.Call) -> Optional[str]:
        f = call.func
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id in self.kernel_aliases):
            return f.attr
        if isinstance(f, ast.Name) and f.id in self.kernel_names:
            return f.id
        return None

    def visit_Call(self, node: ast.Call) -> None:
        callee = self._callee(node)
        if callee is not None and callee in self.dispatching:
            self.sites.append(_Site(self.path, node.lineno, callee,
                                    tuple(self._funcs), self._spans > 0))
        f = node.func
        bare = (f.id if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else None)
        if bare is not None:
            self.calls.setdefault(bare, []).append(
                (tuple(self._funcs), self._spans > 0))
        self.generic_visit(node)


def _kernel_imports(tree: ast.AST) -> Tuple[Set[str], Set[str]]:
    aliases: Set[str] = set()
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            mod = node.module
            if "kernels" in mod.split("."):
                for a in node.names:
                    asname = a.asname or a.name
                    # `from repro.kernels import match_swar as _swar`
                    # imports a *module* as an alias; `from
                    # repro.kernels.match_swar import match_swar`
                    # imports a function by name.  Treat both: alias if
                    # the module path ends at the kernels package,
                    # bare name otherwise.
                    if mod.rstrip(".").endswith("kernels"):
                        aliases.add(asname)
                    else:
                        names.add(asname)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if "kernels" in a.name.split("."):
                    aliases.add((a.asname or a.name).split(".")[0])
    return aliases, names


def main(root: Optional[Path] = None) -> int:
    root = Path(root) if root is not None else REPO
    kernels_dir = root / "src" / "repro" / "kernels"
    match_dir = root / "src" / "repro" / "match"
    dispatching = dispatching_kernel_functions(kernels_dir)
    if not dispatching:
        print("lint_obs_spans: no pallas_call found under "
              f"{kernels_dir} -- wrong tree?", file=sys.stderr)
        return 1

    all_sites: List[_Site] = []
    # bare function name -> call sites across all runtime modules
    all_calls: Dict[str, List[Tuple[Tuple[str, ...], bool]]] = {}
    for path in sorted(match_dir.glob("*.py")):
        if path.name in EXCLUDE:
            continue
        tree = _parse(path)
        aliases, names = _kernel_imports(tree)
        v = _Visitor(str(path.relative_to(root)), aliases, names,
                     dispatching)
        v.visit(tree)
        all_sites.extend(v.sites)
        for name, sites in v.calls.items():
            all_calls.setdefault(name, []).extend(sites)

    # Fixpoint: a function is covered if every one of its call sites is
    # lexically in a span or inside a covered function.
    covered_funcs: Set[str] = set()

    def _site_ok(stack: Tuple[str, ...], in_span: bool) -> bool:
        return in_span or any(f in covered_funcs for f in stack)

    changed = True
    while changed:
        changed = False
        for name, sites in all_calls.items():
            if name in covered_funcs:
                continue
            if sites and all(_site_ok(st, sp) for st, sp in sites):
                covered_funcs.add(name)
                changed = True

    violations = [s for s in all_sites
                  if not _site_ok(s.func_stack, s.in_span)]
    unnamed = unnamed_pallas_calls(kernels_dir, root)
    if violations or unnamed:
        for s in violations:
            where = ".".join(s.func_stack) or "<module>"
            print(f"{s.path}:{s.line}: pallas dispatch `{s.callee}` in "
                  f"`{where}` is not under a tracer span (and not every "
                  f"call site of `{where}` is)", file=sys.stderr)
        for site in unnamed:
            print(f"{site}: pallas_call without a literal name=",
                  file=sys.stderr)
        print(f"lint_obs_spans: {len(violations)} uncovered dispatch "
              f"site(s) of {len(all_sites)}, {len(unnamed)} unnamed "
              "kernel(s)", file=sys.stderr)
        return 1
    print(f"lint_obs_spans: OK -- {len(all_sites)} pallas dispatch sites "
          f"across {match_dir.relative_to(root)} all run under spans "
          f"({len(dispatching)} dispatching kernel fns)")
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1]) if len(sys.argv) > 1 else None))

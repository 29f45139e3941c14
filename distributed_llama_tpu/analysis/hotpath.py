"""Hot-path host-sync lint (rules `hot-sync`, `hot-impure`).

The failure mode this pass exists for: a silent device->host sync (or an
accidental recompile) landing on the decode hot path and reaching hardware
undetected, halving throughput with no test failing. The conventions:

    def _issue_super_step(...):  # hot-path
        A host-side hot function (scheduler issue/deliver/chain paths, the
        sampler). Must not contain IMPLICIT device->host syncs:
          - `.item()`, `.tolist()` calls
          - `np.asarray(...)` / `np.array(...)` (fetches a jax array)
          - `jax.device_get(...)`
          - `float(x[i])` / `int(x[i])` / `bool(x[i])` on subscripted values
            (the classic scalar-read sync)
          - `print(...)` (printing a tracer/array syncs and stalls)
        Names assigned FROM an `np.asarray(...)` call earlier in the same
        function are known host arrays; subsequent `.tolist()`/`int(x[i])`
        on them are exempt — only the fetch itself is the sync to triage.

    def step(carry, i):  # hot-path: traced
        A jit-traced body (device_loop scan/verify bodies). All of the
        above, plus trace-impure calls that would bake a host value into
        the compiled program or recompile per call: `time.*`, `random.*`,
        `np.random.*`, `np.asarray` on traced values, `uuid.*`,
        `os.environ` reads.

Deliberate syncs (the delivery fence in `_deliver_super_step`) carry
`# dlint: ignore[hot-sync] -- reason` — the point is that every sync on a
hot path is WRITTEN DOWN, not that none exist.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, Source, marker_on

_HOT_RE = re.compile(r"#\s*hot-path(?::\s*(traced))?\b")

_SYNC_ATTRS = {"item", "tolist"}
_IMPURE_MODULES = {"time", "random", "uuid"}


def _dotted(fn: ast.AST) -> str | None:
    """'a.b.c' for nested attribute of names, else None."""
    parts = []
    while isinstance(fn, ast.Attribute):
        parts.append(fn.attr)
        fn = fn.value
    if isinstance(fn, ast.Name):
        parts.append(fn.id)
        return ".".join(reversed(parts))
    return None


class _HotChecker(ast.NodeVisitor):
    def __init__(self, source: Source, fn_name: str, traced: bool,
                 findings: list[Finding]):
        self.source = source
        self.fn_name = fn_name
        self.traced = traced
        self.findings = findings
        self.host_names: set[str] = set()  # assigned from np.asarray & co.

    def _flag(self, rule: str, node: ast.AST, msg: str) -> None:
        self.findings.append(Finding(
            rule, self.source.relpath, node.lineno,
            f"{msg} in hot-path function `{self.fn_name}`"))

    # HOST hot-path status does not flow into nested defs (a closure built
    # here may run on a different path; the author marks it explicitly) —
    # but TRACED status does: a scan/verify `step` defined inside a jitted
    # `loop` body executes at trace time, so its impurities are the loop's
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if not self.traced:
            return
        inner = _HotChecker(self.source, f"{self.fn_name}.{node.name}",
                            traced=True, findings=self.findings)
        for stmt in node.body:
            inner.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    @staticmethod
    def _is_fetch(node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and _dotted(node.func) in ("np.asarray", "np.array",
                                           "numpy.asarray", "numpy.array",
                                           "jax.device_get"))

    def visit_Assign(self, node: ast.Assign) -> None:
        # np.asarray(...) result names are HOST arrays from here on — also
        # through a conditional fetch (`x = np.asarray(a) if cond else None`)
        val = node.value
        fetched = (self._is_fetch(val)
                   or (isinstance(val, ast.IfExp)
                       and (self._is_fetch(val.body)
                            or self._is_fetch(val.orelse))))
        if fetched:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.host_names.add(tgt.id)
        self.generic_visit(node)

    def _roots_host(self, node: ast.AST) -> bool:
        """True when the expression's root name is a known host array."""
        while isinstance(node, (ast.Subscript, ast.Attribute)):
            node = node.value
        return isinstance(node, ast.Name) and node.id in self.host_names

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        dotted = _dotted(fn)
        # -- implicit device->host syncs --------------------------------
        if isinstance(fn, ast.Attribute) and fn.attr in _SYNC_ATTRS:
            if not self._roots_host(fn.value):
                self._flag("hot-sync", node,
                           f"`.{fn.attr}()` forces a device->host sync")
        elif dotted in ("np.asarray", "np.array", "numpy.asarray",
                        "numpy.array"):
            self._flag("hot-sync", node,
                       f"`{dotted}(...)` blocks on a device->host transfer "
                       "when given a device array")
        elif dotted == "jax.device_get":
            self._flag("hot-sync", node, "`jax.device_get(...)` is an "
                       "explicit device->host sync")
        elif (isinstance(fn, ast.Name) and fn.id in ("float", "int", "bool")
              and node.args and isinstance(node.args[0], ast.Subscript)
              and not self._roots_host(node.args[0])):
            self._flag("hot-sync", node,
                       f"`{fn.id}(x[...])` reads one element to host "
                       "(a per-call sync)")
        elif isinstance(fn, ast.Name) and fn.id == "print":
            self._flag("hot-sync", node,
                       "`print(...)` on a hot path (stalls; printing an "
                       "array or tracer also syncs)")
        # -- trace-impure calls inside jitted bodies ---------------------
        if self.traced and dotted is not None:
            root = dotted.split(".", 1)[0]
            if root in _IMPURE_MODULES or dotted.startswith("np.random."):
                self._flag("hot-impure", node,
                           f"`{dotted}(...)` is trace-impure: its value is "
                           "baked in at compile time (or recompiles per "
                           "call) inside a jitted body")
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if self.traced and _dotted(node.value) == "os.environ":
            self._flag("hot-impure", node,
                       "`os.environ[...]` read inside a jitted body is "
                       "baked in at compile time")
        self.generic_visit(node)


def check_hot_paths(sources: list[Source]) -> list[Finding]:
    findings: list[Finding] = []
    for source in sources:
        if source.tree is None:
            continue
        for node in ast.walk(source.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            m = marker_on(source, node, _HOT_RE)
            if m is None:
                continue
            checker = _HotChecker(source, node.name,
                                  traced=m.group(1) == "traced",
                                  findings=findings)
            for stmt in node.body:
                checker.visit(stmt)
    return findings

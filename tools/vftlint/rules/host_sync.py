"""host-sync: device arrays may only reach the host through accounted sites.

Every ``np.asarray``/``float``/``int``/``.item()`` on a device array blocks
the host on the device stream — a stall of the whole pipeline, and
invisible to profiling because the cost books to whatever Python line happened
to touch the array. The extractor contract routes all materialization through
``Extractor._wait`` (``utils.metrics`` ``device_wait``-accounted) so the
per-video stage report stays honest and stray syncs can't creep into step
loops.

Two analyses:

1. **Extractor taint scan** (``extractors/*.py``): a line-order dataflow pass
   marks values produced by device-step calls (``self._*step*``,
   ``_device_call``), ``runner.put``/``put_replicated``, ``jnp.*``,
   ``prefetch_to_device``, and device-pinned ``*params`` attributes; flags
   host-materializing sinks on tainted values outside ``_wait``.
2. **Traced-body scan** (whole package): host-materializing calls inside
   jit/shard_map-traced functions are flagged unconditionally — they force a
   concretization mid-trace.

Single pass, no back-edge fixpoint: a taint born at the bottom of a loop body
is not seen at its top. Good enough — step results are consumed below their
dispatch everywhere in this tree, and the fixture tests pin the contract.

Suppress a deliberate sync with ``# host-sync: <reason>`` (e.g. the flow
precompile warmup thread, which blocks off the critical path by design).
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, List, Set

from ..core import Finding, Rule, SourceFile, register
from ..dataflow import LineOrderScanner
from ..tracing import dotted_name, walk_body

# attribute names whose CALL yields a device value
_STEP_ATTR = re.compile(r"(^|_)step(_|$)|(^|_)device_call$")
# attribute READS that are device-pinned values (MeshRunner.put_replicated)
_PARAMS_ATTR = re.compile(r"params$")
# methods that ARE the accounted materialization site
_ACCOUNTED_METHODS = {"_wait"}

_SINK_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "jax.block_until_ready",
}
_SINK_BUILTINS = {"float", "int"}
_SINK_METHODS = {"item", "block_until_ready"}


def _is_device_callable_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute):
        return bool(_STEP_ATTR.search(node.attr))
    if isinstance(node, ast.IfExp):
        return (_is_device_callable_expr(node.body)
                or _is_device_callable_expr(node.orelse))
    return False


class _TaintScanner(LineOrderScanner):
    """One function body's line-order taint pass.

    The statement walk (branch-union ``if``, closure-seeded nested defs,
    compound heads visited before their blocks) lives in
    :class:`~tools.vftlint.dataflow.LineOrderScanner`; this class supplies
    the host-sync state — tainted names and device-callable names — and the
    sink checks."""

    def __init__(self, rule: "HostSyncRule", src: SourceFile,
                 findings: List[Finding]):
        self.rule = rule
        self.src = src
        self.findings = findings
        self.tainted: Set[str] = set()
        self.device_callables: Set[str] = set()

    # -- LineOrderScanner state protocol ------------------------------------

    def snapshot(self):
        return (set(self.tainted), set(self.device_callables))

    def restore(self, token) -> None:
        self.tainted, self.device_callables = set(token[0]), set(token[1])

    def merged(self, tokens):
        out_t: Set[str] = set()
        out_c: Set[str] = set()
        for t, c in tokens:
            out_t |= t
            out_c |= c
        return (out_t, out_c)

    # -- expression taint ---------------------------------------------------

    def is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            return bool(_PARAMS_ATTR.search(node.attr))
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value)
        if isinstance(node, ast.Starred):
            return self.is_tainted(node.value)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.BinOp):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.IfExp):
            return self.is_tainted(node.body) or self.is_tainted(node.orelse)
        if isinstance(node, ast.Call):
            return self.call_returns_device(node)
        return False

    def call_returns_device(self, call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        last = name.rsplit(".", 1)[-1]
        if isinstance(call.func, ast.Attribute):
            if _STEP_ATTR.search(call.func.attr):
                return True
            if call.func.attr in ("put", "put_replicated"):
                return True
            # method on a device value stays on device (.astype, .reshape…)
            if (call.func.attr not in _SINK_METHODS
                    and self.is_tainted(call.func.value)):
                return True
        if name.startswith(("jnp.", "jax.numpy.")):
            return True
        if last == "prefetch_to_device":
            return True
        if isinstance(call.func, ast.Name):
            return call.func.id in self.device_callables
        return False

    # -- sink detection -----------------------------------------------------

    def check_sinks(self, root: ast.AST) -> None:
        """Flag sinks in ``root`` — a simple statement or a bare expression.
        Compound statements must NOT be passed whole: their blocks are
        scanned by :meth:`scan_block` after the state updates that scope
        them, so walking them here would re-check inner sinks against the
        stale pre-block taint (e.g. a value re-assigned from ``_wait``
        inside a branch would still read as tainted)."""
        for node in ast.walk(root):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            hit = None
            if name in _SINK_CALLS or name in _SINK_BUILTINS:
                if any(self.is_tainted(a) for a in node.args):
                    hit = f"{name}()"
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SINK_METHODS
                    and self.is_tainted(node.func.value)):
                hit = f".{node.func.attr}()"
            if hit is None:
                continue
            if self.rule.suppressed(self.src, node.lineno, self.findings):
                continue
            self.findings.append(Finding(
                self.src.rel, node.lineno, self.rule.id,
                f"{hit} on a device array outside the accounted sites — "
                "route host materialization through self._wait() "
                "(metrics 'device_wait') instead"))

    # -- statement-walk hooks (structure lives in LineOrderScanner) ---------

    def visit_expr(self, expr: ast.AST) -> None:
        self.check_sinks(expr)

    def on_for(self, stmt) -> None:
        if self.is_tainted(stmt.iter):
            self._mark(stmt.target, True)

    def visit_simple(self, stmt: ast.stmt) -> None:
        # simple statement: no nested blocks, safe to walk whole
        self.check_sinks(stmt)
        if isinstance(stmt, ast.Assign):
            self._assign(stmt.targets, stmt.value)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._assign([stmt.target], stmt.value)
        elif isinstance(stmt, ast.AugAssign):
            if self.is_tainted(stmt.value):
                self._mark(stmt.target, True)

    def _assign(self, targets, value) -> None:
        tainted = self.is_tainted(value)
        callable_ = _is_device_callable_expr(value)
        for target in targets:
            self._mark(target, tainted)
            if isinstance(target, ast.Name):
                if callable_:
                    self.device_callables.add(target.id)
                else:
                    self.device_callables.discard(target.id)

    def _mark(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._mark(elt, tainted)
        elif isinstance(target, ast.Starred):
            self._mark(target.value, tainted)


@register
class HostSyncRule(Rule):
    id = "host-sync"
    title = "device→host materialization only via accounted sites"
    roots = ("video_features_tpu",)

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        findings: List[Finding] = []
        if src.rel.startswith("video_features_tpu/extractors/"):
            defs = [n for n in ast.walk(src.tree)
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
            # nested defs are scanned by their parent with closure state
            nested = {sub for fn in defs for sub in ast.walk(fn)
                      if sub is not fn
                      and isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))}
            for node in defs:
                if node.name in _ACCOUNTED_METHODS or node in nested:
                    continue
                scanner = _TaintScanner(self, src, findings)
                scanner.scan_block(node.body)
        # traced bodies anywhere: a host-materializing call mid-trace forces
        # concretization (or burns a constant) regardless of dataflow
        for fn in src.traced():  # memoized: shared with jit-purity
            for node in walk_body(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                hit = None
                if name in _SINK_CALLS:
                    hit = f"{name}()"
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SINK_METHODS):
                    hit = f".{node.func.attr}()"
                if hit is None:
                    continue
                if self.suppressed(src, node.lineno, findings):
                    continue
                findings.append(Finding(
                    src.rel, node.lineno, self.id,
                    f"{hit} inside traced function '{fn.name}' forces a "
                    "mid-trace host sync — keep the traced body on device"))
        # the two scans can overlap on extractor step bodies
        return sorted(set(findings),
                      key=lambda f: (f.path, f.line, f.message))

"""fast-registry: every test module is deliberately tiered.

The suite has three tiers (tests/conftest.py): ``fast`` (module listed in
``_FAST_MODULES`` — pre-commit signal), ``slow`` (module-level
``pytestmark = pytest.mark.slow`` — parity/e2e, excluded by the pyproject
default ``-m 'not slow'``), and the default tier in between. A new test
module silently landing in the default tier inflates the tier-1 wall-clock
budget (870 s timeout, docs/budgets.md) without anyone choosing that — so
membership is declared:

1. listed in conftest ``_FAST_MODULES``; or
2. module-level ``pytestmark = pytest.mark.slow``; or
3. listed in ``DEFAULT_TIER`` below AND carrying a
   ``# fast-registry: <reason>`` comment in the file saying why it sits in
   the default tier.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Set

from ..core import Finding, Rule, SourceFile, register

# Deliberate default-tier modules: too compile-heavy for the fast tier, too
# load-bearing for slow-only CI. Each file carries a matching annotation.
DEFAULT_TIER: Dict[str, str] = {
    "test_chip_bringup": "subprocesses that import jax + TPU cross-lowerings",
    "test_decode_pool": "real-sleep concurrency tests on the decode pool",
    "test_device_preproc": "device-preproc parity over real-model compiles",
    "test_fault_injection": "e2e extraction under injected faults (compiles)",
    "test_flow_bf16": "bf16 drift measurement over flow compiles",
    "test_flow_frames": "shared-frame flow forward parity (flow compiles)",
    "test_kernels": "kernel parity vs torch mirrors",
    "test_laguna": "page program compiles (both Pallas kernels in the interpreter)",
    "test_sarvam": "page program compiles (both Pallas kernels in the interpreter)",
    "test_qwen3_next": "page program compiles (three Pallas kernels in the interpreter)",
    "test_jamba": "page program compiles (both Pallas kernels in the interpreter)",
    "test_lfm2": "page program compiles (three Pallas kernels in the interpreter)",
    "test_moe_chunks": "jitted routed layers (the grouped product in the Pallas interpreter)",
    "test_metrics": "stage-clock tests with real sleeps",
    "test_multihost": "loopback two-process jax.distributed init",
    "test_packer_models": "real-model packed parity (jit compiles)",
    "test_paged": "paged dispatch parity (jit compiles)",
    "test_pwc": "the decoder's dense block by source; whole-model parity stays slow, test by test",
    "test_resnet": "resnet50 forward parity (heavy compile)",
    "test_segmented_decode": "real-sleep pool concurrency + e2e parity runs",
    "test_setup_spans": "page program compiles (Pallas kernels in the interpreter)",
    "test_vggish": "vggish DSP + forward parity",
    "test_weights_store": "checkpoint store roundtrips",
    "test_windows": "pre-dates the fast registry; re-tier on the next sweep",
}


def _fast_modules(conftest: SourceFile) -> Set[str]:
    for node in ast.walk(conftest.tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "_FAST_MODULES"
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Set, ast.List, ast.Tuple)):
            return {e.value for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)}
    return set()


def _slow_marked(src: SourceFile) -> bool:
    """Module-level ``pytestmark = pytest.mark.slow`` (or a list holding it)."""
    for node in src.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "pytestmark"
                   for t in node.targets):
            continue
        marks = (node.value.elts
                 if isinstance(node.value, (ast.List, ast.Tuple))
                 else [node.value])
        for mark in marks:
            if isinstance(mark, ast.Attribute) and mark.attr == "slow":
                return True
    return False


@register
class FastRegistryRule(Rule):
    id = "fast-registry"
    title = "test modules declare their tier (fast / slow / default)"
    roots = ("tests",)

    def __init__(self) -> None:
        self._modules: Dict[str, SourceFile] = {}
        self._conftest: Optional[SourceFile] = None

    def wants(self, rel: str) -> bool:
        name = os.path.basename(rel)
        return name == "conftest.py" or (
            name.startswith("test_") and name.endswith(".py"))

    def annotation_live(self, src: SourceFile, line: int) -> bool:
        # this rule's grammar is file-level, not line-level: the comment
        # declares why a DEFAULT_TIER module sits in the default tier
        # (finalize reads src.comments directly, so the consumed-set default
        # never sees it). Live iff the module is still declared DEFAULT_TIER
        # — a module that leaves the tier makes its comment stale.
        name = os.path.basename(src.rel)
        return name.endswith(".py") and name[:-3] in DEFAULT_TIER

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        name = os.path.basename(src.rel)
        if name == "conftest.py":
            self._conftest = src
        else:
            self._modules[name[:-3]] = src
        return ()

    def finalize(self, root: str) -> Iterable[Finding]:
        findings: List[Finding] = []
        modules, conftest = self._modules, self._conftest
        self._modules, self._conftest = {}, None
        if conftest is None:
            if modules:  # a tests tree without the registry at all
                findings.append(Finding(
                    "tests/conftest.py", 0, self.id,
                    "no conftest.py with _FAST_MODULES found — the fast "
                    "registry is missing"))
            return findings
        fast = _fast_modules(conftest)
        for module, src in sorted(modules.items()):
            if module in fast:
                continue
            if _slow_marked(src):
                continue
            if module in DEFAULT_TIER:
                marker = f"{self.id}:"
                reasons = [c.split(marker, 1)[1].strip()
                           for c in src.comments.values() if marker in c]
                if any(reasons):
                    continue
                if reasons:  # annotation present but reasonless
                    findings.append(Finding(
                        src.rel, 1, self.id,
                        f"'# {self.id}:' comment in '{module}' has no "
                        "reason — say why it sits in the default tier"))
                else:
                    findings.append(Finding(
                        src.rel, 1, self.id,
                        f"'{module}' is declared DEFAULT_TIER but carries no "
                        f"'# {self.id}: <reason>' comment — annotate why it "
                        "sits in the default tier"))
                continue
            findings.append(Finding(
                src.rel, 1, self.id,
                f"'{module}' is in no tier: add it to conftest "
                "_FAST_MODULES, mark it pytestmark = pytest.mark.slow, or "
                "declare it in DEFAULT_TIER "
                "(tools/vftlint/rules/fast_registry.py) with an in-file "
                f"'# {self.id}: <reason>' comment"))
        for module in sorted(set(DEFAULT_TIER) - set(modules)):
            findings.append(Finding(
                f"tests/{module}.py", 0, self.id,
                f"DEFAULT_TIER declares '{module}' but no such test module "
                "exists — prune the stale entry"))
        for module in sorted(set(DEFAULT_TIER) & fast):
            findings.append(Finding(
                f"tests/{module}.py", 0, self.id,
                f"'{module}' is both in _FAST_MODULES and DEFAULT_TIER — "
                "pick one tier"))
        return findings

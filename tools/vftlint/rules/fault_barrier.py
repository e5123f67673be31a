"""fault-barrier: broad exception catches exist only at declared barriers.

Migrated from the standalone ``tools/lint_fault_barrier.py`` (PR 1), which
remains as a thin shim over this module so its CLI contract and
``tests/test_fault_barrier_lint.py`` keep holding. The invariant is unchanged:

1. every ``except Exception`` / ``except BaseException`` / bare ``except:``
   line carries a ``# fault-barrier: <reason>`` comment;
2. per-file broad-catch counts match the ``ALLOWED`` declaration — adding a
   barrier is a deliberate act that edits this file, not a drive-by.

This rule manages its own annotation grammar (the legacy line-level marker,
which is also valid ``# <rule-id>: <reason>`` vftlint grammar) and count
reconciliation; prefer raising the classified taxonomy from
``video_features_tpu/reliability/errors.py`` over adding a barrier.
"""

from __future__ import annotations

import os
import re
from typing import Dict, Iterable, List, Tuple

from ..core import Finding, Rule, register

# Declared barriers: package-relative posix path -> expected broad-catch count.
ALLOWED: Dict[str, int] = {
    "video_features_tpu/cache/store.py": 2,        # read + publish: a cache entry of ANY state must degrade to a miss / pass-through, never crash the video it would have saved
    "video_features_tpu/extractors/base.py": 7,    # per-video fault barrier (per-video + packed loops) + packed finalize + corpus-flush arms + async-write reap arm + unwind-path write accounting + segment-planner probe (falls back to sequential open)
    "video_features_tpu/extractors/flow.py": 3,    # async-copy + imshow probes + precompile warmup
    "video_features_tpu/io/output.py": 1,          # writer thread: error stored on the WriteHandle
    "video_features_tpu/parallel/packer.py": 4,    # stale-flush + corpus-flush, dispatch + scatter arms each: every bucket's victims, not the finisher or a healthy co-resident bucket/model, own the failure
    "video_features_tpu/parallel/pipeline.py": 3,  # distributed-client probe + worker re-raise + segment planner (falls back to sequential scheduling)
    "video_features_tpu/reliability/retry.py": 2,  # classified re-raise + attempts attr
    "video_features_tpu/reliability/watchdog.py": 1,  # hands the exception to the waiter
    "video_features_tpu/serve/daemon.py": 7,       # per-video isolation point (serving loop) + lazy model-construction arm + cache-hit write arm + best-effort rejection/result records (the daemon must outlive a full notify disk) + profile start/stop arms (an on-demand jax.profiler session failing must report over the socket, not kill the API thread)
    "video_features_tpu/serve/ingest.py": 1,       # one bad socket client must not kill the API thread
    "video_features_tpu/serve/wal.py": 1,          # writer-thread wrapper: a dead writer would hang every submitter blocked on its ack event — degrade loudly and keep acking
}

MARKER = "fault-barrier:"
BROAD = re.compile(r"^\s*except\s*(\(\s*)?(Base)?Exception\b|^\s*except\s*:")


def scan(repo_root: str) -> Tuple[List[str], Dict[str, int]]:
    """(findings, per-file broad-catch counts) for the package tree.

    Kept line-based (not AST) deliberately: the marker must sit on the
    ``except`` line itself, and the scan must also work on files that fail
    to parse mid-edit. Message strings are the PR-1 originals — the shim's
    output is part of its contract.
    """
    findings: List[str] = []
    counts: Dict[str, int] = {}
    pkg = os.path.join(repo_root, "video_features_tpu")
    for dirpath, _dirnames, filenames in os.walk(pkg):
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, start=1):
                    if not BROAD.match(line):
                        continue
                    counts[rel] = counts.get(rel, 0) + 1
                    if MARKER not in line:
                        findings.append(
                            f"{rel}:{lineno}: broad except without a "
                            f"'{MARKER}' justification comment — raise a "
                            "classified reliability error instead, or declare "
                            "the barrier"
                        )
    for rel, n in sorted(counts.items()):
        want = ALLOWED.get(rel)
        if want is None:
            findings.append(
                f"{rel}: {n} broad except(s) in a file with no declared "
                "barriers — new broad catches must be added to "
                "tools/lint_fault_barrier.py ALLOWED deliberately"
            )
        elif n != want:
            findings.append(
                f"{rel}: expected {want} declared barrier(s), found {n} — "
                "update tools/lint_fault_barrier.py ALLOWED if intentional"
            )
    for rel, want in sorted(ALLOWED.items()):
        if rel not in counts and os.path.exists(os.path.join(repo_root, rel)):
            findings.append(
                f"{rel}: allowlist expects {want} barrier(s) but none found — "
                "prune the stale ALLOWED entry"
            )
    return findings, counts


@register
class FaultBarrierRule(Rule):
    id = "fault-barrier"
    title = "broad excepts only at declared, annotated fault barriers"
    roots = ("video_features_tpu",)

    def annotation_live(self, src, line: int) -> bool:
        # this rule's grammar is line-level (the marker must sit on the
        # broad-except line itself, or the line above it vftlint-style), so
        # "live" means: the annotated line is still a broad except
        lines = src.text.splitlines()
        for ln in (line, line + 1):
            if 1 <= ln <= len(lines) and BROAD.match(lines[ln - 1]):
                return True
        return False

    # scan() is whole-tree; run it once from finalize instead of per file
    def finalize(self, root: str) -> Iterable[Finding]:
        findings: List[Finding] = []
        for text in scan(root)[0]:
            loc, _, message = text.partition(": ")
            path, _, lineno = loc.partition(":")
            findings.append(Finding(
                path, int(lineno) if lineno else 0, self.id, message))
        return findings

"""Shared by the four set-up readers (``setup_weights_s``, ``setup_compile_s``,
``setup_compiles``, ``setup_program_s``).

The program keeps its set-up records in one process-wide recorder, whatever
the recording switch says, and hands them out in every run's
``_pack_stats["setup"]`` (``{"clock": "time_ns", "records", ...}``, the
format of the window's ``spans``, on the same ``time.time_ns()`` clock):
``construct`` (an extractor's construction), ``load_weights`` (one a
checkpoint; a child ``place_wait``), ``compile`` (one a compile or
persistent-cache load, ids ``program`` and ``cache``: ``hit`` or ``miss``),
``trace`` and ``lower`` (the outermost of each, from JAX's own events), and
one ``run`` a run. The last ``run`` is the window's; the program's set-up is
from its first ``construct`` to that run's start.

A program without set-up records (the parent of the PR that brought them):
every function here returns None, and the reader leaves its metric out.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

COMPILING = ("compile", "trace", "lower")


def setup_of(stats) -> Optional[Tuple[List[dict], int, int]]:
    """``(records, start, end)``: the program's set-up, from its first
    ``construct`` to the start of the last ``run``, and the records that lie
    whole inside it; None without set-up records."""
    records = ((stats or {}).get("setup") or {}).get("records")
    if not records:
        return None
    runs = [r["start"] for r in records if r["name"] == "run"]
    if not runs:
        return None
    end = runs[-1]
    constructs = [r["start"] for r in records if r["name"] == "construct" and r["start"] <= end]
    if not constructs:
        return None
    start = constructs[0]
    inside = [r for r in records
              if r["end"] is not None and start <= r["start"] and r["end"] <= end]
    return inside, start, end


def compiling(records: List[dict]) -> List[List[int]]:
    """The ``compile``, ``trace`` and ``lower`` records' intervals, merged
    where they overlap (another thread's, a trace inside a lowering)."""
    pieces: List[List[int]] = []
    for s, e in sorted((r["start"], r["end"]) for r in records if r["name"] in COMPILING):
        if pieces and s <= pieces[-1][1]:
            pieces[-1][1] = max(pieces[-1][1], e)
        else:
            pieces.append([s, e])
    return pieces


def overlap(pieces: List[List[int]], start: int, end: int) -> int:
    """Nanoseconds of ``pieces`` inside ``[start, end]``."""
    return sum(max(0, min(end, b) - max(start, a)) for a, b in pieces)

"""Shared by the text stream's readers (``attn_core_roofline``,
``moe_experts_roofline``, ``moe_dispatch_pct``, ``attention_pct``,
``expert_load_max_over_mean``): the whole executions of the page program in
the traced slice, the page each one ran (by ``_spans``' clock rule: execution
``k`` of the slice is page ``first_page + k``), what that page held (the
``documents`` the program's ``stage`` span records for a token page), and the
self time of the operations under a scope inside those executions alone, so
that time and work are of the same pages.

A program without token pages or scopes, a trace without whole executions,
no records: every function returns None and the reader leaves its metric out.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from trace_reduce import MODULES_LINE, OPS_LINE, page_program, self_times

from ._spans import clock_check, device_planes, load, records_of


def whole_pages(trace: dict, stats: dict) -> Optional[Tuple[dict, List[Tuple[int, int, List[int]]]]]:
    """→ (the first device's plane, ``[(start, end, documents)]`` of every
    whole execution whose page's documents are on record), times in the
    trace's own nanoseconds."""
    records = records_of(stats)
    space = load(trace.get("path"))
    if records is None or space is None or space["profile_start_ns"] is None:
        return None
    planes = device_planes(space, trace)
    documents = {r["ids"]["page"]: r["ids"]["documents"] for r in records
                 if r["name"] == "stage" and "documents" in r["ids"]}
    if not planes or not documents:
        return None
    plane = planes[0]
    check = clock_check(plane, records, space["profile_start_ns"])
    if check is None:
        return None
    modules = plane["lines"].get(MODULES_LINE, ())
    program = page_program(modules)
    ordered = sorted(modules, key=lambda m: m[1])
    pages = sorted({r["ids"]["page"] for r in records
                    if r["name"] == "launch" and "page" in r["ids"]}
                   & {r["ids"]["page"] for r in records
                      if r["name"] == "device" and "page" in r["ids"]
                      and r.get("end") is not None})  # clock_check's own list
    page_of_run = iter(pages[pages.index(check["first_page"]):])
    whole = []
    for k, (name, start, dur) in enumerate(ordered):
        if name != program:
            continue
        page = next(page_of_run, None)
        if 0 < k < len(ordered) - 1 and page in documents:  # cut by neither end of the slice
            whole.append((start, start + dur, documents[page]))
    return (plane, whole) if whole else None


def scope_seconds_inside(plane: dict, intervals: List[Tuple[int, int]], scope: str) -> float:
    """Self time (s) of the operations whose scope holds ``scope`` and that
    begin inside one of ``intervals``."""
    events = [(m, s, d) for m, s, d in plane["lines"][OPS_LINE]
              if any(a <= s < b for a, b in intervals)]
    ns = sum(t for meta, t in self_times(events).items()
             if scope in plane["metadata"][meta][1])
    return ns / 1e9


def roofline(trace: dict, stats: dict, facts: dict, scope: str, work_of_page) -> Optional[float]:
    """100 x least time of the whole pages' work under ``scope`` over the
    self time of the operations under it in those pages. ``work_of_page
    (documents) -> (operations, bytes)``; least time is the larger of
    operations over the bf16 peak and bytes over the memory bandwidth."""
    from peaks import peaks_for

    found = whole_pages(trace, stats)
    if found is None:
        return None
    plane, whole = found
    seconds = scope_seconds_inside(plane, [(a, b) for a, b, _d in whole], scope)
    if seconds <= 0.0:
        return None
    peaks = peaks_for(facts["device_kind"], facts["peaks"])
    least = 0.0
    for _a, _b, documents in whole:
        ops, nbytes = work_of_page(documents)
        least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def busy_share(trace: dict, scopes: Tuple[str, ...]) -> Optional[float]:
    """Share (%) of the slice's busy time under any of ``scopes``; None
    where the program's operations carry no ``laguna/`` scope."""
    from ._spans import scope_seconds

    if not trace.get("busy_s"):
        return None
    seconds = scope_seconds(trace, scopes)
    if seconds is None or (not seconds and not scope_seconds(trace, ("laguna/",))):
        return None
    return 100.0 * seconds / trace["busy_s"]

"""Shared by the readers that lay the program's own span records beside the
device trace: ``idle_decode_pct``, ``idle_transfer_pct``,
``idle_host_other_pct``, ``flow_resize_pct`` (and the writer's two, which
need the records only).

The program stamps its spans in ``time.time_ns()``
(``_pack_stats["spans"]["records"]``); the profiler gives every event's start
relative to the session's start and the session's start in Unix nanoseconds
(stat ``profile_start_time`` of the plane ``Task Environment``), so
``profile_start_time + start_ns`` is on the same clock. The file is the one
the reduction was made from: its ``path`` (``trace_reduce.reduce_trace_dir``).

The scope of a device operation (``jax.named_scope``) is the ``tf_op`` stat
of its event's METADATA, which ``jax.profiler.ProfileData`` does not show (it
gives an event's own stats only), so the file is read here by its wire format
(``XSpace`` of tsl/profiler/protobuf/xplane.proto), with nothing but Python.

A program without span records (the parent of the PR that brought them), a
trace without ``profile_start_time``, or no trace at all: every function here
returns None, and the reader leaves its metric out.
"""

from __future__ import annotations

import bisect
import os
import struct
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE, page_program, self_times,
                          union_length)

ENVIRONMENT_PLANE = "Task Environment"
START_STAT = "profile_start_time"
SCOPE_STAT = "tf_op"

ALIGN_SLACK_NS = 5_000_000

DECODE_SPANS = ("pull",)
TRANSFER_SPANS = ("stage", "put")


# --- the wire format ---------------------------------------------------------


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for a length-delimited field, raw bytes for fixed ones."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            value = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf) -> Tuple[int, object]:
    """XStat → (metadata id, value); a ``ref_value`` comes back as
    ``("ref", id)`` for the caller to look up among the stat names."""
    meta, value = 0, None
    for number, wire, v in _fields(buf):
        if number == 1:
            meta = v
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif number == 7:
            value = ("ref", v)
    return meta, value


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane_name(buf) -> str:
    for number, _wire, v in _fields(buf):
        if number == 2:
            return bytes(v).decode("utf-8", "replace")
    return ""


def _read_plane(buf, want_lines) -> dict:
    """One XPlane: its stats by name, and of the lines named in
    ``want_lines`` the events as ``(metadata id, start_ns, duration_ns)``
    with ``start_ns`` relative to the session's start, as ``ProfileData``
    gives it; the events' metadata as ``id → (name, scope)``."""
    lines, raw_meta, stat_names, raw_stats = {}, {}, {}, []
    for number, _wire, v in _fields(buf):
        if number == 3:
            name, timestamp_ns, events = "", 0, []
            for n2, _w2, v2 in _fields(v):
                if n2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
                elif n2 == 3:
                    timestamp_ns = _signed(v2)
                elif n2 == 4:
                    events.append(v2)
            if name in want_lines:
                out = []
                for ev in events:
                    meta = offset_ps = duration_ps = 0
                    for n3, _w3, v3 in _fields(ev):
                        if n3 == 1:
                            meta = v3
                        elif n3 == 2:
                            offset_ps = _signed(v3)
                        elif n3 == 3:
                            duration_ps = _signed(v3)
                    out.append((meta, timestamp_ns + offset_ps // 1000,
                                duration_ps // 1000))
                lines.setdefault(name, []).extend(out)
        elif number == 4:
            key, value = _map_entry(v)
            raw_meta[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            for n2, _w2, v2 in _fields(value):
                if n2 == 2:
                    stat_names[key] = bytes(v2).decode("utf-8", "replace")
        elif number == 6:
            raw_stats.append(v)

    def resolve(value):
        if isinstance(value, tuple):
            return stat_names.get(value[1], "")
        return value

    stats = {}
    for raw in raw_stats:
        meta, value = _stat(raw)
        stats[stat_names.get(meta, str(meta))] = resolve(value)
    used = {m for events in lines.values() for m, _s, _d in events}
    metadata = {}
    for key in used:
        name, scope = "", ""
        for n2, _w2, v2 in _fields(raw_meta.get(key, b"")):
            if n2 == 2:
                name = bytes(v2).decode("utf-8", "replace")
            elif n2 == 5:
                meta, value = _stat(v2)
                if stat_names.get(meta) == SCOPE_STAT:
                    scope = str(resolve(value) or "")
        metadata[key] = (name, scope)
    return {"stats": stats, "lines": lines, "metadata": metadata}


def read_xspace(path: str) -> dict:
    """``{"profile_start_ns": int | None, "devices": {plane: {"lines",
    "metadata"}}}`` of one ``.xplane.pb``."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    start, devices = None, {}
    for number, _wire, v in _fields(data):
        if number != 1:
            continue
        name = _plane_name(v)
        if name == ENVIRONMENT_PLANE:
            value = _read_plane(v, ()).get("stats", {}).get(START_STAT)
            if value is not None:
                start = int(value)
        elif name.startswith(DEVICE_PREFIX):
            plane = _read_plane(v, (OPS_LINE, MODULES_LINE))
            if plane["lines"].get(OPS_LINE):
                devices[name] = plane
    return {"profile_start_ns": start, "devices": devices}


# --- this run's trace ----------------------------------------------------------

_CACHE: Dict[Tuple[str, float], dict] = {}


def load(path: Optional[str]) -> Optional[dict]:
    """The trace a reduction was made from (its ``path``), read once per
    process; None where the reduction names no file."""
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_xspace(path)
    return _CACHE[key]


def records_of(stats: dict) -> Optional[List[dict]]:
    spans = (stats or {}).get("spans") or {}
    if spans.get("clock") != "time_ns" or not spans.get("records"):
        return None
    return spans["records"]


def device_planes(space: dict, trace: dict) -> List[dict]:
    """The planes the reduction read, in its order."""
    names = trace.get("planes") or sorted(space["devices"])
    return [space["devices"][n] for n in names if n in space["devices"]]


# --- host spans against idle gaps ---------------------------------------------


def consumer_timeline(records: List[dict]) -> List[Tuple[int, int, str]]:
    """The consumer thread (the one that ran the ``run`` span) as sorted,
    disjoint ``(start, end, innermost span's name)`` pieces, built from the
    records' own parentage. ``records`` is the whole list, so that ``parent``
    indexes it; a span that never ended is left out."""
    runs = [r for r in records if r["name"] == "run" and r.get("end") is not None]
    if not runs:
        return []
    thread = runs[-1]["thread"]
    children: Dict[Optional[int], List[int]] = {}
    for i, r in enumerate(records):
        if r["thread"] == thread and r.get("end") is not None:
            children.setdefault(r.get("parent"), []).append(i)
    pieces: List[Tuple[int, int, str]] = []

    def walk(index: int, lo: int, hi: int) -> None:
        # a record made after the fact (a blocked pull) may reach a few
        # microseconds outside its parent or behind its elder sibling:
        # clamp, so that the pieces stay disjoint
        r = records[index]
        cur, end = max(r["start"], lo), min(r["end"], hi)
        for child in sorted(children.get(index, ()), key=lambda i: records[i]["start"]):
            c0 = min(max(records[child]["start"], cur), end)
            if c0 > cur:
                pieces.append((cur, c0, r["name"]))
            walk(child, c0, end)
            cur = max(c0, min(records[child]["end"], end))
        if end > cur:
            pieces.append((cur, end, r["name"]))

    cur = 0
    for top in sorted(children.get(None, ()), key=lambda i: records[i]["start"]):
        walk(top, cur, records[top]["end"])
        cur = max(cur, records[top]["end"])
    return pieces


def gap_seconds_by_span(gaps: List[Tuple[int, int]],
                        pieces: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of the ``gaps`` (absolute ``(start, end)``) under each
    innermost span name; a gap that straddles a boundary is split at it.
    Time under no span at all is not in the result."""
    out: Dict[str, int] = {}
    starts = [p[0] for p in pieces]
    for g0, g1 in gaps:
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(pieces) and pieces[i][0] < g1:
            p0, p1, name = pieces[i]
            overlap = min(p1, g1) - max(p0, g0)
            if overlap > 0:
                out[name] = out.get(name, 0) + overlap
            i += 1
    return out


def clock_check(plane: dict, records: List[dict], start_ns: int) -> Optional[dict]:
    """Is the device's plane on the ``profile_start_time`` clock? Every
    execution of the page program in the slice (an ``XLA Modules`` event)
    must start after the ``launch`` span of a page starts and end before that
    page's ``device`` span ends, consecutive executions in consecutive pages.
    Returns the worst residual (how far outside its page's interval an
    execution lies, 0 when inside), the one offset that brings every
    execution inside where there is one, and how far out each alignment
    tried was (the first traced execution taken for each page in turn)."""
    launches = {r["ids"]["page"]: r["start"] for r in records
                if r["name"] == "launch" and "page" in r["ids"]}
    fetched = {r["ids"]["page"]: r["end"] for r in records
               if r["name"] == "device" and "page" in r["ids"]
               and r.get("end") is not None}
    pages = sorted(set(launches) & set(fetched))
    modules = plane["lines"].get(MODULES_LINE, ())
    if not pages or not modules:
        return None
    program = page_program(modules)
    runs = sorted((s, s + d) for m, s, d in modules if m == program)
    # which page is the first traced execution's? The alignment under which
    # the executions lie best inside their pages' intervals (a device-bound
    # run launches a page one execution ahead and fetches it as it ends; a
    # host-bound one launches into an idle device and fetches a page late:
    # one of the two ends is tight either way). An idle device begins a page
    # as it is launched, so an execution that the one before did not hold up
    # is also out by what it began later than that: a late fetch alone would
    # let it lie inside the page before its own as well.
    def outside(first: int) -> int:
        out, before = 0, None
        for (s, e), p in zip(runs, pages[first:]):
            out = max(out, launches[p] - (start_ns + s), (start_ns + e) - fetched[p])
            if before is not None and s - before > ALIGN_SLACK_NS:
                out = max(out, (start_ns + s) - launches[p] - ALIGN_SLACK_NS)
            before = e
        return out

    # the least out wins, the later page where two are level. (A plane a few
    # milliseconds off the clock still reads as that and not as a shift by a
    # page: a device-bound run launches page p+1 a few milliseconds after
    # execution p began, 1.9-7.0 ms in PR 32's traced runs, so no fixed slack
    # tells the two apart, and the page's own interval has to.)
    worst = [outside(k) for k in range(max(len(pages) - len(runs), 0) + 1)]
    first = max(k for k, w in enumerate(worst) if w == min(worst))
    matched = list(zip(runs, pages[first:]))
    # offset window: launch - start <= offset <= fetched - end
    low = max(launches[p] - (start_ns + s) for (s, _e), p in matched)
    high = min(fetched[p] - (start_ns + e) for (_s, e), p in matched)
    offset = 0 if low <= 0 <= high else (low if low > 0 else high)
    if low > high:
        offset = (low + high) // 2
    return {"executions": len(runs), "matched": len(matched),
            "worst_residual_ns": max(low, -high, 0),
            "offset_ns": offset, "offset_window_ns": [low, high],
            "first_page": pages[first], "out_by_alignment_ns": worst}


def on_one_clock(trace: dict, stats: dict) -> Optional[Tuple[List[dict], list, int]]:
    """The device planes the reduction read, the consumer thread's timeline,
    and the Unix time (ns) of the trace's zero by the clock rule above; None
    where the records, the trace or its ``profile_start_time`` are missing."""
    records = records_of(stats)
    if records is None:
        return None
    space = load(trace.get("path"))
    if space is None or space["profile_start_ns"] is None:
        return None
    planes = device_planes(space, trace)
    pieces = consumer_timeline(records)
    if not planes or not pieces:
        return None
    start_ns = space["profile_start_ns"]
    check = clock_check(planes[0], records, start_ns)
    if check is not None:
        print(f"[spans] clock: {check}", file=sys.stderr, flush=True)
        start_ns += check["offset_ns"]
    return planes, pieces, start_ns


def idle_shares(trace: dict, stats: dict, facts: dict) -> Optional[Dict[str, float]]:
    """``{"decode", "transfer", "other"}``: the share (%) of the traced span
    in which no device operation ran and the consumer thread's innermost open
    span was ``pull`` / ``stage`` or ``put`` / anything else. Gaps and span
    are ``trace_reduce.union_length``'s over the same first-start-to-last-end
    span ``device_idle_pct`` uses, averaged over the cell's device planes, so
    the three add up to it."""
    if not trace.get("span_s"):
        return None
    aligned = on_one_clock(trace, stats)
    if aligned is None:
        return None
    planes, pieces, start_ns = aligned
    decode = transfer = 0
    for plane in planes:
        _busy, gaps = union_length([(s, s + d) for _m, s, d in plane["lines"][OPS_LINE]])
        by = gap_seconds_by_span([(start_ns + a, start_ns + b) for a, b in gaps], pieces)
        decode += sum(by.get(n, 0) for n in DECODE_SPANS)
        transfer += sum(by.get(n, 0) for n in TRANSFER_SPANS)
    span_ns = trace["span_s"] * 1e9 * len(planes)
    idle = 100.0 * (1.0 - trace["busy_s"] / trace["span_s"])
    shares = {"decode": 100.0 * decode / span_ns,
              "transfer": 100.0 * transfer / span_ns}
    shares["other"] = idle - shares["decode"] - shares["transfer"]
    return shares


GAP_NAMES = ("pull", "stage", "put", "launch", "device", "finalize", "write_reap")


def name_idle_gaps(trace: dict, stats: dict) -> List[list]:
    """``trace["idle_gaps"]`` with each gap named by the consumer thread's
    innermost span at the moment the gap began: one of ``GAP_NAMES``, else
    ``other`` (its own Python, or no span). Where two gaps share a name, each
    keeps its rank as a suffix (``device.0``, ``device.3``). The durations are
    the reduction's own; without records the names by rank stand."""
    gaps = trace["idle_gaps"]
    aligned = on_one_clock(trace, stats)
    if aligned is None or len(trace.get("idle_gap_starts", ())) != len(gaps):
        return gaps
    _planes, pieces, start_ns = aligned
    starts = [p[0] for p in pieces]
    names = []
    for gap_start in trace["idle_gap_starts"]:
        at = start_ns + gap_start
        i = bisect.bisect_right(starts, at) - 1
        inside = i >= 0 and pieces[i][0] <= at < pieces[i][1]
        names.append(pieces[i][2] if inside and pieces[i][2] in GAP_NAMES else "other")
    return [[name if names.count(name) == 1 else f"{name}.{rank}", seconds]
            for rank, (name, (_old, seconds)) in enumerate(zip(names, gaps))]


# --- device operations by scope ------------------------------------------------


def scope_seconds(trace: dict, scopes: Tuple[str, ...]) -> Optional[float]:
    """Self time (s, averaged over the device planes) of the traced
    operations whose scope holds one of ``scopes``; None where no operation
    carries a scope at all (a program without them)."""
    space = load(trace.get("path"))
    if space is None:
        return None
    planes = device_planes(space, trace)
    if not planes or not any(scope for plane in planes
                             for _n, scope in plane["metadata"].values()):
        return None
    total = 0
    for plane in planes:
        for meta, ns in self_times(plane["lines"][OPS_LINE]).items():
            scope = plane["metadata"][meta][1]
            if any(s in scope for s in scopes):
                total += ns
    return total / len(planes) / 1e9

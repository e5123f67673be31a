"""From a profiler trace (``.xplane.pb``) to numbers: device busy intervals,
idle gaps, and per-operation self time by the trace's own names.

Read with nothing but JAX (``jax.profiler.ProfileData``). A TPU's plane is
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed
HLO operation (nested: a ``while`` holds its body's operations). Busy time is
the union of those events' intervals; an operation's self time is its duration
less what its children cover, so a loop is not counted twice.

    python3 benchmark/trace_reduce.py <trace dir or .xplane.pb>   # look by hand
"""

from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(directory: str) -> str:
    if os.path.isfile(directory):
        return directory
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def short_name(hlo: str, limit: int = 96) -> str:
    """An event of the ``XLA Ops`` line is named by its whole HLO instruction;
    keep the instruction's name and its result shape."""
    head = hlo.split(" = ", 1)
    if len(head) == 1:
        return hlo[:limit]
    shape = head[1].split(" ", 1)[0].split("{", 1)[0]
    return f"{head[0].strip()} {shape}"[:limit]


def load_planes(path: str) -> Dict[str, Dict[str, List[Tuple[str, int, int]]]]:
    """plane name → line name → [(event name, start_ns, duration_ns)]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns)) for ev in line.events)
        planes[plane.name] = lines
    return planes


def union_length(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total covered length of ``(start, end)`` intervals, and the gaps
    between the merged pieces."""
    total, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def self_times(events: List[Tuple[str, int, int]]) -> Dict[str, int]:
    """Self time per event name: duration less the time covered by events
    nested inside it (events of one line nest, they do not cross)."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[List] = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] += done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, end, dur])
    while stack:
        done = stack.pop()
        out[done[0]] += done[2]
    return dict(out)


def page_program(modules: List[tuple]):
    """The page program among a device's ``XLA Modules`` events ``(key, start,
    duration)``: the module that holds most of the line's time."""
    seconds: Dict[object, int] = defaultdict(int)
    for key, _s, d in modules:
        seconds[key] += d
    return max(seconds, key=seconds.get)


def whole_executions(modules: List[Tuple[str, int, int]]) -> int:
    """How many whole executions of the page program a device's ``XLA
    Modules`` line holds. A device runs one module at a time, and an
    execution cut by the trace's start or stop is recorded as the piece
    inside the trace: so one that has a predecessor on the line started
    inside the trace, one that has a successor ended inside it, and the
    line's first and last events are not counted."""
    if not modules:
        return 0
    program = page_program(modules)
    ordered = sorted(modules, key=lambda e: e[1])
    return sum(1 for name, _s, _d in ordered[1:-1] if name == program)


def reduce_planes(planes: dict, chips: int = 1) -> dict:
    device_planes = sorted(n for n in planes if n.startswith(DEVICE_PREFIX)
                           and OPS_LINE in planes[n])
    if not device_planes:
        raise ValueError(f"no TPU plane with an {OPS_LINE!r} line; planes: {sorted(planes)}")
    device_planes = device_planes[:chips]
    busy_ns, op_ns, op_n, gaps_all, span = 0, defaultdict(int), defaultdict(int), [], 0
    for name in device_planes:
        events = planes[name][OPS_LINE]
        total, gaps = union_length([(s, s + d) for _n, s, d in events])
        busy_ns += total
        if events:
            span = max(span, max(s + d for _n, s, d in events) - min(s for _n, s, _d in events))
        gaps_all += [(e - s, s) for s, e in gaps]
        for op, ns in self_times(events).items():
            op_ns[op] += ns
        for op, _s, _d in events:
            op_n[op] += 1
    n = len(device_planes)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])
    gaps_all.sort(reverse=True)
    return {
        "busy_s": busy_ns / n / 1e9,
        "span_s": span / 1e9,
        "op_seconds": {k: v / n / 1e9 for k, v in top},
        "op_counts": {k: op_n[k] / n for k, _v in top},
        "top_ops": [[short_name(k), v / n / 1e9] for k, v in top[:10]],
        # named by rank here; the caller that holds the program's span records
        # names them by host span (layer_metrics/_spans.name_idle_gaps)
        "idle_gaps": [[f"gap{i}", g / 1e9] for i, (g, _s) in enumerate(gaps_all[:10])],
        # where the same ten begin, in ns from the session's start
        "idle_gap_starts": [s for _g, s in gaps_all[:10]],
        "slice_pages": min(whole_executions(planes[name].get(MODULES_LINE, []))
                           for name in device_planes),
        "planes": device_planes,
    }


def reduce_trace_dir(directory: str, chips: int = 1) -> dict:
    """The reduction of the one trace under ``directory``, with the file's
    path beside it (``path``) for the readers that need more than this."""
    path = find_xplane(directory)
    return dict(reduce_planes(load_planes(path), chips), path=path)


def describe(path: str) -> dict:
    """A summary to look at by hand: planes, lines, counts, first names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            names = defaultdict(int)
            for ev in evs:
                names[ev.name] += ev.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:40]
            sample_stats = {}
            if evs:
                try:
                    sample_stats = {str(k): str(v)[:300] for k, v in evs[len(evs) // 2].stats}
                except Exception as e:  # noqa: BLE001 — a look by hand, not a metric
                    sample_stats = {"error": repr(e)}
            lines[line.name] = {"events": len(evs), "top_by_time_ns": top,
                                "sample_stats": sample_stats}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    print(json.dumps(describe(sys.argv[1]), indent=1))

"""The benchmark's four set-up readers (``benchmark/layer_metrics/setup_*.py``)
on hand-made ``_pack_stats["setup"]`` records: None where the program left
none (the parent of the PR that brought them), else the exact seconds or
count. The layout, in seconds from the first construction: construct 0–10
holding load_weights 1–6 (a trace 1.5–2 and a compile 2–3 miss inside it, its
place_wait 5–6) and a cached compile 8–9; the warm-up run 11–20 with a
lowering 11.5–12 and a compile 12–14 miss; the window's run from 21, a
compile 22–23 inside it."""

import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from layer_metrics import (setup_compile_s, setup_compiles, setup_program_s,  # noqa: E402
                           setup_weights_s)

READERS = (setup_weights_s, setup_compile_s, setup_compiles, setup_program_s)
T0 = 1_790_000_000_000_000_000  # Unix ns
S = 1_000_000_000


def rec(name, start, end, parent=None, **ids):
    return {"name": name, "thread": "MainThread", "start": T0 + int(start * S),
            "end": None if end is None else T0 + int(end * S), "parent": parent, "ids": ids}


RECORDS = [
    rec("construct", 0, 10, model="laguna"),
    rec("load_weights", 1, 6, 0, checkpoint="laguna", leaves=3, bytes_read=8, bytes_placed=4),
    rec("trace", 1.5, 2, 1, program="convert_element_type"),
    rec("compile", 2, 3, 1, program="jit(convert_element_type)", cache="miss"),
    rec("place_wait", 5, 6, 1),
    rec("compile", 8, 9, 0, program="jit(stack)", cache="hit"),
    rec("run", 11, 20, model="laguna"),
    rec("lower", 11.5, 12, 6, program="jit(paged)"),
    rec("compile", 12, 14, 6, program="jit(paged)", cache="miss"),
    rec("run", 21, None, model="laguna"),
    rec("compile", 22, 23, 9, program="jit(late)", cache="miss"),
]


def stats_with(records):
    return {"setup": {"clock": "time_ns", "records": records, "self_seconds": {}, "dropped": 0}}


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__.rsplit(".", 1)[-1])
def test_setup_readers_read_nothing_without_set_up_records(reader):
    """No ``setup`` (the parent's stats), an empty one, one without a run or
    without a construction: None, and the metric is left out."""
    assert reader.read(None, {"stage_seconds": {}}, {}) is None
    assert reader.read(None, None, {}) is None
    assert reader.read(None, stats_with([]), {}) is None
    assert reader.read(None, stats_with(RECORDS[:6]), {}) is None
    assert reader.read(None, stats_with(RECORDS[6:]), {}) is None


def test_setup_weights_s_leaves_out_the_compiles_inside():
    """load_weights 5 s less the trace and compile inside it (1.5–3): 3.5 s."""
    assert setup_weights_s.read(None, stats_with(RECORDS), {}) == 3.5


def test_setup_compile_s_merges_and_stops_at_the_window():
    """[1.5, 3] + [8, 9] + [11.5, 14], merged, the window's compile left out:
    5.0 s."""
    assert setup_compile_s.read(None, stats_with(RECORDS), {}) == 5.0


def test_setup_compiles_counts_misses_before_the_window():
    """Two misses; the hit is a load, the window's compile is not set-up."""
    assert setup_compiles.read(None, stats_with(RECORDS), {}) == 2


def test_setup_program_s_from_construction_to_the_window():
    """From the first construct's start to the window run's start: 21 s, and
    the other two readers' seconds fit inside it."""
    program = setup_program_s.read(None, stats_with(RECORDS), {})
    assert program == 21.0
    stats = stats_with(RECORDS)
    assert (setup_weights_s.read(None, stats, {}) + setup_compile_s.read(None, stats, {})
            <= program)

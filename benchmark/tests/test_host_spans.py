"""The readers that lay the program's span records beside the device trace
(``layer_metrics/_spans.py``), on hand-made planes and records, and
``flow_resize_pct`` on one small trace recorded on the v5e
(``data/pwc_page.xplane.pb``: one execution of PWC-Net over 2 clips of 5
frames at 256x341, the cell's geometry, with the program's scopes and kernel
names; cut to the TPU plane's ``XLA Ops``/``XLA Modules`` lines, the ``tf_op``
stat and the session's ``profile_start_time``)."""

import os

import pytest

import trace_reduce as tr
from layer_metrics import (_spans, flow_resize_pct, idle_decode_pct,
                           idle_host_other_pct, idle_transfer_pct,
                           pwc_corr_roofline, writer_backlog_max,
                           writer_s_per_video)

HERE = os.path.dirname(os.path.abspath(__file__))
T0 = 1_790_000_000_000_000_000  # the session's start, Unix ns
MS = 1_000_000


def rec(name, start_ms, end_ms, parent=None, thread="MainThread", **ids):
    return {"name": name, "thread": thread, "start": T0 + int(start_ms * MS),
            "end": None if end_ms is None else T0 + int(end_ms * MS),
            "parent": parent, "ids": ids}


def space_of(ops, modules=(), start=T0):
    """A parsed trace with one TPU plane: ops/modules as (start_ms, end_ms)."""
    def events(pairs, meta):
        return [(meta, int(a * MS), int((b - a) * MS)) for a, b in pairs]

    plane = {"lines": {tr.OPS_LINE: events(ops, 1),
                       _spans.MODULES_LINE: events(modules, 2)},
             "metadata": {1: ("%fusion.1", "jit(paged)/i3d/page/x"),
                          2: ("jit_paged(1)", "")}}
    return {"profile_start_ns": start, "devices": {"/device:TPU:0": plane}}


def reduction_of(space):
    ops = space["devices"]["/device:TPU:0"]["lines"][tr.OPS_LINE]
    return tr.reduce_planes({"/device:TPU:0": {tr.OPS_LINE: [("op", s, d) for _m, s, d in ops]}})


@pytest.fixture
def use_space(monkeypatch):
    def use(space):
        monkeypatch.setattr(_spans, "load", lambda path=None: space)
    return use


def shares(trace, stats):
    facts = {"chips": 1}
    return (idle_decode_pct.read(trace, stats, facts),
            idle_transfer_pct.read(trace, stats, facts),
            idle_host_other_pct.read(trace, stats, facts))


# the device ran 0-100, 300-400, 420-500, 560-600 ms: gaps 100-300 (200 ms),
# 400-420 (20 ms), 500-560 (60 ms) of a 600 ms span
OPS = [(0, 100), (300, 400), (420, 500), (560, 600)]
RECORDS = [
    rec("run", -50, 700),                       # 0
    rec("extract", -40, 650, parent=0, video="a"),   # 1
    rec("pull", 90, 310, parent=1, video="a"),       # 2: gap 1 wholly inside
    rec("launch", 405, 430, parent=1, page=3),       # 3
    rec("put", 410, 415, parent=3, page=3),          # 4: gap 2 straddles it
    rec("device", 520, 540, parent=1, page=2),       # 5
    rec("write", 100, 300, thread="output-writer", video="z"),  # another thread
]
STATS = {"spans": {"clock": "time_ns", "records": RECORDS, "dropped": 0},
         "videos_written": 4, "writer_backlog_max": 2}


def test_gap_inside_a_pull_goes_to_decode_and_the_three_sum_to_idle(use_space):
    space = space_of(OPS)
    use_space(space)
    trace = reduction_of(space)
    decode, transfer, other = shares(trace, STATS)
    idle = 100.0 * (1 - trace["busy_s"] / trace["span_s"])
    assert idle == pytest.approx(100 * 280 / 600)
    assert decode == pytest.approx(100 * 200 / 600)     # gap 1, all of it
    # gap 2 (400-420) straddles launch's own time and the put inside it:
    # split at the boundaries, 5 ms to the put
    assert transfer == pytest.approx(100 * 5 / 600)
    # the rest: 15 ms of gap 2 (extract's and launch's own), all of gap 3
    # (extract 500-520, device 520-540, extract 540-560)
    assert other == pytest.approx(100 * 75 / 600)
    assert decode + transfer + other == pytest.approx(idle, abs=1e-9)


def test_innermost_span_wins_and_another_threads_span_takes_nothing():
    pieces = _spans.consumer_timeline(RECORDS)
    assert [p[2] for p in pieces] == ["run", "extract", "pull", "extract", "launch",
                                      "put", "launch", "extract", "device",
                                      "extract", "run"]
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))  # disjoint, sorted
    by = _spans.gap_seconds_by_span([(T0 + 400 * MS, T0 + 420 * MS)], pieces)
    assert by == {"extract": 5 * MS, "launch": 10 * MS, "put": 5 * MS}
    assert "write" not in {p[2] for p in pieces}


def test_time_under_no_span_is_host_other(use_space):
    space = space_of([(0, 100), (300, 400)])
    use_space(space)
    trace = reduction_of(space)
    records = [rec("run", 0, 150), rec("stage", 120, 140, parent=0, page=0)]
    stats = {"spans": {"clock": "time_ns", "records": records}}
    decode, transfer, other = shares(trace, stats)
    assert decode == 0.0
    assert transfer == pytest.approx(100 * 20 / 400)
    assert other == pytest.approx(100 * 180 / 400)  # 30 ms of run, 150 of nothing


def test_a_record_made_after_the_fact_may_overlap_its_sibling():
    records = [rec("run", 0, 100), rec("stage", 10, 20.004, parent=0, page=0),
               rec("pull", 20, 60, parent=0)]  # 4 us behind the stage's end
    pieces = _spans.consumer_timeline(records)
    assert [p[2] for p in pieces] == ["run", "stage", "pull", "run"]
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    assert pieces[2][1] - pieces[2][0] == pytest.approx(40 * MS, abs=5000)


def test_unfinished_and_dropped_spans_are_tolerated():
    records = [rec("run", 0, 100), rec("decode", 5, None, thread="Thread-1"),
               rec("extract", 10, None, parent=0), rec("pull", 20, 30, parent=2)]
    pieces = _spans.consumer_timeline(records)
    # the pull hangs from a span that never ended: it is left out with it
    assert [p[2] for p in pieces] == ["run"]


@pytest.mark.parametrize("broken", ["no_records", "no_start", "no_trace",
                                    "wrong_clock", "no_run_span"])
def test_missing_inputs_give_none_and_never_a_zero(use_space, monkeypatch, broken):
    space = space_of(OPS)
    stats = STATS
    if broken == "no_records":
        stats = {"stage_seconds": {"decode": 1.0}}  # the parent's _pack_stats
    elif broken == "no_start":
        space = space_of(OPS, start=None)
    elif broken == "wrong_clock":
        stats = {"spans": {"clock": "monotonic", "records": RECORDS}}
    elif broken == "no_run_span":
        stats = {"spans": {"clock": "time_ns", "records": RECORDS[1:]}}
    trace = reduction_of(space_of(OPS))
    if broken != "no_trace":  # else the reduction names no file: nothing is looked for
        use_space(space)
    assert shares(trace, stats) == (None, None, None)
    assert _spans.name_idle_gaps(trace, stats) == trace["idle_gaps"]  # ranks stand


def test_idle_gaps_are_named_by_the_span_they_began_in(use_space):
    space = space_of(OPS)
    use_space(space)
    trace = reduction_of(space)
    # by length: 100-300 began inside the pull; 500-560 and 400-420 began in
    # extract's own Python (the launch starts at 405), which is `other`
    assert _spans.name_idle_gaps(trace, STATS) == [
        ["pull", pytest.approx(0.2)], ["other.1", pytest.approx(0.06)],
        ["other.2", pytest.approx(0.02)]]
    assert [g[1] for g in _spans.name_idle_gaps(trace, STATS)] == \
        [g[1] for g in trace["idle_gaps"]]  # labels only
    # a gap that begins under a put inside a launch, and one under no span
    space = space_of([(0, 412), (414, 690), (720, 800)])
    use_space(space)
    trace = reduction_of(space)
    assert [g[0] for g in _spans.name_idle_gaps(trace, STATS)] == ["other", "put"]


def test_clock_check_matches_executions_to_consecutive_pages():
    # pages 4, 5, 6: page p is launched while p-1 runs, fetched after it ends
    records = [rec("run", 0, 4000)]
    for k, page in enumerate((4, 5, 6)):
        records.append(rec("launch", 1000 * k - 900, 1000 * k - 890, parent=0, page=page))
        records.append(rec("device", 1000 * k + 10, 1000 * k + 1001, parent=0, page=page))
    inside = space_of([(0, 1)], modules=[(1000, 2000), (2000, 3000)])
    plane = inside["devices"]["/device:TPU:0"]
    check = _spans.clock_check(plane, records, T0)
    assert check["executions"] == check["matched"] == 2
    assert check["worst_residual_ns"] == 0 and check["offset_ns"] == 0
    # the same plane 3 ms early: the first execution starts before its
    # page's launch... it does not (launched 900 ms ahead); it ENDS inside.
    # 3 ms late: it ends 2 ms after its page's device span does
    late = _spans.clock_check(plane, records, T0 + 3 * MS)
    assert late["worst_residual_ns"] == 2 * MS
    assert late["offset_ns"] == -2 * MS  # the least shift that fits them all


def test_clock_check_host_bound_pages_are_fetched_late():
    """A host-bound run launches into an idle device and fetches page p only
    before it launches p+2: the execution's START is the tight end then."""
    records = [rec("run", 0, 5000)]
    for page in range(5):
        t = 1000 * page
        records.append(rec("launch", t, t + 5, parent=0, page=page))
        # fetched two pages later, just before launch(page + 2)
        records.append(rec("device", t + 1990, t + 1995, parent=0, page=page))
    # executions of pages 1, 2, 3 start 2 ms after their launch, last 400 ms;
    # another, small program runs in between and is not the page program
    space = space_of([(0, 1)], modules=[(1002, 1402), (2002, 2402), (3002, 3402)])
    plane = space["devices"]["/device:TPU:0"]
    plane["lines"][_spans.MODULES_LINE].append((3, 1500 * MS, 1 * MS))
    plane["metadata"][3] = ("jit_other(2)", "")
    check = _spans.clock_check(plane, records, T0)
    assert check["executions"] == check["matched"] == 3
    assert check["worst_residual_ns"] == 0
    assert check["offset_window_ns"][0] == -2 * MS  # tight at the start
    early = _spans.clock_check(plane, records, T0 - 3 * MS)  # plane 3 ms early
    assert early["worst_residual_ns"] == 1 * MS and early["offset_ns"] == 1 * MS


def test_clock_check_device_bound_page_launched_just_after_the_one_before_began():
    """Execution p begins as p-1 ends; the host sees p-1's result 2 ms later
    and launches p+1 another 2.9 ms on, so the alignment one page late is out
    by 4.9 ms only (1.9-7.0 ms in PR 32's traced runs): the one that fits
    wins, and a plane off the clock still reads as that."""
    records = [rec("run", 0, 20000)]
    for page in range(5):
        t = 3900 * page
        records.append(rec("launch", t - 3900 + 4.9, t - 3900 + 6, parent=0, page=page))
        records.append(rec("device", t + 10, t + 3902, parent=0, page=page))
    space = space_of([(0, 1)], modules=[(3900 * k, 3900 * (k + 1)) for k in (1, 2, 3)])
    plane = space["devices"]["/device:TPU:0"]
    check = _spans.clock_check(plane, records, T0)
    assert check["executions"] == check["matched"] == 3
    assert check["worst_residual_ns"] == 0 and check["offset_ns"] == 0
    assert check["offset_window_ns"] == [int(-3895.1 * MS), 2 * MS]  # pages 1, 2, 3
    assert check["first_page"] == 1
    assert check["out_by_alignment_ns"] == [3898 * MS, 0, int(4.9 * MS)]
    late = _spans.clock_check(plane, records, T0 + 3 * MS)  # plane 3 ms late
    assert late["worst_residual_ns"] == 1 * MS and late["offset_ns"] == -1 * MS


def test_writer_metrics_read_spans_and_counters():
    records = RECORDS + [rec("write", 310, 320, thread="output-writer", video="y")]
    stats = dict(STATS, spans={"clock": "time_ns", "records": records})
    assert writer_s_per_video.read({}, stats, {}) == pytest.approx((0.2 + 0.01) / 4)
    assert writer_backlog_max.read({}, stats, {}) == 2
    assert writer_s_per_video.read({}, {"stage_seconds": {}}, {}) is None
    assert writer_backlog_max.read({}, {"stage_seconds": {}}, {}) is None
    assert writer_s_per_video.read({}, dict(stats, videos_written=0), {}) is None


def test_wire_reader_agrees_with_profile_data():
    """The same events, starts and durations as ``jax.profiler.ProfileData``
    gives, plus what it does not give: the scope, from the metadata."""
    path = os.path.join(HERE, "data", "pwc_page.xplane.pb")
    space = _spans.read_xspace(path)
    assert space["profile_start_ns"] == 1790849951511916648
    (name, plane), = space["devices"].items()
    ref = tr.load_planes(path)[name]
    for line in (tr.OPS_LINE, _spans.MODULES_LINE):
        mine = plane["lines"][line]
        assert [(plane["metadata"][m][0], s, d) for m, s, d in mine] == \
            [(n[:200], s, d) for n, s, d in ref[line]]
    scopes = {scope for _n, scope in plane["metadata"].values()}
    assert any("pwc/resize_in" in s for s in scopes)
    assert any("pwc_corr81_tiled" in s for s in scopes)  # the kernel's name=
    assert _spans.read_xspace(os.path.join(HERE, "data", "small.xplane.pb"))[
        "profile_start_ns"] is not None


def test_flow_resize_pct_on_the_recorded_trace(use_space):
    path = os.path.join(HERE, "data", "pwc_page.xplane.pb")
    use_space(_spans.read_xspace(path))
    trace = tr.reduce_trace_dir(path)
    value = flow_resize_pct.read(trace, {}, {})
    # 46.0 of 56.0 ms of this execution: the corner-tap gathers of the two
    # resizes, whatever the fusions are called
    assert value == pytest.approx(82.1, abs=0.1)
    by_scope = {s: _spans.scope_seconds(trace, (s,)) for s in
                ("pwc/resize_in", "pwc/resize_out", "pwc/pyramid", "pwc/corr",
                 "pwc/warp", "pwc/decoder", "pwc/refiner")}
    assert all(v > 0 for v in by_scope.values())
    assert sum(by_scope.values()) == pytest.approx(trace["busy_s"], rel=0.02)


def test_flow_resize_pct_is_silent_on_a_trace_without_scopes(use_space):
    # the parent of the PR that brought the scopes: same operations, no scope
    path = os.path.join(HERE, "data", "small.xplane.pb")
    use_space(_spans.read_xspace(path))
    assert flow_resize_pct.read(tr.reduce_trace_dir(path), {}, {}) is None
    use_space(space_of(OPS))  # scopes, none of them the flow net's
    assert flow_resize_pct.read(reduction_of(space_of(OPS)), {}, {}) is None


def test_flow_resize_pct_reads_zero_where_the_flow_net_ran_without_resizes(use_space):
    # after the resizes are fused away or made free: pwc/ scopes, no resize scope
    space = space_of(OPS)
    space["devices"]["/device:TPU:0"]["metadata"][1] = (
        "%fusion.1", "jit(paged)/i3d/page/i3d/flow/pwc/decoder2/conv")
    use_space(space)
    assert flow_resize_pct.read(reduction_of(space), {}, {}) == 0.0


def _facts():
    import json

    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        return {"device_kind": "TPU v5 lite", "peaks": json.load(f)}


def test_pwc_corr_roofline_on_the_recorded_trace():
    trace = tr.reduce_trace_dir(os.path.join(HERE, "data", "pwc_page.xplane.pb"))
    assert pwc_corr_roofline.read(trace, {}, _facts()) == pytest.approx(3.93, abs=0.01)


def test_pwc_corr_roofline_counts_the_kernels_by_name_and_no_other_mosaic_call():
    call = ('%{name} = f32[16,64,96,81]{{3,2,1,0:T(8,128)}} custom-call('
            'f32[16,64,96,32]{{3,2,1,0}} %bitcast.1, f32[16,72,104,32]{{3,2,1,0}} %pad.1), '
            'custom_call_target="tpu_custom_call"')

    def read(*names):
        ops = {call.format(name=n): 0.01 for n in names}
        return pwc_corr_roofline.read(
            {"op_seconds": ops, "op_counts": {k: 1 for k in ops}}, {}, _facts())

    one = read("pwc_corr81_tiled.23")
    assert one is not None and 0 < one < 100
    assert read("pwc_corr81_tiled") == one                   # the first instance has no suffix
    assert read("pwc_corr81_tiled.23", "resize_matmul.4", "custom-call.7") == one
    assert read("pwc_warp_corr81_fused.2") == one            # same level, same work
    assert read("resize_matmul.4") is None
    assert read("corr81_pallas_tiled.23") is None            # the names before PR 31
    assert read("my_pwc_corr81_tiled.1") is None

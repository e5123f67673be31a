"""The trace reduction: busy union, idle gaps and per-operation self time on
hand-made events, and on one small trace recorded on the v5e
(``data/small.xplane.pb``: 3 calls of a jitted matmul-and-sum chain)."""

import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_gaps():
    total, gaps = tr.union_length([(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)])
    assert total == 12 + 11 + 1
    assert gaps == [(12, 20), (31, 40)]


def test_self_time_of_nested_events():
    # a while loop of 100 ns holding two body ops of 30 and 50 ns
    events = [("while", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 45, 50),
              ("copy", 120, 10)]
    assert tr.self_times(events) == {"while": 20, "fusion.1": 30, "fusion.2": 50, "copy": 10}


def test_reduce_planes_by_hand():
    planes = {"/device:TPU:0": {"XLA Ops": [("%a = f32[2]{0} add(x)", 0, 400),
                                            ("%b = f32[2]{0} mul(x)", 600, 400)],
                                "XLA Modules": [("jit_f", 0, 1000)]},
              "/host:CPU": {"python3": [("x", 0, 5000)]}}
    r = tr.reduce_planes(planes, chips=1)
    assert r["busy_s"] == pytest.approx(800e-9)
    assert r["idle_gaps"][0][1] == pytest.approx(200e-9)
    assert r["top_ops"][0][0] == "%a f32[2]"
    assert r["op_counts"]["%a = f32[2]{0} add(x)"] == 1
    assert r["idle_gap_starts"] == [400]
    assert r["slice_pages"] == 0  # one execution: it may be cut at either end


def test_whole_executions_leave_out_the_pieces_at_the_trace_ends():
    page, other = "jit_paged(1)", "jit_other(2)"
    # the tail of a page, two whole pages, the head of a fourth cut by the stop
    cut = [(page, 0, 200), (page, 201, 3900), (page, 4102, 3900), (page, 8003, 3000)]
    assert tr.whole_executions(cut) == 2
    assert tr.whole_executions(cut[:3]) == 1
    assert tr.whole_executions(cut[:2]) == 0
    assert tr.whole_executions([]) == 0
    # a small program before the first page: that page started inside the trace
    led = [(other, 0, 5)] + [(page, s + 10, d) for _n, s, d in cut[1:]]
    assert tr.whole_executions(led) == 2
    assert tr.whole_executions(list(reversed(led))) == 2  # order on the line is free
    # another program between pages is not a page
    assert tr.whole_executions(cut[:2] + [(other, 4102, 50), (page, 4200, 100)]) == 1
    planes = {"/device:TPU:0": {"XLA Ops": [("%a = f32[2]{0} add(x)", 0, 11003)],
                                "XLA Modules": cut},
              "/device:TPU:1": {"XLA Ops": [("%a = f32[2]{0} add(x)", 0, 11003)],
                                "XLA Modules": cut[:3]}}
    assert tr.reduce_planes(planes, chips=2)["slice_pages"] == 1  # the least over the chips


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes({"/host:CPU": {"python3": []}})


def test_recorded_trace():
    path = os.path.join(HERE, "data", "small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace")
    r = tr.reduce_trace_dir(path)
    assert r["path"] == path
    assert r["planes"] == ["/device:TPU:0"]
    assert 0 < r["busy_s"] <= r["span_s"]
    assert abs(sum(r["op_seconds"].values()) - r["busy_s"]) < 1e-6 + 0.02 * r["busy_s"]
    assert any("fusion" in name or "dot" in name or "convolution" in name
               for name in r["op_seconds"])

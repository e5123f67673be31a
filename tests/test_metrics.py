"""Observability: stage clock semantics (incl. thread safety and the
telemetry-registry feed), the decode-starvation heuristic wired end-to-end
from registry-fed values, histogram bucket/percentile math, and the opt-in
per-video report."""
# fast-registry: default tier — stage-clock tests with real sleeps

import threading
import time

import pytest

from video_features_tpu.obs import Histogram, MetricsRegistry
from video_features_tpu.utils.metrics import (
    BLOCKED_RECORD_SECONDS,
    SpanRecorder,
    StageClock,
    decode_starvation_warning,
    maybe_profiler,
    metrics_enabled,
    span,
)


def test_stage_clock_accumulates():
    c = StageClock()
    with span("pull", c, stage="decode"):
        time.sleep(0.01)
    with span("pull", c, stage="decode"):
        pass
    assert c.counts["decode"] == 2
    assert c.seconds["decode"] >= 0.01


def test_timed_iter_attributes_blocking_time():
    c = StageClock()

    def slow_gen():
        for i in range(3):
            time.sleep(0.005)
            yield i

    assert list(c.timed_iter(slow_gen(), "decode")) == [0, 1, 2]
    assert c.counts["decode"] == 3
    assert c.seconds["decode"] >= 0.015


def test_report_format():
    c = StageClock()
    with span("pull", c, stage="decode"):
        pass
    line = c.report("vid.mp4", wall=1.0)
    assert "vid.mp4" in line and "decode" in line and "overlapped/other" in line


def test_stage_clock_increments_are_thread_safe():
    """add_seconds/add_bytes/add_units arrive from staging-ring commit hooks
    and the writer thread while timed_iter runs on the daemon thread — a
    torn += would silently skew the report, so every mutation locks."""
    c = StageClock()
    n, per = 4, 5000

    def work():
        for _ in range(per):
            c.add_seconds("decode", 1.0)
            c.add_bytes("decode", 3)
            c.add_units("clips", 2)

    threads = [threading.Thread(target=work) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.seconds["decode"] == float(n * per)
    assert c.bytes["decode"] == 3 * n * per
    assert c.units["clips"] == 2 * n * per


def test_stage_clock_feeds_the_registry():
    reg = MetricsRegistry()
    c = StageClock(registry=reg, labels={"model": "resnet50"})
    c.add_seconds("decode", 1.5)
    with span("device", c, stage="device_wait"):
        pass
    c.add_bytes("transfer", 1024)
    c.add_units("packed_slots", 8)
    assert reg.counter_value("stage_seconds_total", stage="decode",
                             model="resnet50") == 1.5
    # the span's exit (StageClock.add) must CREATE the labeled series (a
    # bare >= 0.0 check would pass on the missing-series default of 0.0)
    fed_stages = {tuple(sorted(c["labels"].items()))
                  for c in reg.snapshot()["counters"]
                  if c["name"] == "stage_seconds_total"}
    assert (("model", "resnet50"), ("stage", "device_wait")) in fed_stages
    assert reg.counter_value("stage_bytes_total", stage="transfer",
                             model="resnet50") == 1024
    assert reg.counter_value("stage_units_total", stage="packed_slots",
                             model="resnet50") == 8


def test_timed_iter_feeds_registry_bytes():
    reg = MetricsRegistry()
    c = StageClock(registry=reg)
    items = [b"abcd", b"xy"]
    assert list(c.timed_iter(iter(items), "decode", bytes_of=len)) == items
    assert reg.counter_value("stage_bytes_total", stage="decode") == 6
    assert c.bytes["decode"] == 6


def test_starvation_warning_wired_from_registry_fed_values():
    """The decode-starvation heuristic driven end-to-end from values READ
    BACK out of the registry the stage clock fed — not hand-passed floats:
    the same path the serving daemon's autoscaler/stats consumers take."""
    reg = MetricsRegistry()
    clock = StageClock(registry=reg, labels={"model": "resnet50"})
    clock.add_seconds("decode", 4.5)  # decode-bound interval
    clock.add_units("packed_slots", 100)
    clock.add_units("packed_clips", 60)  # occupancy 0.6 < 0.8

    def counter(metric, stage):
        return reg.counter_value(metric, stage=stage, model="resnet50")

    occupancy = (counter("stage_units_total", "packed_clips")
                 / counter("stage_units_total", "packed_slots"))
    msg = decode_starvation_warning(
        occupancy=occupancy,
        decode_seconds=counter("stage_seconds_total", "decode"),
        wall=10.0,
        transfer_seconds=counter("stage_seconds_total", "transfer"))
    assert msg is not None and "--decode_workers" in msg

    # transfer-bound interval: same registry path, other branch
    reg2 = MetricsRegistry()
    clock2 = StageClock(registry=reg2, labels={"model": "raft"})
    clock2.add_seconds("decode", 0.2)
    clock2.add_seconds("transfer", 4.5)
    clock2.add_units("packed_slots", 100)
    clock2.add_units("packed_clips", 60)
    msg2 = decode_starvation_warning(
        occupancy=0.6,
        decode_seconds=reg2.counter_value("stage_seconds_total",
                                          stage="decode", model="raft"),
        wall=10.0,
        transfer_seconds=reg2.counter_value("stage_seconds_total",
                                            stage="transfer", model="raft"))
    assert msg2 is not None and "float32_wire" in msg2

    # healthy occupancy read back from the registry: no warning
    reg3 = MetricsRegistry()
    clock3 = StageClock(registry=reg3)
    clock3.add_units("packed_slots", 100)
    clock3.add_units("packed_clips", 95)
    assert decode_starvation_warning(
        occupancy=reg3.counter_value("stage_units_total",
                                     stage="packed_clips")
        / reg3.counter_value("stage_units_total", stage="packed_slots"),
        decode_seconds=9.0, wall=10.0) is None


def test_histogram_bucket_boundaries_are_le_inclusive():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 9.0):
        h.observe(v)
    # Prometheus le semantics: a value ON a bound lands in that bucket
    assert h.counts == [2, 2, 2, 1]
    assert h.bucket_index(1.0) == 0 and h.bucket_index(1.0000001) == 1
    assert h.bucket_index(100.0) == 3  # overflow bucket


def test_histogram_percentiles_interpolate_within_buckets():
    h = Histogram(bounds=(1.0, 2.0))
    for k in range(1, 101):
        h.observe(k / 100)  # uniform over (0, 1]
    assert abs(h.quantile(0.5) - 0.5) < 1e-9
    assert abs(h.quantile(0.99) - 0.99) < 1e-9
    # overflow values clamp to the last finite bound
    h_over = Histogram(bounds=(1.0, 2.0))
    for _ in range(10):
        h_over.observe(50.0)
    assert h_over.quantile(0.5) == 2.0
    # empty histogram quantiles are 0 (nothing observed, nothing claimed)
    assert Histogram().quantile(0.99) == 0.0
    # sum/count bookkeeping
    assert h.count == 100 and abs(h.sum - 50.5) < 1e-9


def test_metrics_enabled_gates():
    assert metrics_enabled("/tmp/x")
    assert not metrics_enabled(None)


def test_maybe_profiler_noop():
    with maybe_profiler(None):
        pass  # must not require jax


def test_run_prints_stage_report(tmp_path, sample_video, monkeypatch, capsys):
    monkeypatch.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")
    monkeypatch.setenv("VFT_METRICS", "1")
    from video_features_tpu.config import ExtractionConfig
    from video_features_tpu.extractors.resnet import ExtractResNet50

    cfg = ExtractionConfig(
        feature_type="resnet50", batch_size=64, extraction_fps=2, num_devices=1,
        on_extraction="save_numpy", output_path=str(tmp_path / "o"),
        tmp_path=str(tmp_path / "t"),
    )
    ex = ExtractResNet50(cfg)
    assert ex.run([sample_video]) == 1
    out = capsys.readouterr().out
    assert "decode" in out and "device_wait" in out
    assert "videos/sec" in out


def test_distributed_noop_without_env(monkeypatch):
    monkeypatch.delenv("VFT_MULTIHOST", raising=False)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    from video_features_tpu.parallel import maybe_initialize_distributed

    assert maybe_initialize_distributed() is False


# ---- the one span call: records, nesting, self time ------------------------


def _by_name(records):
    by = {}
    for i, r in enumerate(records):
        by.setdefault(r["name"], []).append((i, r))
    return by


def test_metrics_enabled_is_one_switch_of_three(monkeypatch):
    monkeypatch.delenv("VFT_METRICS", raising=False)
    assert not metrics_enabled(None, None)
    assert metrics_enabled(None, "/tmp/tel")  # --telemetry_dir records too
    monkeypatch.setenv("VFT_METRICS", "1")
    assert metrics_enabled(None, None)


def test_span_nesting_and_self_time_per_thread():
    """A parent's self time is its duration less what its children on the
    SAME thread cover; a span another thread ran meanwhile takes nothing."""
    rec = SpanRecorder()
    clock = StageClock()

    def other():
        with span("write", clock, rec, video="a"):
            time.sleep(0.03)

    with span("run", clock, rec):
        t = threading.Thread(target=other, name="output-writer")
        t.start()
        with span("extract", clock, rec, video="a"):
            with span("device", clock, rec, stage="device_wait"):
                time.sleep(0.02)
            time.sleep(0.01)
        t.join()
    out = rec.export()
    assert out["clock"] == "time_ns" and out["dropped"] == 0
    by = _by_name(out["records"])
    (i_run, run), (i_ext, ext) = by["run"][0], by["extract"][0]
    (_, dev), (_, wr) = by["device"][0], by["write"][0]
    assert run["parent"] is None and ext["parent"] == i_run
    assert dev["parent"] == i_ext
    assert wr["parent"] is None and wr["thread"] == "output-writer"
    assert run["thread"] == ext["thread"] == threading.current_thread().name
    dur = lambda r: (r["end"] - r["start"]) / 1e9  # noqa: E731
    self_s = out["self_seconds"]
    assert self_s["device"] == pytest.approx(dur(dev), abs=1e-6)
    assert self_s["extract"] == pytest.approx(dur(ext) - dur(dev), abs=1e-6)
    # the writer thread's 30 ms lie inside `run` in time and are NOT taken
    # from it: only `extract`, its child on this thread, is
    assert self_s["run"] == pytest.approx(dur(run) - dur(ext), abs=1e-6)
    assert self_s["extract"] >= 0.01 and self_s["device"] >= 0.02
    # the same exit fed the stage clock
    assert clock.seconds["device_wait"] == pytest.approx(dur(dev), abs=2e-3)
    assert clock.counts["device_wait"] == 1
    assert "extract" in rec.report() and "device" in rec.report()


def test_span_records_carry_parent_and_shared_ids():
    """The spans of one unit of work share its identifier: a child inherits
    `video`/`page`/`request` it was not given; ids set inside the span
    (bytes written, retries) land in the record at its end."""
    rec = SpanRecorder()
    with span("job", recorder=rec, request="r1", tenant="t"):
        with span("extract", recorder=rec, video="a.mp4", model="m"):
            with span("launch", recorder=rec, page=7):
                with span("put", recorder=rec) as sp:
                    sp.ids["bytes"] = 12
    recs = rec.export()["records"]
    job, ext, launch, put = recs
    assert [r["parent"] for r in recs] == [None, 0, 1, 2]
    assert ext["ids"] == {"request": "r1", "video": "a.mp4", "model": "m"}
    assert launch["ids"] == {"request": "r1", "video": "a.mp4", "page": 7}
    assert put["ids"] == {"request": "r1", "video": "a.mp4", "page": 7,
                          "bytes": 12}
    assert "tenant" not in ext["ids"]  # only the unit identifiers travel
    assert all(r["end"] >= r["start"] for r in recs)


def test_pull_under_threshold_adds_to_clock_and_leaves_no_record(monkeypatch):
    # a clock the test owns: a real 0.2 ms sleep overshoots the 1 ms threshold
    # on a loaded machine and leaves a second record
    import types

    from video_features_tpu.utils import metrics

    now = [1000.0]
    monkeypatch.setattr(metrics, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0], time_ns=lambda: int(now[0] * 1e9)))

    def sleep(seconds):
        now[0] += seconds

    rec = SpanRecorder()
    clock = StageClock()

    def frames():
        yield 0                      # no wait
        sleep(0.0002)                # under the 1 ms threshold
        yield 1
        sleep(0.02)                  # a stall
        yield 2

    with span("extract", clock, rec, video="v"):
        items = list(clock.timed_iter(frames(), "decode",
                                      on_blocked=lambda s: rec.add("pull", s)))
    assert items == [0, 1, 2]
    assert clock.counts["decode"] == 3           # every pull on the clock
    assert clock.seconds["decode"] == pytest.approx(0.0202)
    pulls = [r for r in rec.records if r["name"] == "pull"]
    assert len(pulls) == 1                       # only the blocked one
    (pull,) = pulls
    assert BLOCKED_RECORD_SECONDS == 1e-3
    assert pull["end"] - pull["start"] == pytest.approx(0.02e9)  # its start and end
    assert pull["parent"] == 0 and pull["ids"] == {"video": "v"}
    assert rec.records[0]["start"] <= pull["start"]


def test_span_list_is_bounded_and_counts_what_it_drops():
    rec = SpanRecorder(limit=3)
    with span("run", recorder=rec):
        for page in range(4):
            with span("stage", recorder=rec, page=page):
                with span("put", recorder=rec):
                    pass
    out = rec.export()
    assert len(out["records"]) == 3 and out["dropped"] == 6
    # run, stage(0), put: the child of a recorded span keeps its parent
    assert [r["name"] for r in out["records"]] == ["run", "stage", "put"]
    assert [r["parent"] for r in out["records"]] == [None, 0, 1]
    assert "dropped 6" in rec.report()
    # the stacks unwound: a span after the bound still nests under `run`'s
    # thread state without error
    with span("late", recorder=rec):
        pass
    assert rec.dropped == 7


def test_span_without_sinks_still_times():
    """A pool, packer or writer built without an extractor uses the bare
    call: no clock, no recorder, no journal — it still times."""
    with span("device", stage="device_wait") as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002


def test_stage_clock_add_is_one_interval():
    reg = MetricsRegistry()
    c = StageClock(registry=reg)
    c.add("transfer", 0.5, nbytes=100)
    c.add("transfer", 0.25)
    assert c.seconds["transfer"] == 0.75 and c.counts["transfer"] == 2
    assert c.bytes["transfer"] == 100
    assert reg.counter_value("stage_seconds_total", stage="transfer") == 0.75
    assert reg.counter_value("stage_bytes_total", stage="transfer") == 100


@pytest.fixture(scope="module")
def packed_run(tmp_path_factory, sample_video, sample_video_2):
    """One packed CPU run of the two sample clips with recording on."""
    from video_features_tpu.config import ExtractionConfig
    from video_features_tpu.extractors.resnet import ExtractResNet50

    tmp = tmp_path_factory.mktemp("packed_spans")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("VFT_METRICS", "1")
        mp.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")
        cfg = ExtractionConfig(
            feature_type="resnet50", batch_size=32, extraction_fps=2,
            num_devices=1, on_extraction="save_numpy", pack_corpus=True,
            decode_workers=2, output_path=str(tmp / "o"),
            tmp_path=str(tmp / "t"))
        ex = ExtractResNet50(cfg)
        assert ex.run([sample_video, sample_video_2]) == 2
    return ex._pack_stats


def test_packed_run_spans_nest_run_extract_page(packed_run):
    """run ⊃ extract ⊃ {pull, stage, launch ⊃ put, device}, finalize under
    run, on the consumer thread; every per-page span carries its page."""
    spans = packed_run["spans"]
    assert spans["clock"] == "time_ns" and spans["dropped"] == 0
    recs = spans["records"]
    by = _by_name(recs)
    assert len(by["run"]) == 1 and len(by["extract"]) == 2
    (i_run, run), = by["run"]
    consumer = run["thread"]

    def ancestors(r):
        out = []
        while r["parent"] is not None:
            r = recs[r["parent"]]
            out.append(r["name"])
        return out

    for _, ext in by["extract"]:
        assert ext["parent"] == i_run and ext["ids"]["model"] == "resnet50"
    for name in ("pull", "stage", "launch", "device"):
        assert by[name], name
        for _, r in by[name]:
            assert r["thread"] == consumer
            assert ancestors(r)[-1] == "run"
    for _, r in by["pull"]:  # inside a video's ingest, and its video's
        assert "extract" in ancestors(r) and "video" in r["ids"]
    for name in ("stage", "launch", "device"):
        # a page fills (and the one before it is fetched) inside a video's
        # ingest; the tail pages of the final flush hang from `run` itself
        assert any("extract" in ancestors(r) for _, r in by[name]), name
    for _, put in by["put"]:
        assert recs[put["parent"]]["name"] == "launch"
        assert put["ids"]["page"] == recs[put["parent"]]["ids"]["page"]
    pages = sorted({r["ids"]["page"] for _, r in by["launch"]})
    assert pages == list(range(len(pages)))  # the running page number
    assert sorted(r["ids"]["page"] for _, r in by["device"]) == pages
    for _, fin in by["finalize"]:
        assert fin["thread"] == consumer and "run" in ancestors(fin)
    assert {r["ids"]["video"] for _, r in by["finalize"]} == \
        {r["ids"]["video"] for _, r in by["extract"]}
    for r in recs:  # children lie inside their parents, on one clock
        if r["parent"] is not None and r["end"] is not None:
            p = recs[r["parent"]]
            assert p["start"] <= r["start"] and r["end"] <= p["end"]
    assert set(spans["self_seconds"]) >= {"run", "extract", "device", "write"}


def test_packed_run_writer_spans_and_counters(packed_run):
    by = _by_name(packed_run["spans"]["records"])
    writes = [r for _, r in by["write"]]
    assert len(writes) == 2
    assert {r["thread"] for r in writes} == {"output-writer"}
    assert all(r["parent"] is None for r in writes)
    assert all(r["ids"]["retries"] == 0 and r["ids"]["bytes"] > 0
               for r in writes)
    assert packed_run["videos_written"] == 2
    assert packed_run["writer_backlog_max"] >= 1
    assert packed_run["write_bytes"] == sum(r["ids"]["bytes"] for r in writes)
    decodes = [r for _, r in by["decode"]]  # the pool's workers
    assert decodes and all(r["thread"] != writes[0]["thread"]
                           and r["parent"] is None for r in decodes)


def test_packed_run_stage_seconds_match_span_totals(packed_run):
    """The clock is fed by the same exits as the records: `device` spans sum
    to the 'device_wait' stage, `put` spans to 'transfer' (less the ring's
    waits under the threshold, none here)."""
    recs = packed_run["spans"]["records"]

    def total(name):
        return sum(r["end"] - r["start"] for r in recs
                   if r["name"] == name) / 1e9

    stage = packed_run["stage_seconds"]
    # (a record's time_ns stamps lie just outside the clock's perf_counter
    # pair, so the two agree to the span call's own overhead)
    assert stage["device_wait"] == pytest.approx(total("device"), abs=0.05)
    assert stage["transfer"] == pytest.approx(total("put"), abs=0.05)
    # short pulls are on the clock only
    assert stage["decode"] >= total("pull") - 0.05

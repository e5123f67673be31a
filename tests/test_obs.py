"""Telemetry subsystem (docs/observability.md): journal discipline (bounded,
drops counted, span pairing), metrics registry + Prometheus exposition,
Chrome-trace export, and the acceptance path — a two-tenant daemon whose
journal exports a complete admitted→done span chain per request with
stats/metrics-op latency histograms consistent with the journal."""

import json
import os
import re
import threading

import numpy as np
import pytest

from test_packer import ToyPacked, _write_video

from video_features_tpu.config import ExtractionConfig
from video_features_tpu.obs import Histogram, MetricsRegistry, SpanJournal
from video_features_tpu.obs.export import (
    load_journal,
    main as export_main,
    to_chrome_trace,
)
from video_features_tpu.reliability import reset_faults
from video_features_tpu.serve import ExtractionService


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("VFT_FAULTS", raising=False)
    reset_faults()
    yield
    reset_faults()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("obs_corpus")
    return [_write_video(d / f"vid{i}.mp4", n)
            for i, n in enumerate((3, 5, 9, 2))]


def _cfg(tmp_path, sub, **kw):
    kw.setdefault("retries", 1)
    kw.setdefault("retry_backoff", 0.01)
    if kw.get("serve"):
        kw.setdefault("spool_dir", str(tmp_path / sub / "spool"))
        kw.setdefault("idle_flush_sec", 0.0)
        os.makedirs(kw["spool_dir"], exist_ok=True)
    return ExtractionConfig(
        feature_type="resnet50", on_extraction="save_numpy", num_devices=1,
        output_path=str(tmp_path / sub), tmp_path=str(tmp_path / "t"), **kw)


def _events_by_name(events):
    by = {}
    for e in events:
        by.setdefault(e["event"], []).append(e)
    return by


# ---- journal discipline ----------------------------------------------------


def test_journal_writes_jsonl_with_open_close_records(tmp_path):
    j = SpanJournal(str(tmp_path / "e.jsonl"))
    assert j.emit("hello", video="/v", skipped_none=None)
    with j.span("work", video="/v") as sid:
        pass
    j.close()
    events, corrupt = load_journal(j.path)
    assert corrupt == 0
    names = [e["event"] for e in events]
    assert names[0] == "journal_open" and names[-1] == "journal_close"
    assert "wall" in events[0] and events[-1]["dropped"] == 0
    hello = next(e for e in events if e["event"] == "hello")
    assert hello["video"] == "/v" and "skipped_none" not in hello
    start = next(e for e in events if e["event"] == "work_start")
    end = next(e for e in events if e["event"] == "work_end")
    assert start["span"] == end["span"] == sid
    assert end["ts"] >= start["ts"]
    # timestamps are monotone within the journal
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)


def test_journal_bounded_queue_drops_and_counts(tmp_path):
    """A stalled writer must never block the hot path: past the bound,
    emits drop and the close record says how many."""
    j = SpanJournal(str(tmp_path / "e.jsonl"), capacity=4, autostart=False)
    for i in range(10):
        j.emit("x", i=i)
    assert j.emitted == 4 and j.dropped == 6
    j.close()  # starts the writer, drains the backlog, appends the summary
    events, _ = load_journal(j.path)
    assert sum(1 for e in events if e["event"] == "x") == 4
    assert events[-1]["event"] == "journal_close"
    assert events[-1]["dropped"] == 6 and events[-1]["emitted"] == 4
    assert j.stats()["written"] == 6  # open + 4 + close


def test_journal_emit_is_thread_safe(tmp_path):
    j = SpanJournal(str(tmp_path / "e.jsonl"), capacity=10000)
    threads = [threading.Thread(
        target=lambda t=t: [j.emit("tick", t=t) for _ in range(500)])
        for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    j.close()
    assert j.emitted + j.dropped == 2000
    events, corrupt = load_journal(j.path)
    assert corrupt == 0
    assert sum(1 for e in events if e["event"] == "tick") == j.emitted


def test_journal_emit_after_close_is_a_noop(tmp_path):
    j = SpanJournal(str(tmp_path / "e.jsonl"))
    j.close()
    assert j.emit("late") is False
    events, _ = load_journal(j.path)
    assert all(e["event"] != "late" for e in events)


def test_journal_unwritable_path_degrades_to_counted_errors(tmp_path,
                                                            capsys):
    j = SpanJournal(str(tmp_path / "nope" / "x" / "e.jsonl"))
    # the parent dirs were created; sabotage by pointing at a directory
    j2 = SpanJournal(str(tmp_path))  # path IS a directory: open fails
    j2.emit("x")
    j2.close()
    assert j2.stats()["write_errors"] >= 1
    j.close()


# ---- registry --------------------------------------------------------------


def test_registry_counters_gauges_and_prometheus_text():
    r = MetricsRegistry()
    r.inc("videos_ok_total", model="resnet50")
    r.inc("videos_ok_total", 2, model="resnet50")
    r.set_gauge("queue_depth", 5, tenant="a")
    for v in (0.01, 0.2, 3.0):
        r.observe("e2e_latency_seconds", v, tenant="a", model="m")
    assert r.counter_value("videos_ok_total", model="resnet50") == 3
    snap = r.snapshot()
    assert {"counters", "gauges", "histograms"} <= set(snap)
    hist = snap["histograms"][0]
    assert hist["count"] == 3 and hist["buckets"][-1][0] == "+Inf"
    text = r.prometheus_text()
    assert '# TYPE vft_videos_ok_total counter' in text
    assert 'vft_queue_depth{tenant="a"} 5' in text
    assert 'vft_e2e_latency_seconds_count{model="m",tenant="a"} 3' in text
    assert 'le="+Inf"} 3' in text


def test_prometheus_escapes_client_supplied_label_values():
    """Tenant names are arbitrary client strings; a quote/backslash/newline
    in one must not corrupt the whole exposition for every tenant."""
    r = MetricsRegistry()
    r.set_gauge("queue_depth", 1, tenant='evil"name\\x\nboom')
    text = r.prometheus_text()
    line = next(ln for ln in text.splitlines() if ln.startswith("vft_queue"))
    assert line == 'vft_queue_depth{tenant="evil\\"name\\\\x\\nboom"} 1'
    assert "\nboom" not in text  # the newline never splits a line


def test_prometheus_counters_render_full_precision():
    """%g would quantize a long-lived daemon's monotone counter to 6
    significant digits — past 1e6 it would read frozen between 10-unit
    quanta and rate() over the exposition would show zero-then-burst."""
    r = MetricsRegistry()
    r.inc("stage_seconds_total", 1000001.5, stage="decode")
    r.observe("e2e_latency_seconds", 1000001.5, tenant="a")
    text = r.prometheus_text()
    assert "vft_stage_seconds_total" in text and "1000001.5" in text
    assert 'vft_e2e_latency_seconds_sum{tenant="a"} 1000001.5' in text
    assert "1e+06" not in text


def test_registry_summaries_roll_up_per_label_set():
    r = MetricsRegistry()
    for v in (0.1, 0.2):
        r.observe("e2e_latency_seconds", v, tenant="a", model="m")
    r.observe("e2e_latency_seconds", 9.0, tenant="b", model="m")
    summaries = {s["labels"]["tenant"]: s
                 for s in r.summaries("e2e_latency_seconds")}
    assert summaries["a"]["count"] == 2 and summaries["b"]["count"] == 1
    assert summaries["a"]["p99"] <= 0.25 and summaries["b"]["p50"] > 5.0


# ---- export ----------------------------------------------------------------


def _mk(ts, event, **fields):
    return {"ts": ts, "event": event, **fields}


def test_export_derives_lifecycle_and_request_spans():
    events = [
        _mk(0.0, "request_admitted", request="r1", tenant="a"),
        _mk(0.1, "video_queued", video="/v1", request="r1", tenant="a"),
        _mk(0.2, "video_popped", video="/v1", request="r1"),
        _mk(0.3, "extract_start", span=7, video="/v1"),
        _mk(0.9, "extract_end", span=7, video="/v1"),
        _mk(1.0, "video_done", video="/v1"),
        _mk(1.1, "request_done", request="r1", state="done"),
    ]
    trace = to_chrome_trace(events)
    xs = {e["name"]: e for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert set(xs) == {"queue_wait", "process", "extract", "request"}
    assert xs["queue_wait"]["dur"] == pytest.approx(1e5, rel=0.01)
    assert xs["extract"]["dur"] == pytest.approx(6e5, rel=0.01)
    assert xs["request"]["dur"] == pytest.approx(1.1e6, rel=0.01)
    # instants keep every milestone visible even when unpaired
    instants = {e["name"] for e in trace["traceEvents"]
                if e.get("ph") == "i"}
    assert "video_done" in instants
    # thread_name metadata labels the tracks
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("ph") == "M"}
    assert "/v1" in tracks and "request r1" in tracks


def test_export_requeue_restarts_queue_wait_and_failed_closes_process():
    events = [
        _mk(0.0, "video_queued", video="/v"),
        _mk(0.1, "video_popped", video="/v"),
        _mk(0.2, "video_requeued", video="/v"),
        _mk(0.5, "video_popped", video="/v"),
        _mk(0.6, "video_failed", video="/v", error_class="DecodeError"),
    ]
    trace = to_chrome_trace(events)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    waits = sorted(e["dur"] for e in xs if e["name"] == "queue_wait")
    assert waits == [pytest.approx(1e5, rel=0.01),
                     pytest.approx(3e5, rel=0.01)]
    proc = [e for e in xs if e["name"] == "process"]
    assert len(proc) == 1 and proc[0]["args"]["state"] == "video_failed"


def test_export_never_pairs_spans_across_journal_sessions():
    """The journal accumulates across runs (append mode) and span ids
    restart per session: a run killed mid-span leaves its start UNPAIRED —
    it must not pair with an unrelated later session's end, nor may two
    different span names share an id within a session."""
    events = [
        _mk(0.0, "journal_open", wall=100.0),
        _mk(0.1, "decode_start", span=7, video="/v1"),  # killed mid-decode
        _mk(5.0, "journal_open", wall=200.0),           # next run, ids reset
        _mk(5.1, "extract_start", span=7, video="/v2"),
        _mk(5.4, "extract_end", span=7, video="/v2"),
    ]
    trace = to_chrome_trace(events)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert [e["name"] for e in xs] == ["extract"]
    assert xs[0]["dur"] == pytest.approx(3e5, rel=0.01)
    assert trace["otherData"]["unpaired_spans"] == 0  # cleared per session
    # same-session id collision across NAMES also never pairs
    mixed = [
        _mk(0.0, "decode_start", span=3, video="/a"),
        _mk(0.5, "extract_end", span=3, video="/b"),
    ]
    assert not [e for e in to_chrome_trace(mixed)["traceEvents"]
                if e.get("ph") == "X"]


def test_export_cli_writes_parseable_trace(tmp_path, capsys):
    j = SpanJournal(str(tmp_path / "events.jsonl"))
    with j.span("decode", video="/v"):
        pass
    j.close()
    out = str(tmp_path / "trace.json")
    assert export_main([j.path, "-o", out]) == 0
    with open(out) as f:
        trace = json.load(f)
    assert any(e.get("ph") == "X" and e["name"] == "decode"
               for e in trace["traceEvents"])
    assert "perfetto" in capsys.readouterr().out
    # a directory argument resolves to its events.jsonl
    assert export_main([str(tmp_path), "-o", out]) == 0


def test_export_skips_corrupt_lines(tmp_path):
    p = str(tmp_path / "events.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"ts": 0.0, "event": "a"}) + "\n")
        f.write("{torn line\n")
        # valid JSON but a non-numeric ts: would crash the ts sort if it
        # slipped through — it is a corrupt line too, counted not fatal
        f.write(json.dumps({"ts": "1.5", "event": "bad"}) + "\n")
        f.write(json.dumps({"ts": True, "event": "bad2"}) + "\n")
        f.write(json.dumps({"ts": 1.0, "event": "b"}) + "\n")
    events, corrupt = load_journal(p)
    assert [e["event"] for e in events] == ["a", "b"] and corrupt == 3


# ---- batch loops journal (--telemetry_dir without --serve) -----------------


@pytest.mark.parametrize("pack", [False, True])
def test_batch_run_journals_per_video_lifecycle(tmp_path, corpus, pack):
    sub = f"batch_{'packed' if pack else 'loop'}"
    ex = ToyPacked(_cfg(tmp_path, sub, pack_corpus=pack,
                        telemetry_dir=str(tmp_path / sub / "tel")))
    assert ex.run(corpus) == len(corpus)
    assert ex._journal is not None and ex._journal.closed
    events, corrupt = load_journal(ex._journal.path)
    assert corrupt == 0
    by = _events_by_name(events)
    assert len(by["video_done"]) == len(corpus)
    assert len(by["extract_start"]) == len(by["extract_end"]) == len(corpus)
    if pack:
        assert by["dispatch"]  # packed batches journal their dispatches
        assert len(by["device_start"]) == len(by["device_end"])
    # the registry counted what the journal says
    assert ex._metrics.counter_value("videos_ok_total",
                                     model="resnet50") == len(corpus)


def test_batch_failure_journals_video_failed(tmp_path, corpus, monkeypatch):
    monkeypatch.setenv("VFT_FAULTS", "extract:raise_permanent:vid1")
    ex = ToyPacked(_cfg(tmp_path, "batch_fail", retries=0,
                        telemetry_dir=str(tmp_path / "batch_fail" / "tel")))
    assert ex.run(corpus) == len(corpus) - 1
    events, _ = load_journal(ex._journal.path)
    by = _events_by_name(events)
    assert len(by["video_failed"]) == 1
    assert by["video_failed"][0]["error_class"] == "InjectedDeviceError"
    assert len(by["video_done"]) == len(corpus) - 1


def test_decode_pool_emits_decode_spans(tmp_path, corpus):
    ex = ToyPacked(_cfg(tmp_path, "batch_pool", decode_workers=2,
                        telemetry_dir=str(tmp_path / "batch_pool" / "tel")))
    assert ex.run(corpus) == len(corpus)
    events, _ = load_journal(ex._journal.path)
    by = _events_by_name(events)
    assert len(by["decode_start"]) == len(by["decode_end"]) == len(corpus)
    trace = to_chrome_trace(events)
    decode = [e for e in trace["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "decode"]
    assert len(decode) == len(corpus)


# ---- acceptance: two-tenant daemon → journal/trace/histogram consistency --


def test_two_tenant_daemon_trace_chain_and_histogram_consistency(tmp_path,
                                                                 corpus):
    tel = str(tmp_path / "svc" / "tel")
    svc = ExtractionService(
        ToyPacked(_cfg(tmp_path, "svc", serve=True, telemetry_dir=tel)),
        poll_interval=0.001)
    ra = svc.submit({"tenant": "alice", "videos": corpus[:2],
                     "request_id": "ra"})
    rb = svc.submit({"tenant": "bob", "videos": corpus[2:],
                     "request_id": "rb"})
    svc.request_drain()
    assert svc.run() == 0
    assert ra.state == "done" and rb.state == "done"

    stats = svc.stats()
    assert stats["schema"] == 1
    assert stats["telemetry"]["dropped"] == 0

    events, corrupt = load_journal(os.path.join(tel, "events.jsonl"))
    assert corrupt == 0
    by = _events_by_name(events)
    # every request has a complete admitted→done chain, every video a
    # queued→popped→done chain
    assert {e["request"] for e in by["request_admitted"]} == {"ra", "rb"}
    assert {e["request"] for e in by["request_done"]} == {"ra", "rb"}
    for name in ("video_queued", "video_popped", "video_done"):
        assert {os.path.basename(e["video"]) for e in by[name]} == \
            {os.path.basename(p) for p in corpus}, name
    trace = to_chrome_trace(events)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert sum(1 for e in xs if e["name"] == "request") == 2
    assert sum(1 for e in xs if e["name"] == "queue_wait") == len(corpus)
    assert sum(1 for e in xs if e["name"] == "process") == len(corpus)

    # stats-op latency histograms: per tenant and per model, and consistent
    # (±1 bucket) with the journal-derived queued→done latencies
    e2e = {s["labels"]["tenant"]: s for s in stats["latency"]["e2e"]}
    assert set(e2e) == {"alice", "bob"}
    for s in e2e.values():
        assert s["labels"]["model"] == "resnet50" and s["count"] == 2
        assert 0 < s["p50"] <= s["p95"] <= s["p99"]
    queued_ts = {e["video"]: e["ts"] for e in by["video_queued"]}
    done_ts = {e["video"]: e["ts"] for e in by["video_done"]}
    tenants = {e["video"]: e["tenant"] for e in by["video_queued"]}
    for video, t_done in done_ts.items():
        tenant = tenants[video]
        hist = svc.metrics.histogram("e2e_latency_seconds", tenant=tenant,
                                     model="resnet50")
        journal_latency = t_done - queued_ts[video]
        assert abs(hist.bucket_index(journal_latency)
                   - hist.bucket_index(hist.quantile(0.5))) <= 1, \
            (video, journal_latency, hist.quantile(0.5))
    # queue-wait histograms observed per pop, tenant-labeled
    qw = {s["labels"]["tenant"]: s for s in stats["latency"]["queue_wait"]}
    assert set(qw) == {"alice", "bob"}
    assert all(s["count"] == 2 for s in qw.values())


def test_daemon_without_telemetry_dir_still_serves_metrics(tmp_path, corpus):
    """The registry (stats/metrics ops) is always on under --serve; only
    the journal is gated on --telemetry_dir."""
    svc = ExtractionService(ToyPacked(_cfg(tmp_path, "nom", serve=True)),
                            poll_interval=0.001)
    r = svc.submit({"videos": corpus[:1]})
    svc.request_drain()
    assert svc.run() == 0 and r.state == "done"
    stats = svc.stats()
    assert stats["schema"] == 1
    assert stats["telemetry"] == {"enabled": False}
    assert stats["latency"]["e2e"][0]["count"] == 1
    m = svc.handle_op({"op": "metrics"})
    assert m["ok"] and "vft_e2e_latency_seconds_count" in m["prometheus"]


# ---- healthz / metrics / profile socket ops --------------------------------


def test_healthz_reports_liveness_and_staleness(tmp_path, corpus):
    svc = ExtractionService(ToyPacked(_cfg(tmp_path, "hz", serve=True)),
                            poll_interval=0.001)
    h = svc.handle_op({"op": "healthz"})
    assert h["ok"] and h["schema"] == 1 and not h["stale"]
    assert h["uptime_sec"] >= 0 and h["profiling"] is None
    svc._last_step -= 60  # a wedged daemon thread ages the stamp
    assert svc.handle_op({"op": "healthz"})["stale"] is True
    svc.step()  # stepping refreshes it
    assert svc.handle_op({"op": "healthz"})["stale"] is False
    svc.request_drain()
    assert svc.run() == 0


def test_profile_op_start_stop_cycle(tmp_path, corpus):
    tel = str(tmp_path / "prof" / "tel")
    svc = ExtractionService(
        ToyPacked(_cfg(tmp_path, "prof", serve=True, telemetry_dir=tel)),
        poll_interval=0.001)
    assert svc.handle_op({"op": "profile"})["ok"] is False  # no action
    assert svc.handle_op({"op": "profile", "action": "stop"})["ok"] is False
    started = svc.handle_op({"op": "profile", "action": "start"})
    assert started["ok"], started
    assert started["profiling"] == os.path.join(tel, "profile")
    # double-start is rejected while a session is live
    assert svc.handle_op({"op": "profile", "action": "start"})["ok"] is False
    r = svc.submit({"videos": corpus[:1]})
    for _ in range(200):
        svc.step()
        if r.complete:
            break
    stopped = svc.handle_op({"op": "profile", "action": "stop"})
    assert stopped["ok"], stopped
    assert os.path.isdir(stopped["trace_dir"])
    # a fresh cycle can start after a stop
    assert svc.handle_op({"op": "profile", "action": "start"})["ok"]
    assert svc.handle_op({"op": "profile", "action": "stop"})["ok"]
    svc.request_drain()
    assert svc.run() == 0


def test_profile_failed_stop_stays_retryable(tmp_path, corpus, monkeypatch):
    """A stop that fails mid-export (full trace disk) must leave the op
    recoverable: the session flag stays set so a retried stop can succeed
    — never a dead end where start says 'already profiling' and stop says
    'not profiling' until a daemon restart."""
    import jax

    svc = ExtractionService(ToyPacked(_cfg(tmp_path, "profr", serve=True)),
                            poll_interval=0.001)
    assert svc.handle_op({"op": "profile", "action": "start",
                          "dir": str(tmp_path / "profr" / "tr")})["ok"]

    real_stop = jax.profiler.stop_trace
    calls = []

    def failing_stop():
        calls.append(1)
        if len(calls) == 1:
            raise OSError("disk full during trace export")
        return real_stop()

    monkeypatch.setattr(jax.profiler, "stop_trace", failing_stop)
    resp = svc.handle_op({"op": "profile", "action": "stop"})
    assert resp["ok"] is False and "disk full" in resp["error"]
    retry = svc.handle_op({"op": "profile", "action": "stop"})  # retryable
    assert retry["ok"], retry
    # and a session jax reports as already gone clears the flag for start
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: (_ for _ in ()).throw(
                            RuntimeError("No profile started")))
    assert svc.handle_op({"op": "profile", "action": "start",
                          "dir": str(tmp_path / "profr" / "tr2")})["ok"]
    assert svc.handle_op({"op": "profile", "action": "stop"})["ok"] is False
    assert svc._profiling is None  # 'no profile' response cleared it
    # the second start opened a REAL jax session; close it so later tests
    # (and this process) are not left with a live global profile
    monkeypatch.setattr(jax.profiler, "stop_trace", real_stop)
    real_stop()
    svc.request_drain()
    assert svc.run() == 0


def test_profile_op_without_any_dir_is_a_clean_error(tmp_path, corpus):
    svc = ExtractionService(ToyPacked(_cfg(tmp_path, "prof2", serve=True)),
                            poll_interval=0.001)
    resp = svc.handle_op({"op": "profile", "action": "start"})
    assert resp["ok"] is False and "trace dir" in resp["error"]
    # an explicit dir in the op works without daemon flags
    resp = svc.handle_op({"op": "profile", "action": "start",
                          "dir": str(tmp_path / "prof2" / "explicit")})
    assert resp["ok"], resp
    assert svc.handle_op({"op": "profile", "action": "stop"})["ok"]
    svc.request_drain()
    assert svc.run() == 0


# ---- daemon event coverage: breaker + requeue + cache hits -----------------


def test_daemon_journals_breaker_failed_and_requeue_events(tmp_path, corpus,
                                                           monkeypatch):
    monkeypatch.setenv("VFT_FAULTS", "extract:raise_permanent:vid1")
    tel = str(tmp_path / "brk" / "tel")
    svc = ExtractionService(
        ToyPacked(_cfg(tmp_path, "brk", serve=True, telemetry_dir=tel,
                       tenant_max_failures=0)),
        poll_interval=0.001)
    svc.submit({"tenant": "alice", "videos": [corpus[1], corpus[0]]})
    svc.request_drain()
    assert svc.run() == 1
    events, _ = load_journal(os.path.join(tel, "events.jsonl"))
    by = _events_by_name(events)
    assert by["breaker_open"][0]["tenant"] == "alice"
    classes = {e["error_class"] for e in by["video_failed"]}
    assert classes == {"InjectedDeviceError", "TenantBreakerOpen"}
    assert svc.metrics.counter_value("breaker_trips_total",
                                     tenant="alice") == 1


def test_lazy_model_construction_failure_journals_video_failed(tmp_path,
                                                               corpus):
    """A co-loaded model whose lazy construction fails has NO extractor to
    run the usual accounting — the daemon arm must still terminate the
    journal lifecycle and keep the failure counter agreeing with it."""
    tel = str(tmp_path / "lazy" / "tel")
    cfg = _cfg(tmp_path, "lazy", serve=True, telemetry_dir=tel, retries=0,
               serve_models=("vggish",))

    def factory(model):
        raise RuntimeError(f"no weights for {model}")

    svc = ExtractionService(ToyPacked(cfg), poll_interval=0.001,
                            factory=factory)
    r = svc.submit({"videos": corpus[:1], "feature_type": "vggish",
                    "request_id": "rl"})
    svc.request_drain()
    assert svc.run() == 1  # the construction failure keeps the exit honest
    assert r.state == "failed"
    events, _ = load_journal(os.path.join(tel, "events.jsonl"))
    by = _events_by_name(events)
    failed = [e for e in by["video_failed"] if e.get("model") == "vggish"]
    assert len(failed) == 1 and failed[0]["error_class"] == "RuntimeError"
    assert svc.metrics.counter_value("videos_failed_total", model="vggish",
                                     error_class="RuntimeError") == 1


def test_daemon_journals_cache_hits(tmp_path, corpus):
    tel = str(tmp_path / "ch" / "tel")
    svc = ExtractionService(
        ToyPacked(_cfg(tmp_path, "ch", serve=True, telemetry_dir=tel,
                       cache_dir=str(tmp_path / "ch" / "cache"))),
        poll_interval=0.001)
    r1 = svc.submit({"videos": corpus[:2], "request_id": "r1"})
    for _ in range(500):
        svc.step()
        if r1.complete:
            break
    r2 = svc.submit({"videos": corpus[:2], "request_id": "r2"})
    svc.request_drain()
    assert svc.run() == 0
    assert r2.cache_hits == 2
    events, _ = load_journal(os.path.join(tel, "events.jsonl"))
    by = _events_by_name(events)
    assert len(by["cache_hit"]) == 2
    # cache-hit videos still close their lifecycle chain
    assert len(by["video_done"]) == 4


# ---- the one span call across its sinks ------------------------------------


def test_recording_off_keeps_no_record_and_stage_seconds_stay(tmp_path, corpus,
                                                              monkeypatch):
    """Without the switch (VFT_METRICS / --profile_dir / --telemetry_dir) no
    record is kept; the accumulators are on all the same, in both loops."""
    monkeypatch.delenv("VFT_METRICS", raising=False)
    for pack in (False, True):
        ex = ToyPacked(_cfg(tmp_path, f"off_{pack}", pack_corpus=pack))
        assert ex.run(corpus) == len(corpus)
        assert ex._recorder is None and ex.clock is not None
        stats = ex._pack_stats
        assert "spans" not in stats
        assert stats["stage_seconds"]["decode"] > 0
        assert stats["stage_seconds"]["device_wait"] > 0
        assert stats["videos_written"] == len(corpus)
        assert stats["writer_backlog_max"] >= 1
    assert "dispatched_slots" in stats  # the packed run's counters besides


def test_recording_on_with_telemetry_dir_alone(tmp_path, corpus, monkeypatch):
    monkeypatch.delenv("VFT_METRICS", raising=False)
    ex = ToyPacked(_cfg(tmp_path, "tel_only", pack_corpus=True,
                        telemetry_dir=str(tmp_path / "tel_only" / "tel")))
    assert ex.run(corpus) == len(corpus)
    spans = ex._pack_stats["spans"]
    names = {r["name"] for r in spans["records"]}
    assert {"run", "extract", "stage", "launch", "device", "finalize",
            "write"} <= names
    # a second run starts a fresh list
    n_first = len(spans["records"])
    assert ex.run(corpus[:1]) == 1
    assert len(ex._pack_stats["spans"]["records"]) < n_first


def test_one_device_span_feeds_clock_journal_histogram_and_record(tmp_path,
                                                                  corpus):
    """`_fetch_batch` has ONE timer: the 'device' span's exit gives the same
    duration to the stage clock, the histogram and the record, and the
    journal's pair brackets it."""
    ex = ToyPacked(_cfg(tmp_path, "one_timer", pack_corpus=True,
                        telemetry_dir=str(tmp_path / "one_timer" / "tel")))
    assert ex.run(corpus) == len(corpus)
    stats = ex._pack_stats
    device = [r for r in stats["spans"]["records"] if r["name"] == "device"]
    assert device and all("page" in r["ids"] and "bucket" in r["ids"]
                          for r in device)
    hist = [h for h in ex._metrics.snapshot()["histograms"]
            if h["name"] == "device_batch_seconds"]
    assert len(hist) == 1 and hist[0]["count"] == len(device)
    clock_s = stats["stage_seconds"]["device_wait"]
    # clock and histogram are fed the very same float per span
    assert hist[0]["sum"] == pytest.approx(clock_s, abs=1e-4 * len(device))
    record_s = sum(r["end"] - r["start"] for r in device) / 1e9
    assert record_s == pytest.approx(clock_s, abs=0.02 * len(device))
    events, _ = load_journal(ex._journal.path)
    by = _events_by_name(events)
    assert len(by["device_start"]) == len(by["device_end"]) == len(device)
    ends = {e["span"]: e for e in by["device_end"]}
    journal_s = sum(ends[s["span"]]["ts"] - s["ts"] for s in by["device_start"])
    assert journal_s == pytest.approx(clock_s, abs=0.02 * len(device))
    assert all(e["page"] == s_["page"] for e, s_ in
               zip(by["device_end"], by["device_start"]))
    # the instant dispatch event kept its fields and gained the page
    assert {"bucket", "real_slots", "batch_slots", "paged", "inflight",
            "page"} <= set(by["dispatch"][0])
    assert sorted(e["page"] for e in by["dispatch"]) == \
        sorted(r["ids"]["page"] for r in device)


def test_profiler_session_shows_the_programs_annotations(tmp_path, corpus):
    """With the host tracer on, any profiler session shows the program's
    spans as TraceAnnotations, their ids as stats — taken from outside, the
    program not knowing (no --profile_dir)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    ex = ToyPacked(_cfg(tmp_path, "annot", pack_corpus=True))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    trace_dir = str(tmp_path / "annot_trace")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        assert ex.run(corpus[:2]) == 2
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("run", "extract", "stage", "launch", "device",
                               "finalize", "write", "write_reap"):
                    seen.setdefault(ev.name, []).append(
                        {str(k): str(v) for k, v in ev.stats})
    assert {"run", "extract", "stage", "launch", "device", "finalize",
            "write"} <= set(seen)
    assert any(s.get("page") == "0" for s in seen["launch"])
    assert {s.get("video") for s in seen["extract"]} == set(corpus[:2])


def test_i3d_flow_step_lowers_with_pwc_scopes(tmp_path, monkeypatch):
    """The device side: the I3D flow step's lowered text, with debug
    information, names the step, the tower and PWC-Net's stages — whatever
    fusion the compiler makes of them later, the operations carry
    `pwc/resize_in` in their metadata."""
    import jax

    from video_features_tpu.extractors.i3d import ExtractI3D

    monkeypatch.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")
    ex = ExtractI3D(ExtractionConfig(
        feature_type="i3d", stack_size=16, step_size=16, flow_type="pwc",
        i3d_pre_crop_size=96, i3d_crop_size=64, num_devices=1,
        output_path=str(tmp_path)))
    stacks = jax.ShapeDtypeStruct((ex.clips_per_batch, 17, 96, 96, 3), np.uint8)
    text = ex._flow_step.lower(ex.i3d_params["flow"], stacks).as_text(
        debug_info=True)
    # mesh.py's scope of the step function's own name, then the tower's
    assert "flow_forward/i3d/flow/pwc/resize_in" in text
    for scope in ("pwc/pyramid", "pwc/corr6", "pwc/warp5", "pwc/corr2",
                  "pwc/decoder4", "pwc/refiner", "pwc/resize_out",
                  "i3d/flow/I3D/i3d/stem"):
        assert scope in text, scope
    assert "jit__flow_forward" in text  # the step keeps its name


def test_pwc_forward_keeps_resize_scopes_at_i3d_geometry():
    """`flow_resize_pct` reads the time of whatever runs under
    `pwc/resize_in` and `pwc/resize_out`: at 256x341 (the /64 grid is
    256x384) both scopes name contractions with constant matrices, pinned
    at float32 products, and no gather is left under either."""
    import jax

    from video_features_tpu.models.pwc import pwc_forward, pwc_init_params

    frames = jax.ShapeDtypeStruct((1, 256, 341, 3), np.uint8)
    text = jax.jit(pwc_forward).lower(pwc_init_params(0), frames, frames).as_text(
        debug_info=True)
    # an operation's line ends in loc(#locN); "#locN = loc("<scopes>/<op>"…)"
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    for scope in ("pwc/resize_in", "pwc/resize_out"):
        ops = [ln for ln in text.splitlines() if " = stablehlo." in ln
               and scope + "/" in named.get(ln[ln.rindex("loc(") + 4:-1], "")]
        dots = [ln for ln in ops if "stablehlo.dot_general" in ln]
        assert dots and all("precision = [HIGHEST, HIGHEST]" in ln for ln in dots), scope
        assert not any("gather" in ln for ln in ops), scope

"""AsyncOutputWriter: ordering, failure attribution, and the PR-1 kill-mid-
write invariants on the asynchronous path.

The writer overlaps ``.npy`` serialization with the next video's compute;
these tests pin the contract that overlap must not weaken: strict submission
order, write-before-done per video, atomic tmp+rename under SIGKILL
(``VFT_FAULTS=save:kill`` extended to the writer thread), per-video failure
attribution through the run loop, and the --sync_writer escape hatch.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from video_features_tpu.config import ExtractionConfig
from video_features_tpu.extractors.base import Extractor
from video_features_tpu.io.output import (
    AsyncOutputWriter,
    load_done_set,
    manifest_path,
)
from video_features_tpu.reliability import (
    OutputError,
    RetryPolicy,
    load_failures,
    reset_faults,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("VFT_FAULTS", raising=False)
    reset_faults()
    yield
    reset_faults()


def test_writer_writes_before_done_in_submission_order(tmp_path):
    out = str(tmp_path)
    w = AsyncOutputWriter(depth=2)
    handles = [
        w.submit({"feat": np.full(4, i, np.float32)}, f"v{i}.mp4", out)
        for i in range(4)
    ]
    for h in handles:
        assert h.wait(timeout=60)
    w.close()
    # every .npy present and loadable before its done record existed
    for i in range(4):
        np.testing.assert_array_equal(
            np.load(os.path.join(out, f"v{i}_feat.npy")), np.full(4, i))
    assert load_done_set(out) == {os.path.abspath(f"v{i}.mp4") for i in range(4)}
    # single queue + single thread: manifest records appear in submission order
    with open(manifest_path(out)) as f:
        videos = [json.loads(line)["video"] for line in f]
    assert videos == [os.path.abspath(f"v{i}.mp4") for i in range(4)]


def test_writer_failure_lands_on_its_own_handle(tmp_path, monkeypatch):
    monkeypatch.setenv("VFT_FAULTS", "save:raise:v1")
    out = str(tmp_path)
    w = AsyncOutputWriter(depth=2)  # no retry: the injected fault must surface
    h0 = w.submit({"feat": np.arange(3, dtype=np.float32)}, "v0.mp4", out)
    h1 = w.submit({"feat": np.arange(3, dtype=np.float32)}, "v1.mp4", out)
    h2 = w.submit({"feat": np.arange(3, dtype=np.float32)}, "v2.mp4", out)
    assert h0.wait(timeout=60)
    with pytest.raises(OutputError):
        h1.wait(timeout=60)
    assert h2.wait(timeout=60)  # the writer survives a failed job
    w.close()
    done = load_done_set(out)
    assert os.path.abspath("v0.mp4") in done and os.path.abspath("v2.mp4") in done
    assert os.path.abspath("v1.mp4") not in done  # failed: never marked done
    assert not os.path.exists(os.path.join(out, "v1_feat.npy"))


def test_writer_retries_transient_save_failures(tmp_path, monkeypatch):
    monkeypatch.setenv("VFT_FAULTS", "save:raise_transient::1")  # first save only
    w = AsyncOutputWriter(depth=2, retry=RetryPolicy(attempts=3, base_delay=0.01))
    h = w.submit({"feat": np.arange(5, dtype=np.float32)}, "vr.mp4", str(tmp_path))
    assert h.wait(timeout=60)  # retry absorbed the transient failure
    w.close()
    np.testing.assert_array_equal(
        np.load(os.path.join(str(tmp_path), "vr_feat.npy")), np.arange(5))
    assert load_done_set(str(tmp_path)) == {os.path.abspath("vr.mp4")}


class DictExtractor(Extractor):
    """Extraction stub: the run loop + writer without decode or a model."""

    def extract(self, video_path):
        return {"feat": np.arange(4, dtype=np.float32)}


def _cfg(tmp_path, **kw):
    kw.setdefault("retries", 0)
    kw.setdefault("retry_backoff", 0.01)
    return ExtractionConfig(
        feature_type="resnet50", on_extraction="save_numpy", num_devices=1,
        output_path=str(tmp_path / "o"), tmp_path=str(tmp_path / "t"), **kw)


def test_run_loop_attributes_async_write_failure_to_its_video(tmp_path, monkeypatch):
    """A write that fails on the writer thread is accounted exactly like a
    compute failure: classified in the failure manifest under ITS video, the
    other videos complete, and the return count excludes it."""
    monkeypatch.setenv("VFT_FAULTS", "save:raise_permanent:vid1")
    ex = DictExtractor(_cfg(tmp_path))
    paths = [f"vid{i}.mp4" for i in range(3)]
    assert ex.run(paths) == 2
    failures = load_failures(ex.output_dir)
    assert set(failures) == {os.path.abspath("vid1.mp4")}
    assert "OutputError" in failures[os.path.abspath("vid1.mp4")]["error_class"]
    assert load_done_set(ex.output_dir) == {
        os.path.abspath("vid0.mp4"), os.path.abspath("vid2.mp4")}


def test_run_loop_write_failures_count_toward_circuit_breaker(tmp_path, monkeypatch):
    from video_features_tpu.reliability import CircuitBreakerTripped

    monkeypatch.setenv("VFT_FAULTS", "save:raise_permanent")
    ex = DictExtractor(_cfg(tmp_path, max_failures=0))
    with pytest.raises(CircuitBreakerTripped, match="max_failures"):
        ex.run([f"vid{i}.mp4" for i in range(4)])


def test_sync_writer_flag_reverts_to_inline_writes(tmp_path):
    ex = DictExtractor(_cfg(tmp_path, async_writer=False))
    assert ex.run(["vid0.mp4"]) == 1
    assert ex._writer is None  # never constructed
    assert load_done_set(ex.output_dir) == {os.path.abspath("vid0.mp4")}


def test_async_writer_kill_mid_write_leaves_no_partial_npy(tmp_path):
    """SIGKILL between the writer thread's tmp-write and rename: identical
    invariants to the synchronous kill-mid-write test — no final .npy, no
    done record, a rerun completes the write."""
    out = str(tmp_path / "out")
    code = (
        "import os\n"
        "os.environ['VFT_FAULTS'] = 'save:kill'\n"
        "import numpy as np\n"
        "from video_features_tpu.io.output import AsyncOutputWriter\n"
        "w = AsyncOutputWriter()\n"
        f"h = w.submit({{'feat': np.arange(100000)}}, 'vidX.mp4', {out!r})\n"
        "h.wait(timeout=60)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 137, proc.stderr
    assert not os.path.exists(os.path.join(out, "vidX_feat.npy"))
    assert load_done_set(out) == set()  # resume will redo this video

    rerun = (
        "import numpy as np\n"
        "from video_features_tpu.io.output import AsyncOutputWriter\n"
        "w = AsyncOutputWriter()\n"
        f"w.submit({{'feat': np.arange(100000)}}, 'vidX.mp4', {out!r})\n"
        "w.close(wait=True)\n"
    )
    env.pop("VFT_FAULTS", None)
    proc = subprocess.run([sys.executable, "-c", rerun], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    np.testing.assert_array_equal(
        np.load(os.path.join(out, "vidX_feat.npy")), np.arange(100000))
    assert load_done_set(out) == {os.path.abspath("vidX.mp4")}


def test_writer_discards_job_cancelled_after_submit(tmp_path):
    """A watchdog cancellation landing AFTER the attempt's pre-submit check
    must still discard the enqueued write before anything touches disk —
    the job carries the cancel event and re-checks it at the same two
    points the inline path does."""
    import threading

    from video_features_tpu.reliability import VideoTimeoutError

    cancel = threading.Event()
    cancel.set()  # cancelled in the check-to-submit window
    w = AsyncOutputWriter(depth=2)
    h = w.submit({"feat": np.arange(3, dtype=np.float32)}, "vc.mp4",
                 str(tmp_path), cancelled=cancel)
    with pytest.raises(VideoTimeoutError):
        h.wait(timeout=60)
    w.close()
    assert not os.path.exists(os.path.join(str(tmp_path), "vc_feat.npy"))
    assert load_done_set(str(tmp_path)) == set()


def test_interrupted_run_still_prunes_drained_writes(tmp_path):
    """An interrupt landing while a video's write is still on the writer
    thread: the shutdown drain completes the write, and the video — which
    previously failed and was being retried — must still be pruned from the
    failure manifest (it would otherwise sit in both manifests forever,
    since later --resume runs skip it via the done set)."""
    from video_features_tpu.reliability import record_failure

    ex = DictExtractor(_cfg(tmp_path))
    # pre-seed a stale failure record for vid0, as after a failed first run
    os.makedirs(ex.output_dir, exist_ok=True)
    record_failure(ex.output_dir, "vid0.mp4", RuntimeError("old failure"), 1)
    assert load_failures(ex.output_dir) != {}

    def interrupting_progress(done, total):
        raise KeyboardInterrupt  # lands before vid0's write is reaped

    with pytest.raises(KeyboardInterrupt):
        ex.run(["vid0.mp4"], progress=interrupting_progress)
    # the drain completed the write + done record AND converged the manifest
    assert load_done_set(ex.output_dir) == {os.path.abspath("vid0.mp4")}
    assert load_failures(ex.output_dir) == {}


def test_writer_close_drains_queued_jobs(tmp_path):
    w = AsyncOutputWriter(depth=2)
    handles = [
        w.submit({"feat": np.arange(2, dtype=np.float32)}, f"c{i}.mp4",
                 str(tmp_path))
        for i in range(3)
    ]
    w.close(wait=True)  # drains everything already queued
    assert all(h.done() for h in handles)
    assert len(load_done_set(str(tmp_path))) == 3
    with pytest.raises(OutputError, match="closed"):
        w.submit({"feat": np.zeros(1)}, "late.mp4", str(tmp_path))


def test_writer_counts_backlog_videos_and_bytes(tmp_path):
    """The writer's three counters, kept where the work happens."""
    w = AsyncOutputWriter(depth=2)
    assert (w.backlog_max, w.videos_written, w.write_bytes) == (0, 0, 0)
    handles = [w.submit({"feat": np.zeros(8, np.float32),
                         "fps": np.array(25.0)}, f"c{i}.mp4", str(tmp_path))
               for i in range(3)]
    for h in handles:
        assert h.wait(timeout=60)
    w.close()
    assert w.counters() == {"writer_backlog_max": w.backlog_max,
                            "videos_written": 3,
                            "write_bytes": 3 * (8 * 4 + 8)}
    # sampled at every submit, the job being submitted included: at least 1,
    # at most the queue's depth plus the job in hand plus the one submitted
    assert 1 <= w.backlog_max <= 4


def test_writer_backlog_counts_queue_plus_job_in_hand(tmp_path, monkeypatch):
    import threading

    gate = threading.Event()
    real = AsyncOutputWriter._run_one

    def slow(*job):
        gate.wait(30)
        return real(*job)

    monkeypatch.setattr(AsyncOutputWriter, "_run_one", staticmethod(slow))
    w = AsyncOutputWriter(depth=2)
    handles = [w.submit({"feat": np.zeros(2, np.float32)}, f"b{i}.mp4",
                        str(tmp_path)) for i in range(3)]
    assert w.backlog_max == 3  # one in hand, two queued
    gate.set()
    for h in handles:
        assert h.wait(timeout=60)
    w.close()
    assert w.counters()["videos_written"] == 3


def test_writer_job_runs_inside_a_write_span_with_bytes_and_retries(
        tmp_path, monkeypatch):
    import threading

    from video_features_tpu.utils.metrics import SpanRecorder, span

    monkeypatch.setenv("VFT_FAULTS", "save:raise_transient::1")
    rec = SpanRecorder()

    def the_span(name, **kw):
        return span(name, recorder=rec, **kw)

    w = AsyncOutputWriter(depth=2, span=the_span,
                          retry=RetryPolicy(attempts=3, base_delay=0.01))
    h = w.submit({"feat": np.arange(5, dtype=np.float32)}, "ws.mp4",
                 str(tmp_path))
    assert h.wait(timeout=60)
    w.close()
    (record,) = rec.export()["records"]
    assert record["name"] == "write" and record["thread"] == "output-writer"
    assert record["thread"] != threading.current_thread().name
    assert record["ids"] == {"video": "ws.mp4", "bytes": 20, "retries": 1}
    assert w.counters()["videos_written"] == 1 and w.write_bytes == 20


def test_failed_write_is_not_counted_as_written(tmp_path, monkeypatch):
    monkeypatch.setenv("VFT_FAULTS", "save:raise_permanent")
    w = AsyncOutputWriter(depth=2)
    h = w.submit({"feat": np.zeros(3, np.float32)}, "bad.mp4", str(tmp_path))
    with pytest.raises(Exception):
        h.wait(timeout=60)
    w.close()
    assert w.counters() == {"writer_backlog_max": 1, "videos_written": 0,
                            "write_bytes": 0}

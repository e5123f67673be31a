"""Reliability subsystem units: taxonomy, retry/backoff, watchdog, manifests.

End-to-end fault-injected runs live in tests/test_fault_injection.py; this
module pins the building blocks' contracts.
"""

import json
import os
import time

import numpy as np
import pytest

from video_features_tpu.io import ffmpeg as ffmpeg_io
from video_features_tpu.io.output import (
    action_on_extraction,
    load_done_set,
    manifest_path,
    mark_done,
)
from video_features_tpu.io.video import open_video, probe_video
from video_features_tpu.reliability import (
    DecodeError,
    DeviceError,
    ExtractionError,
    FfmpegError,
    OutputError,
    RetryPolicy,
    VideoTimeoutError,
    classify,
    failed_manifest_path,
    load_failures,
    prune_failures,
    record_failure,
    retry_call,
    run_with_timeout,
    traceback_digest,
)


# ---- taxonomy -------------------------------------------------------------


def test_transient_tags():
    assert not DecodeError("x").transient
    assert not VideoTimeoutError("x").transient
    assert FfmpegError("x").transient
    assert DeviceError("x").transient
    assert OutputError("x").transient
    for cls in (DecodeError, FfmpegError, DeviceError, OutputError, VideoTimeoutError):
        assert issubclass(cls, ExtractionError)


def test_classify_taxonomy_and_unknown():
    assert classify(FfmpegError("a")) == ("FfmpegError", True)
    assert classify(DecodeError("a")) == ("DecodeError", False)
    assert classify(ValueError("a")) == ("ValueError", False)


def test_classify_jax_runtime_error_is_device_fault():
    """What a failing jitted call really raises on the installed JAX (here: an
    allocation no host can satisfy) classifies as a transient device fault —
    and a look-alike by name only does not."""
    import jax
    import jax.numpy as jnp

    with pytest.raises(jax.errors.JaxRuntimeError) as info:
        jax.jit(lambda: jnp.zeros((1 << 42,), jnp.float32))().block_until_ready()
    assert "RESOURCE_EXHAUSTED" in str(info.value)
    assert classify(info.value) == ("DeviceError", True)
    fake = type("JaxRuntimeError", (RuntimeError,), {})("DEADLINE_EXCEEDED")
    assert classify(fake) == ("JaxRuntimeError", False)


def test_traceback_digest_groups_by_site_not_message():
    def boom(msg):
        raise DecodeError(msg)

    digests = []
    for msg in ("video_a.mp4 bad", "video_b.mp4 bad"):
        try:
            boom(msg)
        except DecodeError as e:
            digests.append(traceback_digest(e))
    assert digests[0] == digests[1]
    assert len(digests[0]) == 12


# ---- retry ---------------------------------------------------------------


def test_retry_policy_delays_exponential_capped():
    p = RetryPolicy(attempts=5, base_delay=1.0, max_delay=3.0)
    assert list(p.delays()) == [1.0, 2.0, 3.0, 3.0]
    with pytest.raises(ValueError):
        RetryPolicy(attempts=0)


def test_retry_transient_succeeds_with_backoff():
    calls, slept = [], []

    def fn():
        calls.append(1)
        if len(calls) < 3:
            raise FfmpegError("child died")
        return "ok"

    out = retry_call(fn, RetryPolicy(attempts=3, base_delay=0.25), sleep=slept.append)
    assert out == "ok" and len(calls) == 3
    assert slept == [0.25, 0.5]


def test_retry_permanent_raises_immediately_with_attempt_count():
    calls = []

    def fn():
        calls.append(1)
        raise DecodeError("corrupt")

    with pytest.raises(DecodeError) as ei:
        retry_call(fn, RetryPolicy(attempts=4, base_delay=0.0), sleep=lambda d: None)
    assert len(calls) == 1
    assert ei.value.attempts == 1


def test_retry_exhaustion_reports_attempts():
    def fn():
        raise DeviceError("flaky")

    with pytest.raises(DeviceError) as ei:
        retry_call(fn, RetryPolicy(attempts=3, base_delay=0.0), sleep=lambda d: None)
    assert ei.value.attempts == 3


def test_retry_on_retry_callback_sees_delay():
    seen = []

    def fn():
        if len(seen) < 1:
            raise OutputError("disk")
        return 1

    retry_call(
        fn,
        RetryPolicy(attempts=2, base_delay=0.125),
        sleep=lambda d: None,
        on_retry=lambda exc, attempt, delay: seen.append((type(exc).__name__, attempt, delay)),
    )
    assert seen == [("OutputError", 1, 0.125)]


# ---- watchdog ------------------------------------------------------------


def test_watchdog_passthrough_and_errors():
    assert run_with_timeout(lambda: 7, None) == 7
    assert run_with_timeout(lambda: 7, 5.0) == 7
    with pytest.raises(DecodeError, match="inner"):
        run_with_timeout(lambda: (_ for _ in ()).throw(DecodeError("inner")), 5.0)


def test_watchdog_cancels_hang():
    t0 = time.monotonic()
    with pytest.raises(VideoTimeoutError, match="video_timeout"):
        run_with_timeout(lambda: time.sleep(10), 0.3, "wedged.mp4")
    assert time.monotonic() - t0 < 5.0
    assert not VideoTimeoutError("x").transient  # watchdog hits are not retried


# ---- failure manifest ----------------------------------------------------


def test_failure_manifest_roundtrip(tmp_path):
    out = str(tmp_path)
    rec = record_failure(out, "a.mp4", DecodeError("corrupt"), attempts=2)
    assert rec["error_class"] == "DecodeError" and rec["transient"] is False
    record_failure(out, "b.mp4", FfmpegError("died"), attempts=3)
    failures = load_failures(out)
    assert set(failures) == {os.path.abspath("a.mp4"), os.path.abspath("b.mp4")}
    assert failures[os.path.abspath("b.mp4")]["attempts"] == 3
    prune_failures(out, ["a.mp4"])
    assert set(load_failures(out)) == {os.path.abspath("b.mp4")}
    prune_failures(out, ["b.mp4"])
    assert load_failures(out) == {}
    # pruning the last record removes the file: "no manifest" == "no failures"
    assert not os.path.exists(failed_manifest_path(out))


def test_failure_manifest_last_record_wins(tmp_path):
    out = str(tmp_path)
    record_failure(out, "a.mp4", FfmpegError("first"), attempts=1)
    record_failure(out, "a.mp4", DecodeError("second"), attempts=2)
    failures = load_failures(out)
    assert failures[os.path.abspath("a.mp4")]["error_class"] == "DecodeError"


def test_failure_manifest_warns_on_corrupt_lines(tmp_path, capsys):
    out = str(tmp_path)
    record_failure(out, "a.mp4", DecodeError("x"))
    with open(failed_manifest_path(out), "a") as f:
        f.write("{truncated\n[]\n")
    failures = load_failures(out)
    assert set(failures) == {os.path.abspath("a.mp4")}
    assert "2 corrupt line(s)" in capsys.readouterr().err


# ---- done-manifest corruption (satellite) --------------------------------


def test_load_done_set_warns_on_corrupt_lines(tmp_path, capsys):
    out = str(tmp_path)
    mark_done(out, "good.mp4", ["rgb"])
    with open(manifest_path(out), "a") as f:
        f.write('{"video": "half\n')  # crash mid-append
        f.write("not json at all\n")
    done = load_done_set(out)
    assert done == {os.path.abspath("good.mp4")}
    err = capsys.readouterr().err
    assert "2 corrupt line(s)" in err and "re-extracted" in err


# ---- atomic save ---------------------------------------------------------


def test_atomic_save_no_tmp_left_behind(tmp_path):
    saved = action_on_extraction(
        {"k": np.arange(5)}, "v.mp4", str(tmp_path), "save_numpy")
    assert os.path.exists(saved["k"])
    assert not os.path.exists(saved["k"] + ".tmp")
    np.testing.assert_array_equal(np.load(saved["k"]), np.arange(5))


def test_atomic_save_injected_fault_cleans_tmp(tmp_path, monkeypatch):
    """An injected OutputError between write and rename must not leave the
    .npy.tmp behind (chaos drills would otherwise accumulate clutter)."""
    monkeypatch.setenv("VFT_FAULTS", "save:raise")
    with pytest.raises(OutputError, match="injected"):
        action_on_extraction({"k": np.arange(5)}, "v.mp4", str(tmp_path), "save_numpy")
    assert list(tmp_path.iterdir()) == []  # no final .npy, no .tmp


def test_atomic_save_failure_classified_and_tmp_cleaned(tmp_path, monkeypatch):
    def bad_replace(src, dst):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(os, "replace", bad_replace)
    with pytest.raises(OutputError, match="No space left"):
        action_on_extraction({"k": np.arange(5)}, "v.mp4", str(tmp_path), "save_numpy")
    assert not any(p.name.endswith(".tmp") for p in tmp_path.iterdir())
    assert OutputError("x").transient  # disk pressure is worth retrying


# ---- classified decode errors --------------------------------------------


@pytest.fixture
def garbage_mp4(tmp_path):
    p = tmp_path / "garbage.mp4"
    p.write_bytes(b"\x00\x01junk" * 1024)
    return str(p)


def test_probe_corrupt_container_raises_decode_error(garbage_mp4):
    with pytest.raises(DecodeError, match="cannot open|corrupt"):
        probe_video(garbage_mp4)


def test_open_corrupt_container_raises_decode_error(garbage_mp4):
    with pytest.raises(DecodeError):
        meta, frames = open_video(garbage_mp4)
        list(frames)


def test_open_missing_video_still_file_not_found(tmp_path):
    with pytest.raises(FileNotFoundError):
        open_video(str(tmp_path / "nope.mp4"))


# ---- ffmpeg classification + graceful degradation ------------------------


def test_run_checked_classifies_spawn_failure(tmp_path):
    with pytest.raises(FfmpegError, match="spawn"):
        ffmpeg_io._run_checked(
            [str(tmp_path / "no_such_ffmpeg")], "src.mp4", str(tmp_path / "out.mp4"))


def test_run_checked_input_caused_exit_is_permanent(tmp_path, monkeypatch):
    """Deterministic input failures (corrupt container, no audio stream) must
    not burn the retry budget; environmental exits stay transient."""
    class FakeProc:
        def __init__(self, rc, stderr):
            self.returncode, self.stderr = rc, stderr

    for rc, stderr, want_transient in [
        (1, "x.mp4: moov atom not found", False),
        (1, "Output file #0 does not contain any stream", False),
        (1, "Invalid data found when processing input", False),
        (1, "Cannot allocate memory", True),     # environmental
        (-9, "", True),                           # killed by a signal
    ]:
        monkeypatch.setattr(
            ffmpeg_io.subprocess, "run",
            lambda cmd, capture_output, text, _p=FakeProc(rc, stderr): _p)
        with pytest.raises(FfmpegError) as ei:
            ffmpeg_io._run_checked(["ffmpeg"], "src.mp4", str(tmp_path / "o.mp4"))
        from video_features_tpu.reliability import classify
        assert classify(ei.value) == ("FfmpegError", want_transient), stderr


@pytest.fixture
def tiny_video(tmp_path):
    import cv2

    p = str(tmp_path / "tiny.mp4")
    w = cv2.VideoWriter(p, cv2.VideoWriter_fourcc(*"mp4v"), 10.0, (32, 24))
    rng = np.random.default_rng(0)
    for _ in range(12):
        w.write(rng.integers(0, 256, (24, 32, 3), dtype=np.uint8))
    w.release()
    return p


def test_ffmpeg_transient_retry_then_success(tiny_video, tmp_path, monkeypatch):
    """First re-encode attempt dies, the bounded retry succeeds — the video
    takes the (faked) ffmpeg path, not the fallback."""
    import shutil

    calls = []

    def fake_reencode(video_path, tmp_dir, fps):
        calls.append(1)
        if len(calls) == 1:
            raise FfmpegError("child OOM-killed")
        os.makedirs(tmp_dir, exist_ok=True)
        copy = os.path.join(tmp_dir, "reencoded.mp4")
        shutil.copy(video_path, copy)
        return copy

    monkeypatch.setattr(ffmpeg_io, "have_ffmpeg", lambda: True)
    monkeypatch.setattr(ffmpeg_io, "reencode_video_with_diff_fps", fake_reencode)
    meta, frames = open_video(
        tiny_video, extraction_fps=10, tmp_path=str(tmp_path / "t"),
        retries=2, retry_backoff=0.0)
    assert len(calls) == 2
    assert meta.fps == 10.0
    assert len(list(frames)) == 12


def test_ffmpeg_permanent_failure_degrades_to_native_sampler(
        tiny_video, tmp_path, monkeypatch, capsys):
    """All re-encode attempts fail under use_ffmpeg='auto' → the native
    sampler takes over instead of killing the video."""
    def always_fail(video_path, tmp_dir, fps):
        raise FfmpegError("no tmp space")

    monkeypatch.setattr(ffmpeg_io, "have_ffmpeg", lambda: True)
    monkeypatch.setattr(ffmpeg_io, "reencode_video_with_diff_fps", always_fail)
    meta, frames = open_video(
        tiny_video, extraction_fps=5, tmp_path=str(tmp_path / "t"),
        use_ffmpeg="auto", retries=1, retry_backoff=0.0)
    got = list(frames)
    assert meta.fps == 5.0 and 5 <= len(got) <= 7  # 12 frames @10fps → ~6 @5fps
    assert "falling back to the native fps sampler" in capsys.readouterr().err

    with pytest.raises(FfmpegError):  # 'always' must not degrade silently
        open_video(tiny_video, extraction_fps=5, tmp_path=str(tmp_path / "t"),
                   use_ffmpeg="always", retries=0, retry_backoff=0.0)


# ---- config validation ---------------------------------------------------


def test_reliability_config_validation():
    from video_features_tpu.config import ExtractionConfig

    base = dict(feature_type="resnet50")
    ExtractionConfig(**base, retries=0, video_timeout=1.5, max_failures=0).validate()
    with pytest.raises(ValueError, match="retries"):
        ExtractionConfig(**base, retries=-1).validate()
    with pytest.raises(ValueError, match="video_timeout"):
        ExtractionConfig(**base, video_timeout=0).validate()
    with pytest.raises(ValueError, match="max_failures"):
        ExtractionConfig(**base, max_failures=-2).validate()
    with pytest.raises(ValueError, match="retry_backoff"):
        ExtractionConfig(**base, retry_backoff=-0.5).validate()


def test_cli_reliability_flags():
    from video_features_tpu.cli import parse_args

    cfg = parse_args([
        "--feature_type", "resnet50", "--video_paths", "a.mp4",
        "--retries", "5", "--retry_backoff", "0.1",
        "--video_timeout", "30", "--max_failures", "10", "--retry_failed",
    ])
    assert cfg.retries == 5 and cfg.retry_backoff == 0.1
    assert cfg.video_timeout == 30.0 and cfg.max_failures == 10
    assert cfg.retry_failed is True


def test_failed_manifest_is_json_lines(tmp_path):
    out = str(tmp_path)
    record_failure(out, "x.mp4", OutputError("disk full"), attempts=4)
    with open(failed_manifest_path(out)) as f:
        rec = json.loads(f.readline())
    assert set(rec) == {"video", "error_class", "transient", "attempts",
                        "message", "traceback_digest"}

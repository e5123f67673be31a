"""Segmented intra-video decode: planner, byte parity, pool, policy.

Pins the tentpole invariant — the stitched segment stream is byte-identical
to sequential decode (frames AND timestamps, raw and fps-resampled) — plus
the scheduling/reliability story around it: all-permits-up-front
reservation, in-order reassembly, poisoned-segment fault attribution,
cooperative timeouts, live resize, and the autoscaler's segment-before-grow
preference. ffmpeg fast-seek is exercised through a fake binary (the image
has no ffmpeg; cv2 is the production backend tier-1 actually decodes with).
"""
# fast-registry: default tier — real-sleep pool concurrency + e2e parity runs

import hashlib
import os
import time

import numpy as np
import pytest

from video_features_tpu.config import ExtractionConfig
from video_features_tpu.extractors.base import Extractor
from video_features_tpu.io import ffmpeg as ffmpeg_io
from video_features_tpu.io.output import load_done_set
from video_features_tpu.io.video import (
    VideoMeta,
    _resampled_frames,
    _require_nonempty,
    _seeked_capture,
    _segment_resampled,
    _segment_source_frames,
    open_video,
    open_video_segment,
    plan_segments,
    probe_video,
)
from video_features_tpu.parallel.pipeline import DecodePrefetcher
from video_features_tpu.reliability import load_failures, reset_faults
from video_features_tpu.reliability.errors import DecodeError, FfmpegError
from video_features_tpu.serve.autoscale import DecodeAutoscaler


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("VFT_FAULTS", raising=False)
    reset_faults()
    yield
    reset_faults()


def _write_video(path, frames=25, size=(32, 24), fps=10.0):
    import cv2

    w = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*"mp4v"), fps, size)
    rng = np.random.default_rng(frames)
    for _ in range(frames):
        w.write(rng.integers(0, 256, (size[1], size[0], 3), dtype=np.uint8))
    w.release()
    return str(path)


# ---------------------------------------------------------------------------
# planner


def test_plan_segments_partitions_source_range():
    meta = VideoMeta(path="v.mp4", fps=10.0, frame_count=25, width=8, height=6)
    plan = plan_segments(meta, 4)
    assert len(plan.bounds) == 4
    assert plan.bounds[0][0] == 0 and plan.bounds[-1][1] == 25
    for (_, e0), (s1, _) in zip(plan.bounds, plan.bounds[1:]):
        assert e0 == s1  # contiguous, no gap/overlap
    assert all(e - s >= 2 for s, e in plan.bounds)
    assert plan.meta.frame_count == 25 and plan.meta.fps == 10.0


def test_plan_segments_resampled_meta_matches_open_video():
    meta = VideoMeta(path="v.mp4", fps=10.0, frame_count=25, width=8, height=6)
    plan = plan_segments(meta, 3, extraction_fps=4)
    assert plan.meta.fps == 4.0
    assert plan.meta.frame_count == int(round(25 * 4 / 10.0))
    assert plan.extraction_fps == 4.0


def test_plan_segments_declines_short_or_degenerate():
    short = VideoMeta(path="v", fps=10.0, frame_count=3, width=8, height=6)
    assert plan_segments(short, 4) is None  # 3 // 2 = 1 segment -> no split
    for bad in (
        VideoMeta(path="v", fps=0.0, frame_count=100, width=8, height=6),
        VideoMeta(path="v", fps=10.0, frame_count=0, width=8, height=6),
        VideoMeta(path="v", fps=10.0, frame_count=100, width=0, height=6),
    ):
        assert plan_segments(bad, 4) is None
    assert plan_segments(short, 4, min_segment_frames=1) is not None


def test_plan_narrow_reslices_for_fewer_permits():
    meta = VideoMeta(path="v.mp4", fps=10.0, frame_count=24, width=8, height=6)
    plan = plan_segments(meta, 6, extraction_fps=5)
    narrowed = plan.narrow(2)
    assert len(narrowed.bounds) == 2
    assert narrowed.bounds[0][0] == 0 and narrowed.bounds[-1][1] == 24
    assert narrowed.meta == plan.meta  # output meta is split-invariant


# ---------------------------------------------------------------------------
# resample math across segment boundaries (pure, no decode)


@pytest.mark.parametrize("n,src,dst", [
    (20, 10.0, 4.0),    # downsample
    (20, 10.0, 5.0),    # exact divisor
    (12, 4.0, 10.0),    # upsample (slot gaps duplicate frames)
    (30, 19.62, 4.0),   # irrational-ish ratio
    (7, 25.0, 25.0),    # identity rate
    (40, 30.0, 10.0),
])
@pytest.mark.parametrize("k", [2, 3, 5])
def test_segment_resample_stitches_to_sequential(n, src, dst, k):
    if k > n:
        pytest.skip("fewer frames than segments")
    frames = [(np.full((2, 2, 3), i % 251, np.uint8), float(i)) for i in range(n)]
    seq = list(_resampled_frames(iter(frames), src, dst))
    stitched = []
    for j in range(k):
        s, e = n * j // k, n * (j + 1) // k
        stitched += list(_segment_resampled(
            iter(frames[s:e]), s, src, dst, j == k - 1, e))
    assert len(stitched) == len(seq)
    for (rgb_a, ts_a), (rgb_b, ts_b) in zip(seq, stitched):
        np.testing.assert_array_equal(rgb_a, rgb_b)
        assert ts_a == ts_b  # exact: both are (slot+1)/dst arithmetic


# ---------------------------------------------------------------------------
# segment source stream: lead-in, first-frame workaround, strict middles


class _FakeCap:
    """Scripted cv2.VideoCapture: a list of (ok, bgr) read results."""

    def __init__(self, results):
        self._results = list(results)
        self.released = False

    def read(self):
        return self._results.pop(0) if self._results else (False, None)

    def get(self, _prop):
        return 0.0

    def release(self):
        self.released = True


def _bgr(i):
    return np.full((2, 2, 3), i % 251, np.uint8)


def test_first_frame_drop_tolerated_at_segment_zero_only():
    hiccup = [(False, None)] + [(True, _bgr(i)) for i in range(2)]
    cap = _FakeCap(hiccup)
    got = list(_segment_source_frames(cap, 0, 2, True, "v.mp4", 0))
    assert len(got) == 2 and cap.released

    cap = _FakeCap(list(hiccup))
    with pytest.raises(DecodeError, match="underran after 0 frames"):
        list(_segment_source_frames(cap, 0, 2, False, "v.mp4", 10))
    assert cap.released


def test_middle_segment_underrun_raises_stitch_error():
    cap = _FakeCap([(True, _bgr(0))])
    with pytest.raises(DecodeError, match="underran after 1 frames"):
        list(_segment_source_frames(cap, 0, 3, False, "v.mp4", 8))


def test_eof_during_lead_in_raises():
    cap = _FakeCap([(True, _bgr(0))])
    with pytest.raises(DecodeError, match="EOF during seek lead-in"):
        list(_segment_source_frames(cap, 3, 2, False, "v.mp4", 12))


def test_final_segment_must_yield_at_least_one_frame():
    with pytest.raises(DecodeError, match="found no frames"):
        list(_require_nonempty(iter(()), "v.mp4", 20))
    passthrough = [(np.zeros((1, 1, 3), np.uint8), 0.0)]
    assert len(list(_require_nonempty(iter(passthrough), "v.mp4", 20))) == 1


def test_cv2_seek_is_frame_exact_on_mp4v(tmp_path):
    """The cv2 POS_FRAMES backend lands exactly on mp4v containers — the
    property that makes 'auto' parity-safe without ffmpeg installed."""
    path = _write_video(tmp_path / "seek.mp4", frames=30)
    _, seq = open_video(path)
    frames = [rgb for rgb, _ in seq]
    cap, lead_in = _seeked_capture(path, 13)
    assert cap is not None
    got = list(_segment_source_frames(cap, lead_in, 5, False, path, 13))
    assert len(got) == 5
    for off, (rgb, _ts) in enumerate(got):
        np.testing.assert_array_equal(rgb, frames[13 + off])


# ---------------------------------------------------------------------------
# stitched parity on real containers (the acceptance invariant)


@pytest.mark.parametrize("efps", [None, 4, 25])
@pytest.mark.parametrize("k", [2, 3])
def test_stitched_stream_byte_identical_to_sequential(tmp_path, efps, k):
    path = _write_video(tmp_path / f"par_{efps}_{k}.mp4", frames=25)
    meta, frames = open_video(path, extraction_fps=efps, use_ffmpeg="never")
    seq = list(frames)
    plan = plan_segments(probe_video(path), k, extraction_fps=efps)
    assert len(plan.bounds) == k
    assert (plan.meta.fps, plan.meta.frame_count) == (meta.fps, meta.frame_count)
    stitched = [item for j in range(k) for item in open_video_segment(plan, j)]
    assert len(stitched) == len(seq)
    for (rgb_a, ts_a), (rgb_b, ts_b) in zip(seq, stitched):
        np.testing.assert_array_equal(rgb_a, rgb_b)
        assert ts_a == ts_b


def test_stitched_parity_with_host_transform(tmp_path):
    path = _write_video(tmp_path / "tr.mp4", frames=20)
    transform = lambda rgb: rgb[::2, ::2].astype(np.float32) / 255.0  # noqa: E731
    _, frames = open_video(path, transform=transform)
    seq = list(frames)
    plan = plan_segments(probe_video(path), 3)
    stitched = [item for j in range(3)
                for item in open_video_segment(plan, j, transform=transform)]
    for (rgb_a, ts_a), (rgb_b, ts_b) in zip(seq, stitched):
        np.testing.assert_array_equal(rgb_a, rgb_b)
        assert ts_a == ts_b


def test_open_video_segment_validates_inputs(tmp_path):
    plan = plan_segments(
        VideoMeta(path="v", fps=10.0, frame_count=20, width=2, height=2), 2)
    with pytest.raises(ValueError, match="segment index"):
        open_video_segment(plan, 2)
    with pytest.raises(ValueError, match="seek must be"):
        open_video_segment(plan, 0, seek="bogus")


# ---------------------------------------------------------------------------
# ffmpeg fast-seek streamer (fake binary — the image ships no ffmpeg)


def _install_fake_ffmpeg(tmp_path, monkeypatch, body):
    d = tmp_path / "bin"
    d.mkdir(exist_ok=True)
    script = d / "ffmpeg"
    script.write_text("#!/bin/sh\n" + body)
    script.chmod(0o755)
    monkeypatch.setenv("PATH", f"{d}:{os.environ.get('PATH', '')}")
    return d


def test_segment_frames_requires_ffmpeg(tmp_path, monkeypatch):
    empty = tmp_path / "nobin"
    empty.mkdir()
    monkeypatch.setenv("PATH", str(empty))
    assert not ffmpeg_io.have_ffmpeg()
    with pytest.raises(RuntimeError, match="cv2 seek backend"):
        next(ffmpeg_io.segment_frames("v.mp4", 0, 2, 10.0, 4, 4))


def test_segment_frames_command_and_rawvideo_parse(tmp_path, monkeypatch):
    d = _install_fake_ffmpeg(
        tmp_path, monkeypatch,
        f'echo "$@" > {tmp_path}/args\nhead -c 96 /dev/zero\n')
    assert ffmpeg_io.which_ffmpeg() == str(d / "ffmpeg")
    frames = list(ffmpeg_io.segment_frames("vid.mp4", 6, 2, 10.0, 4, 4))
    assert len(frames) == 2
    assert all(f.shape == (4, 4, 3) and f.dtype == np.uint8 for f in frames)
    args = (tmp_path / "args").read_text().split()
    # fast seek: -ss half a frame before the target, BEFORE -i
    assert args.index("-ss") < args.index("-i")
    assert float(args[args.index("-ss") + 1]) == pytest.approx(0.55)
    assert args[args.index("-frames:v") + 1] == "2"
    assert args[args.index("-pix_fmt") + 1] == "rgb24"
    assert "-nostdin" in args and args[-1] == "pipe:1"


def test_segment_frames_no_seek_flag_for_segment_zero(tmp_path, monkeypatch):
    _install_fake_ffmpeg(
        tmp_path, monkeypatch,
        f'echo "$@" > {tmp_path}/args\nhead -c 48 /dev/zero\n')
    assert len(list(ffmpeg_io.segment_frames("vid.mp4", 0, None, 10.0, 4, 4))) == 1
    args = (tmp_path / "args").read_text().split()
    assert "-ss" not in args and "-frames:v" not in args


def test_segment_frames_classifies_input_error_permanent(tmp_path, monkeypatch):
    _install_fake_ffmpeg(
        tmp_path, monkeypatch,
        'echo "vid.mp4: moov atom not found" >&2\nexit 1\n')
    with pytest.raises(FfmpegError, match="moov atom") as ei:
        list(ffmpeg_io.segment_frames("vid.mp4", 3, 2, 10.0, 4, 4))
    assert ei.value.transient is False


def test_segment_frames_underrun_is_a_stitch_error(tmp_path, monkeypatch):
    _install_fake_ffmpeg(tmp_path, monkeypatch, "head -c 48 /dev/zero\n")
    with pytest.raises(FfmpegError, match="frame count unreliable"):
        list(ffmpeg_io.segment_frames("vid.mp4", 3, 2, 10.0, 4, 4))


# ---------------------------------------------------------------------------
# decode pool: reservation, reassembly, faults, resize


def _pool_fixture(workers, n_frames=12, poison=None, delay=0.0):
    """Pool + fake segmenter over a synthetic frame-index stream."""
    meta = VideoMeta(path="v.mp4", fps=10.0, frame_count=n_frames,
                     width=4, height=4)

    def open_seq(path):
        return meta, iter([(np.full((4, 4, 3), i % 251, np.uint8), float(i))
                           for i in range(n_frames)])

    def planner(path, max_segments):
        return plan_segments(meta, max_segments)

    def open_segment(plan, index):
        if poison is not None and index == poison:
            raise DecodeError(f"{plan.source_meta.path}#seg{index}: poisoned")

        def gen():
            s, e = plan.bounds[index]
            for i in range(s, e):
                if delay:
                    time.sleep(delay)
                yield np.full((4, 4, 3), i % 251, np.uint8), float(i)

        return gen()

    pool = DecodePrefetcher(open_seq, workers=workers)
    pool.set_segmenter(planner, open_segment)
    return pool


def _wait_for(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def test_pool_segmented_reassembly_in_order():
    pool = _pool_fixture(workers=4, n_frames=12)
    try:
        pool.schedule("v.mp4")
        meta, frames = pool.get("v.mp4")
        got = list(frames)
        assert meta.frame_count == 12
        assert [int(ts) for _rgb, ts in got] == list(range(12))
        for rgb, ts in got:
            assert int(rgb[0, 0, 0]) == int(ts) % 251
        pool.release("v.mp4")
        assert pool.segment_stats() == (1, 4)
        # every segment worker hands its permit back
        assert _wait_for(lambda: pool.spare_permits() == 4)
    finally:
        pool.shutdown()


def test_pool_declines_segmentation_without_two_spare_permits():
    calls = []
    pool = _pool_fixture(workers=1)
    planner = pool._planner
    pool.set_segmenter(lambda p, m: calls.append(m) or planner(p, m),
                       pool._segment_open)
    try:
        pool.schedule("v.mp4")
        _meta, frames = pool.get("v.mp4")
        assert len(list(frames)) == 12
        assert calls == []  # spare < 2: planner never consulted
        assert pool.segment_stats() == (0, 0)
    finally:
        pool.shutdown()


def test_pool_poisoned_segment_fails_only_at_its_offset():
    pool = _pool_fixture(workers=4, n_frames=12, poison=1)
    try:
        pool.schedule("v.mp4")
        _meta, frames = pool.get("v.mp4")
        got = []
        with pytest.raises(DecodeError, match="seg1: poisoned"):
            for item in frames:
                got.append(item)
        # segment 0's frames streamed clean before the error surfaced
        assert [int(ts) for _rgb, ts in got] == list(range(3))
        pool.release("v.mp4")
        assert _wait_for(lambda: pool.spare_permits() == 4)
        # the pool is healthy for the next video
        pool2 = _pool_fixture(workers=4)
    finally:
        pool.shutdown()
    try:
        pool2.schedule("v.mp4")
        assert len(list(pool2.get("v.mp4")[1])) == 12
    finally:
        pool2.shutdown()


def test_pool_release_fans_out_to_all_segment_workers():
    pool = _pool_fixture(workers=4, n_frames=12, delay=0.02)
    try:
        pool.schedule("v.mp4")
        _meta, frames = pool.get("v.mp4")
        next(frames)  # consume one item, then abandon mid-stream
        pool.release("v.mp4")
        assert _wait_for(lambda: pool.spare_permits() == 4)
    finally:
        pool.shutdown()


def test_pool_shrink_never_cancels_mid_flight_segments():
    pool = _pool_fixture(workers=4, n_frames=12, delay=0.01)
    try:
        pool.schedule("v.mp4")
        _wait_for(lambda: pool.spare_permits() == 0, timeout=1.0)
        pool.resize(2)  # shrink while all four segments are in flight
        _meta, frames = pool.get("v.mp4")
        got = [int(ts) for _rgb, ts in frames]
        assert got == list(range(12))  # parity survives the shrink
        pool.release("v.mp4")
        assert pool.segment_stats() == (1, 4)  # all four completed clean
        assert _wait_for(lambda: pool.spare_permits() == 2)
    finally:
        pool.shutdown()


def test_pool_spare_permits_reserved_synchronously_at_schedule():
    pool = _pool_fixture(workers=4, delay=0.05)
    try:
        assert pool.spare_permits() == 4
        pool.schedule("v.mp4")  # segmented: reserves all permits up front
        assert pool.spare_permits() == 0
        list(pool.get("v.mp4")[1])
        pool.release("v.mp4")
        assert _wait_for(lambda: pool.spare_permits() == 4)
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# autoscaler interplay: segment-before-grow


def test_starved_interval_with_spare_permits_segments_instead_of_growing():
    scaler = DecodeAutoscaler(min_workers=1, max_workers=8)
    starved = dict(occupancy=0.5, decode_seconds=6.0, wall_seconds=10.0,
                   dispatched_slots=16, current=4)
    assert scaler.decide(**starved, spare_permits=2) == 4
    assert scaler.decide(**starved, spare_permits=0) == 5


def test_idle_interval_still_shrinks_regardless_of_spare():
    scaler = DecodeAutoscaler(min_workers=1, max_workers=8)
    idle = dict(occupancy=0.95, decode_seconds=0.2, wall_seconds=10.0,
                dispatched_slots=16, current=4)
    assert scaler.decide(**idle, spare_permits=3) == 3
    assert scaler.decide(**idle, spare_permits=0) == 3


# ---------------------------------------------------------------------------
# end-to-end: byte parity through the run loop for two extractor shapes


class StreamHasher(Extractor):
    """Frame-stream consumer that fingerprints the exact decoded bytes."""

    uses_frame_stream = True

    def extract(self, video_path):
        h = hashlib.sha256()
        _meta, frames = self._open_video(video_path)
        for rgb, pos in frames:
            h.update(np.ascontiguousarray(rgb).tobytes())
            h.update(np.float64(pos).tobytes())
        return {"feat": np.frombuffer(h.digest(), np.uint8).astype(np.float32)}


class FlowPairHasher(Extractor):
    """Flow-style consumer: fingerprints consecutive frame PAIRS, the stream
    shape the optical-flow extractors feed their models."""

    uses_frame_stream = True

    def extract(self, video_path):
        h = hashlib.sha256()
        _meta, frames = self._open_video(video_path)
        prev = None
        for rgb, _pos in frames:
            if prev is not None:
                h.update(np.ascontiguousarray(prev).tobytes())
                h.update(np.ascontiguousarray(rgb).tobytes())
            prev = rgb
        return {"feat": np.frombuffer(h.digest(), np.uint8).astype(np.float32)}


@pytest.fixture(scope="module")
def seg_corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("seg_corpus")
    return [_write_video(d / f"vid{i}.mp4", frames=24) for i in range(4)]


def _cfg(tmp_path, sub, **kw):
    kw.setdefault("retries", 1)
    kw.setdefault("retry_backoff", 0.01)
    return ExtractionConfig(
        feature_type="resnet50", on_extraction="save_numpy", num_devices=1,
        output_path=str(tmp_path / sub / "o"),
        tmp_path=str(tmp_path / sub / "t"), **kw)


def _digests(out_dir):
    return {name: np.load(os.path.join(out_dir, name)).tobytes()
            for name in sorted(os.listdir(out_dir)) if name.endswith(".npy")}


@pytest.mark.parametrize("extractor_cls", [StreamHasher, FlowPairHasher])
@pytest.mark.parametrize("efps", [None, 4])
def test_e2e_segmented_run_matches_sequential(
        tmp_path, seg_corpus, extractor_cls, efps):
    seq = extractor_cls(_cfg(tmp_path, "seq", decode_segments=1,
                             extraction_fps=efps, use_ffmpeg="never"))
    assert seq.run(seg_corpus) == len(seg_corpus)
    segd = extractor_cls(_cfg(tmp_path, "seg", decode_workers=4,
                              decode_segments=3, extraction_fps=efps,
                              use_ffmpeg="never"))
    assert segd.run(seg_corpus) == len(seg_corpus)
    a, b = _digests(seq.output_dir), _digests(segd.output_dir)
    assert set(a) == set(b) and len(a) == len(seg_corpus)
    assert a == b  # byte-identical features <=> byte-identical streams


def test_e2e_poisoned_segment_fails_only_its_video_and_retries(
        tmp_path, seg_corpus, monkeypatch):
    monkeypatch.setenv("VFT_FAULTS", "decode_segment:raise:vid2.mp4#seg1")
    # two permits a video: the run loop schedules the whole corpus ahead at
    # its first video, and a video splits only into permits that are spare
    # then — with four, vid0 and vid1 take them all, vid2 decodes in one piece
    # and the poisoned segment is never opened
    ex = StreamHasher(_cfg(tmp_path, "a", decode_workers=2 * len(seg_corpus),
                           decode_segments=2))
    assert ex.run(seg_corpus) == len(seg_corpus) - 1
    failures = load_failures(ex.output_dir)
    assert set(failures) == {os.path.abspath(seg_corpus[2])}
    assert failures[os.path.abspath(seg_corpus[2])]["error_class"] == "DecodeError"

    # --retry_failed semantics: faults cleared, exactly the failed set reruns
    monkeypatch.delenv("VFT_FAULTS")
    reset_faults()
    failed = sorted(load_failures(ex.output_dir))
    assert ex.run(failed) == 1
    assert load_failures(ex.output_dir) == {}
    assert len(load_done_set(ex.output_dir)) == len(seg_corpus)

    # and the recovered video's digest matches a sequential decode
    seq = StreamHasher(_cfg(tmp_path, "b", decode_segments=1))
    assert seq.run([seg_corpus[2]]) == 1
    a, b = _digests(ex.output_dir), _digests(seq.output_dir)
    assert all(a[name] == b[name] for name in b)


def test_e2e_video_timeout_cooperative_across_segments(
        tmp_path, seg_corpus, monkeypatch):
    """A wedged segment worker trips the per-video watchdog; the failure is
    attributed to its video only and the released permits let the rest of
    the corpus finish promptly."""
    monkeypatch.setenv("VFT_FAULTS", "decode_segment:hang(5):vid1.mp4#seg1")
    ex = StreamHasher(_cfg(tmp_path, "a", decode_workers=4, decode_segments=2,
                           video_timeout=0.5, retries=0))
    t0 = time.monotonic()
    assert ex.run(seg_corpus) == len(seg_corpus) - 1
    assert time.monotonic() - t0 < 30.0
    (rec,) = load_failures(ex.output_dir).values()
    assert rec["video"] == os.path.abspath(seg_corpus[1])
    assert rec["error_class"] == "VideoTimeoutError"

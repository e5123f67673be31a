"""Corpus clip packing (--pack_corpus): engine invariants, byte-identical
parity with the per-video loop, slot-level fault attribution, retries,
resume, occupancy accounting, and the unsupported-path fallback — through a
lightweight jitted frame-stream extractor (the real-model packed parity runs
live in tests/test_packer_models.py)."""

import glob
import os

import numpy as np
import pytest

import jax.numpy as jnp

from video_features_tpu.config import ExtractionConfig
from video_features_tpu.extractors.base import Extractor
from video_features_tpu.parallel.pipeline import pad_batch
from video_features_tpu.io.output import FeatureAssembly, load_done_set
from video_features_tpu.parallel.packer import CorpusPacker, PackSpec
from video_features_tpu.reliability import (
    DecodeError,
    load_failures,
    reset_faults,
)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("VFT_FAULTS", raising=False)
    reset_faults()
    yield
    reset_faults()


def _write_video(path, frames, size=(32, 24)):
    import cv2

    w = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*"mp4v"), 10.0, size)
    rng = np.random.default_rng(frames)  # content varies with length
    for _ in range(frames):
        w.write(rng.integers(0, 256, (size[1], size[0], 3), dtype=np.uint8))
    w.release()
    return str(path)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Four decodable tiny videos of mixed lengths (3, 5, 9, 2 frames)."""
    d = tmp_path_factory.mktemp("pack_corpus")
    return [_write_video(d / f"vid{i}.mp4", n)
            for i, n in enumerate((3, 5, 9, 2))]


class ToyPacked(Extractor):
    """Minimal frame-stream model implementing BOTH loops: per-slot features
    are a pure function of the frame, so packed and unpacked outputs must
    match bit for bit."""

    uses_frame_stream = True
    BATCH = 4

    def __init__(self, cfg):
        super().__init__(cfg)

        def fwd(params, frames_u8):  # (B, H, W, 3) uint8
            x = frames_u8.astype(jnp.float32)
            return jnp.stack([x.mean(axis=(1, 2, 3)), x.max(axis=(1, 2, 3))],
                             axis=-1)

        self._step = self.runner.jit(fwd)
        self._params = self.runner.put_replicated(
            {"w": np.zeros((1,), np.float32)})

    def extract(self, video_path):
        # the per-video loop's shape: batch, pad the tail, trim, concat
        meta, frames = self._open_video(video_path)
        ts, valid, batch, outs = [], [], [], []
        for rgb, pos in self._timed_frames(frames):
            ts.append(pos)
            batch.append(rgb)
            if len(batch) == self.BATCH:
                valid.append(len(batch))
                outs.append(self._step(self._params,
                                       self.runner.put(np.stack(batch))))
                batch = []
        if batch:
            valid.append(len(batch))
            outs.append(self._step(self._params, self.runner.put(
                pad_batch(np.stack(batch), self.BATCH))))
        rows = [self._wait(o)[:v] for o, v in zip(outs, valid)]
        feats = np.concatenate(rows) if rows else np.zeros((0, 2), np.float32)
        return {"feat": feats, "timestamps_ms": np.array(ts)}

    def pack_spec(self):
        def open_clips(path):
            meta, frames = self._open_video(path)
            info = {"timestamps_ms": []}

            def clips():
                for rgb, pos in self._timed_frames(frames):
                    info["timestamps_ms"].append(pos)
                    yield rgb

            return info, clips()

        def step(batch):
            return self._step(self._params, self.runner.put(batch))

        def finalize(path, rows, info):
            return {"feat": rows,
                    "timestamps_ms": np.array(info["timestamps_ms"])}

        return PackSpec(batch_size=self.BATCH, empty_row_shape=(2,),
                        open_clips=open_clips, step=step, finalize=finalize)


def _cfg(tmp_path, sub, **kw):
    kw.setdefault("retries", 1)
    kw.setdefault("retry_backoff", 0.01)
    return ExtractionConfig(
        feature_type="resnet50", on_extraction="save_numpy", num_devices=1,
        output_path=str(tmp_path / sub), tmp_path=str(tmp_path / "t"), **kw)


def _outputs(tmp_path, sub):
    return {os.path.basename(p): np.load(p)
            for p in glob.glob(str(tmp_path / sub / "resnet50" / "*.npy"))}


def _assert_bytes_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
        assert a[k].tobytes() == b[k].tobytes(), k


# ---- parity / occupancy ----------------------------------------------------


def test_packed_outputs_byte_identical_to_unpacked(tmp_path, corpus):
    ex_u = ToyPacked(_cfg(tmp_path, "u", pack_corpus=False))
    assert ex_u.run(corpus) == len(corpus)
    ex_p = ToyPacked(_cfg(tmp_path, "p", pack_corpus=True))
    assert ex_p.run(corpus) == len(corpus)
    _assert_bytes_equal(_outputs(tmp_path, "u"), _outputs(tmp_path, "p"))
    assert len(load_done_set(ex_p.output_dir)) == len(corpus)


def test_occupancy_beats_tail_padding(tmp_path, corpus):
    """3+5+9+2 = 19 frames over batch 4: packed dispatches 5 batches
    (20 slots), the per-video loop 7 (28 slots)."""
    ex = ToyPacked(_cfg(tmp_path, "o", pack_corpus=True))
    assert ex.run(corpus) == len(corpus)
    stats = ex._pack_stats
    assert stats["real_slots"] == 19
    assert stats["dispatched_slots"] == 20
    clip_counts = stats["video_clips"].values()
    unpacked_slots = sum(-(-c // ex.BATCH) * ex.BATCH for c in clip_counts)
    assert unpacked_slots == 28
    assert stats["occupancy"] > 19 / 28


def test_packed_resume_skips_done_videos(tmp_path, corpus):
    ex = ToyPacked(_cfg(tmp_path, "r", pack_corpus=True))
    assert ex.run(corpus[:2]) == 2
    ex2 = ToyPacked(_cfg(tmp_path, "r", pack_corpus=True, resume=True))
    assert ex2.run(corpus) == len(corpus)
    # only the two new videos dispatched clips (9 + 2 over batch 4 → 12 slots)
    assert ex2._pack_stats["real_slots"] == 11
    assert len(load_done_set(ex2.output_dir)) == len(corpus)


# ---- fault attribution (acceptance: VFT_FAULTS poisons ONE video) ----------


def test_fault_poisons_only_its_video_and_resume_works(
        tmp_path, corpus, monkeypatch):
    """Poisoning vid1 mid-corpus fails only vid1; co-packed neighbours
    complete byte-identical to a clean unpacked run, and --retry_failed-style
    reprocessing converges the manifests."""
    ex_clean = ToyPacked(_cfg(tmp_path, "clean"))
    assert ex_clean.run(corpus) == len(corpus)

    monkeypatch.setenv("VFT_FAULTS", "extract:raise_permanent:vid1")
    ex = ToyPacked(_cfg(tmp_path, "f", pack_corpus=True))
    assert ex.run(corpus) == len(corpus) - 1
    failures = load_failures(ex.output_dir)
    assert set(failures) == {os.path.abspath(corpus[1])}
    assert len(load_done_set(ex.output_dir)) == len(corpus) - 1
    got = _outputs(tmp_path, "f")
    want = {k: v for k, v in _outputs(tmp_path, "clean").items()
            if not k.startswith("vid1_")}
    _assert_bytes_equal(got, want)

    # resume: reprocess exactly the failed set with the fault cleared
    monkeypatch.delenv("VFT_FAULTS")
    reset_faults()
    failed = sorted(load_failures(ex.output_dir))
    assert ex.run(failed) == 1
    assert load_failures(ex.output_dir) == {}
    assert len(load_done_set(ex.output_dir)) == len(corpus)
    _assert_bytes_equal(_outputs(tmp_path, "f"), _outputs(tmp_path, "clean"))


def test_transient_failure_retries_and_corpus_completes(
        tmp_path, corpus, monkeypatch, capsys):
    monkeypatch.setenv("VFT_FAULTS", "extract:raise_transient:vid2:1")
    ex = ToyPacked(_cfg(tmp_path, "tr", pack_corpus=True, retries=2))
    assert ex.run(corpus) == len(corpus)
    assert load_failures(ex.output_dir) == {}
    out = capsys.readouterr().out
    assert "attempt 1 failed" in out and "retrying in" in out
    ex_clean = ToyPacked(_cfg(tmp_path, "trc"))
    assert ex_clean.run(corpus) == len(corpus)
    _assert_bytes_equal(_outputs(tmp_path, "tr"), _outputs(tmp_path, "trc"))


def test_mid_stream_decode_failure_attributes_to_its_video(tmp_path, corpus):
    """A clip stream that dies AFTER some of its clips were already packed
    (possibly co-dispatched with neighbours) fails only its video."""

    class MidStreamPoison(ToyPacked):
        def pack_spec(self):
            spec = super().pack_spec()
            inner_open = spec.open_clips

            def open_clips(path):
                info, clips = inner_open(path)
                if "vid2" not in path:
                    return info, clips

                def poisoned():
                    for i, clip in enumerate(clips):
                        if i == 2:  # vid2 has 9 frames; die after 2 clips
                            raise DecodeError(f"{path}: injected mid-stream")
                        yield clip

                return info, poisoned()

            spec.open_clips = open_clips
            return spec

    ex = MidStreamPoison(_cfg(tmp_path, "m", pack_corpus=True, retries=0))
    assert ex.run(corpus) == len(corpus) - 1
    assert set(load_failures(ex.output_dir)) == {os.path.abspath(corpus[2])}
    ex_clean = ToyPacked(_cfg(tmp_path, "mc"))
    assert ex_clean.run([p for p in corpus if "vid2" not in p]) == 3
    _assert_bytes_equal(_outputs(tmp_path, "m"), _outputs(tmp_path, "mc"))


def test_flush_batch_device_failure_stays_inside_the_barrier(tmp_path, corpus):
    """A device-step failure on the corpus-flush tail batch must not escape
    run(): every video whose rows were lost lands classified in the failure
    manifest (transient — --retry_failed reprocesses it) and videos already
    complete stay succeeded."""

    class FlushPoison(ToyPacked):
        def pack_spec(self):
            spec = super().pack_spec()
            inner_step = spec.step
            calls = []

            def step(batch):
                calls.append(1)
                # 19 frames over batch 4: calls 1-4 stream, call 5 = flush
                if len(calls) == 5:
                    raise DecodeError("injected device failure at flush")
                return inner_step(batch)

            spec.step = step
            return spec

    ex = FlushPoison(_cfg(tmp_path, "fl", pack_corpus=True, retries=0))
    ok = ex.run(corpus)  # must return, not raise
    failures = load_failures(ex.output_dir)
    # the flush batch held vid2's last clip and all of vid3
    assert set(failures) == {os.path.abspath(corpus[2]),
                             os.path.abspath(corpus[3])}
    for rec in failures.values():
        assert rec["error_class"] == "DeviceError"
        assert "injected device failure at flush" in rec["message"]
    assert ok == 2
    done = load_done_set(ex.output_dir)
    assert done == {os.path.abspath(corpus[0]), os.path.abspath(corpus[1])}


def test_decode_pool_packed_matches_inline(tmp_path, corpus):
    ex = ToyPacked(_cfg(tmp_path, "w", pack_corpus=True, decode_workers=2))
    assert ex.run(corpus) == len(corpus)
    ex_u = ToyPacked(_cfg(tmp_path, "wu"))
    assert ex_u.run(corpus) == len(corpus)
    _assert_bytes_equal(_outputs(tmp_path, "w"), _outputs(tmp_path, "wu"))


def test_unsupported_model_falls_back_with_notice(tmp_path, corpus, capsys):
    class NoPack(ToyPacked):
        def pack_spec(self):
            return None

    ex = NoPack(_cfg(tmp_path, "nb", pack_corpus=True))
    assert ex.run(corpus[:2]) == 2
    assert "--pack_corpus ignored" in capsys.readouterr().out
    # the per-video loop ran: no packing counters, but its stage seconds
    # (the accumulators are on in both loops)
    assert "dispatched_slots" not in ex._pack_stats
    assert "decode" in ex._pack_stats["stage_seconds"]
    assert len(load_done_set(ex.output_dir)) == 2


# ---- engine unit tests (no extractor, host-only spec) ----------------------


def _host_spec(batch_size=3):
    return PackSpec(
        batch_size=batch_size,
        empty_row_shape=(1,),
        open_clips=None,  # engine tests drive begin/add/finish directly
        step=lambda batch: batch.sum(axis=tuple(range(1, batch.ndim)),
                                     keepdims=False)[:, None].astype(np.float32),
        finalize=None,
    )


def test_engine_packs_across_videos_and_pads_only_at_flush():
    packer = CorpusPacker(_host_spec(3), wait=np.asarray)
    clip = lambda v: np.full((2, 2), v, np.float32)  # noqa: E731
    packer.begin("a", {})
    for v in (1, 2):  # a: 2 clips — queue not full
        packer.add("a", clip(v))
    packer.finish("a")
    assert packer.pop_completed() == []  # tail of `a` waits for `b`
    packer.begin("b", {})
    packer.add("b", clip(10))  # fills the batch: [a0, a1, b0] dispatches
    packer.add("b", clip(20))
    packer.finish("b")
    packer.flush()  # partial [b1] zero-padded
    done = {a.video: a for a in packer.pop_completed()}
    assert set(done) == {"a", "b"}
    np.testing.assert_array_equal(done["a"].stacked((1,)), [[4.0], [8.0]])
    np.testing.assert_array_equal(done["b"].stacked((1,)), [[40.0], [80.0]])
    assert packer.real_slots == 4 and packer.dispatched_slots == 6


def test_engine_shape_keyed_queues_never_mix_geometries():
    seen = []

    def step(batch):
        seen.append(batch.shape)
        return batch.reshape(batch.shape[0], -1)[:, :1]

    spec = PackSpec(batch_size=2, empty_row_shape=(1,), open_clips=None,
                    step=step, finalize=None)
    packer = CorpusPacker(spec, wait=np.asarray)
    packer.begin("a", {})
    packer.add("a", np.ones((2, 2), np.float32))
    packer.add("a", np.ones((3, 3), np.float32))  # different geometry
    packer.add("a", np.ones((2, 2), np.float32))  # completes the (2,2) batch
    packer.finish("a")
    packer.flush()
    (done,) = packer.pop_completed()
    assert done.complete
    assert sorted(seen) == [(2, 2, 2), (2, 3, 3)]


def test_engine_discard_unlinks_pending_and_orphans_inflight_rows():
    packer = CorpusPacker(_host_spec(2), wait=np.asarray)
    packer.begin("a", {})
    packer.add("a", np.ones((2,), np.float32))
    packer.begin("b", {})
    packer.add("b", np.ones((2,), np.float32))  # dispatches [a0, b0]
    packer.add("b", np.full((2,), 2, np.float32))
    packer.discard("a")  # a's dispatched row must not resurface
    # retry of `a` under a fresh assembly
    packer.begin("a", {})
    packer.add("a", np.full((2,), 5, np.float32))
    packer.finish("a")
    packer.finish("b")
    packer.flush()
    done = {a.video: a for a in packer.pop_completed()}
    assert set(done) == {"a", "b"}
    np.testing.assert_array_equal(done["a"].stacked((1,)), [[10.0]])
    np.testing.assert_array_equal(done["b"].stacked((1,)), [[2.0], [4.0]])
    assert packer.drain_incomplete() == []


def test_engine_zero_clip_video_completes_empty():
    packer = CorpusPacker(_host_spec(2), wait=np.asarray)
    packer.begin("empty", {})
    packer.finish("empty")
    (done,) = packer.pop_completed()
    assert done.complete and done.expected == 0
    rows = done.stacked((7,))
    assert rows.shape == (0, 7) and rows.dtype == np.float32


def test_feature_assembly_out_of_order_rows_stack_in_order():
    asm = FeatureAssembly("v", {})
    idx = [asm.reserve() for _ in range(3)]
    assert idx == [0, 1, 2]
    asm.put(2, np.array([2.0]))
    asm.put(0, np.array([0.0]))
    assert not asm.complete
    asm.finish()
    assert not asm.complete  # row 1 still missing
    asm.put(1, np.array([1.0]))
    assert asm.complete
    np.testing.assert_array_equal(asm.stacked((1,)), [[0.0], [1.0], [2.0]])

"""Uint8 ingest fast path: wire-dtype invariants, staging-ring discipline,
transfer accounting, and the --device_resize numerics gate.

The tentpole contract (docs/performance.md "ingest fast path"): decoded
frames ride host→device as uint8 end-to-end — the u8→fp32 scale is the
jitted step's first fused op, an EXACT cast, so outputs are byte-identical
to the retired float32 host staging at a quarter of the staged bytes — and
device batches are assembled into reusable staging-ring buffers that are
never rewritten while their ``device_put`` is pending.

Compile budget: everything here runs on stubbed steps or pure host code
except the one model-level byte-parity pin (a single tiny PWC geometry,
whose u8/f32 twin programs share almost all of their XLA work).
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from video_features_tpu.config import ExtractionConfig
from video_features_tpu.parallel.pipeline import HostStagingRing
from video_features_tpu.utils.metrics import StageClock


@pytest.fixture(autouse=True)
def _random_weights(monkeypatch):
    monkeypatch.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")


def _cfg(tmp_path, feature_type, **kw):
    return ExtractionConfig(
        feature_type=feature_type, num_devices=1,
        output_path=str(tmp_path / "out"), tmp_path=str(tmp_path / "tmp"),
        **kw)


def _write_video(path, n_frames, size=(24, 16)):
    import cv2

    wr = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*"mp4v"),
                         10.0, size)
    rng = np.random.default_rng(7)
    for _ in range(n_frames):
        wr.write(rng.integers(0, 256, (size[1], size[0], 3), dtype=np.uint8))
    wr.release()
    return str(path)


class _FakeDev:
    """A committable 'device value': records whether the ring awaited it."""

    def __init__(self):
        self.blocked = False

    def block_until_ready(self):
        self.blocked = True


# ---- host padding into staging rows -----------------------------------------


def test_pad_to_shape_into_matches_pad_to_shape_uint8_round_trip():
    """The in-place staging pad is byte-identical to pad_to_shape (uint8
    stays uint8 on the wire) and unpad recovers the original frame."""
    from video_features_tpu.models.raft import (
        pad_to_shape, pad_to_shape_into, unpad)

    rng = np.random.default_rng(0)
    frame = rng.integers(0, 256, (13, 17, 3), dtype=np.uint8)
    for target in ((16, 24), (13, 17), (14, 17), (13, 20)):
        ref, ref_pads = pad_to_shape(frame, target)
        out = np.full(target + (3,), 99, np.uint8)  # poisoned: full overwrite
        pads = pad_to_shape_into(frame, out)
        assert pads == ref_pads
        np.testing.assert_array_equal(out, ref)
        assert out.dtype == np.uint8
        np.testing.assert_array_equal(unpad(out, pads), frame)
    with pytest.raises(ValueError, match="cannot pad"):
        pad_to_shape_into(frame, np.empty((8, 8, 3), np.uint8))


def test_pad_batch_preserves_uint8_zero_pad():
    from video_features_tpu.parallel.pipeline import pad_batch

    arr = np.full((2, 4, 4, 3), 200, np.uint8)
    padded = pad_batch(arr, 5)
    assert padded.dtype == np.uint8 and padded.shape[0] == 5
    np.testing.assert_array_equal(padded[:2], arr)
    assert not padded[2:].any()


# ---- staging ring -----------------------------------------------------------


def test_staging_ring_reuses_buffers_and_guards_inflight_transfers():
    """The bounded-ring discipline: ≤ depth buffers per geometry, recycled
    least-recently-acquired first, and a buffer is handed out again only
    AFTER its committed transfer reported ready (the in-flight guard)."""
    waits = []
    ring = HostStagingRing(depth=2, on_wait=waits.append)
    b1 = ring.acquire((2, 3), np.uint8)
    d1 = _FakeDev()
    ring.commit(b1, d1)
    b2 = ring.acquire((2, 3), np.uint8)
    d2 = _FakeDev()
    ring.commit(b2, (d2,))  # pytree device values supported (sharded puts)
    assert b2 is not b1 and ring.allocated == 2
    # wrap-around: the oldest buffer comes back, but only after its transfer
    # was awaited — d1 must be blocked on, d2 (still newest) must not
    b3 = ring.acquire((2, 3), np.uint8)
    assert b3 is b1
    assert d1.blocked and not d2.blocked
    assert len(waits) == 1 and ring.wait_seconds >= 0.0
    # distinct geometries/dtypes keep distinct rings
    other = ring.acquire((2, 3), np.float32)
    assert other is not b1 and other.dtype == np.float32
    assert ring.allocated == 3


def test_staging_ring_bounds_geometries_with_lru_eviction():
    """Long-run memory bound: past max_geometries distinct staged shapes,
    the least-recently-acquired geometry's ring is dropped — its pending
    transfer awaited first — so a daemon staging an open-ended geometry mix
    holds at most cap × depth buffers (the ring analogue of packer.forget)."""
    ring = HostStagingRing(depth=2, max_geometries=2)
    b1 = ring.acquire((2, 2), np.uint8)
    d1 = _FakeDev()
    ring.commit(b1, d1)
    ring.acquire((3, 3), np.uint8)
    ring.acquire((4, 4), np.uint8)  # over the cap: evicts the (2,2) ring
    assert ring.evicted_geometries == 1
    assert d1.blocked  # the evicted geometry's in-flight transfer was awaited
    assert set(k[0] for k in ring._rings) == {(3, 3), (4, 4)}
    # the evicted geometry still works — it just re-allocates
    b1b = ring.acquire((2, 2), np.uint8)
    assert b1b is not b1 and ring.evicted_geometries == 2


def test_staging_ring_commit_tolerates_foreign_buffers():
    """commit() is a no-op for batches the ring does not own (pad_batch
    tails, frame-sharded view tuples) — callers need not track which
    dispatched batches were ring-staged."""
    ring = HostStagingRing(depth=2)
    ring.commit(np.zeros((4, 4), np.uint8), _FakeDev())  # unknown geometry
    buf = ring.acquire((4, 4), np.uint8)
    ring.commit(np.zeros((4, 4), np.uint8), _FakeDev())  # same geometry, foreign
    ring.commit((np.zeros(3),), _FakeDev())  # non-array (view tuple)
    # the owned buffer is still free (no stray device value attached)
    d = _FakeDev()
    ring.commit(buf, d)
    ring.acquire((4, 4), np.uint8)
    b3 = ring.acquire((4, 4), np.uint8)
    assert b3 is buf and d.blocked


# ---- flow wire format + transfer accounting ---------------------------------


def _stubbed_flow(tmp_path, sub, **cfg_kw):
    """ExtractFlow whose jitted step is replaced by a host stub recording
    every dispatched window's dtype/shape — zero XLA compiles, so the wire
    and byte-accounting invariants stay fast-tier."""
    from video_features_tpu.extractors.flow import ExtractFlow

    cfg = ExtractionConfig(
        feature_type="raft", batch_size=2, num_devices=1,
        output_path=str(tmp_path / sub / "out"),
        tmp_path=str(tmp_path / sub / "tmp"), **cfg_kw)
    ex = ExtractFlow(cfg)
    seen = {"dtypes": [], "shapes": [], "bufs": []}

    def fake_step(params, dev):
        seen["dtypes"].append(str(dev.dtype))
        seen["shapes"].append(tuple(dev.shape))
        return jnp.zeros((dev.shape[0] - 1,) + tuple(dev.shape[1:3]) + (2,),
                         jnp.float32)

    ex.__dict__["_frames_step"] = fake_step  # cached_property override
    return ex, seen


def test_flow_windows_ride_uint8_and_staged_bytes_drop_4x(tmp_path):
    """The byte-accounting acceptance pin: per-video flow windows dispatch
    as uint8 (quarter the host→device bytes of the --float32_wire escape
    hatch, exactly), the 'transfer' stage records the staged payload, and
    the staging ring reuses its buffers instead of allocating per batch."""
    video = _write_video(tmp_path / "v.mp4", 7)

    ex, seen = _stubbed_flow(tmp_path, "u8")
    ex.clock = StageClock()
    ex.extract(video)
    assert set(seen["dtypes"]) == {"uint8"}
    # 6 frames decoded at (16, 24) → windows of batch_size+1 = 3 frames
    frame_bytes = 16 * 24 * 3
    u8_bytes = ex.clock.bytes["transfer"]
    assert u8_bytes == sum(int(np.prod(s)) for s in seen["shapes"])
    assert u8_bytes > 0 and u8_bytes % frame_bytes == 0
    assert ex.clock.counts["transfer"] == len(seen["shapes"])
    # ring reuse: one buffer per in-flight window, NOT one per batch
    assert ex._staging.allocated <= ex.cfg.prefetch_depth + 2
    assert ex._staging.acquires == len(seen["shapes"])

    ex32, seen32 = _stubbed_flow(tmp_path, "f32", float32_wire=True)
    ex32.clock = StageClock()
    ex32.extract(video)
    assert set(seen32["dtypes"]) == {"float32"}
    assert ex32.clock.bytes["transfer"] == 4 * u8_bytes


def test_packed_collate_stages_uint8_windows(tmp_path):
    """Packed-collate dtype invariant: the shared-frame window the flow
    collate assembles is a ring-staged uint8 buffer (float32 only under the
    --float32_wire escape hatch), with the chain/row-map semantics of the
    retired np.stack path."""
    from video_features_tpu.extractors.flow import ExtractFlow

    ex = ExtractFlow(_cfg(tmp_path, "raft", batch_size=4, pack_corpus=True))
    spec = ex.pack_spec()
    rng = np.random.default_rng(3)
    frames = rng.integers(0, 256, (4, 16, 24, 3), dtype=np.uint8)
    clips = [np.stack([frames[0], frames[1]]),   # stream 1, idx 0
             np.stack([frames[1], frames[2]]),   # stream 1, idx 1 (chained)
             np.stack([frames[2], frames[3]])]   # stream 2 (chain break)
    keys = [(1, 0), (1, 1), (2, 5)]
    batch, n_used, row_of = spec.collate(clips, keys)
    assert batch.dtype == np.uint8
    assert batch.shape == (5, 16, 24, 3)  # capacity = batch_size + 1
    assert n_used == 3 and list(row_of) == [0, 1, 3]
    # chained pair shares the middle frame; the break re-stages its source
    np.testing.assert_array_equal(batch[0], frames[0])
    np.testing.assert_array_equal(batch[1], frames[1])
    np.testing.assert_array_equal(batch[2], frames[2])
    np.testing.assert_array_equal(batch[3], frames[2])
    np.testing.assert_array_equal(batch[4], frames[3])
    assert ex._staging.allocated == 1  # ring-staged, not np.stack'd

    ex32 = ExtractFlow(_cfg(tmp_path / "f32", "raft", batch_size=4,
                            pack_corpus=True, float32_wire=True))
    batch32, _, _ = ex32.pack_spec().collate(clips, keys)
    assert batch32.dtype == np.float32  # escape hatch: exact upcast staging
    np.testing.assert_array_equal(batch32, batch.astype(np.float32))


def test_packer_default_path_stages_uint8_and_accounts_bytes():
    """The no-collate packer path: clip slots stack into a ring buffer at
    their own (uint8) dtype, zero-padded tails included, and staged_bytes
    counts every dispatched batch's host payload."""
    from video_features_tpu.parallel.packer import CorpusPacker, PackSpec

    staged = []

    def step(batch):
        staged.append(batch)
        return np.asarray(batch, np.float32).reshape(batch.shape[0], -1)

    ring = HostStagingRing(depth=2)
    spec = PackSpec(batch_size=2, empty_row_shape=(12,), open_clips=None,
                    step=step, finalize=None)
    packer = CorpusPacker(spec, wait=np.asarray, staging=ring)
    packer.begin("a", {})
    for v in (10, 20, 30):
        packer.add("a", np.full((2, 2, 3), v, np.uint8))
    packer.finish("a")
    packer.flush()
    assert [b.dtype for b in staged] == [np.uint8, np.uint8]
    assert not staged[1][1].any()  # zero-padded tail slot, uint8 zeros
    assert ring.allocated <= 2  # ring-staged, committed against step output
    assert packer.staged_bytes == sum(b.nbytes for b in staged)
    (done,) = packer.pop_completed()
    np.testing.assert_array_equal(
        done.stacked((12,))[:, 0], [10.0, 20.0, 30.0])


# ---- transfer-dtype upcast hoist --------------------------------------------


def test_transfer_dtype_upcast_decision_hoisted_and_output_fp32(tmp_path):
    """The reap-path upcast is decided once from the config (not re-inspected
    per batch), and fetched float16/bfloat16 flow upcasts to float32 — the
    fast-tier output-dtype assertion for the sub-fp32 transfer dtypes."""
    from video_features_tpu.extractors.flow import ExtractFlow

    for td, dev_dtype, expects_upcast in (
            ("float32", jnp.float32, False),
            ("float16", jnp.float16, True),
            ("bfloat16", jnp.bfloat16, True)):
        ex = ExtractFlow(_cfg(tmp_path / td, "raft", batch_size=2,
                              transfer_dtype=td))
        assert ex._upcast is expects_upcast
        # fake dispatched handle: (device flow, n_pairs, pads) — no compile
        handle = (jnp.zeros((3, 16, 24, 2), dev_dtype), 2, (0, 0, 0, 0))
        flow = ex._collect_pairs(handle)
        assert flow.dtype == np.float32
        assert flow.shape == (2, 2, 16, 24)
        # packed finalize shares the hoisted decision
        spec_final = ex.pack_spec().finalize
        rows = np.zeros((2, 16, 24, 2),
                        np.float16 if expects_upcast else np.float32)
        out = spec_final("v", rows, {"fps": 10.0, "timestamps_ms": [0, 1],
                                     "pads": (0, 0, 0, 0),
                                     "native_hw": (16, 24)})
        assert out["raft"].dtype == np.float32


# ---- model-level byte parity (the acceptance pin) ---------------------------


def test_uint8_wire_is_byte_identical_to_float32_wire_pwc():
    """uint8 frames through the real net == the same frames pre-cast to
    float32 on the host, bit for bit: the u8→fp32 scale inside the step is
    an exact cast, so the wire format cannot move output bytes. One tiny
    PWC geometry (the cheapest whole flow net) pins it at model level;
    tests/test_packer_models.py pins the loop-level parity end to end."""
    from video_features_tpu.models.pwc import pwc_forward_frames, pwc_init_params

    params = pwc_init_params(0)
    frames = np.random.default_rng(1).integers(
        0, 256, (3, 16, 16, 3), dtype=np.uint8)
    out_u8 = np.asarray(pwc_forward_frames(params, jnp.asarray(frames)))
    out_f32 = np.asarray(pwc_forward_frames(
        params, jnp.asarray(frames.astype(np.float32))))
    np.testing.assert_array_equal(out_u8, out_f32)


# ---- --device_resize --------------------------------------------------------


def test_device_resize_parity_within_documented_tolerance():
    """jax.image.resize edge-resize+crop vs the PIL host path: NOT bit
    identical (PIL interpolates in uint8 with its own rounding), but within
    the documented tolerance — ≤ 2 uint8 levels max, ≤ 1 mean — for both
    down- and up-scaling geometries (docs/performance.md numerics note)."""
    from video_features_tpu.ops.image import (
        device_resize_crop_hwc, np_center_crop_hwc, pil_edge_resize)

    rng = np.random.default_rng(5)
    for geom in ((37, 53), (20, 28)):  # downscale and upscale to edge 32
        frames = rng.integers(0, 256, (3,) + geom + (3,), dtype=np.uint8)
        host = np.stack([
            np_center_crop_hwc(pil_edge_resize(f, 32), 24, 24)
            for f in frames]).astype(np.float32)
        dev = np.asarray(device_resize_crop_hwc(jnp.asarray(frames), 32, 24))
        assert dev.shape == host.shape and dev.dtype == np.float32
        diff = np.abs(host - dev)
        assert diff.max() <= 2.0, f"{geom}: max drift {diff.max()}"
        assert diff.mean() <= 1.0, f"{geom}: mean drift {diff.mean()}"


def test_device_resize_routing_and_fallback_notice(tmp_path, capsys):
    """--device_resize ships RAW frames from the host on resnet50 (the step
    owns resize+crop) and prints an ignored-flag notice on feature types
    without a device-resize path."""
    from video_features_tpu.extractors.flow import ExtractFlow
    from video_features_tpu.extractors.resnet import ExtractResNet50

    ex = ExtractResNet50(_cfg(tmp_path, "resnet50", device_resize=True))
    raw = np.random.default_rng(0).integers(
        0, 256, (30, 40, 3), dtype=np.uint8)
    assert ex._host_transform(raw) is raw  # raw decoded frame on the wire
    host_ex = ExtractResNet50(_cfg(tmp_path / "h", "resnet50"))
    assert host_ex._host_transform(raw).shape == (224, 224, 3)
    capsys.readouterr()
    ExtractFlow(_cfg(tmp_path / "f", "raft", batch_size=2,
                     device_resize=True))
    assert "--device_resize ignored" in capsys.readouterr().out


# ---- starvation signal ------------------------------------------------------


def test_starvation_warning_distinguishes_transfer_bound():
    """The PR 5 starvation signal now tells decode-bound from
    transfer-bound: low occupancy + decode-dominated wall keeps the
    --decode_workers nudge; low occupancy + transfer-dominated wall names
    the transfer pipe instead; healthy runs stay silent."""
    from video_features_tpu.utils.metrics import decode_starvation_warning

    decode = decode_starvation_warning(
        occupancy=0.5, decode_seconds=6.0, wall=10.0)
    assert decode is not None and "--decode_workers" in decode
    transfer = decode_starvation_warning(
        occupancy=0.5, decode_seconds=1.0, wall=10.0, transfer_seconds=6.0)
    assert transfer is not None and "transfer" in transfer
    assert "--decode_workers" not in transfer
    assert decode_starvation_warning(
        occupancy=0.95, decode_seconds=6.0, wall=10.0,
        transfer_seconds=6.0) is None
    assert decode_starvation_warning(
        occupancy=0.5, decode_seconds=1.0, wall=10.0,
        transfer_seconds=1.0) is None

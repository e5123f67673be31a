"""RAFT / PWC dense-flow extractors: one shared frame-pair pipeline.

Behavioral spec (``/root/reference/models/raft/extract_raft.py``,
``.../pwc/extract_pwc.py`` — the loops are copies of each other):
- decode → optional ``--side_size`` PIL edge resize (``extract_raft.py:32-41``);
- accumulate ``batch_size + 1`` frames, flow for consecutive pairs
  ``batch[:-1] → batch[1:]``, carry the last frame into the next batch, run a final
  partial batch of ≥ 2 frames (``:139-151``);
- RAFT pads frames to /8 (replicate, sintel) and unpads the flow (``:94-101``);
  PWC-Net handles arbitrary sizes internally (/64 resize in-model);
- outputs ``(T-1, 2, H, W)`` float32 flow + fps + per-frame timestamps;
- ``--show_pred`` displays frame + color-wheel flow (``:165-178``).

TPU design: pairs are batched into one jitted call with a static pair count (the
tail batch is padded by repeating its last pair, then trimmed), so each video
geometry compiles exactly once; host decode overlaps device compute through the
prefetcher. Frames ride the wire as decoded **uint8** (per-video windows, the
packed collate chains, and the ``--show_pred`` fallback alike): the u8→fp32
scale is the jitted step's first fused op — an exact cast, so outputs are
byte-identical to the retired float32 host staging at a quarter of the
host→device bytes (``--float32_wire`` restores it as an A/B escape hatch) —
and windows are assembled into reusable staging-ring buffers
(:class:`..parallel.pipeline.HostStagingRing`) instead of fresh per-batch
``np.stack`` allocations. Dense flow is the framework's only D2H-heavy output
(full-res fp32 maps, not embeddings — ``extract_raft.py:99-101``); the e2e
pipeline double-buffers the fetch (``copy_to_host_async`` + a bounded pending
queue, so transfer overlaps both compute and decode) and
``--transfer_dtype float16`` halves the bytes on the wire (cast on device,
upcast on host; outputs stay fp32 ``.npy``).

``--device_preproc`` moves the last host-side preprocess — the /8 (RAFT) or
``--shape_bucket`` replicate pad — inside the jitted step
(``models/raft.device_pad_to_shape``): windows stage and ride the wire at RAW
decoded geometry and the pad runs on the uint8 wire as the step's first fused
op. Replication on integers is arithmetic-free, so outputs stay BYTE-identical
to the host pad (pinned in tests/test_device_preproc.py) — the flag is
execution-only for flow in cache/key.py. Each pad target memoizes its own
jitted step (``_frames_step_for``) so a raw geometry can never reuse a program
traced for a different bucket.
"""

from __future__ import annotations

import functools
import os
import threading
from collections import deque
from typing import Dict, List

import numpy as np

from ..models.raft import (
    device_pad_to_shape,
    pad_split,
    pad_to_multiple,
    pad_to_shape,
    pad_to_shape_into,
    raft_forward,
    raft_forward_frames,
    raft_forward_frames_sharded,
    raft_init_params,
    unpad,
)
from ..ops.image import edge_resize_size, pil_edge_resize
from ..weights.convert_torch import convert_raft
from .base import Extractor


class ExtractFlow(Extractor):
    """feature_type 'raft' or 'pwc'; emits dense flow frames, not embeddings."""

    uses_frame_stream = True
    # --device_preproc: the geometry pad moves inside the jitted step (raw
    # decoded frames on the wire; device_pad_to_shape is byte-exact vs the
    # host pad). The optional --side_size edge resize stays host PIL — it is
    # a parity-bearing reference transform, not padding.
    supports_device_preproc = True

    def __init__(self, cfg):
        super().__init__(cfg)
        import jax.numpy as jnp

        # pairs per device step, rounded to a multiple of the mesh size so the
        # sharded pair axis divides evenly (tail pairs repeat the last frame)
        self.batch_size = self.runner.device_batch(cfg.batch_size)
        self._viz_counter = 0  # --show_pred PNG fallback numbering
        self._async_copy_ok = True  # cleared on first missing-API probe
        # --precompile: geometries already warmed (or warming) in background
        # (vftlint GUARDED_BY: _precompiled under the 'precompile' lock —
        # the run loop and prior warmup threads race on membership)
        self._precompiled: set = set()
        self._precompile_lock = threading.Lock()
        # --pack_corpus: corpus bucket plan (PackSpec.prepare fills it from
        # the container probes before the packed loop starts)
        self._pack_buckets = None
        # --device_preproc: pad-on-device steps, one memoized jitted step per
        # (sharded?, pad target) — jit caches per INPUT shape, so a single
        # step closing over a mutable target could silently reuse a program
        # traced for a different bucket on a repeat raw geometry
        # (vftlint GUARDED_BY: _frames_steps under the 'flow-steps' lock —
        # precompile warmup threads race the run loop on first-build)
        self._device_preproc = cfg.device_preproc
        self._frames_steps: dict = {}
        self._frames_steps_lock = threading.Lock()
        flow_dtype = jnp.bfloat16 if cfg.flow_dtype == "bfloat16" else jnp.float32
        # D2H transfer dtype: the jitted steps cast their output to this on
        # device; the host upcasts back to fp32. float16 halves the fetched
        # bytes at ≤0.01 px quantization for |flow| ≤ 32 (10 mantissa bits);
        # bfloat16 quarters precision (≤0.16 px at |flow|≈20). float32 is the
        # bit-parity default.
        self._transfer_dtype = {"float32": jnp.float32, "float16": jnp.float16,
                                "bfloat16": jnp.bfloat16}[cfg.transfer_dtype]
        # hoisted out of the reap path: the fetched flow needs a host upcast
        # exactly when a sub-fp32 transfer dtype is configured — decided once
        # here, not re-inspected per batch (fast-tier output-dtype assertion
        # in tests/test_ingest.py covers float16/bfloat16)
        self._upcast = cfg.transfer_dtype != "float32"
        # H2D wire dtype: decoded uint8 end-to-end (the jitted steps' first
        # op is the exact u8→fp32 cast); --float32_wire restores the retired
        # host-side cast at 4× the staged bytes (an escape hatch)
        self._wire = np.float32 if cfg.float32_wire else np.uint8
        if self.feature_type == "raft":
            self.params = self._load_params(
                "raft-sintel", convert_torch_fn=convert_raft,
                init_fn=lambda: raft_init_params(seed=0))
            self._forward = functools.partial(
                raft_forward, corr_impl=cfg.raft_corr, dtype=flow_dtype,
                n_devices=self.runner.num_devices)
            self._forward_frames = functools.partial(
                raft_forward_frames, corr_impl=cfg.raft_corr, dtype=flow_dtype,
                n_devices=self.runner.num_devices)
            self._forward_frames_sharded = functools.partial(
                raft_forward_frames_sharded, mesh=self.runner.mesh,
                corr_impl=cfg.raft_corr, dtype=flow_dtype)
            self._pads_input = True
        elif self.feature_type == "pwc":
            from ..models.pwc import (
                pwc_forward,
                pwc_forward_frames,
                pwc_forward_frames_sharded,
                pwc_init_params,
            )
            from ..weights.convert_torch import convert_pwc

            self.params = self._load_params(
                "pwc-sintel", convert_torch_fn=convert_pwc,
                init_fn=lambda: pwc_init_params(seed=0))
            self._forward = functools.partial(
                pwc_forward, corr_impl=cfg.pwc_corr, dtype=flow_dtype)
            self._forward_frames = functools.partial(
                pwc_forward_frames, corr_impl=cfg.pwc_corr, dtype=flow_dtype)
            self._forward_frames_sharded = functools.partial(
                pwc_forward_frames_sharded, mesh=self.runner.mesh,
                corr_impl=cfg.pwc_corr, dtype=flow_dtype)
            self._pads_input = False
        else:
            raise ValueError(f"not a flow feature type: {self.feature_type}")

    @functools.cached_property
    def _step(self):
        fwd = self._forward
        tdt = self._transfer_dtype

        # pair-split step: (prev, nxt) of equal leading size B shard cleanly
        # along the mesh's data axis at the cost of encoding every interior
        # frame twice. No longer the production multi-device path (the
        # encode-once _frames_step_sharded replaced it) — retained as the
        # parity reference the sharded paths are tested against and for the
        # dryrun harness that compares both.
        def step(params, prev, nxt):  # each (B, H, W, 3) float32
            return fwd(params, prev, nxt).astype(tdt)

        return self.runner.jit(step, n_batch_args=2)

    @functools.cached_property
    def _frames_step(self):
        fwd = self._forward_frames
        tdt = self._transfer_dtype

        # single-device meshes skip the pair split: (B+1) frames in, each frame
        # encoded once (the pair-split step encodes interior frames twice —
        # the encoder/pyramid is the flow nets' dominant stage)
        def step(params, frames):  # (B+1, H, W, 3) float32
            return fwd(params, frames).astype(tdt)

        return self.runner.jit(step)

    @functools.cached_property
    def _frames_step_sharded(self):
        fwd = self._forward_frames_sharded
        tdt = self._transfer_dtype

        # multi-device encode-once step: the (B+1)-frame window arrives as its
        # B source frames sharded on the frame axis plus the replicated final
        # frame; each shard's one cross-shard pair is formed on device by halo
        # exchange of the neighbor's boundary feature map
        # (models/{raft,pwc}.*_forward_frames_sharded), so every frame's
        # encoder/pyramid runs exactly once — the pair-split step this
        # replaces encoded every interior frame twice
        def step(params, frames, frame_last):
            # (B, H, W, 3) sharded + (1, H, W, 3) replicated, float32
            return fwd(params, frames, frame_last).astype(tdt)

        return self.runner.jit(step, n_batch_args=1, n_replicated_args=1)

    def _frames_step_for(self, target_hw, sharded: bool):
        """--device_preproc step for one pad target: raw-geometry frames in,
        ``device_pad_to_shape`` to ``target_hw`` as the first fused op (on the
        wire dtype — replicate-pad on uint8 is byte-exact), then the same
        encode-once forward as :attr:`_frames_step` /
        :attr:`_frames_step_sharded`.

        One memoized jitted step PER (sharded?, target): jit caches programs
        by input shape, so a single step closing over a mutable target would
        silently reuse the program traced for a different bucket whenever the
        same raw geometry reappears under a new bucket plan.
        """
        key = (bool(sharded), int(target_hw[0]), int(target_hw[1]))
        with self._frames_steps_lock:
            step = self._frames_steps.get(key)
            if step is None:
                tdt = self._transfer_dtype
                th, tw = key[1], key[2]
                if sharded:
                    fwd = self._forward_frames_sharded

                    def step(params, frames, frame_last):
                        # pad is per-frame (trailing H/W axes), so it shards
                        # trivially along the frame axis
                        return fwd(params,
                                   device_pad_to_shape(frames, (th, tw)),
                                   device_pad_to_shape(frame_last, (th, tw))
                                   ).astype(tdt)

                    step = self.runner.jit(step, n_batch_args=1,
                                           n_replicated_args=1)
                else:
                    fwd = self._forward_frames

                    def step(params, frames):
                        return fwd(params, device_pad_to_shape(
                            frames, (th, tw))).astype(tdt)

                    step = self.runner.jit(step)
                self._frames_steps[key] = step
        return step

    def _host_transform(self, rgb: np.ndarray) -> np.ndarray:
        return pil_edge_resize(rgb, self.cfg.side_size, self.cfg.resize_to_smaller_edge)

    def _device_call(self, frames: np.ndarray, staged: np.ndarray = None,
                     timed: bool = True, pad_target=None):
        """Dispatch one (batch_size+1)-frame window to the jitted step —
        PADDED frames by default; RAW-geometry frames with ``pad_target``
        set (--device_preproc), where the per-target step pads on device.

        Single-device meshes run the shared-frame step whole; multi-device
        meshes shard the B source frames on the frame axis and replicate the
        final frame (encode-once everywhere — no mesh size re-encodes
        interior frames). The --precompile warmup calls this with a zeros
        window of the WIRE dtype so the warmed program is EXACTLY the one
        real dispatch uses.

        ``staged``: the staging-ring buffer backing ``frames``, committed
        against the put results so it is never rewritten while the transfer
        is pending. ``timed=False`` skips the 'transfer' stage attribution —
        the precompile warmup thread must not race the run loop's StageClock.
        """
        put = self._put if timed else self.runner.put
        put_rep = self._put_replicated if timed else self.runner.put_replicated
        if self.runner.num_devices == 1:
            dev = put(np.ascontiguousarray(frames))
            if staged is not None:
                self._staging.commit(staged, dev)
            step = (self._frames_step if pad_target is None
                    else self._frames_step_for(pad_target, sharded=False))
            return step(self.params, dev)
        main = put(np.ascontiguousarray(frames[:-1]))
        last = put_rep(np.ascontiguousarray(frames[-1:]))
        if staged is not None:
            self._staging.commit(staged, (main, last))
        step = (self._frames_step_sharded if pad_target is None
                else self._frames_step_for(pad_target, sharded=True))
        return step(self.params, main, last)

    def _window_geometry(self, h: int, w: int):
        """Padded (TH, TW) a decoded ``h``×``w`` frame dispatches at — the
        shape_bucket (or RAFT /8) arithmetic of :meth:`_dispatch_pairs`,
        shared by the staging-ring window assembly."""
        m = self.cfg.shape_bucket or (8 if self._pads_input else 1)
        return -(-h // m) * m, -(-w // m) * m

    def _window_pad_target(self, h: int, w: int):
        """--device_preproc pad target for a RAW decoded ``h``×``w`` frame:
        the per-video padded geometry, widened to its corpus bucket when a
        packed run's bucket plan is live — the same (TH, TW) the host pad
        would have staged, now applied on device."""
        geom = self._window_geometry(h, w)
        if self._pack_buckets is not None:
            geom = self._pack_buckets.bucket_for(geom)
        return geom

    def _dispatch_window(self, window):
        """Stage one decoded frame window into a reusable staging-ring buffer
        and dispatch it; returns the async handle :meth:`_collect_pairs`
        materializes.

        The production dispatch path: tail repeat and the geometry pad are
        written IN PLACE into the ring buffer at the wire dtype (uint8 unless
        ``--float32_wire``) — no per-batch ``np.stack``/``np.pad``
        allocations. Byte-identical staging to
        ``_dispatch_pairs(np.stack(window))``: replicate-padding each frame
        then repeating the last padded frame equals repeating then padding.
        """
        n_pairs = len(window) - 1
        h, w = window[0].shape[:2]
        if self._device_preproc:
            # raw-pixels wire: the ring buffer keys by the DECODED geometry
            # (no host pad — plain frame copies) and the per-target jitted
            # step replicate-pads on device, byte-exact on the uint8 wire;
            # the host keeps only the pad arithmetic for the final unpad
            th, tw = self._window_pad_target(h, w)
            buf = self._staging.acquire((self.batch_size + 1, h, w, 3),
                                        self._wire)
            for i, frame in enumerate(window):
                buf[i] = frame
            for i in range(len(window), self.batch_size + 1):
                buf[i] = buf[len(window) - 1]  # static shape: repeat the tail
            pads = pad_split(h, w, th, tw)
            if not (self.cfg.shape_bucket or self._pads_input):
                pads = None  # PWC-at-native parity: no unpad slicing
            flow = self._device_call(buf, staged=buf, pad_target=(th, tw))
            self._start_async_copy(flow)
            return flow, n_pairs, pads
        th, tw = self._window_geometry(h, w)
        buf = self._staging.acquire((self.batch_size + 1, th, tw, 3),
                                    self._wire)
        pads = (0, 0, 0, 0)
        for i, frame in enumerate(window):
            pads = pad_to_shape_into(frame, buf[i])
        for i in range(len(window), self.batch_size + 1):
            buf[i] = buf[len(window) - 1]  # static shape: repeat the tail
        if not (self.cfg.shape_bucket or self._pads_input):
            pads = None  # PWC-at-native parity: no unpad slicing
        flow = self._device_call(buf, staged=buf)
        self._start_async_copy(flow)
        return flow, n_pairs, pads

    def _dispatch_pairs(self, frames: np.ndarray):
        """Dispatch one premade pair-window ARRAY to the device; returns an
        async handle. The compatibility seam for callers holding a stacked
        window (tests, the dryrun harness) — the production loops
        stage through :meth:`_dispatch_window` / the packed collate instead.

        The jitted call returns immediately (JAX async dispatch) and
        ``copy_to_host_async`` enqueues the D2H transfer right behind the
        compute, so the fetch rides the DMA engines while the host decodes
        the next window and the device computes the next batch.
        """
        n_pairs = frames.shape[0] - 1
        # static shape: pad the window to batch_size+1 frames by repeating the tail
        if n_pairs < self.batch_size:
            reps = np.repeat(frames[-1:], self.batch_size - n_pairs, axis=0)
            frames = np.concatenate([frames, reps], axis=0)
        # shape_bucket bounds compiled geometries across a mixed-resolution
        # corpus (one program per bucket); RAFT otherwise pads to the /8
        # contract only (reference behavior)
        pads = None
        if self.cfg.shape_bucket:
            frames, pads = pad_to_multiple(frames, self.cfg.shape_bucket)
        elif self._pads_input:
            frames, pads = pad_to_multiple(frames, 8)
        flow = self._device_call(frames)
        self._start_async_copy(flow)
        return flow, n_pairs, pads

    def _start_async_copy(self, flow) -> None:
        """Enqueue the D2H transfer right behind the compute so the fetch
        rides the DMA engines while the host decodes and the device computes
        the next batch — dense flow is the framework's only D2H-heavy output,
        and both the per-video and packed dispatch paths overlap it."""
        if not self._async_copy_ok:
            return
        try:
            flow.copy_to_host_async()
        except Exception as e:  # noqa: BLE001 — fault-barrier: optional-optimization probe (see below)
            # backend lacks async host copy (AttributeError /
            # NotImplementedError / backend-specific UNIMPLEMENTED
            # runtime errors) — probe once, disarm, and say WHICH error
            # disarmed it, so a genuine transfer fault is visible here
            # instead of resurfacing context-free at _wait (the old
            # blanket `pass` hid it; crashing extraction on an optional
            # optimization would be worse)
            self._async_copy_ok = False
            print(f"[flow] async D2H disabled after "
                  f"{type(e).__name__}: {e}; transfers will not "
                  f"overlap compute", flush=True)

    def _collect_pairs(self, handle) -> np.ndarray:
        """Materialize a dispatched window → (n_pairs, 2, H, W) fp32 host flow."""
        flow, n_pairs, pads = handle
        flow = self._wait(flow)
        if self._upcast:  # sub-fp32 transfer_dtype: upcast on host (the
            flow = flow.astype(np.float32)  # decision is hoisted to __init__)
        if pads is not None:
            flow = unpad(flow, pads)
        # NHWC → reference byte layout (B, 2, H, W)
        return flow[:n_pairs].transpose(0, 3, 1, 2)

    def _run_pairs(self, frames: np.ndarray) -> np.ndarray:
        """Flow for all consecutive pairs of (N, H, W, 3) frames (uint8 wire
        dtype or float) → (N-1, 2, H, W)."""
        return self._collect_pairs(self._dispatch_pairs(frames))

    # --- geometry precompile (--precompile) --------------------------------

    def _decoded_geometry(self, width: int, height: int):
        """(H, W) of a decoded frame after ``_host_transform`` — the RAW
        geometry ``--device_preproc`` windows stage and ship at — from the
        container probe's native ``width``×``height``."""
        if self.cfg.side_size is not None:
            w, h = edge_resize_size(width, height, self.cfg.side_size,
                                    self.cfg.resize_to_smaller_edge)
        else:
            w, h = width, height
        return h, w

    def _padded_geometry(self, width: int, height: int):
        """(H, W) of the padded device window a native ``width``×``height``
        video will dispatch: the host edge-resize sizing followed by the
        shape_bucket (or RAFT /8) padding — the same arithmetic
        ``_host_transform`` + ``_dispatch_pairs`` apply per frame."""
        return self._window_geometry(*self._decoded_geometry(width, height))

    def _start_precompile(self, width: int, height: int) -> None:
        """Warm the jitted step for this video's geometry while decode runs.

        Mixed-resolution corpora otherwise pay each new geometry's compile
        serially at the first dispatch, with the mesh idle. The video's decoded geometry is known from the container
        probe before any frame decodes, so a daemon thread runs the step once
        on a zeros window of the padded geometry — jit's signature cache is
        shared across threads, so the real first window either finds the
        program compiled or blocks on the in-flight compile instead of
        starting its own. One wasted zeros execution per NEW geometry; repeat
        geometries return immediately.
        """
        self._start_precompile_padded(
            self._padded_geometry(width, height),
            raw_hw=(self._decoded_geometry(width, height)
                    if self._device_preproc else None))

    def _start_precompile_padded(self, padded_hw, raw_hw=None) -> None:
        """Warm the device program for an already-padded (H, W) geometry —
        the packed loop warms each video's *bucket* geometry (the program the
        packed windows actually dispatch) rather than its own padding.

        ``raw_hw`` (--device_preproc): the decoded geometry real windows
        stage at; the warmed program is then the per-pad-target step over
        raw-geometry input — warming the padded-input program would warm one
        no dispatch ever runs."""
        h, w = padded_hw
        key = (h, w) if raw_hw is None else (h, w) + tuple(raw_hw)
        with self._precompile_lock:
            if key in self._precompiled:
                return
            self._precompiled.add(key)

        def warm():
            try:
                import jax

                # wire dtype (uint8 unless --float32_wire): the warmed
                # program must be the one the real dispatch uses
                if raw_hw is not None:
                    window = np.zeros(
                        (self.batch_size + 1,) + tuple(raw_hw) + (3,),
                        self._wire)
                    handle = self._device_call(window, timed=False,
                                               pad_target=(h, w))
                else:
                    window = np.zeros((self.batch_size + 1, h, w, 3),
                                      self._wire)
                    handle = self._device_call(window, timed=False)
                # host-sync: warmup thread blocks on the zeros window off the critical path by design
                jax.block_until_ready(handle)
            except Exception as e:  # noqa: BLE001 — fault-barrier: best-effort warmup; the real dispatch compiles inline and surfaces any genuine error
                print(f"[flow] geometry precompile ({h}x{w}) failed: "
                      f"{type(e).__name__}: {e}; the first window will "
                      "compile inline", flush=True)

        threading.Thread(target=warm, daemon=True,
                         name=f"flow-precompile:{h}x{w}").start()

    # --- corpus packing (--pack_corpus) ------------------------------------

    def pack_spec(self):
        """Corpus-packing seam for dense flow: a slot is one frame *pair*.

        ``open_clips`` yields ``(2, Hb, Wb, 3)`` uint8 pairs already padded to
        the video's bucket geometry (``ShapeBuckets`` over the corpus's
        container probes — ≤ ``--pack_buckets`` compiled programs for a
        mixed-resolution corpus) — or RAW ``(2, H, W, 3)`` decoded pairs
        under ``--device_preproc``, where queues key per decoded geometry
        and the per-pad-target step replicate-pads on device (byte-exact on
        the uint8 wire; the bucket plan still bounds compiled programs
        because the pad target is bucketed). ``collate`` chains
        stream-consecutive pairs
        back into one ``(batch_size + 1)``-frame shared-frame window — the
        same encode-once program :meth:`_device_call` runs in the per-video
        loop (frame-sharded with halo exchange on multi-device meshes) — so
        the tail of video N's pairs co-batches with the head of video N+1 at
        the cost of one burned frame position per video boundary inside a
        window. Each pair's flow is a pure function of its two frames under
        a fixed program, so packed outputs are byte-identical to the
        per-video loop whenever the bucket equals the video's own padded
        geometry (always true for single-geometry corpora; a merged bucket
        carries --shape_bucket's documented border-perturbation caveat).

        ``--show_pred`` keeps the per-video loop: its frame+flow
        visualizations assume video order.
        """
        if self.cfg.show_pred:
            return None
        from ..parallel.packer import PackSpec, ShapeBuckets

        batch = self.batch_size  # pairs per window

        def prepare(paths):
            from ..io.video import probe_geometries

            geoms = [self._padded_geometry(w, h)
                     for w, h in probe_geometries(paths).values()]
            self._pack_buckets = (
                ShapeBuckets(geoms, self.cfg.pack_buckets) if geoms else None)

        def open_clips(path):
            meta, frames = self._open_video(path)
            geom = self._padded_geometry(meta.width, meta.height)
            bucket = (self._pack_buckets.bucket_for(geom)
                      if self._pack_buckets is not None else geom)
            raw_hw = (self._decoded_geometry(meta.width, meta.height)
                      if self._device_preproc else None)
            if self.cfg.precompile:
                self._start_precompile_padded(bucket, raw_hw=raw_hw)
            info = {
                "fps": meta.fps,
                "timestamps_ms": [],
                # zero-pair videos reproduce the per-video loop's quirk of
                # shaping the empty output from the NATIVE container geometry
                "native_hw": (meta.height, meta.width),
                "pads": (0, 0, 0, 0),
            }
            if raw_hw is not None:
                # raw wire: the step pads on device; the host keeps only the
                # pad arithmetic so finalize can unpad the fetched flow
                info["pads"] = pad_split(raw_hw[0], raw_hw[1], *bucket)

            def clips():
                prev = None
                for rgb, pos in self._timed_frames(frames):
                    info["timestamps_ms"].append(pos)
                    if raw_hw is None:
                        rgb, info["pads"] = pad_to_shape(rgb, bucket)
                    if prev is not None:
                        yield np.stack([prev, rgb])
                    prev = rgb

            return info, clips()

        def collate(clips, stream_keys):
            # chain consecutive pairs (same stream, idx + 1) into a shared-
            # frame window of `batch` pairs / `batch + 1` frame positions; a
            # chain break costs one extra frame position, and the window tail
            # repeats the last frame exactly like the per-video loop's
            # partial-batch padding. Frames are written straight into a
            # staging-ring buffer at the wire dtype (uint8 unless
            # --float32_wire) — no per-batch stack/cast allocation; step()
            # commits the buffer against its device_put below.
            capacity = batch + 1
            buf = self._staging.acquire((capacity,) + clips[0].shape[1:],
                                        self._wire)
            n_frames, n_used, row_of, last = 0, 0, [], None
            for clip, (stream, idx) in zip(clips, stream_keys):
                chained = last == (stream, idx - 1)
                if n_frames + (1 if chained else 2) > capacity:
                    break
                if not chained:
                    buf[n_frames] = clip[0]
                    n_frames += 1
                buf[n_frames] = clip[1]
                n_frames += 1
                row_of.append(n_frames - 2)
                last = (stream, idx)
                n_used += 1
            while n_frames < capacity:
                buf[n_frames] = buf[n_frames - 1]
                n_frames += 1
            return buf, n_used, row_of

        def step(window):
            # --device_preproc windows arrive at RAW decoded geometry (one
            # queue per geometry, so a window never mixes shapes); the pad
            # target is the bucket that geometry maps to — the same pure
            # function of (h, w) open_clips used for info["pads"]
            pad_target = (self._window_pad_target(*window.shape[1:3])
                          if self._device_preproc else None)
            out = self._device_call(window, staged=window,
                                    pad_target=pad_target)
            # same overlap as the per-video loop's _dispatch_window: the
            # packer fetches this batch only when the bucket's NEXT batch
            # dispatches, so the transfer races compute, not the fetch
            self._start_async_copy(out)
            return out

        def finalize(path, rows, info):
            if rows.shape[0] == 0:
                h, w = info["native_hw"]
                flow = np.zeros((0, 2, h, w), np.float32)
            else:
                if self._upcast:  # sub-fp32 transfer_dtype: upcast on host
                    rows = rows.astype(np.float32)  # (hoisted decision)
                if any(info["pads"]):
                    rows = unpad(rows, info["pads"])
                # NHWC rows → reference byte layout (n_pairs, 2, H, W)
                flow = rows.transpose(0, 3, 1, 2)
            return {
                self.feature_type: flow,
                "fps": np.array(info["fps"]),
                "timestamps_ms": np.array(info["timestamps_ms"]),
            }

        return PackSpec(batch_size=batch, empty_row_shape=(0, 0, 2),
                        open_clips=open_clips, step=step, finalize=finalize,
                        collate=collate, prepare=prepare)

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        meta, frames_iter = self._open_video(video_path)
        if self.cfg.precompile:
            # geometry known from the container probe: overlap this video's
            # (possibly first-of-its-geometry) compile with its decode
            self._start_precompile(meta.width, meta.height)
        timestamps_ms: List[float] = []
        flow_frames: List[np.ndarray] = []
        window: List[np.ndarray] = []
        # bounded in-flight device windows: deep enough to overlap fetch with
        # compute + decode, bounded so a long video can't pin every batch's
        # full-res flow in HBM
        pending: deque = deque()
        max_pending = max(self.cfg.prefetch_depth, 1)

        self._viz_counter = 0  # per-video PNG numbering

        def collect_one():
            stack, handle = pending.popleft()
            flow = self._collect_pairs(handle)
            flow_frames.extend(flow)
            if self.cfg.show_pred:
                self._show(stack[:-1], flow, video_path)

        def flush():
            if len(window) > 1:
                # ring-staged dispatch at the wire dtype; a frame stack is
                # (re)materialized only for --show_pred's visualizations
                pending.append((np.stack(window) if self.cfg.show_pred
                                else None,
                                self._dispatch_window(window)))
                while len(pending) > max_pending:
                    collect_one()

        for rgb, pos in self._timed_frames(frames_iter):
            timestamps_ms.append(pos)
            window.append(rgb)
            if len(window) - 1 == self.batch_size:
                flush()
                window = [window[-1]]  # carry last frame (reference :143-146)
        flush()  # final partial batch of ≥ 2 frames (reference :147-151)
        while pending:
            collect_one()

        h, w = (flow_frames[0].shape[-2:]) if flow_frames else (meta.height, meta.width)
        return {
            self.feature_type: (
                np.stack(flow_frames) if flow_frames else np.zeros((0, 2, h, w), np.float32)
            ),
            "fps": np.array(meta.fps),
            "timestamps_ms": np.array(timestamps_ms),
        }

    def _show(self, frames: np.ndarray, flows: np.ndarray, video_path: str = "") -> None:
        """Frame + color-wheel flow side by side (``extract_raft.py:165-178``).

        Headless hosts (every TPU pod) have no display for ``cv2.imshow``; the
        visualizations are written as ``<output>/<type>_viz/<stem>_NNNNN.png``
        instead (the ``<stem>_<key>.npy`` naming convention), so ``--show_pred``
        stays useful over ssh. Without OpenCV installed, degrades to a stats line.
        """
        try:
            import cv2
        except ImportError:
            for flow in flows:
                print(f"flow: mean |u|={np.abs(flow[0]).mean():.3f} "
                      f"|v|={np.abs(flow[1]).mean():.3f} (no cv2 for visualization)")
            return

        from ..utils.flow_viz import flow_to_image

        stem = os.path.splitext(os.path.basename(video_path))[0] or "video"
        # cv2.imshow can hard-crash (not raise) without a display server; only
        # attempt it when one is advertised
        has_display = bool(os.environ.get("DISPLAY") or os.environ.get("WAYLAND_DISPLAY"))
        for frame, flow in zip(frames, flows):
            img = flow_to_image(flow.transpose(1, 2, 0))
            stacked = np.concatenate([frame.astype(np.uint8), img], axis=0)
            bgr = cv2.cvtColor(stacked, cv2.COLOR_RGB2BGR)
            if has_display:
                try:
                    cv2.imshow("frame + flow", bgr)
                    cv2.waitKey(1)
                    continue
                except Exception:  # fault-barrier: headless-host probe; falls back to PNG dump
                    has_display = False
            viz_dir = self.output_dir + "_viz"
            os.makedirs(viz_dir, exist_ok=True)
            cv2.imwrite(os.path.join(viz_dir, f"{stem}_{self._viz_counter:05d}.png"), bgr)
            self._viz_counter += 1

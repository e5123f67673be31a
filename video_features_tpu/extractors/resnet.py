"""ResNet-50 per-frame feature extractor.

Behavioral spec (``/root/reference/models/resnet50/extract_resnet50.py``): decode →
smaller-edge resize 256 (PIL bilinear) → center crop 224 → /255 + ImageNet normalize →
ResNet-50 with identity head → 2048-d per-frame features, batched by ``--batch_size``
with the partial tail batch processed too (``:118-143``); output keys ``resnet50``,
``fps``, ``timestamps_ms``; ``--show_pred`` prints ImageNet top-5 via the saved fc
head (``:54-58,98-101``).

TPU design: host does decode+resize+crop (uint8); the jitted device step fuses
normalize into the conv stack; the tail batch is zero-padded to the static batch
shape so XLA compiles exactly one program per run. ``--device_resize`` (or its
every-model generalization ``--device_preproc``) moves the PIL resize+crop
inside the step too (``ops/image.device_resize_crop_hwc``): raw decoded frames
ride the wire, one compiled program per decoded geometry, at a documented
tolerance vs the PIL parity path (docs/performance.md).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from ..models.resnet import ResNet50, preprocess_frames
from ..parallel import prefetch_to_device
from ..ops.image import device_resize_crop_hwc, np_center_crop_hwc, pil_edge_resize
from ..utils.labels import show_predictions_on_dataset
from ..weights.convert_torch import convert_resnet50
from .base import Extractor

RESIZE_SIZE = 256
CENTER_CROP_SIZE = 224


class ExtractResNet50(Extractor):
    uses_frame_stream = True
    # --device_resize: the host PIL resize+crop moves inside the jitted step
    # (ops/image.device_resize_crop_hwc) — raw decoded frames on the wire,
    # slots keyed per decoded geometry in packed runs; tolerance-gated vs
    # the bit-parity host path (docs/performance.md)
    supports_device_resize = True
    # --device_preproc is the same path here: resnet50's only host preprocess
    # IS the resize+crop, so the general flag folds into _device_resize
    # (cache/key.py resolves the two flags identically for resnet50)
    supports_device_preproc = True

    def __init__(self, cfg):
        super().__init__(cfg)
        self._device_resize = cfg.device_resize or cfg.device_preproc
        # round the user batch up to a multiple of the mesh size so the sharded
        # leading axis always divides evenly (tail rows are zero-padded + trimmed)
        self.batch_size = self.runner.device_batch(cfg.batch_size)
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.model = ResNet50(dtype=self.dtype)
        self.params = self._load_params(
            "resnet50", convert_torch_fn=convert_resnet50, init_fn=self._random_init)
        if cfg.show_pred and "fc" not in self.params:
            raise ValueError(
                "--show_pred needs the classifier head, but the resolved resnet50 "
                "checkpoint has no 'fc' params (feature-only checkpoint)"
            )
        self._step = self.runner.jit(self._forward)

    def _random_init(self):
        from ..weights.store import random_params_like

        rng = jax.random.PRNGKey(0)
        dummy = jnp.zeros((1, CENTER_CROP_SIZE, CENTER_CROP_SIZE, 3), jnp.uint8)
        init = lambda r, d: self.model.init(r, d, features=False)  # noqa: E731
        return random_params_like(init, rng, dummy)["params"]

    def _forward(self, params, frames_u8):
        if self._device_resize:
            # raw decoded frames in: the edge resize + crop run fused into
            # the step (static geometry per compile — each decoded geometry
            # is its own program, like the i3d aspect-ratio queues)
            frames_u8 = device_resize_crop_hwc(
                frames_u8, RESIZE_SIZE, CENTER_CROP_SIZE)
        x = preprocess_frames(frames_u8, dtype=self.dtype)
        feats = self.model.apply({"params": params}, x, features=True)
        return feats.astype(jnp.float32)

    def _host_transform(self, rgb: np.ndarray) -> np.ndarray:
        if self._device_resize:
            return rgb  # ship the raw decoded frame; the step resizes
        rgb = pil_edge_resize(rgb, RESIZE_SIZE)
        return np_center_crop_hwc(rgb, CENTER_CROP_SIZE, CENTER_CROP_SIZE)

    def pack_spec(self):
        """Corpus-packing seam: every device slot is one 224² frame — or one
        RAW decoded frame under ``--device_resize``/``--device_preproc``,
        where queues key by decoded geometry — so same-shape clips share a
        queue and the tail batch of video N fills with the head of video
        N+1. Per-row features are byte-identical to the per-video loop on
        the 224² wire (no cross-sample ops, same jitted program); the raw
        wire is ulp-level instead — pages run the resize prologue at
        page_rows, a different static shape than the per-video batch, and
        XLA's f32 resize is not bitwise-stable across shapes
        (tests/test_device_preproc.py pins 1e-5 relative)."""
        if self.cfg.show_pred:
            return None  # debug path prints per-batch top-5 in video order
        from ..parallel.packer import PackSpec

        # Ragged paged dispatch (--paged_batching): always on. Packer queues
        # are keyed by clip shape, so under --device_resize/--device_preproc
        # each raw decoded geometry pages through its OWN queue — pages never
        # co-host mixed geometries, and every queue shares one compiled
        # jit_paged family per geometry (the same multi-queue paging i3d's
        # aspect-ratio buckets already exercise).
        paged = self._paged_fields(self._forward, self.params,
                                   self.batch_size)

        def open_clips(path):
            meta, frames = self._open_video(path)
            info = {"fps": meta.fps, "timestamps_ms": []}

            def clips():
                for rgb, pos in self._timed_frames(frames):
                    info["timestamps_ms"].append(pos)
                    yield rgb

            return info, clips()

        def step(frames_u8):
            # _put attributes dispatch time + staged bytes to the 'transfer'
            # stage; the packer commits the staged buffer after the step
            return self._step(self.params, self._put(frames_u8))

        def finalize(path, rows, info):
            return {
                self.feature_type: rows,
                "fps": np.array(info["fps"]),
                "timestamps_ms": np.array(info["timestamps_ms"]),
            }

        return PackSpec(batch_size=self.batch_size, empty_row_shape=(2048,),
                        open_clips=open_clips, step=step, finalize=finalize,
                        **paged)

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        meta, frames = self._open_video(video_path)
        timestamps_ms = []
        valid_counts = []

        def batches():
            # frames are stacked into reusable staging-ring buffers (the
            # prefetcher's commit hook guards them until their device_put
            # resolves) — no fresh np.stack/pad_batch allocation per batch
            batch = []
            for rgb, pos in self._timed_frames(frames):
                timestamps_ms.append(pos)
                batch.append(rgb)
                if len(batch) == self.batch_size:
                    valid_counts.append(len(batch))
                    yield self._stage_rows(batch)
                    batch = []
            if batch:  # partial tail batch (reference :139-141), zero-padded
                valid_counts.append(len(batch))
                yield self._stage_rows(batch, self.batch_size)

        if self.cfg.show_pred:
            # debug path: fetch the fc head ONCE per video (device_wait-
            # accounted), not per batch — the head is ~8 MB and re-fetching
            # it every batch was an unaccounted host sync in the step loop
            fc = self.params["fc"]
            fc_kernel = self._wait(fc["kernel"])
            fc_bias = self._wait(fc["bias"])

        vid_feats = []
        # decode of batch k+1 overlaps device compute of batch k; the transfer
        # target is the mesh batch sharding, so frames land pre-split per device.
        # Per-batch features STAY on device — one host fetch per video (each
        # host sync drains the dispatch pipeline)
        for i, device_batch in enumerate(
            prefetch_to_device(
                batches(),
                sharding=self.runner.batch_sharding,
                depth=self.cfg.prefetch_depth,
                span=self._span,
                commit=self._staging.commit,
            )
        ):
            feats = self._step(self.params, device_batch)[: valid_counts[i]]
            if self.cfg.show_pred:  # debug mode: fetch once, reuse for logits
                feats = self._wait(feats)
                logits = feats @ fc_kernel + fc_bias
                show_predictions_on_dataset(logits, "imagenet")
            vid_feats.append(feats)
            self._throttle(vid_feats)

        if not vid_feats:
            feats = np.zeros((0, 2048), np.float32)
        elif isinstance(vid_feats[0], np.ndarray):  # show_pred fetched per batch
            feats = np.concatenate(vid_feats, axis=0)
        else:
            feats = self._wait(jnp.concatenate(vid_feats, axis=0))
        return {
            self.feature_type: feats,
            "fps": np.array(meta.fps),
            "timestamps_ms": np.array(timestamps_ms),
        }

"""R(2+1)D-18 clip extractor: whole-video decode → 16-frame slices → 512-d features.

Behavioral spec — ``/root/reference/models/r21d/extract_r21d.py``:
- whole video into RAM (``read_video``, ``:102``); fps re-encode forbidden by the
  reference ``sanity_check`` (enforced in :mod:`video_features_tpu.config`);
- transforms: /255 → bilinear resize (128, 171) → Kinetics normalize → center crop
  112 (``:32-38``);
- ``form_slices`` full 16-frame windows, step 16, trailing frames dropped (``:107``);
- per-slice r2plus1d_18 with identity head → 512-d; ``--show_pred`` applies the
  saved fc for Kinetics top-5 (``:111-121``);
- output: features only — the reference omits fps/timestamps for this model
  (``:123-125``), reproduced for drop-in parity.

TPU design: slices are batched ``clips_per_batch`` at a time into one jitted step
(static shapes, tail zero-padded then trimmed); preprocess runs on device fused
into the stem.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from ..io.video import decode_all
from ..models.r21d import NUM_FEATURES, R2Plus1D18, r21d_preprocess
from ..utils.labels import show_predictions_on_dataset
from ..utils.windows import form_slices
from ..weights.convert_torch import convert_r21d
from .base import Extractor


class ExtractR21D(Extractor):
    # --device_preproc is a documented no-op here: r21d's whole transform
    # chain (/255 → bilinear resize (128, 171) → Kinetics normalize → center
    # crop 112, r21d_preprocess) has run device-fused since the port — raw
    # native-resolution clips are ALREADY the wire format, so the general
    # flag has nothing left to move and must not print the "ignored" notice
    supports_device_preproc = True

    def __init__(self, cfg):
        super().__init__(cfg)
        cfg = self.cfg  # model defaults resolved by the base class
        self.stack_size = cfg.stack_size
        self.step_size = cfg.step_size
        # clips per device step, rounded to a multiple of the mesh size
        self.clips_per_batch = self.runner.device_batch(cfg.clips_per_batch)
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.model = R2Plus1D18(dtype=self.dtype)
        self.params = self._load_params(
            "r2plus1d_18", convert_torch_fn=convert_r21d, init_fn=self._random_init)
        if cfg.show_pred and "fc" not in self.params:
            raise ValueError(
                "--show_pred needs the classifier head, but the resolved r2plus1d_18 "
                "checkpoint has no 'fc' params"
            )

    def _random_init(self):
        from ..weights.store import random_params_like

        dummy = jnp.zeros((1, 4, 112, 112, 3))
        init = lambda r, d: self.model.init(r, d, features=False)  # noqa: E731
        return random_params_like(init, jax.random.PRNGKey(0), dummy)["params"]

    def _forward(self, params, clips_u8):
        # (N, 16, H, W, 3) uint8 native resolution; pure per-row — the paged
        # dispatch path wraps this same body (parallel/pages.paged_program)
        n, t = clips_u8.shape[:2]
        flat = clips_u8.reshape((n * t,) + clips_u8.shape[2:])
        x = r21d_preprocess(flat, dtype=self.dtype).reshape((n, t, 112, 112, 3))
        return self.model.apply(
            {"params": params}, x, features=True).astype(jnp.float32)

    @functools.cached_property
    def _step(self):
        return self.runner.jit(self._forward)

    def pack_spec(self):
        """Corpus-packing seam: slots are ``(stack, H, W, 3)`` native-
        resolution slices, shape-keyed per video geometry — same-resolution
        videos co-pack; a mixed-resolution corpus fills one queue per
        geometry. Slots are views into the whole-video decode buffer, so a
        pending tail pins at most ``clips_per_batch - 1`` videos' buffers
        per geometry until the next same-shape video (or the corpus flush)
        dispatches them."""
        if self.cfg.show_pred:
            return None  # debug path prints per-clip top-5 in video order
        from ..parallel.packer import PackSpec

        def open_clips(path):
            _meta, frames, _ts = decode_all(
                path, extraction_fps=None, tmp_path=self.tmp_dir)
            slices = form_slices(frames.shape[0], self.stack_size,
                                 self.step_size)

            def clips():
                for s, e in slices:
                    yield frames[s:e]

            return {}, clips()

        def step(clips_u8):
            # _put: 'transfer'-stage attribution (time + staged bytes); the
            # packer commits the staged ring buffer after the step
            return self._step(self.params, self._put(clips_u8))

        def finalize(path, rows, info):
            # reference returns features only for r21d (extract_r21d.py:123-125)
            return {self.feature_type: rows}

        return PackSpec(batch_size=self.clips_per_batch,
                        empty_row_shape=(NUM_FEATURES,),
                        open_clips=open_clips, step=step, finalize=finalize,
                        **self._paged_fields(self._forward, self.params,
                                             self.clips_per_batch))

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        meta, frames, _ts = decode_all(
            video_path,
            extraction_fps=None,  # validated off for r21d
            tmp_path=self.tmp_dir,
        )
        slices = form_slices(frames.shape[0], self.stack_size, self.step_size)
        if self.cfg.show_pred:
            # debug path: fetch the fc head ONCE per video (device_wait-
            # accounted), not per clip batch
            fc = self.params["fc"]
            fc_kernel = self._wait(fc["kernel"])
            fc_bias = self._wait(fc["bias"])
        vid_feats = []
        for i in range(0, len(slices), self.clips_per_batch):
            chunk = slices[i : i + self.clips_per_batch]
            clips = self._stage_rows([frames[s:e] for s, e in chunk],
                                     self.clips_per_batch)
            dev = self._put(clips)
            self._staging.commit(clips, dev)  # guard the ring buffer
            clips = dev
            # stays on device; one host fetch per video
            feats = self._step(self.params, clips)[: len(chunk)]
            if self.cfg.show_pred:  # debug mode: fetch once, reuse for logits
                feats = self._wait(feats)
                logits = feats @ fc_kernel + fc_bias
                for (s, e), row in zip(chunk, logits):
                    print(f"{video_path} @ frames ({s}, {e})")
                    show_predictions_on_dataset(row[None], "kinetics")
            vid_feats.append(feats)
            self._throttle(vid_feats)

        if not vid_feats:
            feats = np.zeros((0, NUM_FEATURES), np.float32)
        elif isinstance(vid_feats[0], np.ndarray):  # show_pred fetched per batch
            feats = np.concatenate(vid_feats, axis=0)
        else:
            feats = self._wait(jnp.concatenate(vid_feats, axis=0))
        # reference returns features only for r21d (extract_r21d.py:123-125)
        return {self.feature_type: feats}

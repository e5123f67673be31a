"""VGGish audio extractor: mp4/wav → log-mel examples → 128-d embeddings.

Behavioral spec — ``/root/reference/models/vggish/extract_vggish.py``:
- ``.wav`` inputs consumed directly; ``.mp4`` goes through the two-stage
  ffmpeg extraction (mp4 → aac → wav, ``utils/utils.py:172-201``), with
  ``keep_tmp_files`` controlling cleanup (``:107-110``);
- wav → (N, 96, 64) log-mel examples on the host (vggish_src DSP — ported in
  :mod:`video_features_tpu.audio.melspec`);
- VGG forward → (N, 128) raw embeddings. The reference instantiates the PCA
  postprocessor but never applies it (``:57,104-116``); reproduced via
  ``postprocess=False`` default with the processor available for opt-in;
- output dict: ``{'vggish': (N, 128)}`` (no fps/timestamps — audio model).

TPU design: examples are padded to a static batch so each audio length bucket
compiles once; the forward runs jitted on device. ``--device_preproc`` moves
the log-mel DSP itself on device: the host ships raw (N, 15600) float32 PCM
slabs (``melspec.wav_to_pcm_slabs``) and the jitted step runs the fused
framing → |rfft| → mel matmul → log prologue
(:func:`video_features_tpu.ops.audio.log_mel_examples`, ≤2e-5 vs the numpy
oracle) before the VGG stack. The wire grows 6144→15600 floats per example
(raw PCM is bigger than its mel summary) — the trade is host-CPU relief: the
strided-FFT DSP leaves the decode pool for the accelerator.
"""

from __future__ import annotations

import functools
import os
from typing import Dict

import numpy as np

import jax.numpy as jnp

from ..audio.melspec import wav_to_examples, wav_to_pcm_slabs
from ..io import ffmpeg as ffmpeg_io
from ..ops.audio import log_mel_examples
from ..parallel.pipeline import pad_batch
from ..models.vggish import (
    EMBEDDING_SIZE,
    Postprocessor,
    VGGish,
    convert_tf_vggish,
    vggish_init_params,
)
from .base import Extractor

# examples per jitted call; audio shorter than this pads, longer chunks
EXAMPLE_BATCH = 32


class ExtractVGGish(Extractor):
    # --device_preproc: the log-mel DSP runs as a fused jitted prologue
    # (ops/audio.log_mel_examples) over raw PCM slabs; host melspec stays the
    # parity oracle (≤2e-5, tests/test_device_preproc.py)
    supports_device_preproc = True

    def __init__(self, cfg):
        super().__init__(cfg)
        self._device_preproc = cfg.device_preproc
        # examples per device step, rounded to a multiple of the mesh size
        self.example_batch = self.runner.device_batch(EXAMPLE_BATCH)
        self.model = VGGish()
        self.params = self._load_params(
            "vggish",
            convert_tf_fn=convert_tf_vggish,  # reference ships a TF-slim checkpoint
            init_fn=lambda: vggish_init_params(seed=0))
        # reference parity: processor constructed, applied only on request —
        # --vggish_postprocess (vendored AudioSet params) or an explicit
        # VFT_VGGISH_PCA_PARAMS path (env var implies opt-in, as before)
        pca_path = os.environ.get("VFT_VGGISH_PCA_PARAMS")
        if pca_path is None and self.cfg.vggish_postprocess:
            pca_path = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "weights", "data", "vggish_pca_params.npz")
        self.postprocessor = Postprocessor(pca_path) if pca_path else None

    def _forward(self, params, examples):
        # (B, 96, 64) float32; pure per-row — the paged dispatch path wraps
        # this same body (parallel/pages.paged_program)
        return self.model.apply({"params": params}, examples)

    @functools.cached_property
    def _step(self):
        return self.runner.jit(self._forward)

    def _pcm_forward(self, params, pcm):
        # (B, 15600) float32 raw PCM; pure per-row — the log-mel prologue
        # fuses into the VGG stack, and the paged dispatch path wraps this
        # same body (parallel/pages.paged_program)
        return self.model.apply({"params": params}, log_mel_examples(pcm))

    @functools.cached_property
    def _pcm_step(self):
        return self.runner.jit(self._pcm_forward)

    def pack_spec(self):
        """Corpus-packing seam: every device slot is one fixed ``(96, 64)``
        log-mel example, so the whole corpus shares a single shape queue —
        the structurally simplest PackSpec in the repo (audio was excluded
        from PR 4's RGB-only packing for no structural reason). The VGG
        forward has no cross-sample ops and packed batches run the SAME
        jitted program at the same static ``example_batch`` shape, so
        embeddings are byte-identical to the per-video loop; the PCA
        postprocessor (when enabled) runs per video in ``finalize``, exactly
        where the per-video loop applies it."""
        from ..parallel.packer import PackSpec

        def open_clips(path):
            wav_path = path
            aac_path = None
            extracted = False
            if not path.endswith(".wav"):
                wav_path, aac_path = ffmpeg_io.extract_wav_from_mp4(
                    path, self.tmp_dir)
                extracted = True

            # --device_preproc slots are (15600,) raw PCM slabs (the log-mel
            # runs in the step); default slots are (96, 64) host examples —
            # both fixed shapes, so either way one corpus-wide shape queue
            to_rows = (wav_to_pcm_slabs if self._device_preproc
                       else wav_to_examples)

            def clips():
                try:
                    for example in to_rows(wav_path):
                        yield example
                finally:
                    # generator close/exhaustion = the per-video loop's
                    # finally: temp audio never outlives its video's stream
                    if extracted and not self.cfg.keep_tmp_files:
                        for p in (wav_path, aac_path):
                            if p and os.path.exists(p):
                                os.remove(p)

            return {}, clips()

        batch_step = self._pcm_step if self._device_preproc else self._step

        def step(examples):
            # _put: 'transfer'-stage attribution (time + staged bytes); the
            # packer commits the staged ring buffer after the step
            return batch_step(self.params, self._put(examples))

        def finalize(path, rows, info):
            if self.postprocessor is not None:
                rows = self.postprocessor.postprocess(rows)
            return {self.feature_type: rows}

        forward = (self._pcm_forward if self._device_preproc
                   else self._forward)
        return PackSpec(batch_size=self.example_batch,
                        empty_row_shape=(EMBEDDING_SIZE,),
                        open_clips=open_clips, step=step, finalize=finalize,
                        **self._paged_fields(forward, self.params,
                                             self.example_batch))

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        wav_path = video_path
        aac_path = None
        extracted = False
        if not video_path.endswith(".wav"):
            wav_path, aac_path = ffmpeg_io.extract_wav_from_mp4(video_path, self.tmp_dir)
            extracted = True
        try:
            if self._device_preproc:  # (N, 15600) raw PCM; log-mel in-step
                examples = wav_to_pcm_slabs(wav_path)
                step = self._pcm_step
            else:
                examples = wav_to_examples(wav_path)  # (N, 96, 64)
                step = self._step
            feats = []
            for i in range(0, len(examples), self.example_batch):
                chunk = examples[i : i + self.example_batch]
                valid = len(chunk)
                batch = self._put(pad_batch(chunk, self.example_batch))
                # stays on device; one host fetch per video
                feats.append(step(self.params, batch)[:valid])
                self._throttle(feats)
            out = (
                self._wait(jnp.concatenate(feats, axis=0))
                if feats
                else np.zeros((0, EMBEDDING_SIZE), np.float32)
            )
            if self.postprocessor is not None:
                out = self.postprocessor.postprocess(out)
            return {self.feature_type: out}
        finally:
            if extracted and not self.cfg.keep_tmp_files:
                for p in (wav_path, aac_path):
                    if p and os.path.exists(p):
                        os.remove(p)

"""Cache keying: content digest × model-config fingerprint × weights version.

A cache hit substitutes a stored array for a device computation, so the key
must cover EVERYTHING that changes the bytes of the output and NOTHING that
doesn't (or the cache never hits). Three components:

1. **content digest** — a streaming SHA-256 of the container bytes
   (:func:`file_digest`). Identical uploads hash identically wherever they
   sit on disk; the video *path* is deliberately not part of the key.
2. **config fingerprint** — the subset of :class:`..config.ExtractionConfig`
   fields that affect feature numerics (:data:`FINGERPRINT_FIELDS`), some
   resolved to their effective value (e.g. ``use_ffmpeg="auto"`` resolves to
   the backend actually used — the same flag value on hosts with and without
   ffmpeg produces different resampled frames). Every dataclass field must
   be classified here or in :data:`EXECUTION_FIELDS`; tests/test_cache.py
   pins the partition, so ADDING A CONFIG FLAG FORCES A KEYING DECISION.
3. **weights version** — pretrained checkpoints have no version string, so
   the fingerprint hashes the resolved checkpoint files for the feature
   type's models (once per extractor, not per video); ``VFT_WEIGHTS_VERSION``
   short-circuits the hashing for operators who pin versions out of band.
   Random-weight runs (``VFT_ALLOW_RANDOM_WEIGHTS``) fingerprint as the
   deterministic seed, never colliding with real weights.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, Optional

# Config fields whose values feed the cache key because they change feature
# numerics. Keep the per-field rationale next to the name — the pin test
# makes adding a field here (or to EXECUTION_FIELDS) a reviewed decision.
FINGERPRINT_FIELDS = (
    "feature_type",            # selects the model
    "streams",                 # i3d rgb/flow subset changes the output keys
    "flow_type",               # raft vs pwc flow in the i3d sandwich
    "extraction_fps",          # temporal resampling changes every frame
    "stack_size",              # clip span per feature row
    "step_size",               # stride between feature rows
    "resize_to_smaller_edge",  # spatial geometry (raft/pwc)
    "side_size",               # spatial geometry (raft/pwc)
    "dtype",                   # bf16 feature nets drift from fp32
    "flow_dtype",              # bf16 flow nets drift (tests/test_flow_bf16)
    "transfer_dtype",          # fp16/bf16 D2H quantizes dense flow
    "matmul_precision",        # MXU pass count changes fp32 accumulation
    "use_ffmpeg",              # resolved: ffmpeg re-encode vs native sampler
    "vggish_postprocess",      # PCA-whiten + uint8 quantize on/off
    "shape_bucket",            # resolved: replicate-pad perturbs flow borders
    "pack_corpus",             # resolved: merged flow buckets pad (caveat)
    "pack_buckets",            # resolved: bucket merging geometry
    "i3d_pre_crop_size",       # i3d resize target
    "i3d_crop_size",           # i3d center crop
    "device_resize",           # resolved: jax.image.resize vs PIL drifts
    "device_preproc",          # resolved: fingerprints only where the device
                               # preprocess is inexact vs the host oracle —
                               # i3d (jax.image.resize vs PIL drifts, like
                               # device_resize) and vggish (f32 log-mel vs
                               # the f64 numpy DSP, ≤2e-5 but not byte-
                               # exact). resnet50 folds into device_resize
                               # (same path, one key). raft/pwc resolve
                               # False: replicate-pad on the uint8 wire is
                               # BYTE-exact (tests/test_device_preproc.py).
                               # r21d resolves False: documented no-op.
)

# Fields declared NOT to affect feature bytes. Each carries its reason; the
# byte-parity claims are pinned by the named test suites.
EXECUTION_FIELDS = (
    "video_paths",             # the work list, not the work
    "file_with_video_paths",   # ditto
    "tmp_path",                # scratch location
    "keep_tmp_files",          # scratch retention
    "on_extraction",           # print vs save — same arrays
    "output_path",             # where results land
    "batch_size",              # per-slot parity pinned (tests/test_packer*)
    "float32_wire",            # u8->fp32 cast is exact; staged bytes only
                               # (byte parity pinned by tests/test_ingest.py)
    "show_pred",               # extra prints; features unchanged
    "clips_per_batch",         # batching, parity pinned
    "num_devices",             # data-parallel sharding, parity pinned
    "resume",                  # skip logic
    "prefetch_depth",          # transfer pipelining
    "decode_workers",          # host decode parallelism
    "decode_segments",         # intra-video segmented decode: the stitched
                               # stream is byte-identical to sequential by
                               # construction (pinned by
                               # tests/test_segmented_decode.py)
    "segment_seek",            # seek mechanics for the same coded frames;
                               # every backend the auto policy accepts lands
                               # frame-exact (parity pinned as above)
    "pack_flush_age",          # dispatch timing, not numerics
    "paged_batching",          # dispatch mechanics; page outputs byte-match
                               # bucketed (pinned by tests/test_paged.py)
    "pages_in_flight",         # in-flight depth, not numerics
    "page_tokens",             # the text stream's page size: which transcripts share
                               # a page moves a row by float rounding only
                               # (attention's blocks fall elsewhere), as which
                               # neighbours it met already does at one size
                               # (pinned by tests/test_laguna.py)
    "raft_corr",               # impl choice, parity pinned (tests/test_raft)
    "pwc_corr",                # impl choice, parity pinned (test_pallas_corr)
    "flow_pair_chunk",         # lax.map chunking, parity pinned
    "precompile",              # compile scheduling
    "async_writer",            # write scheduling, same bytes
    "profile_dir",             # observability
    "telemetry_dir",           # observability: the span journal records the
                               # run, it never touches feature bytes
    "retries",                 # reliability policy
    "retry_backoff",           # reliability policy
    "video_timeout",           # reliability policy
    "max_failures",            # reliability policy
    "retry_failed",            # work-list selection
    "serve",                   # entry point
    "spool_dir",               # serving transport
    "socket_path",             # serving transport
    "notify_dir",              # serving transport
    "tenant_quota",            # admission policy
    "tenant_max_failures",     # per-tenant breaker policy
    "idle_flush_sec",          # dispatch timing
    "spool_poll_sec",          # ingest polling
    "cache_dir",               # the cache's own location
    "cache_max_bytes",         # the cache's own budget
    "serve_models",            # which models a daemon co-loads; each job's
                               # key fingerprints ITS model's derived config
                               # (feature_type et al. above), so co-resident
                               # serving shares entries with single-model
                               # runs — pinned by tests/test_multimodel.py
    "wal_path",                # admission durability, not numerics
    "wal_fsync_sec",           # WAL fsync batching window
    "recover",                 # startup replay policy; replayed extraction
                               # is the same extraction
    "healthz_stale_sec",       # observability threshold
    "spool_retain",            # spool-file retention
    "step_watchdog_sec",       # stall policy; victims requeue, same bytes
)

# checkpoint names each feature type resolves (weights/store.py callers)
_CHECKPOINT_NAMES = {
    "resnet50": ("resnet50",),
    "r21d_rgb": ("r2plus1d_18",),
    "vggish": ("vggish",),
    "raft": ("raft-sintel",),
    "pwc": ("pwc-sintel",),
    "laguna": ("laguna",),
    "sarvam": ("sarvam",),
    "qwen3_next": ("qwen3_next",),
    "jamba": ("jamba",),
    "lfm2": ("lfm2",),
}


def _resolved(cfg):
    """Per-model defaults resolved before any keying decision: a raw
    ``ExtractionConfig(feature_type='i3d')`` (streams/stack/step still None)
    and its resolved equivalent (both streams, 64/64) describe the SAME
    extraction and must fingerprint identically — and the flow stream that
    ``streams=None`` implies must count as a flow stream below."""
    from ..config import resolve_model_defaults

    return resolve_model_defaults(cfg)


def _flow_affected(cfg) -> bool:
    """Flow-net padding knobs perturb numerics only where a flow net runs
    over replicate-padded frames: the flow extractors themselves, and the
    i3d sandwich when its flow stream is on. ``cfg`` must be resolved
    (``_resolved``) so default two-stream i3d counts."""
    if cfg.feature_type in ("raft", "pwc"):
        return True
    return cfg.feature_type == "i3d" and "flow" in (cfg.streams or ())


def _resolve_use_ffmpeg(cfg) -> str:
    """The backend that will actually resample, not the flag spelling —
    ``auto`` differs between hosts with and without ffmpeg installed."""
    if cfg.extraction_fps is None:
        return "unused"
    if cfg.use_ffmpeg == "never":
        return "native"
    if cfg.use_ffmpeg == "always":
        return "ffmpeg"
    from ..io.ffmpeg import have_ffmpeg

    return "ffmpeg" if have_ffmpeg() else "native"


def config_fingerprint(cfg) -> Dict[str, object]:
    """JSON-able ``{field: effective value}`` over FINGERPRINT_FIELDS.

    Conditional resolution keeps keys shared where parity is pinned:
    the flow-padding knobs (``shape_bucket``/``pack_corpus``/``pack_buckets``)
    collapse to None for configs with no flow net (packed RGB/audio outputs
    are byte-identical to the per-video loop), and ``use_ffmpeg`` resolves
    to the backend actually used.
    """
    cfg = _resolved(cfg)
    fp: Dict[str, object] = {}
    flow = _flow_affected(cfg)
    for name in FINGERPRINT_FIELDS:
        value = getattr(cfg, name)
        if name == "use_ffmpeg":
            value = _resolve_use_ffmpeg(cfg)
        elif name in ("shape_bucket", "pack_corpus", "pack_buckets"):
            value = value if flow else None
        elif name == "device_resize":
            # only resnet50 has a device-resize path; other feature types
            # print a notice and keep the (parity) host resize, so the flag
            # must not split their keys. --device_preproc IS the resize for
            # resnet50 (extractors/resnet.py ORs the two flags), so either
            # spelling lands on this one key component
            value = (bool(value or cfg.device_preproc)
                     if cfg.feature_type == "resnet50" else False)
        elif name == "device_preproc":
            # fingerprints only where the device preprocess drifts from the
            # host oracle (see the FINGERPRINT_FIELDS rationale): i3d's
            # device resize and vggish's f32 log-mel. resnet50 already
            # resolved into device_resize above; raft/pwc's device pad is
            # byte-exact and r21d's is a no-op — their keys must not split
            value = (bool(value)
                     if cfg.feature_type in ("i3d", "vggish") else False)
        elif isinstance(value, tuple):
            value = list(value)
        fp[name] = value
    return fp


def file_digest(path: str, chunk_bytes: int = 1 << 20) -> str:
    """Streaming SHA-256 of a file's bytes (bounded memory for any size).

    Raises ``OSError`` for unreadable paths — the caller treats that as a
    cache miss and lets the normal extraction path classify the failure.
    """
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk_bytes)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


def weights_fingerprint(cfg) -> str:
    """Version component for the resolved model weights.

    ``VFT_WEIGHTS_VERSION`` (operator-pinned) wins outright. Otherwise each
    checkpoint the feature type resolves contributes ``name=<sha256[:16]>``
    of its file bytes; a missing checkpoint contributes ``random-seed0``
    when random weights are allowed (they are deterministic) or ``missing``
    (extraction would fail anyway, so the key value is moot). Checkpoint
    directories (``.orbax``) hash their manifest of (relpath, size) — cheap
    and stable for the interchange format's sharded layout.
    """
    pinned = os.environ.get("VFT_WEIGHTS_VERSION")
    if pinned:
        return f"pinned:{pinned}"
    from ..weights.store import ENV_ALLOW_RANDOM, _candidates

    cfg = _resolved(cfg)
    names = list(_CHECKPOINT_NAMES.get(cfg.feature_type, ()))
    if cfg.feature_type == "i3d":
        streams = cfg.streams or ("rgb", "flow")
        names = [f"i3d_{s}" for s in streams]
        if "flow" in streams:
            # the sandwich's flow net: swapping the raft/pwc checkpoint
            # must invalidate default two-stream i3d entries too
            names.append(f"{cfg.flow_type}-sintel")
    parts = []
    allow_random = os.environ.get(ENV_ALLOW_RANDOM) == "1"
    for name in names:
        found: Optional[str] = None
        for cand in _candidates(name):
            if os.path.exists(cand):
                found = cand
                break
        if found is None:
            parts.append(f"{name}=random-seed0" if allow_random
                         else f"{name}=missing")
        elif os.path.isdir(found):
            manifest = sorted(
                (os.path.relpath(os.path.join(dp, fn), found),
                 os.path.getsize(os.path.join(dp, fn)))
                for dp, _dn, fns in os.walk(found) for fn in fns)
            digest = hashlib.sha256(
                json.dumps(manifest).encode()).hexdigest()[:16]
            parts.append(f"{name}={digest}")
        else:
            parts.append(f"{name}={file_digest(found)[:16]}")
    return ";".join(parts) or "none"


def fingerprint_digest(cfg) -> str:
    """One stable hex digest over config fingerprint + weights version."""
    doc = {"config": config_fingerprint(cfg),
           "weights": weights_fingerprint(cfg)}
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()).hexdigest()


def cache_key(content_digest: str, fp_digest: str) -> str:
    """The CAS key for (container bytes, model fingerprint)."""
    return hashlib.sha256(
        f"{content_digest}\n{fp_digest}".encode()).hexdigest()

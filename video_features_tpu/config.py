"""Typed configuration for extraction jobs.

The reference passes a raw argparse ``Namespace`` into every extractor
(``/root/reference/main.py:86``, ``utils/utils.py:88-105``). Here the configuration is a
frozen dataclass: one shared ``ExtractionConfig`` covering the full reference flag
surface (``main.py:52-84``) plus TPU-specific knobs, with per-model defaults resolved by
``resolve_model_defaults``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

FEATURE_TYPES = ("i3d", "vggish", "r21d_rgb", "resnet50", "raft", "pwc", "laguna", "sarvam",
                 "qwen3_next", "jamba", "lfm2")
# the text stream: token transcripts in, the packed path only, one chip's share
TOKEN_TYPES = ("laguna", "sarvam", "qwen3_next", "jamba", "lfm2")
ON_EXTRACTION = ("print", "save_numpy")
FLOW_TYPES = ("raft", "pwc")
STREAMS = ("rgb", "flow")


@dataclass(frozen=True)
class ExtractionConfig:
    """One extraction job: which model, which videos, how to run, where results go.

    Field names intentionally match the reference CLI flags (``main.py:52-84``) so the
    CLI shim is a 1:1 mapping.
    """

    feature_type: str
    video_paths: Tuple[str, ...] = ()
    file_with_video_paths: Optional[str] = None
    tmp_path: str = "./tmp"
    keep_tmp_files: bool = False
    on_extraction: str = "print"
    output_path: str = "./output"
    extraction_fps: Optional[int] = None
    stack_size: Optional[int] = None
    step_size: Optional[int] = None
    streams: Optional[Tuple[str, ...]] = None  # subset of ("rgb", "flow"); None = both
    flow_type: str = "pwc"
    batch_size: int = 1
    resize_to_smaller_edge: bool = True
    side_size: Optional[int] = None
    show_pred: bool = False

    # --- TPU-native knobs (no reference equivalent) ---
    # Compute dtype for model forwards; fp32 gives bit-parity with the torch
    # reference, bf16 maps better onto the MXU.
    dtype: str = "float32"
    # Clips per device step: batches sliding windows into one jit call so the MXU
    # stays busy (the reference runs one 64-frame stack at a time).
    clips_per_batch: int = 1
    # Data-parallel sharding: number of devices in the mesh (None = all local).
    num_devices: Optional[int] = None
    # Resume: skip videos whose outputs are recorded in the done-manifest.
    resume: bool = False
    # Host→HBM prefetch depth (double buffering by default).
    prefetch_depth: int = 2
    # Cross-video decode parallelism: background threads decoding upcoming
    # videos while the device computes (the reference gets this implicitly from
    # thread-per-GPU; SPMD centralizes devices, so decode streams are explicit).
    # 1 = inline decode. Frame-stream models only (resnet50, raft, pwc, i3d).
    decode_workers: int = 1
    # Segmented intra-video decode: split one video into seek-aligned
    # segments decoded concurrently by the pool and streamed back in order —
    # byte-identical to sequential decode by construction (io/video.py
    # plan_segments; docs/performance.md "Segmented decode"). 0 = auto
    # (segment only long videos, and only when the pool has wholly idle
    # permits); 1 = off; N >= 2 caps the split. Needs --decode_workers > 1.
    # The ffmpeg RE-ENCODE resample path (--extraction_fps with ffmpeg
    # installed and use_ffmpeg auto/always) is never segmented — it decodes
    # a different, re-encoded container whose parity anchor is sequential.
    decode_segments: int = 0
    # How a non-first segment lands frame-exact on its start frame: "auto"
    # seeks with cv2 CAP_PROP_POS_FRAMES when the backend's landing verifies
    # (same decoder as sequential decode — the byte-parity guarantee), falls
    # back to the ffmpeg -ss fast-seek rawvideo streamer (keyframe snap +
    # lead-in drop) for resampled streams it cannot land on, else to an
    # exact decode-and-drop rescan. "cv2"/"ffmpeg" force a backend.
    segment_seek: str = "auto"
    # Corpus-level clip packing (--pack_corpus): fill every fixed-shape device
    # batch with clips from however many videos are ready (the tail batch of
    # video N packs with the head of video N+1) instead of zero-padding each
    # video's tail — continuous batching for short-clip corpora
    # (parallel/packer.py, docs/performance.md). Every extractor packs: the
    # RGB paths (resnet50, r21d_rgb, i3d) pack stacked clip slots, the flow
    # extractors (raft/pwc and the i3d flow sandwich) pack frame-pair /
    # sandwich-stack slots, vggish packs fixed log-mel slabs, and mixed
    # geometries pack into ≤ pack_buckets padded shape buckets. The one
    # documented per-video fallback is --show_pred (its per-batch prints
    # assume video order; a notice is printed), plus the single-clip
    # frame-sharded flow sandwich, where one clip already fills the mesh.
    # Per-video fault attribution, resume, and retries are preserved, and
    # features are byte-identical to the per-video loop EXCEPT where a
    # merged flow bucket replicate-pads frames (the pack_buckets /
    # --shape_bucket border caveat; single-geometry corpora always match);
    # --video_timeout becomes a cooperative per-stream bound.
    pack_corpus: bool = False
    # --pack_corpus, flow extractors: cluster the corpus's probed (padded)
    # geometries into at most this many shape buckets before decode starts
    # (parallel/packer.py ShapeBuckets) — a mixed-resolution corpus compiles
    # ≤ K programs and co-packs inside each bucket instead of filling one
    # queue per distinct geometry. Merged buckets replicate-pad frames up to
    # the bucket geometry, which carries --shape_bucket's documented
    # border-perturbation caveat; single-geometry corpora are unaffected.
    pack_buckets: int = 4
    # --pack_corpus anti-starvation flush: dispatch a shape bucket's partial
    # queue (zero-padded) once this many videos have finished while it sat
    # waiting, so a rare geometry cannot strand its videos until corpus end.
    # Trades padding (occupancy) for latency on rare buckets; 0 disables
    # (partial queues then flush only at corpus end, the PR 4 behavior).
    pack_flush_age: int = 8
    # --pack_corpus ragged paged dispatch (parallel/pages.py,
    # docs/performance.md): default ON for the shape-compatible RGB/audio
    # paths (resnet50, r21d, i3d clip stacks, vggish slabs) — buckets ship
    # fixed (page_rows, ...) pages plus an int32 row table instead of
    # batch_size padded batches, keep pages_in_flight pages in flight per
    # bucket, and donate the row table's device buffer (mesh.py jit_paged).
    # Outputs stay byte-identical to bucketed dispatch (tests/test_paged.py);
    # pad waste drops to at most one partial page per flush. Raw-pixels wire
    # formats (--device_resize / --device_preproc) page too — queues key by
    # decoded geometry, so pages never co-host mixed shapes (ulp-level vs
    # the per-video loop, tests/test_device_preproc.py). Models that collate
    # their own windows (raft/pwc, the i3d flow sandwich) opt out per
    # PackSpec and dispatch bucketed exactly as before.
    paged_batching: bool = True
    # Paged in-flight depth per bucket: the host refills page k+1's staging
    # buffer while the device chews on page k (>= 2 = double-buffered
    # dispatch; page_rows = ceil(batch budget / depth), so total in-flight
    # rows stay at one bucketed batch regardless of depth).
    pages_in_flight: int = 2
    # laguna, sarvam, qwen3_next, jamba, lfm2 (the text stream): token slots of one device page. A page holds
    # whole transcripts (the oldest queued, and of two pages' worth the others
    # that fill it best), so this is also the longest transcript the
    # type takes; a multiple of the attention kernel's block of 512. One
    # program per value; which transcripts share a page moves a row by
    # rounding only (docs/models/laguna.md).
    page_tokens: int = 16384
    # Flow-net (RAFT/PWC) conv compute + correlation storage dtype, independent
    # of `dtype` (which governs the feature networks): bfloat16 halves flow-net
    # HBM traffic and MXU passes; correlation ACCUMULATION and coordinate math
    # stay fp32 either way. float32 (default) is the reference-parity path.
    # Measured bf16 drift: tests/test_flow_bf16.py and docs/architecture.md.
    flow_dtype: str = "float32"
    # RAFT correlation: "auto" (default) materializes the all-pairs pyramid
    # (reference default path, same numerics) unless the volume would outgrow
    # HBM for the frame geometry, then switches to "on_demand" (the
    # alt_cuda_corr equivalent — O(H·W·D) memory; models/raft.py
    # resolve_corr_impl); explicit "volume"/"volume_gather"/"on_demand"/
    # "on_demand_matmul" (the MXU volume remat, never auto's choice: no 1080p
    # TPU sweep justifies it yet) force a path.
    raft_corr: str = "auto"
    # PWC cost volume: "auto" (default) picks the Pallas tile kernel where its
    # VMEM gates admit the shape and the fused XLA formulation elsewhere
    # (which is faster on the installed compiler: not measured); "xla" forces
    # the XLA formulation, "pallas" the kernels — and raises where they
    # cannot run rather than substituting XLA (ops/pallas_corr).
    pwc_corr: str = "auto"
    # I3D flow sandwich: decode the PWC pairs in sub-batches of this size
    # under lax.map to bound peak decoder memory (the 64-pair stack at the
    # sample videos' 256×341 geometry exceeds HBM in one piece). None = auto
    # (chunk to 16 when pairs × flow-grid area is large); 0 = never chunk.
    flow_pair_chunk: Optional[int] = None
    # Flow models: replicate-pad frames up to multiples of this size before the
    # device step (flow unpadded after), so a mixed-resolution corpus compiles
    # one program per BUCKET instead of one per distinct video geometry.
    # Numerics caveat: like the reference's own /8 pad, edge padding perturbs
    # flow near borders — parity runs leave it off.
    shape_bucket: Optional[int] = None
    # --extraction_fps resampling backend: "auto" re-encodes through ffmpeg
    # when installed (exact reference parity, utils/utils.py:147-169) and
    # falls back to the native vf_fps-semantics sampler; "never" forces the
    # native sampler (deterministic across hosts with/without ffmpeg — the
    # frozen-golden tests pin this); "always" errors without ffmpeg.
    use_ffmpeg: str = "auto"
    # VGGish: apply the AudioSet PCA-whiten + uint8 quantize postprocessor
    # (vendored params). Off by default — the reference constructs the
    # postprocessor but never applies it (extract_vggish.py:57,104-116).
    vggish_postprocess: bool = False
    # Flow extractors: as soon as a video's container is probed (its decoded
    # geometry is then known), warm the jitted device program for that
    # (bucketed) geometry in a background thread while the host decodes —
    # a mixed-resolution corpus overlaps its serial mid-run recompiles with
    # decode instead of stalling the mesh on each new geometry. Combine with
    # --shape_bucket to bound the geometry count; the persistent compile
    # cache (parallel/mesh.py enable_compilation_cache, always on) keeps the
    # results across runs.
    precompile: bool = False
    # Overlap feature serialization with the next video's compute: .npy
    # writes and done-manifest records run on a bounded single-writer thread
    # (io/output.py AsyncOutputWriter) that preserves the atomic tmp+rename
    # and write-before-done ordering; write failures surface classified per
    # video (docs/performance.md). False = write inline in the video loop.
    async_writer: bool = True
    # jax.profiler trace directory; also enables the per-video stage report
    # (decode vs device_wait vs overlapped time). VFT_METRICS=1 enables the
    # report without tracing.
    profile_dir: Optional[str] = None
    # Telemetry directory (docs/observability.md): a structured span/event
    # journal (<dir>/events.jsonl) records every request and video lifecycle
    # (queued → popped → decode → dispatched → device → done/failed, plus
    # cache hits, stale flushes, autoscale resizes, breaker trips) with
    # monotonic timestamps, appended by a bounded single-writer thread that
    # NEVER blocks the hot path (a full queue drops the event and counts the
    # drop). Export to a Chrome/Perfetto trace with
    # `python -m video_features_tpu.obs.export <dir>/events.jsonl`. Works in
    # batch runs and the --serve daemon (which also serves healthz/metrics/
    # profile socket ops from the same subsystem). None = off (no journal;
    # the daemon's in-memory metrics registry stays on regardless).
    telemetry_dir: Optional[str] = None
    # TPU fp32 convs default to bf16 MXU passes; "highest" gives true-fp32
    # accumulation for the bit-parity path (None = XLA default).
    matmul_precision: Optional[str] = None
    # Host wire-format escape hatch (flow extractors): stage frame windows as
    # float32 on the host — the pre-uint8 behavior — instead of shipping the
    # decoded uint8 bytes and casting inside the jitted step. 4× the
    # host→device bytes and host staging churn for IDENTICAL output bytes
    # (the u8→fp32 cast is exact; pinned by tests/test_ingest.py); exists as
    # an escape hatch if a backend ever mishandles uint8 transfers (nothing
    # measures it: ROADMAP D4).
    float32_wire: bool = False
    # Device-side resize (resnet50): ship RAW decoded frames and run the
    # smaller-edge bilinear resize + center crop inside the jitted step
    # (jax.image.resize) instead of per-frame host PIL — removes the largest
    # remaining host CPU cost per frame (ROADMAP item 4). NOT bit-identical
    # to the PIL host path (PIL's uint8 rounding vs XLA's float bilinear —
    # tolerance pinned in tests/test_ingest.py, documented in
    # docs/performance.md), so off by default per the ops/image.py parity
    # contract. Packed runs queue slots per decoded geometry (like i3d);
    # other feature types route the same idea through --device_preproc.
    device_resize: bool = False
    # Device-side preprocessing everywhere (generalizes --device_resize
    # from resnet50 to every feature type — ROADMAP item 4 completed): each
    # model ships its RAWEST wire format and runs the remaining host-side
    # transform as a fused prologue op inside the jitted step, so the
    # CPU-bound decode pool stops paying per-frame PIL/numpy costs.
    # Per model: resnet50 behaves exactly as --device_resize; i3d moves the
    # PIL edge resize on device (ops/image.device_edge_resize_hwc,
    # tolerance-gated like resnet's — fingerprints); raft/pwc ship RAW
    # decoded frames and replicate-pad to the /8 (or bucket) geometry on
    # device (models/raft.device_pad_to_shape on the uint8 wire — BYTE-exact
    # vs the host pad, execution-only); vggish ships raw PCM slabs and runs
    # the log-mel STFT/mel pipeline on device (ops/audio.log_mel_examples,
    # ≤2e-5 vs the numpy oracle — fingerprints); r21d's transform has been
    # fully device-fused since its port (the flag is a documented no-op
    # there). It trades host decode seconds for host→device bytes (not
    # measured on this installation); parity pins live in
    # tests/test_device_preproc.py.
    device_preproc: bool = False
    # Dense-flow D2H transfer dtype (raft/pwc extractors): the device casts
    # the flow before the host fetch and the host upcasts back to fp32 (.npy
    # outputs stay fp32). "float16" halves the fetched bytes at ≤0.01 px
    # quantization for |flow| ≤ 32; "bfloat16" at ≤0.16 px for |flow| ≈ 20.
    # "float32" (default) is bit-parity.
    transfer_dtype: str = "float32"
    # --- reliability knobs (docs/reliability.md) ---
    # Bounded retry for transient per-video failures (FfmpegError, DeviceError,
    # OutputError): number of RE-attempts after the first failure. Permanent
    # classes (DecodeError, VideoTimeoutError) never retry.
    retries: int = 2
    # First backoff delay in seconds; doubles per retry (capped at 30 s).
    retry_backoff: float = 0.5
    # Per-video watchdog: cancel and classify any video whose attempt exceeds
    # this many seconds (a wedged cv2 read or ffmpeg child must not stall the
    # fleet). None (default) = no timeout.
    video_timeout: Optional[float] = None
    # Circuit breaker: abort the run (exit code 2) once MORE THAN this many
    # videos have terminally failed — a job drowning in errors usually has a
    # systemic cause (bad mount, dead device) and burning the rest of the
    # corpus hides it. None = never abort. 0 = abort on the first failure.
    max_failures: Optional[int] = None
    # Reprocess exactly the videos recorded in the failure manifest
    # (.failed_manifest.jsonl beside the done-manifest) instead of the given
    # video list; retried entries are pruned and re-append only if they fail
    # again.
    retry_failed: bool = False
    # --- serving knobs (--serve daemon, docs/serving.md) ---
    # Run the always-on extraction service instead of the batch loop: watch
    # --spool_dir for per-tenant request files (plus a local-socket API),
    # schedule videos weighted-fair + deadline across tenants, and keep the
    # corpus packer's slot queues warm across requests (serve/daemon.py).
    serve: bool = False
    # Watched request directory (required with --serve): tenants drop
    # <request_id>.json files here; <spool_dir>/tenants.json holds per-tenant
    # weights/quotas (SIGHUP re-reads it).
    spool_dir: Optional[str] = None
    # Unix socket for the submit/status/stats/drain/reload API. None =
    # <spool_dir>/control.sock; "none" disables the socket listener.
    socket_path: Optional[str] = None
    # Where per-request .result.json completion records land. None =
    # <spool_dir>/results.
    notify_dir: Optional[str] = None
    # Default per-tenant pending-video quota: a submission that would push a
    # tenant past it is rejected at admission (tenants.json overrides).
    tenant_quota: int = 64
    # Per-tenant circuit breaker: once MORE THAN this many of a tenant's
    # videos have terminally failed, its queued videos fail fast and new
    # submissions are rejected until a SIGHUP reload — other tenants keep
    # flowing. None = never trip (the batch --max_failures analogue, scoped
    # to one tenant instead of the run).
    tenant_max_failures: Optional[int] = None
    # Idle flush latency: with the ingest queue empty and partial slot
    # queues pending, wait this long for more work before pad-flushing so
    # in-flight requests complete (latency over occupancy when there is
    # nothing to pack with).
    idle_flush_sec: float = 0.5
    # Spool directory poll interval.
    spool_poll_sec: float = 0.25
    # Co-resident serving models (--serve only): additional feature types to
    # serve from the SAME daemon and mesh. --feature_type stays the default
    # for requests that omit "feature_type"; each co-loaded model's
    # extractor is constructed lazily on first traffic with its own
    # reference stack/step/stream defaults (explicit per-model overrides
    # apply only to the primary), its own output subtree and manifests, and
    # its own cache fingerprint — while sharing the mesh, the staging ring,
    # the decode pool, the output writer, and the packer's interleaved
    # (model, geometry) dispatch (docs/serving.md). None/empty = the
    # single-model daemon.
    serve_models: Optional[Tuple[str, ...]] = None
    # --- serving durability (serve/wal.py, docs/serving.md "Crash
    # recovery") ---
    # Write-ahead admission log path. None = <spool_dir>/admission.wal
    # (durable admission on by default whenever there is a spool to serve);
    # "none" disables the WAL entirely — an acknowledged submit then lives
    # only in process memory and dies with the daemon.
    wal_path: Optional[str] = None
    # WAL group-commit window: admissions acknowledged within this many
    # seconds of the last fsync share one (batched) fsync. 0 (default) =
    # fsync every appended record before acknowledging — strongest
    # durability; set ~0.05 under high submit rates.
    wal_fsync_sec: float = 0.0
    # Replay unresolved WAL admissions at startup (--no_recover disables):
    # each entry is deduped against published result records and per-model
    # done-manifests, survivors re-enter the scheduler with their original
    # admission seqs and deadlines. With recovery off, unresolved entries
    # are resolved failed and dropped (loudly).
    recover: bool = True
    # healthz `stale` threshold: the op flags the daemon once the serving
    # loop has not stepped for this many seconds (wedged, or a legitimately
    # long first-traffic compile — both mean "not serving right now").
    healthz_stale_sec: float = 10.0
    # Keep claimed <id>.json.accepted spool files after their request's
    # result record publishes (debugging aid); default removes them — the
    # result record is the durable trace.
    spool_retain: bool = False
    # Hung-step watchdog: when the serving loop has not stepped for this
    # many seconds, fail the in-flight videos transiently so they requeue
    # (slot attribution charges no tenant's breaker) instead of waiting out
    # a stalled device step forever. None (default) = off. Set it well above
    # the worst expected compile time.
    step_watchdog_sec: Optional[float] = None
    # --- feature cache (docs/caching.md) ---
    # Content-addressed feature cache directory: sha256(container bytes) ×
    # model-config fingerprint → finished feature dict. A hit skips decode
    # AND the device entirely (outputs + done-manifest entry still written,
    # so --resume composes deterministically); both the batch loops and the
    # --serve daemon consult it before decode, and the daemon additionally
    # coalesces in-flight identical requests (cache/ package). None = off.
    cache_dir: Optional[str] = None
    # Byte cap for the cache directory: publishing past it evicts the
    # least-recently-hit entries (a hit refreshes recency). None = unbounded.
    cache_max_bytes: Optional[int] = None
    # I3D geometry: smaller-edge resize target and center-crop size. The
    # reference hard-codes 256/224 (extract_i3d.py:25 + transforms); these stay
    # the parity defaults. Overriding shrinks the SAME jitted two-stream
    # programs for CI/dry runs (the driver's dryrun_multichip runs the real
    # sandwich at 96/64 so it fits a 1-core host's wall-clock budget).
    i3d_pre_crop_size: int = 256
    i3d_crop_size: int = 224

    def validate(self) -> None:
        """Mirror the reference ``sanity_check`` (``utils/utils.py:88-105``)."""
        import os

        if self.feature_type not in FEATURE_TYPES:
            raise ValueError(
                f"unknown feature_type {self.feature_type!r}; expected one of {FEATURE_TYPES}"
            )
        if self.on_extraction not in ON_EXTRACTION:
            raise ValueError(f"on_extraction must be one of {ON_EXTRACTION}")
        if self.flow_type not in FLOW_TYPES:
            raise ValueError(f"flow_type must be one of {FLOW_TYPES}")
        if self.streams is not None:
            bad = set(self.streams) - set(STREAMS)
            if bad:
                raise ValueError(f"unknown streams {sorted(bad)}; expected subset of {STREAMS}")
        if os.path.relpath(self.output_path) == os.path.relpath(self.tmp_path):
            raise ValueError("The same path for out & tmp")
        if self.feature_type == "r21d_rgb" and self.extraction_fps is not None:
            raise ValueError(
                "r21d_rgb only supports extraction at the original fps; remove extraction_fps"
            )
        if self.batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if self.clips_per_batch < 1:
            raise ValueError("clips_per_batch must be >= 1")
        if self.flow_dtype not in ("float32", "bfloat16"):
            raise ValueError("flow_dtype must be float32|bfloat16")
        if self.raft_corr not in ("auto", "volume", "volume_gather", "on_demand",
                                  "on_demand_matmul"):
            raise ValueError(
                "raft_corr must be auto|volume|volume_gather|on_demand|on_demand_matmul")
        if self.pwc_corr not in ("auto", "xla", "pallas"):
            raise ValueError("pwc_corr must be auto|xla|pallas")
        if self.matmul_precision not in (None, "default", "high", "highest"):
            raise ValueError("matmul_precision must be default|high|highest")
        if self.decode_workers < 0:
            raise ValueError("decode_workers must be >= 1, or 0 for auto "
                             "(start small; the --serve daemon resizes the "
                             "pool live from the measured decode-starvation "
                             "signal)")
        if self.decode_segments < 0:
            raise ValueError("decode_segments must be >= 2 to cap the split, "
                             "1 to disable, or 0 for auto")
        if self.segment_seek not in ("auto", "ffmpeg", "cv2"):
            raise ValueError("segment_seek must be auto|ffmpeg|cv2")
        if self.pack_buckets < 1:
            raise ValueError("pack_buckets must be >= 1")
        if self.pack_flush_age < 0:
            raise ValueError("pack_flush_age must be >= 0 (0 = flush only at "
                             "corpus end)")
        if self.pages_in_flight < 1:
            raise ValueError("pages_in_flight must be >= 1 (2 = the "
                             "double-buffered default)")
        if self.page_tokens < 1:
            raise ValueError("page_tokens must be >= 1")
        if self.retries < 0:
            raise ValueError("retries must be >= 0")
        if self.retry_backoff < 0:
            raise ValueError("retry_backoff must be >= 0")
        if self.video_timeout is not None and self.video_timeout <= 0:
            raise ValueError("video_timeout must be > 0 seconds (omit to disable)")
        if self.max_failures is not None and self.max_failures < 0:
            raise ValueError("max_failures must be >= 0 (0 = abort on first failure)")
        if self.flow_pair_chunk is not None and self.flow_pair_chunk < 0:
            raise ValueError("flow_pair_chunk must be >= 0 (0 = never chunk)")
        if self.use_ffmpeg not in ("auto", "always", "never"):
            raise ValueError("use_ffmpeg must be auto|always|never")
        if self.shape_bucket is not None and (
            self.shape_bucket < 8 or self.shape_bucket % 8
        ):
            raise ValueError("shape_bucket must be a multiple of 8 (RAFT /8 contract)")
        if self.transfer_dtype not in ("float32", "float16", "bfloat16"):
            raise ValueError("transfer_dtype must be float32|float16|bfloat16")
        if self.i3d_crop_size < 32:
            raise ValueError("i3d_crop_size must be >= 32 (five /2 stages)")
        if self.i3d_crop_size % 32:
            # five stride-2 stages: a non-multiple-of-32 crop produces odd
            # intermediate dims (implementation-defined pooling geometry).
            # Legal — 112 is a common I3D crop — so warn instead of rejecting
            # (ADVICE r5); README documents that features may drift across
            # backends at such sizes.
            import sys

            print(f"warning: i3d_crop_size {self.i3d_crop_size} is not a "
                  "multiple of 32; five stride-2 stages produce odd "
                  "intermediate dims (implementation-defined pooling "
                  "geometry) — features may differ across backends",
                  file=sys.stderr)
        if self.i3d_pre_crop_size < self.i3d_crop_size:
            raise ValueError("i3d_pre_crop_size must be >= i3d_crop_size")
        if self.tenant_quota < 1:
            raise ValueError("tenant_quota must be >= 1")
        if self.tenant_max_failures is not None and self.tenant_max_failures < 0:
            raise ValueError("tenant_max_failures must be >= 0 (0 = trip on "
                             "the first failure)")
        if self.idle_flush_sec < 0:
            raise ValueError("idle_flush_sec must be >= 0")
        if self.cache_max_bytes is not None and self.cache_max_bytes < 1:
            raise ValueError("cache_max_bytes must be >= 1 (omit for an "
                             "unbounded cache)")
        if self.cache_max_bytes is not None and self.cache_dir is None:
            raise ValueError("cache_max_bytes needs --cache_dir (it caps the "
                             "cache directory)")
        if self.spool_poll_sec <= 0:
            raise ValueError("spool_poll_sec must be > 0")
        if self.wal_fsync_sec < 0:
            raise ValueError("wal_fsync_sec must be >= 0 (0 = fsync every "
                             "record)")
        if self.healthz_stale_sec <= 0:
            raise ValueError("healthz_stale_sec must be > 0")
        if self.step_watchdog_sec is not None and self.step_watchdog_sec <= 0:
            raise ValueError("step_watchdog_sec must be > 0 (omit to disable "
                             "the watchdog)")
        if self.serve_models:
            if not self.serve:
                raise ValueError("--serve_models co-loads models into the "
                                 "serving daemon; it needs --serve")
            bad = set(self.serve_models) - set(FEATURE_TYPES)
            if bad:
                raise ValueError(f"unknown serve_models {sorted(bad)}; "
                                 f"expected a subset of {FEATURE_TYPES}")
        if self.serve:
            if not self.spool_dir:
                raise ValueError("--serve requires --spool_dir (the watched "
                                 "request directory)")
            if self.on_extraction != "save_numpy":
                raise ValueError("--serve requires --on_extraction "
                                 "save_numpy: the service's product is saved "
                                 "features plus per-request result records")
            if self.retry_failed:
                raise ValueError("--retry_failed is a batch-run flag; the "
                                 "--serve daemon re-enqueues transient "
                                 "failures through its scheduler instead")
            if self.max_failures is not None:
                raise ValueError("--max_failures aborts the whole RUN — a "
                                 "policy that crosses tenant boundaries; "
                                 "use --tenant_max_failures, the per-tenant "
                                 "breaker, with --serve")
            if self.show_pred:
                raise ValueError("--show_pred is batch-only (per-batch "
                                 "prints assume video order; no packing "
                                 "path)")

    def replace(self, **kw) -> "ExtractionConfig":
        return dataclasses.replace(self, **kw)


# Per-model defaults; reference keeps these as module constants
# (extract_i3d.py:21-29, extract_r21d.py:15-20, extract_resnet50.py:17-20).
MODEL_DEFAULTS = {
    "i3d": dict(stack_size=64, step_size=64),
    "r21d_rgb": dict(stack_size=16, step_size=16),
    "resnet50": dict(),
    "raft": dict(),
    "pwc": dict(),
    "vggish": dict(),
    # the text stream has one path, the packed one, and one page program on
    # one chip: the checkpoint is that chip's share of the experts
    **{t: dict(num_devices=1) for t in TOKEN_TYPES},
}


def resolve_model_defaults(cfg: ExtractionConfig) -> ExtractionConfig:
    """Fill in per-model stack/step defaults when the user did not override them."""
    defaults = MODEL_DEFAULTS.get(cfg.feature_type, {})
    updates = {k: v for k, v in defaults.items() if getattr(cfg, k) is None}
    streams = cfg.streams
    if cfg.feature_type == "i3d" and streams is None:
        streams = ("rgb", "flow")
    if streams is not None:
        updates["streams"] = tuple(streams)
    if cfg.feature_type in TOKEN_TYPES and not cfg.pack_corpus:
        updates["pack_corpus"] = True
    return cfg.replace(**updates) if updates else cfg


def config_from_namespace(ns) -> ExtractionConfig:
    """Build an ExtractionConfig from an argparse namespace using reference flag names."""
    fields = {f.name for f in dataclasses.fields(ExtractionConfig)}
    kw = {}
    for key, value in vars(ns).items():
        if key not in fields:
            continue
        if key in ("video_paths", "streams", "serve_models") and value is not None:
            value = tuple(value)
        kw[key] = value
    if kw.get("video_paths") is None:
        kw["video_paths"] = ()
    cfg = ExtractionConfig(**kw)
    cfg = resolve_model_defaults(cfg)
    cfg.validate()
    return cfg

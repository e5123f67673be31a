"""Reference-compatible command line (``/root/reference/main.py:52-84`` flag surface).

``--device_ids`` (CUDA ordinals) is accepted for drop-in compatibility but maps to the
TPU runtime's device count; new TPU-specific flags are added under the same parser.
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

from .config import (
    FEATURE_TYPES,
    FLOW_TYPES,
    ON_EXTRACTION,
    STREAMS,
    ExtractionConfig,
    config_from_namespace,
)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="Extract Features (TPU-native)")
    parser.add_argument("--feature_type", required=True, choices=list(FEATURE_TYPES))
    parser.add_argument("--video_paths", nargs="+", help="space-separated paths to videos")
    parser.add_argument("--file_with_video_paths", help=".txt file where each line is a path")
    parser.add_argument("--device_ids", type=int, nargs="+",
                        help="compat shim: length = number of TPU devices to use")
    parser.add_argument("--tmp_path", default="./tmp",
                        help="folder for temporary files (re-encoded videos, wav files)")
    parser.add_argument("--keep_tmp_files", action="store_true", default=False,
                        help="keep temp files after extraction (vggish and i3d)")
    parser.add_argument("--on_extraction", default="print", choices=list(ON_EXTRACTION),
                        help="what to do once the stack is extracted")
    parser.add_argument("--output_path", default="./output", help="where to store results if saved")
    parser.add_argument("--extraction_fps", type=int, help="do not specify for original video fps")
    parser.add_argument("--stack_size", type=int, help="feature time span in frames")
    parser.add_argument("--step_size", type=int, help="feature step size in frames")
    parser.add_argument("--streams", nargs="+", choices=list(STREAMS),
                        help="streams to use for i3d; both if not specified")
    parser.add_argument("--flow_type", choices=list(FLOW_TYPES), default="pwc",
                        help="flow net used in i3d. PWC is faster, RAFT more accurate.")
    parser.add_argument("--batch_size", type=int, default=1,
                        help="batch size for frame-wise / frame-pair extractors")
    parser.add_argument("--resize_to_larger_edge", dest="resize_to_smaller_edge",
                        action="store_false", default=True,
                        help="resize the larger side to --side_size instead of the smaller")
    parser.add_argument("--side_size", type=int,
                        help="if specified, inputs are edge-resized to this size (raft/pwc)")
    parser.add_argument("--show_pred", action="store_true", default=False,
                        help="print model predictions (kinetics/imagenet top-5)")

    # TPU-native flags (no reference equivalent)
    parser.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"],
                        help="device compute dtype; float32 gives reference parity")
    parser.add_argument("--clips_per_batch", type=int, default=1,
                        help="clips per jitted device step (MXU utilization)")
    parser.add_argument("--num_devices", type=int, default=None,
                        help="devices in the data-parallel mesh (default: all local)")
    parser.add_argument("--resume", action="store_true", default=False,
                        help="skip videos recorded in the output done-manifest")
    parser.add_argument("--flow_dtype", default="float32",
                        choices=["float32", "bfloat16"],
                        help="RAFT/PWC conv + correlation storage dtype; "
                             "correlation ACCUMULATION and coordinate math stay "
                             "fp32 either way (float32 = reference parity; "
                             "measured bf16 drift in tests/test_flow_bf16.py)")
    parser.add_argument("--raft_corr",
                        choices=["auto", "volume", "volume_gather", "on_demand",
                                 "on_demand_matmul"],
                        default="auto",
                        help="RAFT correlation: auto (default) = materialized "
                             "pyramid with MXU matmul lookup unless the volume "
                             "would outgrow HBM for the frame size, then "
                             "on_demand (the alt_cuda_corr equivalent, O(H*W) "
                             "memory); or force volume / volume_gather / "
                             "on_demand / on_demand_matmul (the MXU volume "
                             "remat, never auto's choice)")
    parser.add_argument("--pwc_corr", choices=["auto", "xla", "pallas"],
                        default="auto",
                        help="PWC cost-volume implementation: auto picks the "
                             "Pallas tile kernel where its VMEM gate admits "
                             "the shape, else the fused XLA formulation")
    parser.add_argument("--flow_pair_chunk", type=int, default=None,
                        help="i3d flow sandwich: decode PWC pairs in sub-batches "
                             "of this size to bound HBM (default: auto; 0 = never; "
                             "PWC only — the RAFT sandwich bounds memory via "
                             "--raft_corr auto instead)")
    parser.add_argument("--float32_wire", action="store_true", default=False,
                        help="flow models: stage frame windows as float32 on "
                             "the host (the pre-uint8 wire format) — 4x the "
                             "host->device bytes for byte-identical outputs; "
                             "an escape hatch "
                             "(docs/performance.md ingest fast path)")
    parser.add_argument("--device_resize", action="store_true", default=False,
                        help="resnet50: ship RAW decoded frames and run the "
                             "edge resize + center crop inside the jitted "
                             "step (jax.image.resize) — removes the host PIL "
                             "resize cost; NOT bit-identical to the PIL path "
                             "(documented tolerance, docs/performance.md); "
                             "off = bit-parity. --device_preproc is the "
                             "every-model generalization")
    parser.add_argument("--device_preproc", action="store_true", default=False,
                        help="move every remaining host-side preprocess "
                             "inside the jitted step (generalizes "
                             "--device_resize to all feature types): "
                             "resnet50/i3d resize on device (documented "
                             "tolerance), raft/pwc ship raw frames and "
                             "replicate-pad on device (byte-exact), vggish "
                             "ships raw PCM and computes the log-mel on "
                             "device (<=2e-5 vs the numpy oracle); r21d has "
                             "been fully device-side since its port. Frees "
                             "the decode pool from per-frame PIL/numpy work "
                             "at more host->device bytes per video "
                             "(docs/performance.md ingest fast path)")
    parser.add_argument("--transfer_dtype", default="float32",
                        choices=["float32", "float16", "bfloat16"],
                        help="raft/pwc: cast dense flow to this on device "
                             "before the host fetch (halves/quarters D2H "
                             "bytes; host upcasts, .npy outputs stay fp32; "
                             "float16 quantizes <=0.01 px for |flow|<=32)")
    parser.add_argument("--i3d_pre_crop_size", type=int, default=256,
                        help="i3d smaller-edge resize target (reference: 256); "
                             "override only for CI/dry runs — non-default values "
                             "change features")
    parser.add_argument("--i3d_crop_size", type=int, default=224,
                        help="i3d center-crop size (reference: 224); override "
                             "only for CI/dry runs — non-default values change "
                             "features")
    parser.add_argument("--decode_workers", type=int, default=1,
                        help="background threads decoding upcoming videos while the "
                             "device computes (frame-stream models); 1 = inline; "
                             "0 = auto (start from a CPU-derived size; the "
                             "--serve daemon then grows/shrinks the pool live "
                             "from the measured occupancy vs decode signal)")
    parser.add_argument("--decode_segments", type=int, default=0,
                        help="segmented intra-video decode: split one video "
                             "into seek-aligned segments decoded concurrently "
                             "by the pool and streamed back in order, "
                             "byte-identical to sequential decode; 0 = auto "
                             "(segment long videos when the pool has idle "
                             "permits), 1 = off, N caps the split; needs "
                             "--decode_workers > 1")
    parser.add_argument("--segment_seek", default="auto",
                        choices=["auto", "ffmpeg", "cv2"],
                        help="seek backend landing a segment on its start "
                             "frame: auto = verified cv2 CAP_PROP_POS_FRAMES "
                             "seek with ffmpeg -ss fast-seek fallback for "
                             "resampled streams cv2 cannot land on; "
                             "cv2/ffmpeg force a backend")
    parser.add_argument("--pack_corpus", action="store_true", default=False,
                        help="corpus-level clip packing: fill every device "
                             "batch with clips from however many videos are "
                             "ready instead of zero-padding each video's tail "
                             "batch. Every feature type packs (RGB stacks, "
                             "flow frame-pairs, i3d sandwich stacks, vggish "
                             "log-mel slabs; flow models bucket mixed "
                             "geometries via --pack_buckets, other models "
                             "queue per decoded shape) — the per-video "
                             "fallbacks are "
                             "--show_pred and the single-clip frame-sharded "
                             "flow sandwich, each with a printed notice. "
                             "Per-video fault attribution and resume "
                             "preserved; features are byte-identical to the "
                             "per-video loop except where a merged flow "
                             "bucket pads frames (--pack_buckets border "
                             "caveat) — docs/performance.md")
    parser.add_argument("--pack_buckets", type=int, default=4,
                        help="--pack_corpus flow models: cluster the corpus's "
                             "probed geometries into at most this many padded "
                             "shape buckets (one compiled program each) "
                             "before decode starts; merged buckets carry "
                             "--shape_bucket's border-perturbation caveat")
    parser.add_argument("--no_paged_batching", dest="paged_batching",
                        action="store_false", default=True,
                        help="disable ragged paged dispatch under "
                             "--pack_corpus: buckets fall back to batch_size "
                             "padded batches (one in flight) instead of "
                             "fixed-size pages with an int32 row table and "
                             "a donated table buffer. Paged dispatch is on "
                             "by default for the slot-shaped paths "
                             "(resnet50 — including raw-wire "
                             "--device_resize/--device_preproc frames, "
                             "r21d, i3d stacks, vggish), with "
                             "mixed-geometry slots paging per-queue under "
                             "one compiled family; the collate models "
                             "(raft/pwc) always dispatch bucketed — "
                             "docs/performance.md")
    parser.add_argument("--pages_in_flight", type=int, default=2,
                        help="paged dispatch: in-flight pages per bucket "
                             "(page_rows = ceil(batch budget / depth), so "
                             "total in-flight rows match one bucketed "
                             "batch; >= 2 overlaps host refill with device "
                             "compute)")
    parser.add_argument("--page_tokens", type=int, default=16384,
                        help="laguna, sarvam, qwen3_next, jamba, lfm2: token slots of one device page (whole "
                             "transcripts share a page: the oldest queued and, "
                             "of two pages' worth, the others that fill it "
                             "best; a longer transcript is refused); a "
                             "multiple of 512")
    parser.add_argument("--pack_flush_age", type=int, default=8,
                        help="--pack_corpus anti-starvation flush: dispatch a "
                             "bucket's partial queue once this many videos "
                             "finished while it waited, so a rare geometry "
                             "cannot strand its videos until corpus end "
                             "(0 = flush only at corpus end)")
    parser.add_argument("--shape_bucket", type=int, default=None,
                        help="flow models: replicate-pad frames to multiples of this "
                             "size (multiple of 8) so a mixed-resolution corpus "
                             "compiles one program per bucket, not per geometry; "
                             "off = reference-exact /8 padding only")
    parser.add_argument("--use_ffmpeg", choices=["auto", "always", "never"],
                        default="auto",
                        help="--extraction_fps backend: ffmpeg re-encode when "
                             "installed (auto; reference parity) or the native "
                             "vf_fps-semantics sampler (never; host-independent)")
    parser.add_argument("--vggish_postprocess", action="store_true", default=False,
                        help="apply the AudioSet PCA-whiten + uint8 quantize "
                             "postprocessor to VGGish embeddings (vendored params; "
                             "the reference loads but never applies it)")
    # Reliability flags (docs/reliability.md)
    parser.add_argument("--retries", type=int, default=2,
                        help="re-attempts after a TRANSIENT per-video failure "
                             "(FfmpegError/DeviceError/OutputError); permanent "
                             "classes (DecodeError, watchdog timeouts) never retry")
    parser.add_argument("--retry_backoff", type=float, default=0.5,
                        help="first retry delay in seconds; doubles per retry "
                             "(capped at 30s)")
    parser.add_argument("--video_timeout", type=float, default=None,
                        help="per-video watchdog: cancel any video whose attempt "
                             "exceeds this many seconds and record it as "
                             "VideoTimeoutError (default: no timeout)")
    parser.add_argument("--max_failures", type=int, default=None,
                        help="circuit breaker: abort the run (exit code 2) once "
                             "more than this many videos have terminally failed "
                             "(0 = abort on first failure; default: never)")
    parser.add_argument("--retry_failed", action="store_true", default=False,
                        help="reprocess exactly the videos in the failure manifest "
                             "(<output>/<feature_type>/.failed_manifest.jsonl) "
                             "instead of --video_paths/--file_with_video_paths")
    parser.add_argument("--precompile", action="store_true", default=False,
                        help="flow models: warm the device program for each "
                             "video's (bucketed) geometry in a background "
                             "thread while the host decodes, overlapping "
                             "mixed-resolution recompiles with decode "
                             "(combine with --shape_bucket; the persistent "
                             "compile cache keeps the results across runs)")
    parser.add_argument("--sync_writer", dest="async_writer",
                        action="store_false", default=True,
                        help="disable the async output writer and serialize "
                             ".npy writes inside the per-video loop (the "
                             "default writer thread overlaps serialization "
                             "with the next video's compute, preserving "
                             "atomic writes and write-before-done ordering)")
    # Serving flags (--serve daemon, docs/serving.md)
    parser.add_argument("--serve", action="store_true", default=False,
                        help="run the always-on extraction service instead "
                             "of the batch loop: watch --spool_dir for "
                             "per-tenant request files (+ a local-socket "
                             "API), schedule videos weighted-fair + deadline "
                             "across tenants, and keep the corpus packer's "
                             "slot queues warm across requests; SIGTERM "
                             "drains, SIGHUP reloads (docs/serving.md)")
    parser.add_argument("--spool_dir", default=None,
                        help="--serve: watched request directory — tenants "
                             "drop <request_id>.json files here; "
                             "tenants.json in the same directory sets "
                             "per-tenant weights/quotas")
    parser.add_argument("--socket_path", default=None,
                        help="--serve: Unix socket for the submit/status/"
                             "stats/drain/reload API (default: "
                             "<spool_dir>/control.sock; 'none' disables)")
    parser.add_argument("--notify_dir", default=None,
                        help="--serve: directory for per-request "
                             "<request_id>.result.json completion records "
                             "(default: <spool_dir>/results)")
    parser.add_argument("--tenant_quota", type=int, default=64,
                        help="--serve: default per-tenant pending-video "
                             "quota; submissions past it are rejected at "
                             "admission (tenants.json overrides per tenant)")
    parser.add_argument("--tenant_max_failures", type=int, default=None,
                        help="--serve: per-tenant circuit breaker — once "
                             "more than this many of a tenant's videos "
                             "terminally failed, fail its queue fast and "
                             "reject its submissions until SIGHUP reload "
                             "(0 = trip on first failure; default: never)")
    parser.add_argument("--idle_flush_sec", type=float, default=0.5,
                        help="--serve: with the ingest queue idle, wait this "
                             "long before pad-flushing partial slot queues "
                             "so in-flight requests complete (latency over "
                             "occupancy when there is nothing to pack with)")
    parser.add_argument("--spool_poll_sec", type=float, default=0.25,
                        help="--serve: spool directory poll interval")
    parser.add_argument("--serve_models", nargs="+",
                        choices=list(FEATURE_TYPES), default=None,
                        help="--serve: co-load these additional feature "
                             "types into the SAME daemon and mesh — "
                             "requests pick one via their 'feature_type' "
                             "key (--feature_type stays the default) and "
                             "the packer interleaves dispatch round-robin "
                             "across models, so mixed traffic never drains "
                             "the device. Each model keeps its own output "
                             "subtree, manifests, reference stack/step "
                             "defaults, and cache fingerprint "
                             "(docs/serving.md)")
    # Serving durability (serve/wal.py, docs/serving.md "Crash recovery")
    parser.add_argument("--wal_path", default=None,
                        help="--serve: write-ahead admission log — every "
                             "accepted request is on disk before its submit "
                             "is acknowledged, and a crashed daemon replays "
                             "unresolved entries at the next start (default: "
                             "<spool_dir>/admission.wal; 'none' disables "
                             "durable admission)")
    parser.add_argument("--wal_fsync_sec", type=float, default=0.0,
                        help="--serve: WAL group-commit window — admissions "
                             "within this many seconds share one batched "
                             "fsync (default 0: fsync every record before "
                             "acknowledging; ~0.05 recommended under high "
                             "submit rates)")
    parser.add_argument("--no_recover", dest="recover", action="store_false",
                        default=True,
                        help="--serve: do NOT replay unresolved WAL "
                             "admissions at startup — they are resolved "
                             "failed and dropped (default: replay, deduped "
                             "against published results and done-manifests, "
                             "with original admission seqs and deadlines)")
    parser.add_argument("--healthz_stale_sec", type=float, default=10.0,
                        help="--serve: healthz flags the daemon `stale` once "
                             "the serving loop has not stepped for this many "
                             "seconds (wedge, or a legitimately long "
                             "first-traffic compile)")
    parser.add_argument("--spool_retain", action="store_true", default=False,
                        help="--serve: keep claimed <id>.json.accepted spool "
                             "files after their result record publishes "
                             "(debugging; default removes them)")
    parser.add_argument("--step_watchdog_sec", type=float, default=None,
                        help="--serve: hung-step watchdog — when the serving "
                             "loop stalls past this many seconds, fail the "
                             "in-flight videos transiently so they requeue "
                             "instead of waiting out a wedged device step "
                             "(set well above the worst expected compile "
                             "time; default: off)")
    # Feature cache (docs/caching.md)
    parser.add_argument("--cache_dir", default=None,
                        help="content-addressed feature cache: "
                             "sha256(container bytes) x model-config "
                             "fingerprint -> finished features. A hit costs "
                             "zero decode and zero device steps and still "
                             "writes outputs + a done-manifest entry "
                             "(--resume composes); the --serve daemon also "
                             "coalesces in-flight identical requests so N "
                             "tenants submitting the same video run one "
                             "extraction (docs/caching.md)")
    parser.add_argument("--cache_max_bytes", type=int, default=None,
                        help="--cache_dir byte cap: publishing past it "
                             "evicts the least-recently-hit entries "
                             "(default: unbounded)")
    parser.add_argument("--profile_dir", default=None,
                        help="write a jax.profiler trace here and print per-video "
                             "stage timing (decode vs device wait)")
    parser.add_argument("--telemetry_dir", default=None,
                        help="write a structured span/event journal "
                             "(<dir>/events.jsonl) of every request/video "
                             "lifecycle — queued, popped, decode, device, "
                             "done/failed, cache hits, breaker trips — via a "
                             "bounded writer thread that never blocks the "
                             "hot path; export a Chrome/Perfetto trace with "
                             "`python -m video_features_tpu.obs.export "
                             "<dir>/events.jsonl` (docs/observability.md)")
    parser.add_argument("--matmul_precision", default=None,
                        choices=["default", "high", "highest"],
                        help="TPU fp32 matmul/conv precision; 'highest' for "
                             "bit-parity with the torch reference")
    return parser


def parse_args(argv: Optional[Sequence[str]] = None) -> ExtractionConfig:
    ns = build_parser().parse_args(argv)
    if ns.device_ids is not None and ns.num_devices is None:
        ns.num_devices = len(ns.device_ids)
    if ns.show_pred:
        # reference forces a single device for prediction printing (utils/utils.py:95-97)
        print("You want to see predictions. So, I will use only one device.")
        ns.num_devices = 1
        if ns.feature_type == "vggish":
            print("Showing class predictions is not implemented for VGGish")
    if ns.on_extraction == "save_numpy":
        print(f"Saving features to {ns.output_path}")
    if ns.keep_tmp_files:
        print(f"Keeping temp files in {ns.tmp_path}")
    return config_from_namespace(ns)

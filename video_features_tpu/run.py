"""Extract features from videos — TPU-native CLI driver.

Drop-in surface of the reference ``main.py`` (same flags), invoked via the repo's
``main.py`` shim or the ``video-features-tpu`` console script::

    python main.py --feature_type i3d --video_paths a.mp4 b.mp4 --on_extraction save_numpy

Videos are embarrassingly parallel: the list is processed by the extractor, whose
device step is jit-compiled for the local TPU mesh; multi-host jobs shard the list
round-robin per host (``--num_devices`` governs the local mesh size).

Exit codes: 0 — every video succeeded; 1 — some videos failed (classified records
in the failure manifest, reprocess with ``--retry_failed``) or the video list was
empty; 2 — the run aborted before processing the full list: the ``--max_failures``
circuit breaker tripped, or the invocation was invalid (``--retry_failed`` on a
multi-host job; argparse flag errors also exit 2). See docs/reliability.md.

``--serve`` runs the always-on extraction service instead (ingest queue,
tenant scheduler, continuous-batching daemon — docs/serving.md): exit 0 after
a clean drain, 1 when some videos terminally failed, 2 on invalid invocation.
"""

import sys

from video_features_tpu.cli import parse_args
from video_features_tpu.extractors import get_extractor
from video_features_tpu.parallel.mesh import describe_devices, enable_compilation_cache


def main(argv=None) -> int:
    enable_compilation_cache()
    cfg = parse_args(argv)

    if cfg.serve:
        # the always-on extraction service (docs/serving.md): single-host by
        # design — the spool/socket ingest and the shared manifests assume
        # one process owns the output tree
        from video_features_tpu.serve import serve

        return serve(cfg)

    # Multi-host bootstrap (DCN): must precede the first device access so every
    # process sees the global topology; no-op on single-host jobs.
    from video_features_tpu.parallel import maybe_initialize_distributed

    if maybe_initialize_distributed():
        import jax

        print(f"multi-host job: process {jax.process_index()}/{jax.process_count()}")

    extractor = get_extractor(cfg)
    print(f"[run] {describe_devices(extractor.runner.mesh)}")
    if cfg.retry_failed:
        # reprocess exactly the failure-manifest set; each video's record is
        # pruned as it succeeds (an interrupted retry run loses no records)
        # and re-appends only if it fails again. Single-host only — enforced,
        # because concurrent per-host manifest rewrites would clobber records.
        import jax

        from video_features_tpu.reliability import load_failures

        if jax.process_count() > 1:
            print("--retry_failed is single-host only: concurrent hosts "
                  "rewriting the shared failure manifest would lose records. "
                  "Run it from one host (it processes only the failed set).",
                  file=sys.stderr)
            return 2
        paths = sorted(load_failures(extractor.output_dir))
        if not paths:
            print("No failed videos to retry (failure manifest is empty).")
            return 0
        print(f"--retry_failed: reprocessing {len(paths)} video(s) from the failure manifest")
    else:
        paths = extractor.video_list()
    if not paths:
        print("No videos to process.")
        return 1

    # Multi-host jobs: each process owns a round-robin shard of the video list
    # (the reference's gen_file_list.py split, without the manual file juggling).
    from video_features_tpu.parallel import shard_video_list

    paths = shard_video_list(paths)
    if not paths:
        print("No videos assigned to this host.")
        return 0

    def progress(done, total):
        print(f"\r[{done}/{total}] videos processed", end="", flush=True)

    from video_features_tpu.reliability import CircuitBreakerTripped, failed_manifest_path

    try:
        ok = extractor.run(paths, progress=progress)
    except CircuitBreakerTripped as e:
        print()
        print(f"aborted: {e}")
        return 2
    print()
    # --pack_corpus: how full the dispatched device batches actually were
    # (real clips / device slots; the per-video loop's tail padding is the
    # baseline this should beat on short-clip corpora)
    stats = getattr(extractor, "_pack_stats", None)
    if stats and stats.get("dispatched_slots"):
        print(f"packing occupancy: {stats['real_slots']}/"
              f"{stats['dispatched_slots']} device slots "
              f"({stats['occupancy']:.1%})")
        buckets = stats.get("buckets") or {}
        if len(buckets) > 1:  # mixed-geometry corpus: per-bucket accounting
            for name, b in buckets.items():
                print(f"  bucket {name}: {b['real_slots']}/"
                      f"{b['dispatched_slots']} slots "
                      f"({b['occupancy']:.1%}, "
                      f"stale_flushes={b['stale_flushes']})")
    # --cache_dir: how much work the content-addressed feature cache saved
    # (a hit = zero decode + zero device steps; docs/caching.md)
    cache = getattr(extractor, "_cache", None)
    if cache is not None:
        s = cache.stats()
        line = (f"feature cache: {s['hits']} hit(s) / {s['misses']} miss(es) "
                f"({s['hit_rate']:.1%} hit rate), "
                f"{s['hit_bytes'] / 1e6:.1f} MB served, "
                f"{s['puts']} published")
        if s["evictions"] or s["quarantined"]:
            line += (f", {s['evictions']} evicted, "
                     f"{s['quarantined']} quarantined")
        print(line)
    # --telemetry_dir: where the span journal landed and whether the bounded
    # writer had to drop events (docs/observability.md)
    journal = getattr(extractor, "_journal", None)
    if journal is not None:
        s = journal.stats()
        line = (f"telemetry: {s['written']} event(s) journaled to "
                f"{journal.path}")
        if s["dropped"]:
            line += f", {s['dropped']} dropped (bounded queue)"
        if s["write_errors"]:
            line += f", {s['write_errors']} write error(s)"
        print(line)
        print("  view:  python -m video_features_tpu.obs.export "
              f"{journal.path}")
    failed = len(paths) - ok
    if failed:
        print(f"{failed} video(s) failed; classified records in "
              f"{failed_manifest_path(extractor.output_dir)} "
              "(rerun with --retry_failed after fixing the cause)")
    return 0 if failed == 0 else 1


if __name__ == "__main__":
    sys.exit(main())

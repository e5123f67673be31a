"""Shared extraction pipeline skeleton.

Factors the loop every reference extractor re-implements (``extract_*.py``): iterate
videos with a per-video fault barrier (log & continue — ``extract_i3d.py:107-117``),
hand each finished feature dict to the output action, track progress. Adds what the
reference lacks: a done-manifest for resume, device-count awareness, and the
reliability layer (:mod:`..reliability`) — classified errors, bounded retry with
backoff for transient failures, a per-video watchdog, a failure manifest, and a
``--max_failures`` circuit breaker.
"""

from __future__ import annotations

import abc
import contextlib
import functools
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from ..config import ExtractionConfig, resolve_model_defaults
from ..io.filelist import form_video_list
from ..io.output import (
    AsyncOutputWriter,
    WriteHandle,
    feature_output_dir,
    feats_nbytes,
    load_done_set,
    write_outputs,
)
from ..io.video import open_video, open_video_segment, plan_segments, probe_video
from ..io import ffmpeg as ffmpeg_io
from ..parallel import MeshRunner
from ..parallel.pipeline import DecodePrefetcher, HostStagingRing
from ..reliability import (
    CircuitBreakerTripped,
    DeviceError,
    RetryPolicy,
    VideoTimeoutError,
    classify,
    failed_manifest_path,
    fault_point,
    prune_failures,
    record_failure,
    retry_call,
    run_with_timeout,
)
from ..obs import MetricsRegistry, SpanJournal
from ..obs.journal import JOURNAL_NAME
from ..utils.metrics import (
    BLOCKED_RECORD_SECONDS,
    SpanRecorder,
    StageClock,
    decode_starvation_warning,
    maybe_profiler,
    metrics_enabled,
    setup_recorder,
    setup_report,
    setup_span,
    span,
)
from ..weights.store import load_weights, resolve_params


# Active only while the multi-model serving layer (MultiModelSessions)
# constructs a co-resident model's extractor: the dict names already-built
# resources (mesh runner, host staging ring) the new extractor must REUSE
# instead of building its own — co-resident models share one mesh and one
# staging budget by design. Set/cleared on the constructing (daemon) thread
# only, inside _shared_construction; never touched from worker threads.
_CONSTRUCTION_SHARING: Dict[str, object] = {}


@contextlib.contextmanager
def _shared_construction(**resources):
    """Make ``Extractor.__init__`` reuse ``resources`` for the duration."""
    _CONSTRUCTION_SHARING.update(resources)
    try:
        yield
    finally:
        _CONSTRUCTION_SHARING.clear()


class _Constructed(abc.ABCMeta):
    """An extractor's whole construction, its subclass's included, is one
    ``construct`` set-up span: the mesh, the checkpoint and the jitted
    wrappers are built inside it (docs/observability.md "Set-up")."""

    def __call__(cls, cfg, *args, **kwargs):
        with setup_span("construct", model=cfg.feature_type):
            return super().__call__(cfg, *args, **kwargs)


class Extractor(abc.ABC, metaclass=_Constructed):
    """Base class for all per-model pipelines."""

    # True for models that consume the open_video frame stream (resnet50, flow,
    # i3d); r21d (whole-video torchvision-style decode) and vggish (audio)
    # don't, so the decode pool would prefetch frames nobody reads
    uses_frame_stream = False

    # True for models with a --device_resize path (the host PIL edge resize
    # moves inside the jitted step); others print a notice and keep the
    # bit-parity host resize
    supports_device_resize = False

    # True for models with a --device_preproc path (the remaining host-side
    # preprocess — edge resize, /8 pad, log-mel — runs as a fused jitted
    # prologue and the host ships raw decoded data); models without one
    # print a notice and keep their host preprocess
    supports_device_preproc = False

    def __init__(self, cfg: ExtractionConfig):
        cfg = resolve_model_defaults(cfg)
        cfg.validate()
        self.cfg = cfg
        self.feature_type = cfg.feature_type
        # per-feature-type subdirs, as the reference joins them (extract_i3d.py:77-78)
        self.output_dir = feature_output_dir(cfg.output_path, cfg.feature_type)
        self.tmp_dir = os.path.join(cfg.tmp_path, cfg.feature_type)
        # data-parallel mesh every device step runs on; --num_devices selects the
        # mesh size (None = all local devices), replacing the reference's
        # thread-per-GPU dispatch (/root/reference/main.py:37-47). A model
        # co-loaded by the multi-model serving layer reuses the primary
        # extractor's runner (one mesh for all co-resident models).
        self.runner = (_CONSTRUCTION_SHARING.get("runner")
                       or MeshRunner(cfg.num_devices, cfg.matmul_precision))
        # stage clock: always on, never None. The per-video loop opens one
        # per video, the packed loop one per run, the daemon one for its
        # lifetime. The span records (and the printed report) are behind the
        # one switch — VFT_METRICS=1, --profile_dir, --telemetry_dir — and
        # open with the run resources
        self.clock = StageClock()
        self._recorder: Optional[SpanRecorder] = None
        self._setup_reported = False  # the stage report's one set-up line
        # telemetry (docs/observability.md): the span/event journal
        # (--telemetry_dir) and the metrics registry. Opened by
        # _open_telemetry (run resources); a co-loaded serving model shares
        # the primary's instances — one journal file, one registry, one
        # writer thread across every co-resident model
        self._journal: Optional[SpanJournal] = \
            _CONSTRUCTION_SHARING.get("journal")
        self._metrics: Optional[MetricsRegistry] = \
            _CONSTRUCTION_SHARING.get("metrics")
        self._owns_journal = False
        # cross-video decode pool; created by run() when --decode_workers > 1
        # (0 = auto: _resolve_decode_workers picks the start size and the
        # serving daemon resizes it live); _decode_workers is the resolved
        # pool size the run loops use as their schedule-ahead window
        self._decode_pool: Optional[DecodePrefetcher] = None
        self._decode_workers = max(cfg.decode_workers, 1)
        # reusable host staging buffers (docs/performance.md "ingest fast
        # path"): frame-path device batches are assembled into a small
        # per-geometry ring of preallocated buffers instead of a fresh
        # np.stack allocation per batch; a buffer is never rewritten while
        # its device_put is pending, and blocked-on-transfer time lands on
        # the 'transfer' stage. Depth covers the prefetch pipeline (`depth`
        # transfers in flight + one being consumed + one being filled).
        # (a co-loaded model shares the primary's ring: one staging budget,
        # one commit discipline, across every co-resident model's batches)
        self._staging = (_CONSTRUCTION_SHARING.get("staging")
                         or HostStagingRing(
                             depth=max(cfg.prefetch_depth, 1) + 2,
                             on_wait=self._transfer_wait))
        if cfg.device_resize and not type(self).supports_device_resize:
            print(f"--device_resize ignored: {cfg.feature_type} has no "
                  "device-side resize path (use --device_preproc for the "
                  "every-model device preprocessing surface); keeping the "
                  "host PIL resize")
        if cfg.device_preproc and not type(self).supports_device_preproc:
            print(f"--device_preproc ignored: {cfg.feature_type} has no "
                  "device-side preprocessing path; keeping the host "
                  "preprocess")
        # async output writer; created by run() for save_numpy jobs unless
        # --sync_writer opted out. _pending_writes holds (path, WriteHandle)
        # for extractions whose output is still on the writer thread — on
        # self (not loop-local) so an interrupted run can still account the
        # writes the writer drains during shutdown
        self._writer: Optional[AsyncOutputWriter] = None
        self._pending_writes: deque = deque()
        # videos that succeeded in the current run() (failure-manifest pruning)
        self._succeeded: List[str] = []
        # per-run accounting shared by the per-video and packed loops
        self._ok = 0
        self._failures = 0
        self._inline_writes = {"videos_written": 0, "write_bytes": 0}
        # --pack_corpus occupancy of the last packed run (the benchmark reads it):
        # {"real_slots", "dispatched_slots", "occupancy", "video_clips"}
        self._pack_stats: Optional[Dict] = None
        # content-addressed feature cache (--cache_dir, docs/caching.md):
        # the config+weights fingerprint is hashed ONCE here; per-video keys
        # combine it with each container's streaming content digest.
        # _cache_keys remembers consult-time keys until publish (or terminal
        # failure) so the miss → extract → publish path never re-hashes.
        self._cache = None
        self._cache_fp: Optional[str] = None
        self._cache_keys: Dict[str, str] = {}
        if cfg.cache_dir:
            from ..cache import FeatureCache, fingerprint_digest

            try:
                self._cache_fp = fingerprint_digest(cfg)
                # a co-loaded serving model reuses the primary's store (one
                # LRU clock over the shared dir, and no redundant restart
                # rescan on the daemon thread); the fingerprint above stays
                # per model, so entries never collide. Key PRESENT with None
                # inherits the primary's disabled state (its store failed to
                # open — two independent stores over one dir would be worse)
                if "cache" in _CONSTRUCTION_SHARING:
                    self._cache = _CONSTRUCTION_SHARING["cache"]
                else:
                    self._cache = FeatureCache(cfg.cache_dir,
                                               cfg.cache_max_bytes)
            except OSError as e:
                # an unreadable checkpoint / cache dir disables the cache for
                # this run (pass-through), it must not block extraction
                print(f"warning: --cache_dir disabled: {e}", file=sys.stderr)
                self._cache = None
                self._cache_fp = None

    # --- per-model API ---

    @abc.abstractmethod
    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        """Extract features for one video; keys become output-file suffixes."""

    def _host_transform(self, rgb: np.ndarray) -> np.ndarray:
        """Per-frame host transform applied during decode (override per model)."""
        return rgb

    def pack_spec(self):
        """Corpus-packing seam (``--pack_corpus``): a
        :class:`..parallel.packer.PackSpec` wiring this model's fixed-shape
        clip stream, jitted device step, and output assembly into the
        cross-video packer — or None when the config has no packing path.
        Every extractor packs: RGB paths (resnet50, r21d_rgb, i3d) use
        stacked clip slots, the flow extractors pack frame-pair slots through
        the collate seam into shared-frame windows, and vggish packs fixed
        log-mel slabs. The remaining per-video fallbacks are ``--show_pred``
        debug runs (per-batch prints assume video order) and the single-clip
        frame-sharded flow sandwich (one clip already fills the mesh)."""
        return None

    def _paged_fields(self, forward, params, batch_size: int) -> dict:
        """PackSpec kwargs switching this model's buckets to ragged paged
        dispatch (:mod:`..parallel.pages`, ``--paged_batching``).

        ``forward(params, page)`` is the model's pure per-row device step
        (preprocess + apply, NOT jitted — this helper compiles the paged
        wrapper once via :meth:`..parallel.mesh.MeshRunner.jit_paged`, which
        donates the row-table buffer). ``batch_size`` is the model's bucketed
        batch budget; the page holds ``ceil(batch_size / pages_in_flight)``
        rows so total in-flight rows match one bucketed batch. Returns ``{}``
        when ``--no_paged_batching`` globally opts out — callers splat the
        result into their PackSpec; models that must stay bucketed
        (geometry-variable wire formats, collate dispatch) simply never call
        this, which is the per-model opt-out the spec documents."""
        if not self.cfg.paged_batching:
            return {}
        from ..parallel.pages import page_rows_for, paged_program

        depth = self.cfg.pages_in_flight
        page_rows = page_rows_for(batch_size, depth, self.runner.device_batch)
        # memoized per (forward, page budget): pack_spec() runs once per
        # run()/retry pass, and a fresh jax.jit instance would recompile the
        # whole paged program each time (forwards are bound methods, so key
        # by the underlying function — stable across pack_spec calls)
        key = (getattr(forward, "__func__", forward), page_rows, depth)
        cache = self.__dict__.setdefault("_paged_programs", {})
        jitted = cache.get(key)
        if jitted is None:
            jitted = self.runner.jit_paged(paged_program(forward))
            cache[key] = jitted

        def paged_step(page, table):
            # the table's device value is DONATED into the jitted call; the
            # packer holds the host staging buffers until `out` resolves
            return jitted(params, self._put(page), self._put(table))

        return {"paged_step": paged_step, "page_rows": page_rows,
                "pages_in_flight": depth}

    # --- decode (frame-stream models route through the prefetcher) ---

    def _open_inline(self, video_path: str):
        return open_video(
            video_path,
            extraction_fps=self.cfg.extraction_fps,
            tmp_path=self.tmp_dir,
            keep_tmp_files=self.cfg.keep_tmp_files,
            use_ffmpeg=self.cfg.use_ffmpeg,
            transform=self._host_transform,
            retries=self.cfg.retries,
            retry_backoff=self.cfg.retry_backoff,
        )

    def _open_video(self, video_path: str):
        """(meta, frames_iter) — prefetched by a decode worker when the pool
        is active (``--decode_workers``), else decoded inline."""
        if self._decode_pool is not None:
            return self._decode_pool.get(video_path)
        return self._open_inline(video_path)

    # auto-segmentation thresholds (--decode_segments 0): a video is worth
    # splitting only when its decode time plausibly dominates a pool slot —
    # proxied by source length — and each resulting segment amortizes its
    # seek + thread cost over a meaningful run of frames
    AUTO_MIN_SOURCE_FRAMES = 256
    AUTO_MIN_SEGMENT_FRAMES = 96

    def _plan_inline(self, video_path: str, max_segments: int):
        """Segment planner handed to the decode pool (``set_segmenter``).

        Returns None (decode sequentially) unless segmentation is both
        enabled and worthwhile. Never raises: a probe failure here falls
        back to the sequential open, which classifies the container with
        full per-video fault attribution.
        """
        cfg = self.cfg
        if cfg.decode_segments == 1 or max_segments < 2:
            return None
        if (cfg.extraction_fps is not None and cfg.use_ffmpeg != "never"
                and ffmpeg_io.have_ffmpeg()):
            # the ffmpeg re-encode resample path decodes a different
            # (re-encoded) container — its parity anchor is the sequential
            # re-encode, so it is never segmented
            return None
        try:
            meta = probe_video(video_path)
        except Exception:  # noqa: BLE001 — fault-barrier: the real open classifies
            return None
        if cfg.decode_segments:
            limit = min(cfg.decode_segments, max_segments)
            min_frames = 2
        else:
            if meta.frame_count < self.AUTO_MIN_SOURCE_FRAMES:
                return None
            limit = max_segments
            min_frames = self.AUTO_MIN_SEGMENT_FRAMES
        return plan_segments(meta, limit, extraction_fps=cfg.extraction_fps,
                             min_segment_frames=min_frames)

    def _open_segment_inline(self, plan, index: int):
        """Decode one planned segment with this model's host transform."""
        return open_video_segment(plan, index, transform=self._host_transform,
                                  seek=self.cfg.segment_seek)

    # --- observability hooks ---

    def _open_telemetry(self) -> None:
        """Open the span journal (``--telemetry_dir``) and the metrics
        registry. Part of the run resources — the batch loops get it per
        ``run()``, the serving daemon for its lifetime. Idempotent; a
        registry set externally (the daemon's) or a journal inherited from
        the construction-sharing seam (a co-loaded model) is kept."""
        if self._metrics is None and (self.cfg.telemetry_dir or self.cfg.serve):
            self._metrics = MetricsRegistry()
        if self.cfg.telemetry_dir and (
                self._journal is None or self._journal.closed):
            self._journal = SpanJournal(
                os.path.join(self.cfg.telemetry_dir, JOURNAL_NAME))
            self._owns_journal = True
        if self._cache is not None:
            # the store reports quarantines/evictions into the same journal
            self._cache.journal = self._journal
        if self._recorder is None and metrics_enabled(self.cfg.profile_dir,
                                                      self.cfg.telemetry_dir):
            self._recorder = SpanRecorder()

    def _emit(self, event: str, **fields) -> None:
        """Append one journal event (no-op without --telemetry_dir); the
        emit is a non-blocking queue put — never the hot path's problem."""
        if self._journal is not None:
            self._journal.emit(event, model=self.feature_type, **fields)

    def _span(self, name: str, stage: Optional[str] = None, nbytes: int = 0,
              **ids):
        """THE span call: the one place a layer boundary is marked and timed
        (:func:`..utils.metrics.span`). One entry/exit adds its seconds (and
        ``nbytes``) to the stage clock under ``stage``, enters a
        ``jax.profiler.TraceAnnotation``, emits the journal's
        ``<name>_start``/``<name>_end`` pair (``--telemetry_dir``) and, with
        recording on, keeps one record. Yields a handle: ``.ids`` takes what
        is only known inside the span, ``.seconds`` is its duration after.
        The decode pool, the packer and the output writer are handed this
        bound method; they start no timer of their own."""
        return span(name, self.clock, self._recorder, self._journal,
                    stage, nbytes, **ids)

    def _mark_succeeded(self, path: str) -> None:
        """Shared per-video success accounting: the run counter, the
        failure-manifest prune list, and telemetry — every success arm
        (inline write, async-write reap, packed finalize, cache-hit replay)
        lands here so the journal's ``video_done`` stream and the
        ``videos_ok_total`` counter agree with the manifests exactly."""
        self._ok += 1
        self._succeeded.append(path)
        self._emit("video_done", video=path)
        if self._metrics is not None:
            self._metrics.inc("videos_ok_total", model=self.feature_type)

    def _timed_frames(self, frames_iter):
        """The ``pull`` span: host time blocked in ``next()`` on the frame
        stream. Every pull adds to the 'decode' stage, with the decoded
        payload bytes (the ingest-throughput counter the stage report derives
        decode MB/s from); only a pull that blocked a millisecond or longer
        leaves a record (:data:`..utils.metrics.BLOCKED_RECORD_SECONDS`), so
        nothing but the ``perf_counter`` pair runs per frame. The record
        takes its ``video`` from the ``extract`` span it lies in."""
        on_blocked = None
        if self._recorder is not None:
            on_blocked = functools.partial(self._recorder.add, "pull")
        return self.clock.timed_iter(frames_iter, "decode",
                                     bytes_of=lambda item: item[0].nbytes,
                                     on_blocked=on_blocked)

    def _wait(self, device_out, **ids) -> np.ndarray:
        """The ``device`` span: gather a device result, the blocked time on
        the 'device_wait' stage."""
        with self._span("device", stage="device_wait", **ids):
            return np.asarray(device_out)

    def _transfer_wait(self, seconds: float) -> None:
        """Staging-ring backpressure (blocked until a pending host→device
        copy finished) is transfer time: the ring measured it for its own
        counter, so it is added to the 'transfer' stage and recorded as a
        ``put`` span after the fact."""
        self.clock.add_seconds("transfer", seconds)
        if (self._recorder is not None
                and seconds >= BLOCKED_RECORD_SECONDS):
            self._recorder.add("put", seconds, ring_wait=1)

    def _put(self, arr):
        """The ``put`` span: transfer a host batch onto the mesh (sharded
        along axis 0). Host dispatch time and the staged payload bytes land
        on the 'transfer' stage — the host→device MB/s counter the run report
        and the serve stats op derive from."""
        with self._span("put", stage="transfer", nbytes=int(arr.nbytes)):
            return self.runner.put(arr)

    def _load_params(self, name: str, **resolve):
        """A checkpoint's param tree (:func:`..weights.store.resolve_params`)
        placed on the mesh, replicated, ONCE, under its ``load_weights``
        set-up span."""
        with load_weights(name) as load:
            tree = load.read(resolve_params, name, **resolve)
            return load.place(self.runner.put_replicated(tree))

    def _put_replicated(self, arr):
        """Replicated transfer with the same 'transfer' attribution. Bytes
        count the HOST payload once (the replication fan-out across devices
        rides the interconnect, not the host staging path)."""
        with self._span("put", stage="transfer", nbytes=int(arr.nbytes)):
            return self.runner.put_replicated(arr)

    def _stage_rows(self, rows: Sequence[np.ndarray],
                    batch_size: Optional[int] = None) -> np.ndarray:
        """Stack equal-shape host rows into a reusable staging-ring buffer
        (zero-padded to ``batch_size``) instead of a fresh ``np.stack`` +
        ``pad_batch`` allocation per batch. The caller must route the staged
        buffer's device value back through ``self._staging.commit`` (the
        prefetcher's ``commit`` hook does this) so the buffer is not
        rewritten while its transfer is pending."""
        with self._span("stage"):
            return self._staging.stage(rows, batch_size)

    def _throttle(self, outputs: Sequence) -> None:
        """Bound in-flight device work when per-batch results stay on device.

        Deferring the host fetch to one per video removes the implicit
        backpressure the old per-batch ``np.asarray`` provided; without a bound
        the host dispatches every batch of a long video ahead of compute and
        pins them all in HBM. Blocking on the (prefetch_depth+1)-oldest output
        keeps at most ~prefetch_depth batches outstanding.
        """
        depth = max(self.cfg.prefetch_depth, 1)
        if len(outputs) > depth:
            jax.block_until_ready(outputs[-depth - 1])

    # --- shared driver ---

    def video_list(self) -> List[str]:
        return form_video_list(self.cfg.video_paths, self.cfg.file_with_video_paths)

    def run(self, video_paths: Optional[Sequence[str]] = None, progress=None) -> int:
        """Process all videos with the per-video fault barrier; returns #succeeded.

        ``progress``: optional callable invoked after each video (done, total).
        Terminal failures are classified (:func:`..reliability.classify`),
        recorded in the failure manifest, and survived — unless they exceed
        ``--max_failures``, which raises :class:`CircuitBreakerTripped`.
        """
        paths = list(video_paths) if video_paths is not None else self.video_list()
        done = load_done_set(self.output_dir) if self.cfg.resume else set()
        with_metrics = metrics_enabled(self.cfg.profile_dir,
                                       self.cfg.telemetry_dir)
        pack = None
        if self.cfg.pack_corpus:
            pack = self.pack_spec()
            if pack is None:
                print(f"--pack_corpus ignored: {self.feature_type} has no "
                      "packing path under this config (--show_pred debug "
                      "runs and the single-clip frame-sharded flow sandwich "
                      "use the per-video loop)")
        # a fresh record list per run, opened with the run resources where
        # the switch is on (the daemon keeps one for its lifetime)
        self._recorder = None
        self._open_run_resources()
        try:
            if pack is not None:
                ok = self._run_packed(pack, paths, done, with_metrics, progress)
            else:
                ok = self._run_loop(paths, done, with_metrics, progress)
        finally:
            self._close_run_resources()
        if with_metrics and not self._setup_reported:
            self._setup_reported = True
            print(setup_report(self._pack_stats["setup"]))
        return ok

    @contextlib.contextmanager
    def _run_span(self):
        """The ``run`` span, and its one record in the set-up recorder: the
        last ``run`` there is the window's, and what ended before it began
        is set-up."""
        recorder = setup_recorder()
        index = recorder.begin("run", {"model": self.feature_type})
        try:
            with self._span("run") as handle:
                yield handle
        finally:
            recorder.end(index)

    def _resolve_decode_workers(self) -> int:
        """``--decode_workers 0`` = auto (ROADMAP item 4, first step).

        Starts from a modest CPU-derived pool; the serving daemon then grows
        or shrinks it live from the measured occupancy / decode-MB/s signal
        (:mod:`..serve.autoscale`). Batch runs keep the initial value — they
        have no between-request boundary to resize at.
        """
        workers = self.cfg.decode_workers
        if workers == 0:
            workers = min(4, max(2, (os.cpu_count() or 2) // 2))
            print(f"--decode_workers 0 (auto): starting the decode pool at "
                  f"{workers} worker(s)")
        return workers

    def _open_run_resources(self) -> None:
        """Decode pool + async writer + telemetry + per-run accounting,
        shared by :meth:`run` and the serving daemon's caller-managed
        session."""
        self._open_telemetry()
        workers = self._resolve_decode_workers()
        self._decode_workers = workers
        if workers > 1 and self.uses_frame_stream:
            self._decode_pool = DecodePrefetcher(self._open_inline, workers,
                                                 span=self._span)
            self._decode_pool.set_segmenter(self._plan_inline,
                                            self._open_segment_inline)
        elif workers > 1:
            print(f"--decode_workers ignored: {self.feature_type} does not "
                  "consume the frame stream (whole-video / audio decode)")
        if self.cfg.async_writer and self.cfg.on_extraction == "save_numpy":
            # bounded single-writer thread: .npy serialization overlaps the
            # next video's compute; write failures retry like any other
            # transient OutputError, then surface at the per-video reap.
            # depth 2 + the loop's reap-to-one discipline (_run_loop
            # reap_writes(1)) guarantee submit() never blocks inside a
            # video's watchdog window on a predecessor's slow write.
            self._writer = AsyncOutputWriter(
                depth=2,
                retry=RetryPolicy(attempts=self.cfg.retries + 1,
                                  base_delay=self.cfg.retry_backoff),
                span=self._span)
        self._succeeded = []  # pruned from the failure manifest at exit
        self._ok = 0
        self._failures = 0
        self._inline_writes = {"videos_written": 0, "write_bytes": 0}

    def _close_run_resources(self) -> None:
        """Unwind-safe teardown (run()'s ``finally`` and the daemon's)."""
        # KeyboardInterrupt / a raising progress callback must not leak
        # decode workers busy-waiting on full queues — shut the pool down
        # FIRST so a raising manifest prune can't skip it
        if self._decode_pool is not None:
            self._decode_pool.shutdown()
            self._decode_pool = None
        # drain the writer even on interrupt/breaker: queued jobs finish
        # their atomic writes + done records (write-before-done holds),
        # then account the drained handles so videos that DID complete
        # reach _succeeded (their stale failure records must be pruned —
        # a --retry_failed pass interrupted after its last extract would
        # otherwise leave a video in both manifests forever)
        if self._writer is not None:
            self._writer.close(wait=True)
            self._writer = None
            self._reap_abandoned_writes()
        # even on KeyboardInterrupt / circuit breaker: converge the failure
        # manifest for everything that DID succeed this run
        self._prune_succeeded(self._succeeded)
        # the journal closes LAST so every unwind arm above could still emit;
        # the closed object is kept for the run report's counters (a second
        # run() reopens in append mode). Shared journals (a co-loaded serving
        # model) are closed by their owning primary only.
        if self._owns_journal and self._journal is not None:
            self._journal.close()

    def _process_one(self, path: str,
                     cancelled: Optional[threading.Event] = None,
                     ) -> Optional[WriteHandle]:
        """One attempt at one video: extract → output action → mark done.

        With the async writer active the action + done record are SUBMITTED
        (not performed): the returned :class:`WriteHandle` resolves on the
        writer thread while the loop moves to the next video, and the run
        loop's reap attributes any write failure back to this video. Inline
        mode returns None after writing synchronously.

        ``cancelled`` is set by the watchdog on timeout: an abandoned attempt
        that later wakes up (typically over a partial frame stream — releasing
        the decode-pool slot turns the remaining frames into a clean-looking
        EOF) must discard its results, not write truncated features and a
        done-manifest record for a video the run already counted as failed.
        The check sits BEFORE the submit, so watchdog-cancelled attempts
        never enqueue writes — and the submitted job carries the event, so a
        cancellation landing after this check is still discarded by the
        writer before the done record.
        """

        def check_cancelled(stage: str) -> None:
            if cancelled is not None and cancelled.is_set():
                raise VideoTimeoutError(
                    f"{path}: attempt was cancelled by the watchdog; {stage}")

        fault_point("extract", path)
        feats_dict = self.extract(path)
        check_cancelled("discarding possibly-partial features")
        return self._submit_outputs(path, feats_dict, cancelled=cancelled)

    def _submit_outputs(self, path: str, feats_dict: Dict[str, np.ndarray],
                        cancelled: Optional[threading.Event] = None,
                        from_cache: bool = False) -> Optional[WriteHandle]:
        """One video's output action — shared by the per-video loop's
        :meth:`_process_one`, the packed loop's finalize, and the cache-hit
        replay (``from_cache=True`` skips the republish). A freshly-extracted
        video whose key was consulted this run publishes to the cache HERE,
        before the (possibly async) write — by the time the write resolves,
        concurrent duplicates already hit."""
        if (self._cache is not None and not from_cache
                and (cancelled is None or not cancelled.is_set())):
            key = self._cache_keys.pop(os.path.abspath(path), None)
            if key is not None:
                self._cache.put(key, feats_dict)  # best-effort, never raises
        if self._writer is not None:
            # the job carries the cancel event: a timeout landing between
            # the caller's check and the writer thread picking the job up (or
            # mid-write) still discards before the done record. This put
            # cannot block on a full queue — the run loop reaps down to one
            # outstanding write before starting the next attempt — so a
            # PREDECESSOR's slow write stalls the loop in reap_writes
            # (outside any watchdog), never this video's timeout budget.
            with self._span("write_reap", video=path):
                return self._writer.submit(feats_dict, path, self.output_dir,
                                           self.cfg.on_extraction,
                                           cancelled=cancelled)
        # inline mode: the same shared write contract, on this thread
        nbytes = feats_nbytes(feats_dict)
        with self._span("write", video=path, bytes=nbytes):
            write_outputs(feats_dict, path, self.output_dir,
                          self.cfg.on_extraction, cancelled=cancelled)
        self._inline_writes["videos_written"] += 1
        self._inline_writes["write_bytes"] += nbytes
        return None

    def _write_counters(self) -> Dict[str, int]:
        """The writer's three counters, kept where the work happens (the
        async writer's own, or the inline path's): deepest backlog (queue
        plus the job in hand, sampled at every submit; 0 inline), videos
        written and their payload bytes."""
        if self._writer is not None:
            return self._writer.counters()
        return {"writer_backlog_max": 0, **self._inline_writes}

    # --- feature cache (--cache_dir, docs/caching.md) -------------------------

    def _cache_key_for(self, path: str) -> Optional[str]:
        """Compute (and remember) the cache key for ``path``; None when the
        cache is off or the container cannot be hashed — hashing failures are
        plain misses here, the extraction attempt owns classifying them."""
        if self._cache is None:
            return None
        ap = os.path.abspath(path)
        key = self._cache_keys.get(ap)
        if key is not None:
            return key
        from ..cache import cache_key, file_digest

        try:
            key = cache_key(file_digest(path), self._cache_fp)
        except OSError as e:
            print(f"warning: cache skipped for {path} (cannot hash): {e}",
                  file=sys.stderr)
            return None
        self._cache_keys[ap] = key
        return key

    def _cache_fetch(self, path: str) -> Optional[Dict[str, np.ndarray]]:
        """The cached feature dict for ``path``, or None (miss/disabled).
        Never raises: both loops call it BEFORE their fault barrier. Hash +
        lookup time lands on the 'cache' stage of the report."""
        if self._cache is None:
            return None
        with self._span("cache", stage="cache", video=path):
            key = self._cache_key_for(path)
            feats = self._cache.get(key) if key is not None else None
        if feats is not None:
            # the key's job is done; a hit republishes nothing
            self._cache_keys.pop(os.path.abspath(path), None)
            self._emit("cache_hit", video=path)
        return feats

    def _publish_cache_hit(self, path: str, feats: Dict[str, np.ndarray],
                           on_done=None) -> None:
        """Serve a hit through the SHARED output path: same atomic writes,
        same done-manifest record (pinned — ``--resume`` must compose), same
        pending-write accounting; zero decode, zero device steps. The caller
        owns the fault barrier (a failed write fails this video like any
        other write failure)."""
        handle = self._submit_outputs(path, feats, from_cache=True)
        if handle is not None:
            self._pending_writes.append((path, handle))
        else:
            self._mark_succeeded(path)
            if on_done is not None:
                on_done(path)

    def _attempt_with_retries(self, path: str) -> Optional[WriteHandle]:
        """Run one video under the watchdog + transient-retry policy.

        Each attempt is watchdog-bounded individually (``--video_timeout``
        limits an *attempt*, not the retry budget). Between attempts the
        decode-pool slot is released so the retry decodes fresh — the stale
        prefetched stream may itself be the failure. Returns the async
        writer's handle for this video's pending output (None in inline
        mode).
        """

        def on_retry(exc, attempt, delay):
            err_class, _ = classify(exc)
            print(f"[{err_class}] attempt {attempt} failed for {path}: {exc}; "
                  f"retrying in {delay:.2g}s")
            if self._decode_pool is not None:
                self._decode_pool.release(path)

        def attempt_once():
            cancel = threading.Event()
            return run_with_timeout(
                lambda: self._process_one(path, cancel),
                self.cfg.video_timeout, path, on_timeout=cancel.set,
            )

        return retry_call(
            attempt_once,
            RetryPolicy(attempts=self.cfg.retries + 1,
                        base_delay=self.cfg.retry_backoff),
            on_retry=on_retry,
        )

    def _reap_abandoned_writes(self) -> None:
        """Account writes the closed writer drained after the loop stopped.

        Runs in ``run()``'s ``finally`` with the writer already closed, so
        every handle has resolved: successes join ``_succeeded`` (their
        stale failure records get pruned), failures are best-effort recorded
        — never raised (this is an unwind path; the in-flight exception, if
        any, must win) and never circuit-breaker counted.
        """
        while self._pending_writes:
            wpath, handle = self._pending_writes.popleft()
            try:
                handle.wait()
            except Exception as e:  # noqa: BLE001 — fault-barrier: unwind-path write accounting; must not mask the in-flight exception
                try:
                    record_failure(self.output_dir, wpath,
                                   e, getattr(e, "attempts", 1))
                except OSError as rec_err:
                    print(f"warning: could not record failure for {wpath}: "
                          f"{rec_err}", file=sys.stderr)
                continue
            self._succeeded.append(wpath)

    def _prune_succeeded(self, succeeded: List[str]) -> None:
        """Drop stale failure records for videos that just succeeded.

        One batched rewrite (not one per success — a mostly-successful retry
        pass over F failures would otherwise cost O(F²) manifest I/O), in the
        run's ``finally`` so KeyboardInterrupt and the circuit breaker still
        converge the manifest. Single-host only: the read-modify-replace
        rewrite would race other hosts' ``record_failure`` appends; on
        multi-host runs stale records simply remain until a single-host
        ``--retry_failed`` pass clears them.
        """
        if not succeeded or jax.process_count() > 1:
            return
        if not os.path.exists(failed_manifest_path(self.output_dir)):
            return
        try:
            prune_failures(self.output_dir, succeeded)
        except (OSError, ValueError) as e:
            # ValueError covers UnicodeDecodeError from a byte-corrupted
            # manifest; raised from run()'s finally it would mask the
            # in-flight exception, so warn instead
            print(f"warning: could not prune {len(succeeded)} failure "
                  f"record(s): {e}", file=sys.stderr)

    def _fail(self, path: str, e: BaseException) -> None:
        """Per-video failure accounting — both run loops' barriers and the
        write reap share it so a write failure is recorded exactly like a
        compute one (classified, manifested, circuit-breaker counted)."""
        self._failures += 1
        # drop the consult-time cache key (nothing will publish it; the
        # daemon's requeue path, which WILL retry, claims the failure before
        # reaching here and keeps the key so retries skip the re-hash)
        self._cache_keys.pop(os.path.abspath(path), None)
        err_class, transient = classify(e)
        attempts = getattr(e, "attempts", 1)
        self._emit("video_failed", video=path, error_class=err_class,
                   transient=transient, attempts=attempts)
        if self._metrics is not None:
            self._metrics.inc("videos_failed_total", model=self.feature_type,
                              error_class=err_class)
        # best-effort: the manifest write hitting the same dying
        # disk as the failure itself must not escape the barrier
        try:
            record = record_failure(self.output_dir, path, e, attempts)
            digest = record["traceback_digest"]
        except OSError as rec_err:
            digest = "unrecorded"
            print(f"warning: could not record failure for {path}: "
                  f"{rec_err}", file=sys.stderr)
        print(e)
        print(f"Extraction failed at: {path} with error (↑). "
              f"Continuing extraction "
              f"[{err_class}, transient={transient}, "
              f"attempts={attempts}, digest={digest}]")
        if (self.cfg.max_failures is not None
                and self._failures > self.cfg.max_failures):
            raise CircuitBreakerTripped(
                f"{self._failures} videos failed (> --max_failures "
                f"{self.cfg.max_failures}); aborting — a failure "
                "rate this high usually has a systemic cause. "
                "Failures so far are recorded in the failure "
                "manifest; fix the cause and rerun with "
                "--retry_failed."
            ) from e

    def _reap_writes(self, limit: int, on_done=None, on_failed=None) -> None:
        """Resolve oldest pending writes until ≤ ``limit`` remain.

        Peek-then-pop: a KeyboardInterrupt inside ``handle.wait()``
        (Event.wait is signal-interruptible) must leave the tuple in the
        deque so the shutdown drain (:meth:`_reap_abandoned_writes`) can
        still account the write — a popped-then-lost handle would strand
        its video's stale failure record forever.

        ``on_done(path)`` / ``on_failed(path, exc)``: the serving daemon's
        per-request bookkeeping hooks. A truthy ``on_failed`` return claims
        the failure (the daemon re-enqueued the video); the shared terminal
        accounting then does not run.
        """
        pending_writes = self._pending_writes
        while len(pending_writes) > limit:
            wpath, handle = pending_writes[0]
            try:
                if handle.done():
                    handle.wait()
                else:  # blocks on a predecessor's write: worth a span
                    with self._span("write_reap", video=wpath):
                        handle.wait()
            except KeyboardInterrupt:
                raise
            except Exception as e:  # noqa: BLE001 — fault-barrier: the write-side arm of the per-video isolation point
                pending_writes.popleft()
                if on_failed is not None and on_failed(wpath, e):
                    continue
                self._fail(wpath, e)
                continue
            pending_writes.popleft()
            self._mark_succeeded(wpath)
            if on_done is not None:
                on_done(wpath)

    def _run_loop(self, paths, done, with_metrics, progress) -> int:
        todo = [p for p in paths if os.path.abspath(p) not in done]
        workers = self._decode_workers
        extracted = 0  # excludes resume-skipped videos (throughput honesty)
        resumed = 0  # tracked directly: ok - extracted no longer equals it
        # when an async write fails (extracted counts the successful extract,
        # self._ok only counts writes that resolved)
        cursor = 0  # decode-window cursor over `todo`
        # async-writer mode: a video counts `ok` only once its write
        # resolved, so the done/failure manifests and the return value agree
        # with the synchronous path exactly; the deque lives on self so
        # run()'s finally can account handles an interrupt abandoned
        pending_writes = self._pending_writes
        pending_writes.clear()
        t_run = time.perf_counter()
        stage_seconds: Dict[str, float] = {}  # summed over the per-video clocks

        with maybe_profiler(self.cfg.profile_dir), self._run_span():
            for n, path in enumerate(paths, start=1):
                if os.path.abspath(path) in done:
                    self._ok += 1
                    resumed += 1
                    if progress:
                        progress(n, len(paths))
                    continue
                # one clock per video: the per-video report's
                self.clock = StageClock(registry=self._metrics,
                                        labels={"model": self.feature_type})
                t0 = time.perf_counter()
                # consult the cache BEFORE decode: a hit dispatches nothing —
                # no decode stream, no device step (_cache_fetch never raises;
                # a hit's WRITE failure still lands on the barrier below)
                feats = self._cache_fetch(path)
                if self._decode_pool is not None:
                    if feats is None:
                        # keep `workers` videos decoding ahead of the consumer
                        for p in todo[cursor : cursor + workers]:
                            self._decode_pool.schedule(p)
                    else:
                        # an earlier miss's window may have prefetch-scheduled
                        # this path — cancel it, nothing will consume it
                        self._decode_pool.release(path)
                    cursor += 1
                try:
                    if feats is not None:
                        self._publish_cache_hit(path, feats)
                        handle = None  # accounted inside the helper
                    else:
                        with self._span("extract", model=self.feature_type,
                                        video=path):
                            handle = self._attempt_with_retries(path)
                        extracted += 1
                    if with_metrics:
                        print(self.clock.report(path, time.perf_counter() - t0))
                    if handle is not None:
                        pending_writes.append((path, handle))
                    elif feats is None:
                        self._mark_succeeded(path)
                except KeyboardInterrupt:
                    raise
                except Exception as e:  # noqa: BLE001 — fault-barrier: the per-video isolation point
                    self._fail(path, e)
                finally:
                    for stage, seconds in dict(self.clock.seconds).items():
                        stage_seconds[stage] = (stage_seconds.get(stage, 0.0)
                                                + seconds)
                    if self._decode_pool is not None:
                        # cancel this video's decode stream whether it was fully
                        # drained or abandoned by a compute error — an orphaned
                        # worker would pin a permit + max_buffered frames forever
                        self._decode_pool.release(path)
                # bound in-flight writes: the current video's serialization
                # overlaps the NEXT video's decode/compute, older writes must
                # resolve (and be accounted) first. OUTSIDE the barrier: a
                # CircuitBreakerTripped from the reap must abort the run, not
                # be swallowed as video `path`'s failure.
                self._reap_writes(1)
                if progress:
                    progress(n, len(paths))
            self._reap_writes(0)  # tail videos' writes resolve before run() returns
        self._pack_stats = self._run_stats(stage_seconds)
        if with_metrics and (extracted or
                             (self._cache is not None and self._cache.hits)):
            dt = time.perf_counter() - t_run
            if self._recorder is not None:
                print(self._recorder.report())
            hits = f", {self._cache.hits} cache hit(s)" if self._cache else ""
            print(f"extracted {extracted}/{len(paths)} videos "
                  f"({resumed} resumed{hits}) in {dt:.2f}s "
                  f"({extracted / dt:.3f} videos/sec)")
        return self._ok

    def _run_packed(self, spec, paths, done, with_metrics, progress) -> int:
        """Corpus-level continuous batching (``--pack_corpus``).

        Every fixed-shape device batch is filled with clips from however many
        videos are ready (the packer holds partial shape queues ACROSS video
        boundaries — tail of video N packs with head of video N+1) and per-
        clip results scatter back to per-video assemblies that flush through
        the shared output path as each video's last clip lands. The per-video
        invariants of :meth:`_run_loop` are preserved: a poisoned clip stream
        fails only its contributing video (slot-level attribution), transient
        failures retry with a fresh decode, resume/done/failure manifests and
        the circuit breaker behave identically, and per-slot features are
        byte-identical to the unpacked loop (each slot's row is a pure
        function of its clip — no cross-sample ops in the packed steps).

        ``--video_timeout`` here bounds a video's *clip stream* cooperatively
        (checked between clips): with the decode pool active a wedged decode
        thread still trips it, but a hard-wedged inline decode needs the
        per-video loop's thread-cancelling watchdog.
        """
        todo = [p for p in paths if os.path.abspath(p) not in done]
        workers = self._decode_workers
        extracted = 0
        resumed = 0
        cursor = 0  # decode-window cursor over `todo`
        if spec.prepare is not None:
            # corpus-level planning (e.g. the flow extractors' shape-bucket
            # clustering over container probes) before any decode starts
            spec.prepare(todo)
        self.clock = StageClock(registry=self._metrics,
                                labels={"model": self.feature_type})  # corpus-level
        session = PackedSession(self, spec)
        packer = session.packer
        self._pending_writes.clear()
        t_run = time.perf_counter()

        with maybe_profiler(self.cfg.profile_dir), self._run_span():
            for n, path in enumerate(paths, start=1):
                if os.path.abspath(path) in done:
                    self._ok += 1
                    resumed += 1
                    if progress:
                        progress(n, len(paths))
                    continue
                # cache consult precedes decode here too: a hit never enters
                # the packer (its rows were never going to dispatch)
                feats = self._cache_fetch(path)
                if self._decode_pool is not None:
                    if feats is None:
                        for p in todo[cursor : cursor + workers]:
                            self._decode_pool.schedule(p)
                    else:
                        self._decode_pool.release(path)
                    cursor += 1
                try:
                    if feats is not None:
                        self._publish_cache_hit(path, feats)
                    else:
                        session.ingest(path)
                        extracted += 1
                except KeyboardInterrupt:
                    raise
                except Exception as e:  # noqa: BLE001 — fault-barrier: the per-video isolation point (packed loop)
                    session.fail(path, e)
                finally:
                    if self._decode_pool is not None:
                        self._decode_pool.release(path)
                session.emit_completed()
                if progress:
                    progress(n, len(paths))
            session.drain(final=True)
        self._pack_stats = {
            "real_slots": packer.real_slots,
            "dispatched_slots": packer.dispatched_slots,
            "occupancy": round(packer.occupancy, 4),
            "video_clips": dict(packer.video_clips),
            "buckets": packer.bucket_stats(),
            "stale_flushes": packer.stale_flushes,
            # host bytes staged per dispatched device batch (the wire
            # format's counter: uint8 against --float32_wire, tests/test_ingest.py)
            "staged_bytes": packer.staged_bytes,
            # paged dispatch (parallel/pages.py): page count and the deepest
            # observed in-flight ring (tests/test_paged.py)
            "pages_dispatched": packer.pages_dispatched,
            "max_in_flight": packer.max_in_flight,
            # token pages: table rows dispatched, and what the model counted
            # on the device (the text stream's routing counters)
            "segments": packer.segments,
            **self._extra_pack_stats(),
            # per-stage wall seconds for the whole corpus, the writer's
            # counters and — with recording on — the span records
            **self._run_stats(dict(self.clock.seconds)),
        }
        if with_metrics:
            dt = time.perf_counter() - t_run
            # the stage report carries pack_occupancy; run.py prints the
            # canonical standalone occupancy line (once) after the run
            print(self.clock.report(
                f"packed corpus ({extracted} videos)", dt))
            if self._recorder is not None:
                print(self._recorder.report())
            # ROADMAP item 4: pin the decode-starvation signal — padding
            # burned while the run sat blocked on decode means the decode
            # pool, not the mesh, is the ceiling
            starved = decode_starvation_warning(
                occupancy=packer.occupancy,
                decode_seconds=self.clock.seconds.get("decode", 0.0),
                wall=dt, stale_flushes=packer.stale_flushes,
                transfer_seconds=self.clock.seconds.get("transfer", 0.0))
            if starved:
                print(starved, file=sys.stderr)
            hits = f", {self._cache.hits} cache hit(s)" if self._cache else ""
            print(f"extracted {extracted}/{len(paths)} videos "
                  f"({resumed} resumed{hits}) in {dt:.2f}s")
        return self._ok

    def _extra_pack_stats(self) -> Dict:
        """Counters a model keeps itself for ``_pack_stats`` (override)."""
        return {}

    def _run_stats(self, stage_seconds: Dict[str, float]) -> Dict:
        """What every run leaves in ``_pack_stats`` whatever its loop: the
        stage clock's seconds, the writer's counters, the process's set-up
        records (``setup``, whatever the switch says) and — with recording on
        — ``spans``; both ``{"clock": "time_ns", "records", "self_seconds",
        "dropped"}`` (:meth:`..utils.metrics.SpanRecorder.export`)."""
        stats = {"stage_seconds": {k: round(v, 4)
                                   for k, v in stage_seconds.items()},
                 **self._write_counters(),
                 "setup": setup_recorder().export()}
        if self._recorder is not None:
            stats["spans"] = self._recorder.export()
        return stats


class PackedSession:
    """A live packed run: one :class:`..parallel.packer.CorpusPacker` plus the
    per-video ingest → finalize → write machinery that used to live inline in
    :meth:`Extractor._run_packed`.

    Factored out so the run loop is *resumable against a live queue*: the
    batch CLI creates one session per ``run()`` and calls :meth:`drain` after
    the last video, while the serving daemon (:mod:`..serve`) keeps ONE
    session alive for its whole lifetime — slot queues stay warm across
    requests, :meth:`ingest` is called per scheduled video in whatever order
    the tenant scheduler decides, and :meth:`drain` runs only at queue-idle
    flushes and graceful shutdown.

    ``on_done(path)`` / ``on_failed(path, exc)`` fire after the shared
    accounting (done/failure manifests, counters) — the daemon's per-request
    and per-tenant bookkeeping. ``forget_completed=True`` additionally drops
    the packer's per-video stats as each video resolves, bounding memory over
    an unbounded request stream (batch runs keep them for ``_pack_stats``).

    ``packer``/``model``: the multi-model serving layer
    (:class:`MultiModelSessions`) passes an already-built SHARED packer and
    registers this session's spec under its feature-type name — every
    co-resident model's session then feeds one ``(model, geometry)``-keyed
    packer on one mesh. Default (batch runs): build a private single-spec
    packer, keys unscoped.
    """

    def __init__(self, ex: Extractor, spec, on_done=None, on_failed=None,
                 forget_completed: bool = False, packer=None,
                 model: Optional[str] = None):
        from ..parallel.packer import CorpusPacker

        self.ex = ex
        self.spec = spec
        self.model = model
        if packer is None:
            packer = CorpusPacker(spec, clock=ex.clock, span=ex._span,
                                  flush_age=ex.cfg.pack_flush_age,
                                  staging=ex._staging, journal=ex._journal,
                                  metrics=ex._metrics)
            if model is not None:
                packer.register_model(model, spec)
        else:
            packer.register_model(model, spec)
        self.packer = packer
        self._on_done = on_done
        self._on_failed = on_failed
        self._forget = forget_completed

    # --- ingest ---------------------------------------------------------------

    def ingest(self, path: str, retries: Optional[int] = None) -> None:
        """Drain one video's clip stream into the packer.

        ``retries`` bounds IN-PLACE re-attempts (None = the config budget;
        the daemon passes 0 and re-enqueues transient failures through its
        scheduler instead of sleeping backoffs in the serving hot loop).
        Raises on terminal failure — the caller owns the fault barrier and
        must then call :meth:`fail` (or re-enqueue after ``packer.discard``).
        """
        ex = self.ex
        if retries is None:
            retries = ex.cfg.retries

        def on_retry(exc, attempt, delay):
            err_class, _ = classify(exc)
            print(f"[{err_class}] attempt {attempt} failed for {path}: "
                  f"{exc}; retrying in {delay:.2g}s")
            # the retry decodes fresh and repacks from clip 0: the failed
            # attempt's queued/dispatched slots are orphaned by discard()
            self.packer.discard(path)
            if ex._decode_pool is not None:
                ex._decode_pool.release(path)

        with ex._span("extract", model=ex.feature_type, video=path):
            retry_call(
                lambda: self._drain_stream(path),
                RetryPolicy(attempts=retries + 1,
                            base_delay=ex.cfg.retry_backoff),
                on_retry=on_retry,
            )

    def _drain_stream(self, path: str) -> None:
        """One attempt at one video: pack every clip of its stream."""
        timeout = self.ex.cfg.video_timeout
        packer = self.packer
        deadline = (time.perf_counter() + timeout) if timeout else None
        fault_point("extract", path)
        info, clips = self.spec.open_clips(path)
        packer.begin(path, info, model=self.model)
        try:
            for clip in clips:
                packer.add(path, clip)
                if deadline is not None and time.perf_counter() > deadline:
                    raise VideoTimeoutError(
                        f"{path}: packed clip stream exceeded "
                        f"--video_timeout ({timeout:.3g}s); failing this "
                        f"video")
        finally:
            # an abandoned generator's cleanup (temp-wav deletion, capture
            # release) must run before any retry re-opens the same path,
            # not whenever GC collects the frame
            close = getattr(clips, "close", None)
            if close is not None:
                close()
        packer.finish(path)

    def fail(self, path: str, e: BaseException) -> None:
        """Terminal per-video failure: orphan its slots, run the accounting."""
        self.packer.discard(path)
        self._video_failed(path, e)

    # --- results --------------------------------------------------------------

    def emit_completed(self, reap_limit: int = 1) -> None:
        """Finalize every video whose last clip's features have landed."""
        ex = self.ex
        for asm in self.packer.pop_completed(model=self.model):
            try:
                with ex._span("finalize", video=asm.video):
                    feats = self.spec.finalize(
                        asm.video, asm.stacked(self.spec.empty_row_shape),
                        asm.info)
                handle = ex._submit_outputs(asm.video, feats)
            except KeyboardInterrupt:
                raise
            except Exception as e:  # noqa: BLE001 — fault-barrier: the finalize/write arm of the packed per-video isolation point
                asm.release()
                self._video_failed(asm.video, e)
                self._forget_video(asm.video)
                continue
            # rows are views into whole fetched batches; finalize copied
            # what it needed, so release them now (long-run memory bound)
            asm.release()
            if handle is not None:
                ex._pending_writes.append((asm.video, handle))
            else:
                ex._mark_succeeded(asm.video)
                if self._on_done is not None:
                    self._on_done(asm.video)
            self._forget_video(asm.video)
        ex._reap_writes(reap_limit, on_done=self._on_done,
                        on_failed=self._on_failed)

    def drain(self, final: bool = False) -> None:
        """Dispatch partial shape queues (zero-padded tails), resolve the
        in-flight batches, and fail the videos whose rows a co-packed batch
        failure lost.

        The batch loop calls this once after the last video (``final=True``
        also reaps every pending write); the daemon calls it with
        ``final=False`` whenever the ingest queue goes idle — latency over
        occupancy when there is nothing left to pack with — and once more at
        graceful shutdown. (A multi-model daemon flushes the SHARED packer
        once and then runs each session's :meth:`_resolve_drained` —
        :meth:`MultiModelSessions.drain`.)
        """
        self._resolve_drained(final, _contained_flush(self.packer))
        self.packer.clear_flush_causes()

    def _resolve_drained(self, final: bool, flush_error) -> None:
        """Post-flush resolution for THIS session's model: finalize what
        completed, fail the videos whose rows a co-packed batch failure
        lost (each wearing only its own buckets' recorded causes)."""
        packer = self.packer
        self.emit_completed(reap_limit=0 if final else 1)
        for asm in packer.drain_incomplete(model=self.model):
            # rows lost to a failed co-packed batch (mid-run, at a stale
            # flush, or at this flush): fail each contributing video so it
            # lands in the failure manifest (DeviceError is transient — a
            # --retry_failed pass reprocesses exactly these) instead of
            # crashing the run or silently denting the return value
            causes = packer.flush_causes(asm.video)
            if flush_error is not None:
                causes.append(str(flush_error))
            cause = f": {'; '.join(causes)}" if causes else ""
            asm.release()
            self._video_failed(asm.video, DeviceError(
                f"{asm.video}: a co-packed device batch failed before "
                f"this video's clips resolved{cause}; rerun with "
                "--retry_failed"))
            self._forget_video(asm.video)

    # --- shared accounting ----------------------------------------------------

    def _video_failed(self, path: str, e: BaseException) -> None:
        # the daemon's hook runs FIRST: _fail may raise CircuitBreakerTripped
        # (batch-mode --max_failures) and the request bookkeeping must not be
        # skipped by the unwind. A truthy return CLAIMS the failure — the
        # daemon re-enqueues a transient victim (a co-packed batch failure,
        # a failed async write) through its scheduler instead of recording a
        # terminal failure here.
        if self._on_failed is not None and self._on_failed(path, e):
            return
        self.ex._fail(path, e)

    def _forget_video(self, path: str) -> None:
        if self._forget:
            self.packer.forget(path)


def _contained_flush(packer):
    """Flush ``packer``, returning (not raising) any non-dispatch failure.

    Tail-batch device failures are contained per bucket inside ``flush()``
    and surface as flush_causes on the drained victims; this wrapper is the
    safety net for failures outside that containment."""
    try:
        packer.flush()
        return None
    except KeyboardInterrupt:
        raise
    except Exception as e:  # noqa: BLE001 — fault-barrier: the corpus-flush arm of the per-video isolation point
        return e


# Flags that shape ONE model's windows/geometry/streams: reset to their
# dataclass defaults for a co-loaded serving model, so each model resolves
# its own reference behavior (an i3d daemon's resolved stack_size=64, or a
# primary-only --extraction_fps that r21d would reject outright, must not
# leak into a co-resident model's derived config).
_MODEL_SCOPED_FIELDS = ("stack_size", "step_size", "streams",
                        "extraction_fps", "side_size",
                        "resize_to_smaller_edge", "i3d_pre_crop_size",
                        "i3d_crop_size")


def derive_model_config(cfg: ExtractionConfig, model: str) -> ExtractionConfig:
    """The config a co-loaded serving model (``--serve_models``) runs under.

    Same flag surface as the daemon's primary config, with the model-scoped
    fields (``_MODEL_SCOPED_FIELDS``) RESET to their defaults so each model
    resolves its own reference behavior. Explicit per-model overrides
    therefore apply only to the primary ``--feature_type``; co-loaded
    models run their reference geometry."""
    import dataclasses

    defaults = {f.name: f.default for f in dataclasses.fields(cfg)
                if f.name in _MODEL_SCOPED_FIELDS}
    return cfg.replace(feature_type=model, **defaults)


class MultiModelSessions:
    """Co-resident models on one mesh: per-model :class:`PackedSession`\\ s
    over ONE shared ``(model, geometry)``-keyed packer (docs/serving.md).

    The serving daemon's session layer (ROADMAP item 2): the primary
    extractor (already constructed, run resources open) is joined by
    lazily-constructed extractors for each co-loaded feature type — built on
    first traffic, so a daemon configured for three models but seeing two
    pays nothing for the third — all sharing the primary's mesh runner, host
    staging ring (its geometry cap scaled by the loaded model count), async
    output writer, decode pool (rerouted per path to the owning model's host
    transform), service clock, and feature-cache store. Outputs, manifests,
    and cache fingerprints stay per model: each extractor keeps its own
    ``<output>/<feature_type>/`` tree, so a two-model daemon's outputs are
    byte-identical to the corresponding single-model daemons'.

    Dispatch interleaving lives in the shared packer (round-robin across
    models whenever several have ready batches); arrival-order interleaving
    comes from the tenant scheduler, which stays global across tenants —
    fairness is never siloed per model.
    """

    def __init__(self, primary: Extractor, models: Sequence[str],
                 on_done=None, on_failed=None, factory=None,
                 primary_spec=None):
        from ..parallel.packer import CorpusPacker

        self.primary = primary
        self.models = tuple(models)
        self._on_done = on_done
        self._on_failed = on_failed
        self._factory = factory if factory is not None else self._build_real
        if len(self.models) > 1:
            # each co-resident model brings its own working set of batch
            # geometries — scale the shared ring's cap so model B's buckets
            # don't thrash model A's staged buffers out of the ring
            primary._staging = HostStagingRing(
                depth=max(primary.cfg.prefetch_depth, 1) + 2,
                on_wait=primary._transfer_wait,
                max_geometries=(HostStagingRing.DEFAULT_MAX_GEOMETRIES
                                * len(self.models)))
        self.packer = CorpusPacker(
            clock=primary.clock, span=primary._span,
            flush_age=primary.cfg.pack_flush_age, staging=primary._staging,
            journal=primary._journal, metrics=primary._metrics)
        self._extractors: Dict[str, Extractor] = {
            primary.feature_type: primary}
        # path → extractor, for the shared decode pool's router; written on
        # the daemon thread at schedule time, read by pool workers at decode
        # start (schedule() happens-before the worker thread starts)
        self._ex_for_path: Dict[str, Extractor] = {}
        self._pool = None  # a pool this layer created (primary had none)
        # the daemon validates the primary spec BEFORE opening run resources
        # (so a spec-less config errors without leaking pool threads) and
        # passes it via primary_spec; the re-check here covers callers that
        # construct this layer directly
        spec = primary_spec if primary_spec is not None \
            else primary.pack_spec()
        if spec is None:
            raise ValueError(
                f"--serve needs a packing path, but {primary.feature_type} "
                "has none under this config (--show_pred and the "
                "single-clip frame-sharded flow sandwich are batch-only)")
        self._sessions: Dict[str, PackedSession] = {
            primary.feature_type: PackedSession(
                primary, spec, on_done=on_done, on_failed=on_failed,
                forget_completed=True, packer=self.packer,
                model=primary.feature_type)}
        if primary._decode_pool is not None and len(self.models) > 1:
            primary._decode_pool.set_opener(self._open_routed)
            primary._decode_pool.set_segmenter(self._plan_routed,
                                               self._open_segment_routed)

    # --- lazy model construction ---------------------------------------------

    def _build_real(self, model: str) -> Extractor:
        from . import get_extractor

        return get_extractor(derive_model_config(self.primary.cfg, model))

    def extractor(self, model: str) -> Extractor:
        """The model's extractor, constructed (and wired into the shared
        resources) on first use. Raises on an unknown model name or a
        construction failure — the daemon turns that into a clean per-video
        failure, never a crash."""
        ex = self._extractors.get(model)
        if ex is not None:
            return ex
        if model not in self.models:
            raise ValueError(f"feature_type {model!r} is not loaded "
                             f"(serving: {', '.join(self.models)})")
        primary = self.primary
        with _shared_construction(runner=primary.runner,
                                  staging=primary._staging,
                                  cache=primary._cache,
                                  journal=primary._journal,
                                  metrics=primary._metrics):
            ex = self._factory(model)
        ex.clock = primary.clock
        ex._recorder = primary._recorder
        ex._writer = primary._writer
        ex._decode_pool = (self._shared_pool()
                           if ex.uses_frame_stream else None)
        spec = ex.pack_spec()
        if spec is None:
            raise ValueError(
                f"feature_type {model!r} has no packing path under this "
                "config; it cannot be served")
        self._sessions[model] = PackedSession(
            ex, spec, on_done=self._on_done, on_failed=self._on_failed,
            forget_completed=True, packer=self.packer, model=model)
        self._extractors[model] = ex
        return ex

    def peek_extractor(self, model: str) -> Optional[Extractor]:
        """The model's extractor if already constructed, else None (never
        triggers construction — cleanup paths must stay cheap)."""
        return self._extractors.get(model)

    def session(self, model: str) -> PackedSession:
        self.extractor(model)
        return self._sessions[model]

    # --- shared decode pool ----------------------------------------------------

    @property
    def decode_pool(self):
        return self.primary._decode_pool or self._pool

    def _shared_pool(self):
        """The one decode pool all frame-stream models share (None when the
        config runs inline decode). Created here when the primary model does
        not consume the frame stream but a co-loaded model does."""
        if self.primary._decode_pool is not None:
            return self.primary._decode_pool
        if self._pool is None and self.primary._decode_workers > 1:
            self._pool = DecodePrefetcher(self._open_routed,
                                          self.primary._decode_workers,
                                          span=self.primary._span)
            self._pool.set_segmenter(self._plan_routed,
                                     self._open_segment_routed)
        return self._pool

    def _open_routed(self, path: str):
        """Pool opener: decode ``path`` with its owning model's transform."""
        ex = self._ex_for_path.get(path, self.primary)
        return ex._open_inline(path)

    def _plan_routed(self, path: str, max_segments: int):
        """Pool segment planner: route to the path's owning model's policy."""
        ex = self._ex_for_path.get(path, self.primary)
        return ex._plan_inline(path, max_segments)

    def _open_segment_routed(self, plan, index: int):
        """Pool segment opener: the plan's source path names the owner."""
        ex = self._ex_for_path.get(plan.source_meta.path, self.primary)
        return ex._open_segment_inline(plan, index)

    def schedule_decode(self, path: str, model: str) -> None:
        """Prefetch-hint ``path`` on the shared pool under its model's
        decode transform. Hints never CONSTRUCT a model (weights + compile
        on the daemon thread would stall the currently-popped job): a
        not-yet-built model's jobs simply decode unhinted until their first
        pop pays construction. No-op for non-frame-stream models."""
        ex = self._extractors.get(model)
        if ex is None:
            return
        pool = ex._decode_pool
        if pool is None or not ex.uses_frame_stream:
            return
        self._ex_for_path[path] = ex
        pool.schedule(path)

    def release_decode(self, path: str) -> None:
        """Cancel/forget a path's decode on the shared pool (idempotent)."""
        self._ex_for_path.pop(path, None)
        pool = self.decode_pool
        if pool is not None:
            pool.release(path)

    # --- session routing -------------------------------------------------------

    def ingest(self, path: str, model: str, retries=None) -> None:
        self.session(model).ingest(path, retries=retries)

    def fail(self, path: str, model: str, e: BaseException) -> None:
        self.session(model).fail(path, e)

    def emit_completed(self, reap_limit: int = 1) -> None:
        for s in list(self._sessions.values()):
            s.emit_completed(reap_limit=reap_limit)

    def drain(self, final: bool = False) -> None:
        """Flush the shared packer ONCE (interleaved round-robin across
        models), then resolve each model's completions and drained victims
        — healthy models' videos finish even when one model's bucket died."""
        flush_error = _contained_flush(self.packer)
        for s in list(self._sessions.values()):
            s._resolve_drained(final, flush_error)
        self.packer.clear_flush_causes()

    # --- aggregate accounting --------------------------------------------------
    # dict(self._extractors) snapshots atomically (C-level, under the GIL):
    # the serve socket's stats op reads these from the API thread while the
    # daemon thread lazily registers a new model — Python-level iteration
    # over the live dict could raise "changed size during iteration"

    @property
    def ok(self) -> int:
        return sum(ex._ok for ex in dict(self._extractors).values())

    @property
    def failures(self) -> int:
        return sum(ex._failures for ex in dict(self._extractors).values())

    def pending_writes(self) -> int:
        return sum(len(ex._pending_writes)
                   for ex in dict(self._extractors).values())

    def model_counts(self) -> Dict[str, Dict[str, int]]:
        """Per-model completion counters for the serve stats op."""
        return {m: {"videos_ok": ex._ok, "videos_failed": ex._failures}
                for m, ex in sorted(dict(self._extractors).items())}

    def close(self) -> None:
        """Tear down: the primary closes the shared pool + writer (draining
        every model's queued writes), then each co-loaded extractor accounts
        its own abandoned handles and prunes its own failure manifest."""
        primary = self.primary
        secondaries = [ex for ex in self._extractors.values()
                       if ex is not primary]
        for ex in secondaries:
            ex._decode_pool = None  # shared (or never owned): primary closes
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None
        for ex in secondaries:
            ex._journal = None  # shared: the primary closes it (after its
            # own unwind arms have emitted their last events)
        primary._close_run_resources()
        for ex in secondaries:
            ex._writer = None  # the shared writer is closed and drained
            ex._reap_abandoned_writes()
            ex._prune_succeeded(ex._succeeded)

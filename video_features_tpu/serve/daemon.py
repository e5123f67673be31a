"""The always-on extraction daemon: ingest queue → tenant scheduler → packer.

Turns the batch pipeline into a serving loop (ROADMAP item 1): one
:class:`..extractors.base.PackedSession` lives for the daemon's lifetime, so
the corpus packer's slot queues stay warm ACROSS requests — the tail batch
of tenant A's request packs with the head of tenant B's — and the mesh never
drains while there is backlog. The Podracer split (PAPERS.md) is preserved:
CPU-bound decode producers (the byte-capped ``DecodePrefetcher``) are the
buffer that absorbs bursts, the device consumer runs one batch always in
flight per bucket, and the scheduler in between decides *whose* video feeds
the queues next (weighted-fair + deadline, :mod:`.scheduler`).

Lifecycle:

- **drain** (SIGTERM / SIGINT / ``{"op": "drain"}``): stop admitting, finish
  every admitted video, pad-flush the partial queues, resolve all writes,
  write every request's result record, exit 0/1.
- **reload** (SIGHUP / ``{"op": "reload"}``): re-read ``tenants.json`` from
  the spool directory (weights/quotas) and close all tenant breakers.
- a second SIGTERM/SIGINT aborts immediately (KeyboardInterrupt semantics;
  the write-before-done and atomic-write invariants still hold on unwind).

Failure semantics: a video failure is attempted once per schedule; transient
classes re-enter the queue (same admission seq — retries do not go to the
back of the line) until ``--retries`` is spent, then fail terminally into
the shared failure manifest AND the owning request's result record. Terminal
failures count against the tenant's breaker (``--tenant_max_failures``):
tripping fails that tenant's queued videos fast and rejects its new
submissions until a reload, while other tenants keep completing.

With ``--cache_dir`` (docs/caching.md) every popped job consults the
content-addressed feature cache first — a hit writes outputs + manifests
with zero decode and zero device steps — and identical MISSES coalesce
in flight (:class:`..cache.InflightCoalescer`): N tenants submitting the
same bytes run ONE extraction, waiters replay from the fresh entry with
quota/fairness charged per waiter, and a leader failure re-enqueues the
waiters (next replay leads on its own retry budget) instead of charging a
neighbour's fault to their breakers.

Observability (docs/observability.md): the daemon always keeps a metrics
registry — queue-wait / end-to-end latency / decode / transfer histograms
labeled tenant × model, per-bucket occupancy gauges, stage counters mirrored
from the service clock — served by the ``stats`` (p50/p99 summaries,
``"schema": 1``) and ``metrics`` (full snapshot + Prometheus text) socket
ops. With ``--telemetry_dir`` every request and video additionally gets a
journaled lifecycle (admitted → queued → popped → decode → dispatch →
device → done/failed, plus cache/coalesce/stale-flush/autoscale/breaker
events) exportable as a Chrome/Perfetto trace; ``healthz`` reports liveness
+ staleness from the API thread, and the ``profile`` op drives an on-demand
``jax.profiler`` session in the live daemon.

With ``--serve_models`` (ROADMAP item 2) several feature types co-reside on
ONE mesh: requests pick a model via their ``feature_type`` key (admission
validates it against the loaded set and rejects unknown models with a clean
record), each model's extractor is constructed lazily on first traffic
sharing the primary's run resources (:class:`..extractors.base.
MultiModelSessions`), and the packer interleaves dispatch round-robin across
models — mixed traffic never drains the device while ANY model has backlog.
Tenant fairness and deadlines stay GLOBAL across models (a tenant cannot
dodge its weight by spreading over models), breakers stay per tenant across
models, and a graceful drain finishes every admitted model's in-flight
batches.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from typing import Dict, Optional

from ..cache import InflightCoalescer
from ..config import resolve_model_defaults
from ..extractors.base import MultiModelSessions, derive_model_config
from ..io.output import (
    feature_output_dir,
    load_done_set,
    request_result_path,
    write_request_result,
)
from ..obs import MetricsRegistry
from ..parallel.mesh import describe_devices, enable_compilation_cache
from ..reliability import (
    DeviceError,
    TenantBreaker,
    TenantBreakerOpen,
    classify,
    record_failure,
)
from ..reliability.faults import fault_point
from ..utils.metrics import StageClock, metrics_enabled, setup_recorder, setup_report
from .autoscale import DecodeAutoscaler
from .ingest import SPOOL_TENANTS_FILE, SocketAPI, SpoolWatcher, accepted_path
from .request import RequestRejected, ServiceRequest, VideoJob, parse_request
from .scheduler import RequestQueue
from .wal import WAL_NAME, AdmissionLog

# healthz `stale` threshold default (--healthz_stale_sec): the serving loop
# stamps every step (idle steps included, ~poll_interval apart), so an age
# past this means the daemon thread is stuck — wedged, or in a legitimately
# long first-traffic compile
HEALTH_STALE_SEC = 10.0


class ExtractionService:
    """One extractor serving a live request stream until drained."""

    def __init__(self, extractor, poll_interval: float = 0.05,
                 factory=None):
        cfg = extractor.cfg
        self.ex = extractor
        self.cfg = cfg
        spec = extractor.pack_spec()
        if spec is None:
            raise ValueError(
                f"--serve needs a packing path, but {cfg.feature_type} has "
                "none under this config (--show_pred and the single-clip "
                "frame-sharded flow sandwich are batch-only)")
        self.spec = spec
        # co-resident model set (--serve_models): the primary first (the
        # default for requests without a feature_type), extras deduped in
        # flag order. Each extra's DERIVED config (its own reference
        # stack/step defaults) must validate NOW — a daemon that would die
        # constructing model B on its first request should die at startup
        extras = []
        for m in cfg.serve_models or ():
            if m != cfg.feature_type and m not in extras:
                extras.append(m)
        self.models = (cfg.feature_type, *extras)
        for m in extras:
            resolve_model_defaults(derive_model_config(cfg, m)).validate()
        self._poll = poll_interval
        # telemetry (docs/observability.md): _open_run_resources opens the
        # span journal (--telemetry_dir, may be None) and the metrics
        # registry (always on under --serve — `stats`/`metrics` ops need it);
        # the service clock runs for the daemon's lifetime and MIRRORS its
        # per-stage seconds/bytes into the registry, so decode/device/
        # transfer attribution feeds the autoscaler, the stats op, and the
        # Prometheus exposition from one accumulator
        extractor._open_run_resources()
        self.journal = extractor._journal
        if extractor._metrics is None:  # a directly-constructed service
            extractor._metrics = MetricsRegistry()
        self.metrics = extractor._metrics
        extractor.clock = StageClock(registry=self.metrics)
        # ``factory(model) -> Extractor`` overrides lazy co-model
        # construction (tests wire toy models); the default builds the real
        # extractor for the derived config, sharing the primary's mesh
        self.sessions = MultiModelSessions(
            extractor, self.models, on_done=self._video_done,
            on_failed=self._video_failed, factory=factory,
            primary_spec=spec)
        self.session = self.sessions
        self.packer = self.sessions.packer
        # the queue owns the queue-wait signal: it emits queued/popped
        # journal events and feeds the queue_wait_seconds histogram + the
        # per-tenant depth gauges (serve/scheduler.py)
        self.queue = RequestQueue(default_quota=cfg.tenant_quota,
                                  journal=self.journal,
                                  metrics=self.metrics)
        self.breaker = TenantBreaker(cfg.tenant_max_failures)
        self.notify_dir = cfg.notify_dir or os.path.join(
            cfg.spool_dir or cfg.output_path, "results")
        # write-ahead admission log (serve/wal.py): every accepted request
        # is on disk before its submit is acknowledged, so a crashed daemon's
        # admitted-but-unfinished requests replay at the next startup
        # (recover()). Default location: beside the spool it serves.
        wal_file = cfg.wal_path
        if wal_file is None and cfg.spool_dir:
            wal_file = os.path.join(cfg.spool_dir, WAL_NAME)
        self._wal = (AdmissionLog(wal_file, fsync_sec=cfg.wal_fsync_sec,
                                  journal=self.journal, metrics=self.metrics)
                     if wal_file and wal_file.lower() != "none" else None)
        self._autoscaler = (DecodeAutoscaler()
                            if cfg.decode_workers == 0 else None)
        self._as_snapshot = (time.perf_counter(), 0.0, 0, 0)
        # --resume strips already-done videos at admission, per model (each
        # feature type keeps its own output subtree and done-manifest)
        self._done_sets: Dict[str, frozenset] = {}
        self._lock = threading.RLock()
        self._requests: Dict[str, ServiceRequest] = {}
        self._jobs: Dict[str, object] = {}  # abspath -> in-flight VideoJob
        # completed requests whose result record is still being written
        # (the write runs OUTSIDE the service lock): status() answers from
        # here during the window, and submit() still rejects the id as live
        self._publishing: Dict[str, dict] = {}
        # in-flight dedup (--cache_dir): identical (content, fingerprint)
        # misses run one extraction; touched only on the daemon thread
        self._coalescer = InflightCoalescer()
        self._draining = threading.Event()
        self._hup = threading.Event()
        # hung-step watchdog (--step_watchdog_sec): the monitor thread SETS
        # this when the loop has not stepped past the threshold; the daemon
        # thread clears it at its next step and fails the stalled batch
        # transiently (Events only — no unguarded cross-thread stores)
        self._stalled = threading.Event()
        self._watchdog_stop = threading.Event()
        self._idle_since: Optional[float] = None
        self._completed_requests = 0
        # healthz liveness: the loop stamps _last_step every step(); the
        # socket's healthz op reports the age so a wedged daemon thread is
        # visible from the (still-responsive) API thread. An on-demand
        # jax.profiler session (`profile` op) is tracked by its trace dir.
        self._started = time.monotonic()
        self._last_step = self._started
        self._profiling: Optional[str] = None
        # terminal failures with no extractor to account them (a co-loaded
        # model whose lazy construction failed) — the exit code includes them
        self._service_failures = 0
        self._closed = False
        if cfg.spool_dir:
            self._load_tenants_config(initial=True)

    def _emit(self, event: str, **fields) -> None:
        """One journal event (no-op without --telemetry_dir; never blocks)."""
        if self.journal is not None:
            self.journal.emit(event, **fields)

    # --- submission (ingest threads + tests call these) ----------------------

    def submit(self, payload, request_id: Optional[str] = None,
               source: str = "api") -> ServiceRequest:
        """Admit one request end to end; raises :class:`RequestRejected`."""
        if self._draining.is_set():
            raise RequestRejected("service is draining; resubmit after "
                                  "restart")
        request = parse_request(payload, request_id=request_id, source=source)
        # resolve the model at admission: the daemon's default when omitted,
        # and ANY named model must be in the loaded set — an unknown model is
        # a clean synchronous rejection (record written where the submitter
        # looks), never a daemon crash or a silent terminal failure
        ft = request.feature_type or self.cfg.feature_type
        if ft not in self.models:
            raise RequestRejected(
                f"feature_type {ft!r} is not loaded (serving: "
                f"{', '.join(self.models)}); start the daemon with "
                "--serve_models to co-load it")
        request.feature_type = ft
        # the resume manifest read is disk I/O — do it BEFORE taking the
        # service lock; submitters on other ingest threads and the serving
        # loop's pop all convoy on this lock (no blocking work under it)
        done = self._resume_done(ft)
        to_queue = request.videos
        resumed = ()
        if done:
            resumed = tuple(v for v in request.videos
                            if os.path.abspath(v) in done)
            to_queue = tuple(v for v in request.videos
                             if os.path.abspath(v) not in done)
        with self._lock:
            if (request.request_id in self._requests
                    or request.request_id in self._publishing):
                raise RequestRejected(
                    f"request_id {request.request_id!r} is already live")
            if self.breaker.tripped(request.tenant):
                raise RequestRejected(
                    f"tenant {request.tenant!r} breaker is open "
                    f"({self.breaker.failures(request.tenant)} terminal "
                    "failures); fix the inputs and SIGHUP-reload")
            # the scheduler rejects duplicates against its QUEUED set; a
            # path that was already popped (ingested, rows/writes pending)
            # is only visible here — without this check a resubmission
            # (same or another model) would overwrite _jobs[path] and
            # packer.begin() would discard the first attempt's in-flight
            # assembly, silently losing the original request's video
            inflight = [v for v in to_queue
                        if os.path.abspath(v) in self._jobs]
            if inflight:
                raise RequestRejected(
                    f"video(s) currently in flight under a live request: "
                    f"{', '.join(sorted(inflight)[:3])}"
                    + ("…" if len(inflight) > 3 else ""))
            # hold=True when the WAL is on: the jobs get their admission
            # seqs and reserve quota/duplicate slots, but stay invisible to
            # the serving loop until the admission record is durable — a
            # pop-dispatch-crash before the append lands would lose the
            # request (the spool claim is already consumed by then)
            jobs = (self.queue.submit(request, videos=to_queue,
                                      hold=self._wal is not None)
                    if to_queue else [])
            # mark BEFORE releasing the lock: _publish_result (daemon
            # thread) checks this flag to resolve the WAL entry, and an
            # early resolve must find the flag already set (the log itself
            # annihilates a resolve-before-append race)
            request.wal_logged = self._wal is not None and bool(jobs)
            # after queue.submit: a quota rejection there must not leave an
            # admitted event for a request that was never admitted (the
            # per-video queued events landing µs earlier is harmless — the
            # exporter anchors the request span on THIS event)
            self._emit("request_admitted", request=request.request_id,
                       tenant=request.tenant, model=ft,
                       videos=len(request.videos), queued=len(to_queue),
                       resumed=len(resumed))
            self._requests[request.request_id] = request
            for v in resumed:
                request.done.append(os.path.abspath(v))
            finished = self._finish_request_locked(request)
        # the ack barrier (docs/serving.md "Crash recovery"): the admitted
        # record — id, tenant, paths, model, deadline, admission seqs — is
        # durably appended BEFORE this submit returns/acknowledges. Disk
        # I/O, so outside the service lock like every other write.
        if request.wal_logged:
            self._wal.append_admitted({
                "request": request.request_id, "tenant": request.tenant,
                "feature_type": ft, "deadline": request.deadline,
                "source": source, "videos": [j.path for j in jobs],
                "seqs": [j.seq for j in jobs], "wall": time.time(),
            })
            # record durable (or the log degraded loudly): NOW the jobs may
            # feed the serving loop
            self.queue.release(jobs)
        # result record + prints are blocking work: outside the lock
        print(f"[serve] accepted {request.request_id} "
              f"(tenant={request.tenant}, {len(to_queue)} queued"
              + (f", {len(resumed)} resumed" if resumed else "") + ")")
        self._publish_result(finished)
        return request

    def _resume_done(self, feature_type: str) -> frozenset:
        """The model's done-manifest set (empty without --resume). The memo
        is service-lock-guarded; the manifest READ runs off-lock (disk I/O
        never happens under the service lock), and a lost race between two
        first submitters just loads the same set twice."""
        if not self.cfg.resume:
            return frozenset()
        with self._lock:
            done = self._done_sets.get(feature_type)
        if done is None:
            loaded = frozenset(load_done_set(feature_output_dir(
                self.cfg.output_path, feature_type)))
            with self._lock:
                done = self._done_sets.setdefault(feature_type, loaded)
        return done

    def recover(self) -> int:
        """Replay a crashed predecessor's unresolved WAL admissions
        (``--recover``, serve/wal.py; docs/serving.md "Crash recovery").

        Runs at startup BEFORE the ingest transports: each unresolved entry
        is deduped against its already-published result record and the
        per-model done-manifests (``--resume`` semantics — recovery always
        dedupes, whatever ``--resume`` says: exactly-once needs it), then
        the survivors re-enter the scheduler with their ORIGINAL admission
        seqs and deadlines through the requeue machinery, so a recovered
        video never goes to the back of the line behind post-restart
        traffic. Returns how many requests were re-admitted.
        """
        if self._wal is None:
            return 0
        entries = self._wal.replayable()
        if not entries:
            return 0
        if not self.cfg.recover:
            print(f"[serve] --recover off: dropping {len(entries)} "
                  "unresolved WAL admission(s) from a previous daemon",
                  file=sys.stderr)
            for rec in entries:
                self._wal.resolve(rec["request"], "failed")
            return 0
        self._emit("recovery_started", entries=len(entries),
                   corrupt=self._wal.corrupt_lines or None)
        print(f"[serve] recovery: {len(entries)} unresolved admission(s) "
              f"in {self._wal.path}"
              + (f" ({self._wal.corrupt_lines} torn/corrupt line(s) "
                 "tolerated)" if self._wal.corrupt_lines else ""))
        # new admissions must never collide with a replayed seq (the tenant
        # heaps tiebreak on seq), and replays should keep their priority
        self.queue.advance_seq(self._wal.max_seq())
        replayed = 0
        for rec in entries:
            rid = rec["request"]
            ft = rec.get("feature_type") or self.cfg.feature_type
            if os.path.exists(request_result_path(self.notify_dir, rid)):
                # the crash hit between publish and resolve: the submitter
                # already has its answer
                self._wal.resolve(rid, "done")
                self._emit("recovery_skipped_duplicate", request=rid,
                           reason="result record exists")
                print(f"[serve] recovery: {rid} already published; skipped")
                continue
            if ft not in self.models:
                print(f"[serve] recovery: {rid} wants model {ft!r} which "
                      "this daemon no longer loads; dropping the entry",
                      file=sys.stderr)
                self._wal.resolve(rid, "failed")
                continue
            request = ServiceRequest(
                rid, rec.get("tenant") or "default",
                tuple(rec.get("videos") or ()),
                deadline=rec.get("deadline"),
                source=rec.get("source") or "recovery", feature_type=ft)
            request.wal_logged = True
            done = frozenset(load_done_set(feature_output_dir(
                self.cfg.output_path, ft)))
            seqs = rec.get("seqs") or []
            jobs = []
            with self._lock:
                self._requests[rid] = request
                for i, path in enumerate(request.videos):
                    if path in done:
                        request.done.append(path)  # landed pre-crash
                        continue
                    seq = seqs[i] if i < len(seqs) else 0
                    jobs.append(VideoJob(path, request, seq=seq))
                finished = (self._finish_request_locked(request)
                            if not jobs else None)
            if jobs:
                # original seqs + deadlines, through the same requeue path
                # a transient retry takes (video_requeued journal events)
                self.queue.requeue_all(jobs)
            replayed += 1
            self.metrics.inc("recovery_replayed_total")
            self._emit("recovery_replayed", request=rid,
                       tenant=request.tenant, model=ft, videos=len(jobs),
                       resumed=len(request.done))
            print(f"[serve] recovery: re-admitted {rid} "
                  f"({len(jobs)} video(s) to run, {len(request.done)} "
                  "already done)")
            # every video already landed: publish now (resolves the entry)
            self._publish_result(finished)
        return replayed

    def reject(self, request_id: str, reason: str, source: str = "api",
               payload=None) -> None:
        """Record a rejected submission where the submitter will look."""
        tenant = (payload or {}).get("tenant") if isinstance(payload, dict) \
            else None
        print(f"[serve] rejected {request_id}: {reason}")
        self._emit("request_rejected", request=request_id, tenant=tenant,
                   reason=reason[:200])
        try:
            write_request_result(self.notify_dir, request_id, {
                "request_id": request_id,
                "tenant": tenant if isinstance(tenant, str) else None,
                "state": "rejected",
                "reason": reason,
                "source": source,
                "completed_at": time.time(),
            })
        except Exception as e:  # noqa: BLE001 — fault-barrier: a rejection record is best-effort; the daemon must outlive a full notify disk
            print(f"[serve] could not record rejection {request_id}: {e}",
                  file=sys.stderr)

    # --- the serving loop (daemon thread only) -------------------------------

    def step(self) -> bool:
        """One scheduling step; True when it did video work."""
        self._last_step = time.monotonic()  # healthz liveness stamp
        if self._stalled.is_set():
            # the watchdog flagged a stall while the previous step was
            # wedged (hung device dispatch, stuck decode): now that the
            # loop is stepping again, fail the stalled batch transiently —
            # its victims requeue through the same slot-attribution path
            # as any co-packed batch failure
            self._stalled.clear()
            self._requeue_stalled()
        if self._hup.is_set():
            self._hup.clear()
            self.reload()
        with self._lock:
            # pop + register atomically: between leaving the scheduler's
            # queued set and appearing in _jobs, a resubmission of the same
            # path would pass BOTH duplicate checks (service lock → queue
            # lock here matches the submit path's ordering)
            job = self.queue.next_job()
            if job is not None:
                self._jobs[job.path] = job
        if job is None:
            # resolve outstanding writes so finished videos complete their
            # requests even while no new work arrives
            self.session.emit_completed(reap_limit=0)
            if self.packer.has_pending():
                now = time.perf_counter()
                if self._idle_since is None:
                    self._idle_since = now
                if (self._draining.is_set()
                        or now - self._idle_since >= self.cfg.idle_flush_sec):
                    # nothing left to pack with: latency beats occupancy —
                    # pad-flush the partial queues so in-flight requests
                    # complete now instead of at the next burst
                    self.session.drain(final=False)
                    self._idle_since = None
            return False
        self._idle_since = None
        path = job.path
        model = job.feature_type or self.cfg.feature_type
        tenant = job.request.tenant
        if self.breaker.tripped(tenant):
            # raced a trip while queued (requeue after drain_tenant)
            self._fail_job_fast(job, "breaker opened while queued")
            return True
        try:
            # first traffic for a co-loaded model constructs its extractor
            # here, on the daemon thread, sharing the primary's resources
            ex = self.sessions.extractor(model)
        except KeyboardInterrupt:
            raise
        except Exception as e:  # noqa: BLE001 — fault-barrier: a model whose lazy construction fails (missing weights, invalid derived config) must fail ITS job cleanly, not kill the daemon serving the other models
            if not self._video_failed(path, e):
                # terminal: no session exists to run the shared accounting,
                # so record + count + journal here (the exit code must stay
                # honest AND the journal's video_failed stream must agree
                # with the failure counter — ex._fail, the usual emitter,
                # never runs when no extractor exists)
                print(f"[serve] cannot construct model {model!r} for "
                      f"{path}: {e}", file=sys.stderr)
                self._service_failures += 1
                err_class, transient = classify(e)
                self._emit("video_failed", video=path, model=model,
                           error_class=err_class, transient=transient)
                self.metrics.inc("videos_failed_total", model=model,
                                 error_class=err_class)
                try:
                    record_failure(feature_output_dir(
                        self.cfg.output_path, model), path, e)
                except OSError as rec_err:
                    print(f"warning: could not record failure for {path}: "
                          f"{rec_err}", file=sys.stderr)
            return True
        if self._try_cache(job, ex):
            return True
        # decode hints route per model and must not gate on the CURRENT
        # job's pool: a popped non-frame-stream job (vggish) still hints
        # queued frame-stream jobs of co-resident models (schedule_decode
        # no-ops for models without a frame stream)
        self.sessions.schedule_decode(path, model)
        pool = self.sessions.decode_pool
        if pool is not None:
            for j in self.queue.peek_jobs(max(pool.workers - 1, 0)):
                self.sessions.schedule_decode(
                    j.path, j.feature_type or self.cfg.feature_type)
        # per-video decode/transfer histograms: ingest pulls the clip stream
        # synchronously on this thread, so the service clock's stage deltas
        # over the ingest window are this video's attribution (approximate
        # by construction — concurrent staging-ring commits land in whatever
        # window is open — but the distribution is what capacity questions
        # need, not per-video forensics)
        clock = self.ex.clock
        d0 = clock.seconds.get("decode", 0.0)
        x0 = clock.seconds.get("transfer", 0.0)
        try:
            # the 'job' span: one popped job of the serving loop. Its
            # request id is inherited by the spans inside it ('extract' and
            # everything under that)
            with ex._span("job", request=job.request.request_id,
                          tenant=tenant, video=path):
                self.session.ingest(path, model, retries=0)
        except KeyboardInterrupt:
            raise
        except Exception as e:  # noqa: BLE001 — fault-barrier: the per-video isolation point (serving loop)
            # one schedule = one attempt; _video_failed (the session's
            # on_failed hook) owns the requeue-vs-terminal decision so this
            # path, failed writes, and co-packed batch victims all share one
            # retry budget
            self.session.fail(path, model, e)
        finally:
            self.sessions.release_decode(path)
            self.metrics.observe(
                "decode_seconds",
                max(clock.seconds.get("decode", 0.0) - d0, 0.0),
                tenant=tenant, model=model)
            self.metrics.observe(
                "transfer_seconds",
                max(clock.seconds.get("transfer", 0.0) - x0, 0.0),
                tenant=tenant, model=model)
        self.session.emit_completed(reap_limit=1)
        return True

    def run(self) -> int:
        """Serve until drained; returns 0 (no terminal failures) or 1."""
        if self.cfg.step_watchdog_sec:
            threading.Thread(target=self._watchdog_loop, daemon=True,
                             name="step-watchdog").start()
        try:
            while True:
                did = self.step()
                if self._draining.is_set() and self._quiescent():
                    # everything admitted has been ingested; pad-flush what
                    # still sits in the queues and resolve every write. A
                    # failed flush/write may REQUEUE its transient victims —
                    # quiescent again only once they resolved too
                    self.session.drain(final=True)
                    if self._quiescent():
                        break
                if not did:
                    time.sleep(self._poll)
            with self._lock:
                pending = [self._finish_request_locked(request, force=True)
                           for request in list(self._requests.values())]
            for finished in pending:
                self._publish_result(finished)
        finally:
            self.close()
        return (0 if self.sessions.failures == 0
                and self._service_failures == 0 else 1)

    def request_drain(self) -> None:
        if not self._draining.is_set():
            print("[serve] drain requested: finishing admitted videos, then "
                  "exiting")
        self._draining.set()

    def reload(self) -> None:
        """SIGHUP: re-read tenants.json, close every tenant breaker."""
        if self.cfg.spool_dir:
            self._load_tenants_config()
        self.breaker.reset()
        print("[serve] reload: tenant config re-read, breakers closed")

    def close(self) -> None:
        """Tear down run resources (idempotent; run() calls it on exit)."""
        if self._closed:
            return
        self._closed = True
        self._watchdog_stop.set()
        if self._wal is not None:
            self._wal.close()
        self.sessions.close()

    def _try_cache(self, job, ex) -> bool:
        """Feature-cache consult + in-flight coalescing for one popped job.

        ``ex`` is the job's MODEL extractor (multi-model daemons route every
        consult, publish, and key memo through the owning model — its config
        fingerprint keys the entry, so models never collide in the shared
        store). True when no extraction should run this step: the job was
        served from the cache (outputs + manifests written, zero device
        steps) or parked behind an identical in-flight extraction. Fairness
        holds either way — the pop that got us here already advanced the
        tenant's virtual time, and a parked waiter's replay is another pop.
        """
        if ex._cache is None:
            return False
        path = job.path
        model = job.feature_type or self.cfg.feature_type
        feats = ex._cache_fetch(path)
        if feats is not None:
            self.sessions.release_decode(path)  # may have been hint-scheduled
            job.from_cache = True
            try:
                ex._publish_cache_hit(path, feats,
                                      on_done=self._video_done)
            except KeyboardInterrupt:
                raise
            except Exception as e:  # noqa: BLE001 — fault-barrier: a hit's write failure is this video's own failure, owned by the shared requeue-vs-terminal logic
                self.session.fail(path, model, e)
                return True
            self.session.emit_completed(reap_limit=1)
            return True
        key = ex._cache_keys.get(os.path.abspath(path))
        if key is None:
            return False  # unhashable content: extract without coalescing
        if self._coalescer.wait(key, job):
            # identical extraction already in flight: park this job — the
            # leader's completion (or failure) re-enqueues it
            self.sessions.release_decode(path)
            self._emit("coalesced", video=path,
                       request=job.request.request_id,
                       tenant=job.request.tenant, model=model)
            return True
        self._coalescer.lead(key, path)
        return False

    # --- bookkeeping (PackedSession callbacks; daemon thread) ----------------

    def _release_waiters_locked(self, path: str) -> None:
        """Leader ``path`` resolved: re-enqueue its coalesced waiters with
        their original admission seqs (replays do not go to the back). After
        a successful leader they replay as cache hits; after a failed one the
        first replay becomes the next leader on its OWN retry budget — a
        leader's fault never reaches a waiter tenant's breaker."""
        waiters = self._coalescer.finish(path)
        if not waiters:
            return
        for wjob in waiters:
            self._jobs.pop(wjob.path, None)
        self.queue.requeue_all(waiters)

    def _video_done(self, path: str) -> None:
        with self._lock:
            self._release_waiters_locked(path)
            job = self._jobs.pop(path, None)
            if job is None:
                return
            if job.from_cache:
                job.request.cache_hits += 1
            # end-to-end latency: admission → outputs landed (requeues and
            # write resolution included) — the per-tenant/per-model p50/p99
            # the stats op reports and the journal's queued→done chain pins
            self.metrics.observe(
                "e2e_latency_seconds",
                max(time.monotonic() - job.admitted_at, 0.0),
                tenant=job.request.tenant,
                model=job.feature_type or self.cfg.feature_type)
            job.request.done.append(path)
            finished = self._finish_request_locked(job.request)
        self._publish_result(finished)

    def _video_failed(self, path: str, exc: BaseException) -> bool:
        """Claim a transient failure by re-enqueueing (returns True — the
        shared terminal accounting is skipped), else record it terminally.

        This is where a co-packed batch failure's VICTIMS land (a device
        fault on one dispatched batch fails every co-resident video): they
        are transient by classification, so they re-enter the scheduler
        under the same retry budget as a directly-failing video — an
        innocent tenant's video lost to a neighbour's poisoned batch must
        not count against that tenant's breaker."""
        finished = trip_tenant = None
        requeued = False
        with self._lock:
            self._release_waiters_locked(path)
            job = self._jobs.pop(path, None)
            if job is None:
                return False
            request = job.request
            err_class, transient = classify(exc)
            job.attempts += 1
            if (transient and job.attempts <= self.cfg.retries
                    and not self.breaker.tripped(request.tenant)):
                self.packer.discard(path)
                self.queue.requeue(job)
                requeued = True
            else:
                try:
                    exc.attempts = job.attempts  # manifest records attempts
                except AttributeError:
                    pass
                request.failed.append({
                    "video": path, "error_class": err_class,
                    "transient": transient, "message": str(exc)[:500],
                })
                finished = self._finish_request_locked(request)
                if self.breaker.record_failure(request.tenant):
                    # breaker state + queue drain stay atomic with the
                    # terminal count (submit checks tripped() under this
                    # lock); the per-job manifests + prints run after release
                    trip_tenant = request.tenant
                    trip_jobs = self.queue.drain_tenant(request.tenant)
        if requeued:
            print(f"[serve] [{err_class}] attempt {job.attempts} failed "
                  f"for {path}: {exc}; re-enqueued "
                  f"({self.cfg.retries + 1 - job.attempts} attempt(s) "
                  "left)")
            return True
        self._publish_result(finished)
        if trip_tenant is not None:
            self._fail_fast_tenant(trip_tenant, trip_jobs)
        return False

    def _fail_fast_tenant(self, tenant: str, jobs) -> None:
        """Breaker tripped: fail the tenant's already-drained queued videos
        without decoding (called with NO lock held — each fast failure
        writes a manifest line)."""
        self._emit("breaker_open", tenant=tenant,
                   failures=self.breaker.failures(tenant))
        self.metrics.inc("breaker_trips_total", tenant=tenant)
        print(f"[serve] tenant {tenant!r} breaker OPEN "
              f"({self.breaker.failures(tenant)} terminal failures): "
              f"failing {len(jobs)} queued video(s) fast; new submissions "
              "rejected until reload")
        for job in jobs:
            self._fail_job_fast(job, "tenant breaker open")

    def _fail_job_fast(self, job, why: str) -> None:
        exc = TenantBreakerOpen(
            f"{job.path}: {why} (tenant {job.request.tenant!r}); not "
            "attempted")
        # manifest the fast failure under the job's OWN model's output tree
        # (derivable without constructing a never-used model's extractor)
        model = job.feature_type or self.cfg.feature_type
        ex = self.sessions.peek_extractor(model)
        out_dir = (ex.output_dir if ex is not None
                   else feature_output_dir(self.cfg.output_path, model))
        try:
            record_failure(out_dir, job.path, exc)
        except OSError as e:
            print(f"warning: could not record failure for {job.path}: {e}",
                  file=sys.stderr)
        self.sessions.release_decode(job.path)  # may have been hint-scheduled
        # fast failures skip the extractor's _fail (no decode, no attempt)
        # so they journal AND count here — the lifecycle chain must still
        # terminate and the failure counter must agree with the journal's
        # video_failed stream during exactly the incident it exists for
        self._emit("video_failed", video=job.path, model=model,
                   error_class="TenantBreakerOpen", transient=False)
        self.metrics.inc("videos_failed_total", model=model,
                         error_class="TenantBreakerOpen")
        # a fast-failed ex-waiter still holds its consult-time cache key
        # (abspath-keyed, matching the memo — job.path is absolute by
        # admission, the abspath here is belt-and-braces)
        if ex is not None:
            ex._cache_keys.pop(os.path.abspath(job.path), None)
        with self._lock:
            self._jobs.pop(job.path, None)  # registered at pop; breaker-
            # drained queue jobs were never popped, so the default is taken
            job.request.failed.append({
                "video": job.path, "error_class": "TenantBreakerOpen",
                "transient": False, "message": str(exc)[:500],
            })
            finished = self._finish_request_locked(job.request)
        self._publish_result(finished)

    def _finish_request_locked(self, request: ServiceRequest,
                               force: bool = False):
        """Pop a completed request and build its result record (service lock
        HELD — callers pass the return to :meth:`_publish_result` after
        releasing). None when the request is still live."""
        if not request.complete and not force:
            return None
        record = request.result_record()
        if force and not request.complete:
            record["state"] = "aborted"  # drain unwound before completion
        self._requests.pop(request.request_id, None)
        # stay visible to status()/submit() until the record write lands —
        # a client polling the instant after completion must never see
        # "unknown request_id" for a request that just succeeded
        self._publishing[request.request_id] = record
        self._completed_requests += 1
        return (request, record)

    def _publish_result(self, finished) -> None:
        """Write + announce one finished request's record (NO lock held —
        the record write is disk I/O, and submitters on the ingest threads
        convoy on the service lock). Once a request left ``_requests`` its
        done/failed lists are final: no job references it, so reading them
        here is race-free; ``_publishing`` keeps it answerable meanwhile."""
        if finished is None:
            return
        request, record = finished
        published = False
        try:
            # post-extract / pre-publish chaos seam: a kill here leaves the
            # WAL entry unresolved, so the restarted daemon replays the
            # request, dedupes its done videos, and re-publishes the record
            fault_point("publish", request.request_id)
            write_request_result(self.notify_dir, request.request_id, record)
            published = True
        except Exception as e:  # noqa: BLE001 — fault-barrier: the notification is advisory; outputs + manifests already landed
            print(f"[serve] could not write result for "
                  f"{request.request_id}: {e}", file=sys.stderr)
        if published:
            # resolve only after the record landed: a failed publish keeps
            # the WAL entry live, and recovery re-publishes from the
            # done-manifests instead of losing the notification
            if self._wal is not None and request.wal_logged:
                self._wal.resolve(
                    request.request_id,
                    "done" if record.get("state") == "done" else "failed")
            self._cleanup_spool(request)
        with self._lock:
            self._publishing.pop(request.request_id, None)
        self._emit("request_done", request=request.request_id,
                   tenant=request.tenant, state=record["state"],
                   done=len(request.done), failed=len(request.failed))
        self.metrics.inc("requests_total", state=record["state"])
        print(f"[serve] request {request.request_id} {record['state']}: "
              f"{len(request.done)} done, {len(request.failed)} failed")
        self._autoscale_tick()

    def _cleanup_spool(self, request: ServiceRequest) -> None:
        """Spool hygiene: drop the claimed ``.accepted`` request file once
        its result record is published (and the WAL entry resolved) — the
        result record is the durable trace from here on. ``--spool_retain``
        keeps the files for debugging."""
        if (request.source != "spool" or not self.cfg.spool_dir
                or self.cfg.spool_retain):
            return
        try:
            os.remove(accepted_path(self.cfg.spool_dir, request.request_id))
        except OSError:
            pass  # already gone, or submitted pre-upgrade under a raw name

    # --- hung-step watchdog (--step_watchdog_sec) ---------------------------

    def _watchdog_loop(self) -> None:
        """Monitor thread: flag the daemon when the serving loop has not
        stepped past the threshold. Communication is Events only (SETS
        ``_stalled``; the daemon thread clears it and requeues) — the
        monitor never touches request state, so a false positive during a
        legitimately long first-traffic compile costs one transient requeue
        of the in-flight batch, not correctness."""
        thresh = self.cfg.step_watchdog_sec
        poll = min(max(thresh / 4.0, 0.05), 1.0)
        while not self._watchdog_stop.wait(poll):
            age = time.monotonic() - self._last_step
            if age > thresh and not self._stalled.is_set():
                self._stalled.set()
                self._emit("watchdog_stale", age_sec=round(age, 3),
                           threshold_sec=thresh)
                self.metrics.inc("watchdog_trips_total")
                print(f"[serve] watchdog: no step for {age:.1f}s "
                      f"(threshold {thresh}s); in-flight videos will fail "
                      "transiently and requeue once the loop resumes",
                      file=sys.stderr)

    def _requeue_stalled(self) -> None:
        """The watchdog tripped while the previous step was wedged: turn the
        stall into a transient batch failure. Every in-flight video fails
        through the session's slot-attribution path (the same machinery a
        poisoned co-packed batch uses), so victims requeue with their retry
        budgets and breakers charge nobody for a device stall."""
        with self._lock:
            victims = [(path, job.feature_type or self.cfg.feature_type)
                       for path, job in self._jobs.items()]
        if not victims:
            return
        print(f"[serve] watchdog: failing {len(victims)} stalled in-flight "
              f"video(s) transiently for requeue", file=sys.stderr)
        for path, model in victims:
            self.sessions.release_decode(path)
            self.session.fail(path, model, DeviceError(
                f"{path}: device step stalled past "
                f"--step_watchdog_sec={self.cfg.step_watchdog_sec}; "
                "attempt abandoned"))
        self.session.emit_completed(reap_limit=0)

    def _autoscale_tick(self) -> None:
        """Between requests: act on the interval's decode-starvation signal.
        Measure + snapshot swap + decide run under the service lock as one
        unit (request completions land from the daemon thread AND submit-
        time all-resumed completions from ingest threads — a torn interval
        would regress the snapshot and double-apply a resize step); decide()
        is pure arithmetic, so only the print and the internally-locked
        ``pool.resize`` stay outside."""
        pool = self.sessions.decode_pool
        if self._autoscaler is None or pool is None:
            return
        # read the pool's idle-permit headroom BEFORE taking the service
        # lock: spare_permits() takes the pool's resize lock, and the
        # declared lock order has no service→resize edge to lean on
        spare = pool.spare_permits()
        with self._lock:
            now = time.perf_counter()
            decode = self.ex.clock.seconds.get("decode", 0.0)
            real, slots = self.packer.real_slots, self.packer.dispatched_slots
            t0, d0, r0, s0 = self._as_snapshot
            self._as_snapshot = (now, decode, real, slots)
            d_slots = slots - s0
            occupancy = (real - r0) / d_slots if d_slots else 1.0
            current = pool.workers
            new = self._autoscaler.decide(occupancy, decode - d0, now - t0,
                                          current,
                                          dispatched_slots=d_slots,
                                          spare_permits=spare)
        if new != current:
            print(f"[serve] decode autoscale: {current} → {new} "
                  f"worker(s) (interval occupancy {occupancy:.1%}, decode "
                  f"{decode - d0:.2f}s of {now - t0:.2f}s)")
            self._emit("autoscale", workers_from=current, workers_to=new,
                       occupancy=round(occupancy, 4), spare_permits=spare)
            pool.resize(new)
            self.metrics.set_gauge("decode_workers", new)

    def _quiescent(self) -> bool:
        with self._lock:
            return (self.queue.pending() == 0 and not self._jobs
                    and not self.packer.has_pending()
                    and not self.sessions.pending_writes())

    def _load_tenants_config(self, initial: bool = False) -> None:
        path = os.path.join(self.cfg.spool_dir, SPOOL_TENANTS_FILE)
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                self.queue.configure(json.load(f))
            print(f"[serve] tenant config loaded from {path}")
        except (OSError, ValueError) as e:
            msg = f"[serve] bad tenant config {path}: {e}"
            if initial:
                raise ValueError(msg) from e
            print(msg + " — keeping the previous config", file=sys.stderr)

    # --- socket API ----------------------------------------------------------

    def status(self, request_id: str) -> dict:
        with self._lock:
            request = self._requests.get(request_id)
            if request is not None:
                return {"ok": True, "state": request.state,
                        "tenant": request.tenant,
                        "feature_type": request.feature_type,
                        "videos": len(request.videos),
                        "done": len(request.done),
                        "failed": len(request.failed)}
            publishing = self._publishing.get(request_id)
            if publishing is not None:
                # completed, record write still in flight: answer from the
                # in-memory record rather than racing the disk
                return {"ok": True, **publishing}
        path = request_result_path(self.notify_dir, request_id)
        if os.path.exists(path):
            try:
                with open(path) as f:
                    record = json.load(f)
                return {"ok": True, **record}
            except (OSError, ValueError) as e:
                return {"ok": False, "error": f"unreadable result: {e}"}
        return {"ok": False, "error": f"unknown request_id {request_id!r}"}

    def _transfer_stats(self) -> dict:
        """Host→device staging counters from the service-lifetime clock plus
        the staging ring's reuse/backpressure accounting."""
        clock = self.ex.clock
        seconds = clock.seconds.get("transfer", 0.0)
        nbytes = clock.bytes.get("transfer", 0)
        ring = self.ex._staging
        return {
            "seconds": round(seconds, 3),
            "bytes": int(nbytes),
            "mb_per_s": round(nbytes / seconds / 1e6, 2) if seconds else 0.0,
            "staging_buffers": ring.allocated,
            "staging_acquires": ring.acquires,
            "staging_evicted_geometries": ring.evicted_geometries,
            "staging_wait_sec": round(ring.wait_seconds, 3),
        }

    def stats(self) -> dict:
        pool = self.sessions.decode_pool
        seg_videos, seg_segments = (pool.segment_stats() if pool is not None
                                    else (0, 0))
        # per-model rollup: packer occupancy by model × completion counters
        # (only models that saw traffic appear — lazily-built extractors)
        model_occ = self.packer.model_stats()
        models = {}
        for name, counts in self.sessions.model_counts().items():
            models[name] = dict(counts)
            models[name].update(model_occ.get(name, {}))
        with self._lock:
            return {
                "ok": True,
                # payload version (docs/serving.md documents the field tree):
                # external scrapers pin this and treat a bump as a breaking
                # change; additive fields do not bump it
                "schema": 1,
                "feature_type": self.cfg.feature_type,
                "serving_models": list(self.models),
                "uptime_sec": round(time.monotonic() - self._started, 3),
                "draining": self._draining.is_set(),
                "live_requests": len(self._requests),
                "in_flight_videos": len(self._jobs),
                "queued_videos": self.queue.pending(),
                "completed_requests": self._completed_requests,
                "videos_ok": self.sessions.ok,
                "videos_failed": (self.sessions.failures
                                  + self._service_failures),
                # per-model occupancy/throughput (multi-model daemons: the
                # one-line answer to "is model B starving the mesh?")
                "models": models,
                "packing": {
                    "real_slots": self.packer.real_slots,
                    "dispatched_slots": self.packer.dispatched_slots,
                    "occupancy": round(self.packer.occupancy, 4),
                    # per-shape-bucket occupancy (operators watch a rare
                    # bucket starving without tailing the daemon log)
                    "buckets": self.packer.bucket_stats(),
                    "stale_flushes": self.packer.stale_flushes,
                    # ragged paged dispatch (parallel/pages.py; additive —
                    # no schema bump): page count, the deepest observed
                    # in-flight ring, and the page-level occupancy (real
                    # rows / dispatched page rows — the page_occupancy
                    # gauge's corpus-cumulative answer)
                    "pages_dispatched": self.packer.pages_dispatched,
                    "max_in_flight": self.packer.max_in_flight,
                    "page_occupancy": (round(self.packer.occupancy, 4)
                                       if self.packer.pages_dispatched
                                       else 0.0),
                },
                # host→device staging health (ingest fast path): operators
                # can tell a transfer-bound daemon from a decode-bound one
                # without tailing the log (seconds/bytes are defaultdict
                # .get reads — atomic enough against the daemon thread)
                "transfer": self._transfer_stats(),
                # per-stage wall seconds from the service-lifetime clock
                # (additive, no schema bump): the decode/transfer split that
                # tells WHERE preprocessing cost lives — --device_preproc
                # moves the per-frame PIL/DSP work out of the decode pool
                # and into the jitted step, and this is the operator-visible
                # meter for it (tools/service_smoke.py pins the section).
                # dict() snapshots atomically under the GIL before iterating
                # — the run loop may be inserting a first-seen stage key
                "stages": {k: round(v, 3)
                           for k, v in dict(self.ex.clock.seconds).items()},
                "cache": (dict(self.ex._cache.stats(),
                               coalesced=self._coalescer.coalesced,
                               waiting=self._coalescer.waiting())
                          if self.ex._cache is not None
                          else {"enabled": False}),
                # admission durability (serve/wal.py): additive section, no
                # schema bump — durable flag, unresolved depth, compactions
                "wal": (self._wal.stats() if self._wal is not None
                        else {"enabled": False}),
                "decode_workers": pool.workers if pool is not None else 0,
                # segmented intra-video decode (additive, no schema bump):
                # videos split across permits and segment streams completed
                "segmented_decode": {
                    "videos": seg_videos,
                    "segments": seg_segments,
                },
                "tenants": self.queue.stats(),
                "breaker_open": list(self.breaker.open_tenants()),
                # per-tenant × per-model latency distributions (p50/p95/p99
                # + counts) from the live histograms — the after-the-fact
                # "why was tenant B's p99 bad?" answer the point-in-time
                # counters above cannot give; full bucket detail is on the
                # `metrics` op
                "latency": {
                    "e2e": self.metrics.summaries("e2e_latency_seconds"),
                    "queue_wait": self.metrics.summaries(
                        "queue_wait_seconds"),
                },
                "telemetry": (self.journal.stats() if self.journal is not None
                              else {"enabled": False}),
            }

    def healthz(self) -> dict:
        """Liveness + staleness, served from the API thread WITHOUT the
        service lock — a wedged daemon thread (or one stalled in a long
        first-traffic compile) still answers, and ``last_step_age_sec`` is
        how an operator tells the two apart. ``stale`` trips once the loop
        has not stepped for ``--healthz_stale_sec``; a legitimate cause
        (a 60 s flow compile) looks identical to a wedge by design — both
        mean "the daemon is not serving right now". The ``wal`` section is
        the durability signal: ``durable: false`` means admissions are
        being acknowledged WITHOUT a landed WAL record (ENOSPC degrade) and
        a crash would lose them — page on it."""
        now = time.monotonic()
        age = now - self._last_step
        return {
            "ok": True,
            "schema": 1,
            "uptime_sec": round(now - self._started, 3),
            "last_step_age_sec": round(age, 3),
            "stale": age > self.cfg.healthz_stale_sec,
            "stale_threshold_sec": self.cfg.healthz_stale_sec,
            "draining": self._draining.is_set(),
            "profiling": self._profiling,
            "wal": (self._wal.health() if self._wal is not None
                    else {"enabled": False}),
        }

    def _profile_op(self, action: str, trace_dir: Optional[str]) -> dict:
        """On-demand ``jax.profiler`` session in the LIVE daemon (`profile`
        op): start captures device/host activity from now, stop writes the
        trace for TensorBoard/XProf. Runs on the API thread —
        ``jax.profiler.start_trace`` is process-global, so it sees the
        daemon thread's device work."""
        import jax

        if action == "start":
            if self._profiling is not None:
                return {"ok": False, "error": f"already profiling into "
                                              f"{self._profiling}; stop first"}
            trace_dir = trace_dir or self.cfg.profile_dir or (
                os.path.join(self.cfg.telemetry_dir, "profile")
                if self.cfg.telemetry_dir else None)
            if not trace_dir:
                return {"ok": False,
                        "error": "no trace dir: pass {\"dir\": ...} or start "
                                 "the daemon with --profile_dir/"
                                 "--telemetry_dir"}
            try:
                os.makedirs(trace_dir, exist_ok=True)
                jax.profiler.start_trace(trace_dir)
            except Exception as e:  # noqa: BLE001 — fault-barrier: a profiler that cannot start (backend quirk, bad dir) must report, not kill the API thread serving the live daemon
                return {"ok": False, "error": f"start_trace failed: {e}"}
            self._profiling = trace_dir
            self._emit("profile_start", dir=trace_dir)
            return {"ok": True, "profiling": trace_dir}
        if action == "stop":
            if self._profiling is None:
                return {"ok": False, "error": "not profiling; start first"}
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — fault-barrier: a failing stop (full trace disk mid-export) must report over the socket and stay RETRYABLE, not dead-end the op
                # keep _profiling set: jax's global session is usually still
                # live after a failed export, so a retried stop can succeed.
                # If jax says there IS no session (export failed after the
                # session ended), clear the flag so a fresh start works —
                # either way the op recovers without a daemon restart.
                if "not started" in str(e).lower() \
                        or "no profile" in str(e).lower():
                    self._profiling = None
                return {"ok": False, "error": f"stop_trace failed: {e}"}
            trace_dir, self._profiling = self._profiling, None
            self._emit("profile_stop", dir=trace_dir)
            return {"ok": True, "trace_dir": trace_dir}
        return {"ok": False,
                "error": "profile needs \"action\": \"start\" or \"stop\""}

    def handle_op(self, op: dict) -> dict:
        """Dispatch one socket-API operation (transport in :mod:`.ingest`)."""
        kind = op.get("op")
        if kind == "ping":
            return {"ok": True}
        if kind == "healthz":
            return self.healthz()
        if kind == "metrics":
            # full registry dump + Prometheus text exposition from ONE
            # series copy: scrapers take the text, humans/tools the
            # structured snapshot
            snapshot, text = self.metrics.export()
            return {"ok": True, "schema": 1,
                    "metrics": snapshot, "prometheus": text}
        if kind == "profile":
            return self._profile_op(str(op.get("action", "")), op.get("dir"))
        if kind == "submit":
            try:
                request = self.submit(op, request_id=op.get("request_id"),
                                      source="socket")
            except RequestRejected as e:
                self._emit("request_rejected",
                           request=op.get("request_id"),
                           reason=str(e)[:200])
                return {"ok": False, "error": str(e)}
            return {"ok": True, "request_id": request.request_id,
                    "state": request.state}
        if kind == "status":
            return self.status(str(op.get("request_id", "")))
        if kind == "stats":
            return self.stats()
        if kind == "drain":
            self.request_drain()
            return {"ok": True, "draining": True}
        if kind == "reload":
            # applied by the daemon loop before its next pop (thread safety:
            # reload mutates scheduler weights and breakers)
            self._hup.set()
            return {"ok": True, "reload": "scheduled"}
        return {"ok": False, "error": f"unknown op {kind!r}"}


def serve(cfg) -> int:
    """Run the daemon for ``cfg`` (``--serve`` / ``python -m …serve``)."""
    from ..extractors import get_extractor

    if not cfg.spool_dir:
        print("--serve requires --spool_dir (the watched request directory)",
              file=sys.stderr)
        return 2
    os.makedirs(cfg.spool_dir, exist_ok=True)
    extractor = get_extractor(cfg)
    try:
        service = ExtractionService(extractor)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    watcher = SpoolWatcher(cfg.spool_dir, service,
                           poll_interval=cfg.spool_poll_sec)
    sock_path = cfg.socket_path
    if sock_path is None:
        sock_path = os.path.join(cfg.spool_dir, "control.sock")
    api = (SocketAPI(sock_path, service)
           if sock_path and sock_path.lower() != "none" else None)

    def on_term(signum, frame):
        if service._draining.is_set():
            raise KeyboardInterrupt  # second signal: abort now
        service.request_drain()

    def on_hup(signum, frame):
        service._hup.set()

    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGTERM, on_term)
        signal.signal(signal.SIGINT, on_term)
        signal.signal(signal.SIGHUP, on_hup)
    # replay a crashed predecessor's unresolved admissions BEFORE the ingest
    # transports open: recovered jobs hold their original seqs, and no fresh
    # submission can race the seq fast-forward
    service.recover()
    watcher.start()
    if api is not None:
        api.start()
        print(f"[serve] socket API at {sock_path}")
    print(f"[serve] {describe_devices(extractor.runner.mesh)}")
    if metrics_enabled(cfg.profile_dir, cfg.telemetry_dir):
        # what the start-up cost: construction, checkpoints, compiles so far
        print(f"[serve] {setup_report(setup_recorder().export())}")
    print(f"[serve] watching {cfg.spool_dir} "
          f"(results → {service.notify_dir}); SIGTERM drains, SIGHUP "
          "reloads")
    try:
        return service.run()
    finally:
        watcher.stop()
        if api is not None:
            api.stop()


def main(argv=None) -> int:
    """``python -m video_features_tpu.serve`` — the batch CLI surface with
    ``--serve`` implied."""
    from ..cli import parse_args

    enable_compilation_cache()
    cfg = parse_args(list(argv) if argv is not None else None)
    if not cfg.serve:
        cfg = cfg.replace(serve=True)
        try:
            cfg.validate()
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 2
    return serve(cfg)

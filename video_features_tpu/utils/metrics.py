"""Per-stage timing and profiler hooks (SURVEY.md §5: the reference has tqdm
bars and nothing else; diagnosing whether decode, transfer, or compute bounds a
run is the whole perf game on TPU).

The stage accumulators (:class:`StageClock`) are always on. One switch —
``VFT_METRICS=1``, ``--profile_dir DIR`` (which also wraps the run in a
``jax.profiler`` trace; view with TensorBoard/XProf) or ``--telemetry_dir DIR``
— turns on the printed stage report and the in-memory span records
(:class:`SpanRecorder`).

One span call marks every layer boundary: :func:`span`, reached through
``Extractor._span``. One entry/exit of it adds to the stage clock, enters a
``jax.profiler.TraceAnnotation``, emits the journal's start/end pair and, with
recording on, keeps one record stamped in ``time.time_ns()`` — the clock a
profiler trace is on too (``profile_start_time`` of its ``Task Environment``
plane plus an event's ``start_ns``), so host spans and device operations lie
on one axis without the program knowing that a trace is being taken
(docs/observability.md).

Stage semantics (async device dispatch makes naive timing lie):
- ``decode``: host time blocked pulling frames from the decoder/transform
  iterator — real decode-bound time.
- ``device_wait``: host time blocked on device results (``np.asarray`` /
  ``block_until_ready``) — compute-bound time NOT hidden by prefetch.
- ``transfer``: host time staging batches onto the mesh (``device_put``
  dispatch plus any staging-ring wait for a pending host→device copy to
  finish before its buffer is rewritten), with the staged payload bytes
  attached — the report derives host→device MB/s from them, so a run can be
  told apart as decode-bound vs transfer-bound (docs/performance.md ingest
  fast path).
- ``wall``: end-to-end per video. ``wall − decode − device_wait`` ≈ host
  stacking/bookkeeping overlapped with device work.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Callable, Dict, Iterable, Iterator, Optional


def metrics_enabled(profile_dir=None, telemetry_dir=None) -> bool:
    """The one switch of the printed stage report and the span records."""
    return (bool(profile_dir) or bool(telemetry_dir)
            or os.environ.get("VFT_METRICS") == "1")


# decode-starvation heuristic (--pack_corpus): warn when the packer burned a
# lot of padding (occupancy below the threshold) while the run spent most of
# its wall blocked pulling frames — the decode pool, not the mesh, was the
# ceiling (ROADMAP item 4). Thresholds are deliberately loose: this is a
# "look at --decode_workers" nudge, not an SLO.
STARVED_OCCUPANCY = 0.8
STARVED_DECODE_FRACTION = 0.4
STARVED_TRANSFER_FRACTION = 0.4


def decode_starvation_warning(occupancy: float, decode_seconds: float,
                              wall: float, stale_flushes: int = 0,
                              transfer_seconds: float = 0.0,
                              ) -> Optional[str]:
    """Message when a packed run's padding is decode- (or transfer-)
    starvation, else None.

    ``occupancy``: real clips / dispatched device slots for the whole corpus.
    ``decode_seconds``: host time blocked on the frame stream ('decode' stage).
    ``wall``: packed-run wall-clock. ``stale_flushes``: anti-starvation
    flushes taken (each one trades padding for latency, so a high count with
    low occupancy strengthens the signal — it is reported, not gated on).
    ``transfer_seconds``: host time blocked staging batches onto the mesh
    ('transfer' stage) — when the padding is burned waiting on the host→device
    pipe rather than on decode, raising --decode_workers would do nothing, so
    the message names the right lever instead.
    """
    if wall <= 0 or occupancy >= STARVED_OCCUPANCY:
        return None
    decode_fraction = decode_seconds / wall
    flushes = (f" and {stale_flushes} anti-starvation flush(es)"
               if stale_flushes else "")
    if decode_fraction >= STARVED_DECODE_FRACTION:
        return (f"warning: packing occupancy {occupancy:.1%} with "
                f"{decode_fraction:.0%} of wall blocked on decode"
                + flushes
                + " — the decode pool is starving the mesh; raise "
                "--decode_workers (docs/performance.md)")
    transfer_fraction = transfer_seconds / wall
    if transfer_fraction >= STARVED_TRANSFER_FRACTION:
        return (f"warning: packing occupancy {occupancy:.1%} with "
                f"{transfer_fraction:.0%} of wall blocked on host→device "
                "transfer" + flushes
                + " — the transfer pipe, not decode, is starving the mesh; "
                "check the transfer-stage MB/s and drop --float32_wire if "
                "set (docs/performance.md)")
    return None


class StageClock:
    """Accumulates seconds per named stage.

    Thread-safe: increments arrive from the run-loop/daemon thread
    (``timed_iter``, a span's ``add``), the staging ring's commit hooks, and the
    async writer's reap concurrently, so every mutation holds ``_lock`` — a
    lost ``+=`` would silently skew the report and the starvation heuristic.
    The accumulator dicts are declared under the ``clock`` lock in vftlint's
    ``GUARDED_BY`` map (docs/static-analysis.md), which mechanizes exactly
    that bug class within this module; the daemon's cross-module
    ``clock.seconds.get`` peeks are deliberate dirty reads of defaultdict
    floats, documented at their sites.

    ``registry``/``labels``: an optional :class:`..obs.MetricsRegistry` that
    every accumulation is mirrored into (``stage_seconds_total``,
    ``stage_bytes_total``, ``stage_units_total``, labeled ``stage=<name>``
    plus ``labels``) — the serving daemon's long-lived clock feeds the
    ``metrics`` socket op and the Prometheus exposition through this seam
    (docs/observability.md).
    """

    def __init__(self, registry=None, labels: Optional[Dict] = None):
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self.counts: Dict[str, int] = collections.defaultdict(int)
        # dimensionless counters (no time attached), e.g. the packed loop's
        # dispatched device slots vs real clips (packing occupancy)
        self.units: Dict[str, int] = collections.defaultdict(int)
        # payload bytes attributed per stage (timed_iter bytes_of): the report
        # derives stage throughput (MB/s) from bytes/seconds — decode MB/s is
        # the ingest-rate signal the starvation heuristic keys on
        self.bytes: Dict[str, int] = collections.defaultdict(int)
        self._lock = threading.Lock()
        self._registry = registry
        self._labels = dict(labels) if labels else {}

    def _feed(self, metric: str, stage: str, value) -> None:
        if self._registry is not None:
            self._registry.inc(metric, value, stage=stage, **self._labels)

    def add_units(self, name: str, n: int = 1) -> None:
        """Accumulate a dimensionless counter reported alongside the stages."""
        with self._lock:
            self.units[name] += n
        self._feed("stage_units_total", name, n)

    def add_seconds(self, name: str, seconds: float) -> None:
        """Attribute externally-measured blocked time to a stage (e.g. the
        staging ring's wait for a pending host→device copy)."""
        with self._lock:
            self.seconds[name] += seconds
        self._feed("stage_seconds_total", name, seconds)

    def add_bytes(self, name: str, n: int) -> None:
        """Attribute payload bytes to a stage (timed_iter's ``bytes_of``
        does this for iterator stages, :meth:`add` for a span's)."""
        with self._lock:
            self.bytes[name] += n
        self._feed("stage_bytes_total", name, n)

    def add(self, name: str, seconds: float, nbytes: int = 0) -> None:
        """One finished interval of a stage: its seconds, one count and its
        payload bytes (what one :func:`span` exit adds)."""
        with self._lock:
            self.seconds[name] += seconds
            self.counts[name] += 1
            if nbytes:
                self.bytes[name] += nbytes
        self._feed("stage_seconds_total", name, seconds)
        if nbytes:
            self._feed("stage_bytes_total", name, nbytes)

    # registry mirroring from timed_iter is batched: the iterator runs per
    # FRAME on the decode hot path, and a per-item registry inc (label-key
    # build + the registry lock, contended against the stats API thread)
    # would tax exactly the path telemetry promises not to. The local dicts
    # stay per-item-exact under _lock; the mirror flushes every N items and
    # on generator exit (StopIteration, abandonment, GC close — the finally
    # runs for all of them), so the registry lags by at most one flush.
    _FEED_EVERY = 64

    def timed_iter(self, it: Iterable, name: str,
                   bytes_of: Optional[Callable] = None,
                   on_blocked: Optional[Callable] = None) -> Iterator:
        """Wrap an iterator, attributing time blocked in ``next()`` to ``name``.

        ``bytes_of(item)``, when given, accounts each item's payload size so
        the report can state the stage's throughput (e.g. decoded MB/s).
        ``on_blocked(seconds)``, when given, is called right after a
        ``next()`` that blocked :data:`BLOCKED_RECORD_SECONDS` or longer (the
        ``pull`` span records: bounded by blocked time, not by item count).
        """
        it = iter(it)
        pending_s = 0.0
        pending_b = 0
        pending_n = 0
        try:
            while True:
                t0 = time.perf_counter()
                try:
                    item = next(it)
                except StopIteration:
                    dt = time.perf_counter() - t0
                    with self._lock:
                        self.seconds[name] += dt
                    pending_s += dt
                    if on_blocked is not None and dt >= BLOCKED_RECORD_SECONDS:
                        on_blocked(dt)
                    return
                dt = time.perf_counter() - t0
                if on_blocked is not None and dt >= BLOCKED_RECORD_SECONDS:
                    on_blocked(dt)
                nbytes = bytes_of(item) if bytes_of is not None else 0
                with self._lock:
                    self.seconds[name] += dt
                    self.counts[name] += 1
                    if nbytes:
                        self.bytes[name] += nbytes
                pending_s += dt
                pending_b += nbytes
                pending_n += 1
                if pending_n >= self._FEED_EVERY:
                    self._feed("stage_seconds_total", name, pending_s)
                    if pending_b:
                        self._feed("stage_bytes_total", name, pending_b)
                    pending_s, pending_b, pending_n = 0.0, 0, 0
                yield item
        finally:
            if pending_s:
                self._feed("stage_seconds_total", name, pending_s)
            if pending_b:
                self._feed("stage_bytes_total", name, pending_b)

    def report(self, label: str, wall: float) -> str:
        with self._lock:
            seconds = dict(self.seconds)
            counts = dict(self.counts)
            nbytes = dict(self.bytes)
            units = dict(self.units)
        parts = [f"{label}: wall {wall:.2f}s"]
        for name in sorted(seconds):
            stage = f"{name} {seconds[name]:.2f}s/{counts.get(name, 0)}"
            if nbytes.get(name) and seconds[name] > 0:
                mbps = nbytes[name] / seconds[name] / 1e6
                stage += f" ({mbps:.1f} MB/s)"
            parts.append(stage)
        accounted = sum(seconds.values())
        parts.append(f"overlapped/other {max(wall - accounted, 0.0):.2f}s")
        for name in sorted(units):
            parts.append(f"{name}={units[name]}")
        if units.get("packed_slots"):
            # packing-occupancy stage: real clips per dispatched device slot
            occ = units["packed_clips"] / units["packed_slots"]
            parts.append(f"pack_occupancy {occ:.1%}")
        return " | ".join(parts)


# a blocked interval shorter than this leaves no span record (it still adds
# to the stage clock): an idle gap under a millisecond is not worth a name
BLOCKED_RECORD_SECONDS = 1e-3

# the identifiers the spans of one unit of work share; a span opened inside
# another inherits those it was not given (a `put` inside page 7's `launch`
# belongs to page 7)
UNIT_IDS = ("video", "page", "request")


class SpanRecorder:
    """Bounded in-memory list of span records, on the ``time.time_ns()`` clock.

    A record is ``{"name", "thread", "start", "end", "parent", "ids"}``:
    ``parent`` is the index of the span that was open on the same thread when
    this one started (None for a root). Records are appended at a span's start,
    so a parent's index is below its children's; beyond ``limit`` a span is
    counted in ``dropped`` and leaves no record (its children then hang from
    the nearest recorded ancestor). Any thread may record; one lock guards the
    list, each thread keeps its own stack of open spans.
    """

    # a 40 s window of the I3D configuration keeps a few hundred records, of
    # the ResNet one (16 s blocked on decode, 1 ms or more at a time) at most
    # some ten thousand
    DEFAULT_LIMIT = 65536

    def __init__(self, limit: int = DEFAULT_LIMIT):
        self.limit = limit
        self.records: list = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._open = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _append(self, name: str, start: int, end: Optional[int],
                stack: list, ids: Dict) -> Optional[int]:
        parent = next((i for i in reversed(stack) if i is not None), None)
        with self._lock:
            if len(self.records) >= self.limit:
                self.dropped += 1
                return None
            if parent is not None:
                inherited = self.records[parent]["ids"]
                ids = {**{k: inherited[k] for k in UNIT_IDS if k in inherited},
                       **ids}
            self.records.append({
                "name": name, "thread": threading.current_thread().name,
                "start": start, "end": end, "parent": parent, "ids": ids})
            return len(self.records) - 1

    def begin(self, name: str, ids: Dict) -> Optional[int]:
        stack = self._stack()
        index = self._append(name, time.time_ns(), None, stack, ids)
        stack.append(index)
        return index

    def end(self, index: Optional[int], ids: Optional[Dict] = None) -> None:
        """Close the innermost open span of this thread; ``ids`` adds what
        was only known at the end (bytes written, retries)."""
        self._stack().pop()
        if index is not None:
            record = self.records[index]
            if ids:
                record["ids"].update(ids)
            record["end"] = time.time_ns()

    def add(self, name: str, seconds: float, **ids) -> None:
        """A span that just ended and lasted ``seconds``, recorded after the
        fact with its real start and end (a blocked ``next()``, a wait for a
        pending transfer)."""
        end = time.time_ns()
        self.interval(name, end - int(seconds * 1e9), end, **ids)

    def interval(self, name: str, start: int, end: int, **ids) -> None:
        """A span that ended, given its start and end in ``time.time_ns()``
        (JAX's compile events say both)."""
        self._append(name, start, end, self._stack(), ids)

    def self_seconds(self) -> Dict[str, float]:
        """Per span name, duration less what child spans on the same thread
        cover: the time that belongs to the span's own code. (Spans of one
        thread nest and never overlap, so a span's children are disjoint.)"""
        with self._lock:
            records = list(self.records)
        covered = collections.defaultdict(int)
        for r in records:
            if r["parent"] is not None and r["end"] is not None:
                covered[r["parent"]] += r["end"] - r["start"]
        out: Dict[str, float] = collections.defaultdict(float)
        for i, r in enumerate(records):
            if r["end"] is not None:
                out[r["name"]] += (r["end"] - r["start"] - covered[i]) / 1e9
        return dict(out)

    def export(self) -> Dict:
        """What ``_pack_stats["spans"]`` holds at the end of a run."""
        self_seconds = self.self_seconds()
        with self._lock:
            records = [dict(r) for r in self.records]
            dropped = self.dropped
        return {"clock": "time_ns", "records": records,
                "self_seconds": {k: round(v, 6)
                                 for k, v in self_seconds.items()},
                "dropped": dropped}

    def report(self) -> str:
        """One line for the stage report: self time per span name."""
        parts = [f"{name} {s:.2f}s" for name, s in
                 sorted(self.self_seconds().items(), key=lambda kv: -kv[1])]
        tail = f" | dropped {self.dropped}" if self.dropped else ""
        return "span self time: " + " | ".join(parts) + tail


class SpanHandle:
    """What :func:`span` yields: ``ids`` takes what is only known inside the
    span (it lands in the record and the journal's end event); ``seconds`` is
    the span's duration once it has ended."""

    __slots__ = ("ids", "seconds")

    def __init__(self):
        self.ids: Dict = {}
        self.seconds = 0.0


@contextlib.contextmanager
def span(name: str, clock: Optional[StageClock] = None,
         recorder: Optional[SpanRecorder] = None, journal=None,
         stage: Optional[str] = None, nbytes: int = 0, **ids):
    """THE span call (module docstring). ``stage`` names the clock stage the
    seconds (and ``nbytes``) are added to; ``ids`` go to the annotation, the
    journal pair and the record. None-valued ids are left out. Every sink is
    optional: a decode pool, packer or writer built without an extractor
    (the tests') uses the bare call, which still times and annotates."""
    from jax.profiler import TraceAnnotation

    ids = {k: v for k, v in ids.items() if v is not None}
    handle = SpanHandle()
    index = recorder.begin(name, dict(ids)) if recorder is not None else None
    sid = journal.begin(name, **ids) if journal is not None else None
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name, **ids):
            yield handle
    finally:
        handle.seconds = time.perf_counter() - t0
        if stage is not None and clock is not None:
            clock.add(stage, handle.seconds, nbytes)
        if sid is not None:
            journal.end(name, sid, **{**ids, **handle.ids})
        if recorder is not None:
            recorder.end(index, handle.ids)


# --- set-up ------------------------------------------------------------------
#
# What a process does before its first run — an extractor's construction, the
# checkpoint read and placed, every compile or persistent-cache load — is
# recorded whatever the switch says, in ONE process-wide recorder (compiles
# are process-wide): spans that happen a few times a process, never per page
# or video. Each run's ``_pack_stats["setup"]`` is its export
# (docs/observability.md "Set-up").

SETUP_LIMIT = 4096
_SETUP = SpanRecorder(SETUP_LIMIT)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# after-the-fact records of what precedes a compile: Python to a jaxpr, the
# jaxpr to an MLIR module
LOWERING_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}

_listening = False
_listen_lock = threading.Lock()
# per thread: whether a persistent-cache hit was announced inside the compile
# event that has not ended yet (JAX records the hit first, on the same
# thread), and how deep it is in lowering events that have begun and not
# ended (a jit traced inside another's trace reports its own event: only the
# outermost is kept, so the records neither count a second twice nor grow
# with every jnp function a model's trace calls)
_thread = threading.local()


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _thread.cache_hit = True


def _on_scalar(event: str, _value, **_kw) -> None:
    # JAX marks the start of each timed compile-path event with a scalar
    if event in LOWERING_EVENTS:
        _thread.depth = getattr(_thread, "depth", 0) + 1


def _on_time_span(event: str, start: float, end: float, fun_name: str = "", **_kw) -> None:
    if event != COMPILE_EVENT:
        return
    hit = getattr(_thread, "cache_hit", False)
    _thread.cache_hit = False
    _SETUP.interval("compile", int(start * 1e9), int(end * 1e9), program=fun_name,
                    cache="hit" if hit else "miss")


def _on_duration(event: str, seconds: float, fun_name: str = "", **_kw) -> None:
    kind = LOWERING_EVENTS.get(event)
    if kind is None:
        return
    _thread.depth = depth = max(getattr(_thread, "depth", 1) - 1, 0)
    if depth == 0:
        _SETUP.add(kind, seconds, program=fun_name)


def setup_recorder() -> SpanRecorder:
    """The process-wide set-up recorder; the first call registers the
    ``jax.monitoring`` listeners that fill it with ``compile``, ``trace`` and
    ``lower`` records (once a process)."""
    global _listening
    with _listen_lock:
        if not _listening:
            from jax import monitoring

            monitoring.register_event_listener(_on_event)
            monitoring.register_scalar_listener(_on_scalar)
            monitoring.register_event_time_span_listener(_on_time_span)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
    return _SETUP


def setup_span(name: str, **ids):
    """THE span call (:func:`span`) into the set-up recorder, and no other
    sink: no clock stage, no journal (the name goes by keyword, so vftlint's
    telemetry-schema rule does not take this for a journal wrapper)."""
    return span(name=name, recorder=setup_recorder(), **ids)


def setup_report(setup: Dict) -> str:
    """One line for the stage report from ``_pack_stats["setup"]``: the last
    construction, the checkpoints it read and placed, and the compile records
    since it began (each a load from the persistent cache or a compile)."""
    records = [r for r in setup["records"] if r["end"] is not None]
    constructs = [r for r in records if r["name"] == "construct"]
    if not constructs:
        return "set-up: no construction recorded"
    c = constructs[-1]
    since = [r for r in records if r["start"] >= c["start"]]

    def seconds(name):
        return sum(r["end"] - r["start"] for r in since if r["name"] == name) / 1e9

    loads = [r["ids"] for r in since if r["name"] == "load_weights"]
    compiles = [r["ids"] for r in since if r["name"] == "compile"]
    hits = sum(ids.get("cache") == "hit" for ids in compiles)
    return (f"set-up: construct {(c['end'] - c['start']) / 1e9:.2f}s | "
            f"weights {len(loads)} checkpoint(s) {seconds('load_weights'):.2f}s: "
            f"read {sum(ids.get('read_s', 0.0) for ids in loads):.2f}s, "
            f"place wait {seconds('place_wait'):.2f}s, "
            f"{sum(ids.get('bytes_read', 0) for ids in loads) / 1e9:.2f} GB read, "
            f"{sum(ids.get('bytes_placed', 0) for ids in loads) / 1e9:.2f} GB placed | "
            f"compile {seconds('compile'):.2f}s: "
            f"{hits} program(s) loaded, {len(compiles) - hits} compiled")


@contextlib.contextmanager
def maybe_profiler(profile_dir=None):
    """``jax.profiler`` trace context when a directory is given, else no-op."""
    if not profile_dir:
        yield
        return
    import jax

    os.makedirs(profile_dir, exist_ok=True)
    with jax.profiler.trace(profile_dir):
        yield

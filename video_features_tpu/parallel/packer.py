"""Cross-video clip packing: a corpus-level continuous-batching scheduler.

The per-video loop (:meth:`..extractors.base.Extractor._run_loop`) pays a
zero-padded tail batch per video (``pad_batch``) and drains the mesh between
videos — on a corpus of short clips a large fraction of device steps are
padding or idle. Fixed-shape continuous batching is the standard TPU answer
to ragged workloads (Ragged Paged Attention, arXiv:2604.15464), and
decoupling producers from fixed-shape device batches is the Podracer recipe
(arXiv:2104.06272): here, decoded clips stream into **shape-keyed slot
queues** and every dispatched ``(batch_size, …)`` device batch is filled with
clips from however many videos are ready — the tail of video N packs with the
head of video N+1. Per-clip results scatter back to per-video assembly
buffers (:class:`..io.output.FeatureAssembly`) that the run loop flushes
through the output writer as each video's last clip lands.

Four generalizations beyond the original RGB-only packer:

- **collate seam** — a :class:`PackSpec` may supply ``collate`` to build the
  device batch itself (and decide how many queued slots actually fit). The
  flow extractors use it to chain stream-consecutive frame-*pair* slots into
  one ``(batch_size + 1)``-frame shared-frame window: each video boundary
  inside a window burns one frame position, and the returned row map tells
  the scatter which output row belongs to which slot.
- **shape buckets** — :class:`ShapeBuckets` clusters the corpus's probed
  (padded) geometries into ≤ K buckets before decode starts, so a mixed
  720p/1080p corpus compiles K programs and co-packs inside each bucket
  instead of filling one queue per distinct geometry.
- **per-bucket dispatch** — each shape key keeps its own one-batch-in-flight
  pipeline (batch *k* is fetched when that bucket's batch *k+1* dispatches),
  and an anti-starvation flush dispatches a bucket's partial queue once
  ``flush_age`` videos have finished while it sat waiting — a rare geometry
  cannot strand its videos until corpus end.
- **co-resident models** — the bucket key is really ``(model, geometry)``:
  :meth:`CorpusPacker.register_model` adds further :class:`PackSpec`\\ s (one
  per feature type, each with its own step callable and batch size) to one
  packer, so a mixed resnet50/i3d/vggish request stream feeds ONE mesh and
  the device never drains while *any* model has backlog (ROADMAP item 2 —
  a model is "just" another bucket dimension). Whenever more than one
  model's queues are ready to dispatch (the corpus/idle flush, the
  anti-starvation flush, collate leftovers), batches interleave round-robin
  across models so no single model's backlog monopolizes the device;
  in-stream, arrival order already interleaves models because the serving
  scheduler pops videos tenant-fair, not model-grouped. One-batch-in-flight
  overlap, flush-age aging, occupancy stats, and slot-level fault
  attribution all hold per ``(model, geometry)`` key unchanged.

Threading model — deliberately single-threaded: the packed run loop (one
consumer) pulls each video's clip stream in corpus order and calls
:meth:`CorpusPacker.add`; decode parallelism comes from the
``DecodePrefetcher`` worker threads *upstream* of the clip stream. Every
cross-thread store therefore stays inside the already-declared
``parallel/pipeline.py`` / ``io/output.py`` seams (vftlint
``thread-shared-state``), and the packer itself needs no locks.

The packer makes NO corpus-end assumption: :meth:`flush` drains the partial
queues whenever the caller decides (the batch loop calls it once after the
last video; the serving daemon — :mod:`..serve` — calls it when the ingest
queue goes idle and again at graceful drain) and the queues keep accepting
slots afterwards, so one packer instance serves a daemon's whole lifetime
with the tail of request N packing into the head of request N+1. Long-run
callers bound per-video bookkeeping with :meth:`forget` and clear consumed
flush causes with :meth:`clear_flush_causes`.

Fault attribution is slot-level, not batch-level: a poisoned clip stream
fails only its contributing video. Slots reference their attempt's assembly
object directly (not the video path), so a retry opens a fresh assembly and
stale in-flight rows from the failed attempt land in the orphaned object and
die with it.
"""

from __future__ import annotations

import heapq
import sys
from collections import Counter, deque
from dataclasses import dataclass
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from ..io.output import FeatureAssembly
from ..reliability.faults import fault_point
from ..utils.metrics import span as bare_span
from .pipeline import pad_batch
from .pages import (PAGES_QUEUED, TABLE_COLS, TOKEN_PLANES, build_row_table,
                    build_token_page, fit_documents)


@dataclass
class PackSpec:
    """How one model plugs into the corpus packer (``Extractor.pack_spec``).

    ``open_clips(path)`` returns ``(info, clip_iter)``: a mutable per-video
    info dict the stream fills as it decodes (fps, timestamps) and an iterator
    of fixed-shape uint8 clip arrays — one device-batch *slot* each. Clips of
    equal shape co-pack; each distinct shape fills its own queue (the flow
    extractors bound the shape count with :class:`ShapeBuckets`).

    ``step(batch)`` runs the model's existing jitted device step on a full
    host batch and returns the per-slot device features; the packer fetches
    them through the extractor's device_wait-accounted ``_wait``.
    ``finalize(path, rows, info)`` assembles the video's output dict from the
    in-order ``(n_clips, *row)`` host feature array.

    ``empty_row_shape`` shapes the zero-clip video output (e.g. ``(2048,)``
    for ResNet-50), matching the per-video loop's empty result.

    ``collate(clips, stream_keys)``, when given, replaces the default
    ``np.stack + pad_batch`` batch assembly: it receives up to ``batch_size``
    queued clips plus their ``(stream_id, clip_idx)`` continuity keys
    (consecutive iff same stream and ``idx + 1``) and returns
    ``(batch, n_used, row_of)`` — the device batch, how many of the offered
    slots it consumed (≥ 1), and for each consumed slot the row of
    ``step(batch)``'s output holding its features.

    ``prepare(paths)``, when given, runs once before the packed loop starts —
    the flow extractors use it to probe the corpus's container geometries and
    plan the shape buckets.

    ``paged_step(page, table)``, when given (and ``collate`` is not — the
    flow extractors' window chaining is its own dispatch shape), switches the
    model's buckets to **ragged paged dispatch** (:mod:`.pages`): batches are
    fixed ``(page_rows, …)`` pages shipped with an int32 row table, the step
    returns ``(device_rows, device_table)`` with padding rows masked on
    device, and each bucket keeps ``pages_in_flight`` pages in flight (the
    depth-k generalization of the one-batch-in-flight pipeline). NOT setting
    ``paged_step`` is the per-model opt-out: the extractor's ``pack_spec``
    omits it (``--paged_batching`` off, ``--show_pred``-adjacent fallbacks,
    the flow collate seam) and the bucket dispatches exactly as before.
    Raw-pixels wire formats (``--device_resize``/``--device_preproc``) DO
    page — their slot queues key by decoded geometry, so every page is
    shape-homogeneous and runs that geometry's compiled family.

    ``page_tokens``, when set (with ``paged_step``), makes the model's pages
    **token pages**: a slot is one whole *document* (``open_clips`` yields
    one object with ``ids`` and cumulative ``segment_ends`` per video) instead
    of one fixed-shape array, a page goes once two pages' worth of documents
    are queued (``pages.PAGES_QUEUED``; a flush sends what there is) and holds
    the oldest of them and the others that fill its ``page_tokens`` token
    slots best within its ``page_rows`` table rows
    (:func:`.pages.fit_documents`), and a document's output is its block of
    segment rows. One queue and one compiled program whatever the lengths;
    ``real_slots`` and ``dispatched_slots`` count tokens. A document that
    cannot fit an empty page is the model's to refuse in ``open_clips``.
    """

    batch_size: int
    empty_row_shape: Tuple[int, ...]
    open_clips: Callable[[str], Tuple[dict, Iterator[np.ndarray]]]
    step: Callable[[Any], Any]
    finalize: Callable[[str, np.ndarray, dict], Dict[str, np.ndarray]]
    collate: Optional[
        Callable[[List[np.ndarray], List[Tuple[int, int]]],
                 Tuple[Any, int, Sequence[int]]]] = None
    prepare: Optional[Callable[[Sequence[str]], None]] = None
    # ragged paged dispatch (parallel/pages.py): (page, row_table) ->
    # (device_rows, device_table); None = bucketed dispatch (the opt-out)
    paged_step: Optional[Callable[[Any, np.ndarray], Tuple[Any, Any]]] = None
    # rows per page (defaults to batch_size when unset); the extractor sizes
    # it per family via pages.page_rows_for (batch budget / depth, rounded
    # up to the mesh multiple)
    page_rows: Optional[int] = None
    # in-flight pages per bucket under paged dispatch (≥ 2 = the host
    # refills page k+1 while the device chews on page k AND k-1's scatter
    # overlaps); bucketed dispatch always keeps exactly 1
    pages_in_flight: int = 2
    # token slots per page: set = token pages of whole documents (above)
    page_tokens: Optional[int] = None


class ShapeBuckets:
    """Cluster probed (padded) geometries into at most ``max_buckets``.

    Built from the corpus's container probes before decode starts. Each
    bucket is the elementwise max of its member geometries; merging is
    greedy — while over the cap, merge the pair whose union adds the least
    video-weighted padding area. ``bucket_for`` maps a geometry to the
    smallest covering bucket (a geometry no planned bucket covers — e.g. a
    video whose probe failed — becomes its own ad-hoc bucket, preserving
    correctness at the cost of one extra compiled program).
    """

    def __init__(self, geometries: Iterable[Tuple[int, int]],
                 max_buckets: int):
        if max_buckets < 1:
            raise ValueError("max_buckets must be >= 1")
        counts = Counter(tuple(g) for g in geometries)
        # {id: (h, w, weight)} working set; weight = videos whose padding the
        # bucket's growth would touch. The greedy merge (pop the cheapest
        # union while over the cap) runs on a lazily-invalidated pair-cost
        # heap — a dead id (already merged) just skips — so planning a very
        # heterogeneous corpus costs O(G^2 log G), not O(G^3) rescans of
        # every pair per round.
        alive: Dict[int, Tuple[int, int, int]] = {
            k: (h, w, n) for k, ((h, w), n) in enumerate(counts.items())}
        next_id = len(alive)

        def pair_cost(a, b):
            ha, wa, na = alive[a]
            hb, wb, nb = alive[b]
            mh, mw = max(ha, hb), max(wa, wb)
            return (mh * mw * (na + nb) - ha * wa * na - hb * wb * nb,
                    (mh, mw, na + nb))

        heap = []
        if len(alive) > max_buckets:
            ids = list(alive)
            for x, a in enumerate(ids):
                for b in ids[x + 1:]:
                    heap.append((pair_cost(a, b)[0], a, b))
            heapq.heapify(heap)
        while len(alive) > max_buckets:
            cost, a, b = heapq.heappop(heap)
            if a not in alive or b not in alive:
                continue  # a stale pair: one side was merged away
            _, merged = pair_cost(a, b)
            del alive[a], alive[b]
            alive[next_id] = merged
            for other in list(alive):
                if other != next_id:
                    heapq.heappush(
                        heap, (pair_cost(other, next_id)[0], other, next_id))
            next_id += 1
        self.buckets: List[Tuple[int, int]] = sorted(
            (h, w) for h, w, _n in alive.values())

    def bucket_for(self, geometry: Tuple[int, int]) -> Tuple[int, int]:
        h, w = geometry
        covering = [(bh * bw, (bh, bw)) for bh, bw in self.buckets
                    if bh >= h and bw >= w]
        if not covering:
            return (h, w)
        return min(covering)[1]


class _Slot:
    """One occupied device-batch slot: a clip and where its row scatters.

    ``vid`` is the attempt's monotonic video id (assigned per ``begin()``) —
    the row table's first column under paged dispatch; a retry's fresh
    attempt gets a fresh id, so stale rows of a discarded attempt can never
    be confused with the retry's in any journaled table."""

    __slots__ = ("assembly", "idx", "clip", "vid")

    def __init__(self, assembly: FeatureAssembly, idx: int, clip: np.ndarray,
                 vid: int = -1):
        self.assembly = assembly
        self.idx = idx
        self.clip = clip
        self.vid = vid


class CorpusPacker:
    """Shape-keyed continuous batching across videos.

    Each shape key keeps a depth-k ring of dispatched batches in flight
    (k = ``PackSpec.pages_in_flight`` under paged dispatch, 1 bucketed): a
    key's batch *k* results are fetched (and scattered) only when the ring is
    full at its next dispatch, at an anti-starvation flush, or at
    :meth:`flush` — so host decode/stacking of the next batch overlaps device
    compute of the in-flight ones, the packed loop's analogue of the
    per-video loop's prefetch + ``_throttle`` backpressure (bounded unfetched
    batches per bucket; the bucket planner bounds the bucket count).

    **Paged dispatch** (``PackSpec.paged_step``, :mod:`.pages`): instead of
    ``batch_size`` padded batches, the bucket ships fixed ``page_rows`` pages
    plus an int32 row table mapping page rows → (video id, clip idx, valid);
    the jitted paged program masks by the table and passes it through (the
    donation-legal pair — ``mesh.py::MeshRunner.jit_paged``). The host's only
    per-page work is refilling a staging-ring buffer (page + table) and the
    ``device_put`` inside ``paged_step``; with ``pages_in_flight >= 2`` the
    scatter of page k overlaps the device chewing on page k+1. Slot-level
    fault attribution, stale flushes, round-robin fairness, and the stats
    surface are unchanged — a page is just a smaller, table-carrying batch.

    ``flush_age`` > 0 arms the anti-starvation flush: when a key's queue has
    sat non-empty while ``flush_age`` videos finished their streams, its
    partial queue is dispatched zero-padded and resolved eagerly, so a rare
    bucket's videos complete (and their writes land) mid-run instead of at
    corpus end.
    """

    def __init__(self, spec: Optional[PackSpec] = None,
                 wait: Callable[[Any], np.ndarray] = np.asarray,
                 clock=None, flush_age: int = 0, staging=None,
                 journal=None, metrics=None, span=bare_span):
        # model name -> PackSpec. Single-model callers (the batch loop, the
        # engine tests) pass one spec, registered under None; the multi-model
        # serving layer constructs spec-less and register_model()s each
        # feature type — every internal key is (model, clip shape) either way
        self._specs: Dict[Optional[str], PackSpec] = {}
        if spec is not None:
            self._specs[None] = spec
        self._video_model: Dict[str, Optional[str]] = {}
        self._rr_last: Optional[str] = None  # last model dispatched (RR seed)
        self._wait = wait
        self._clock = clock  # optional StageClock: packed_slots/packed_clips units
        self._flush_age = flush_age
        # the extractor's one span call (``Extractor._span``; the bare
        # ``utils.metrics.span`` without one). Per dispatched page: a 'stage'
        # span around the page's assembly, a 'launch' span around the step
        # call (the extractor's 'put' spans nest inside it) and a 'device'
        # span around the fetch, all carrying ``page=<n>``, the running page
        # number — the packer starts no timer of its own
        self._span = span
        self._page_seq = 0
        # telemetry (docs/observability.md): the span journal gets a
        # 'dispatch' instant per dispatched batch and 'stale_flush' instants;
        # the metrics registry gets per-bucket occupancy gauges and the
        # device_batch_seconds histogram, fed by the 'device' span's own
        # duration. Both optional and emit-only — never block dispatch.
        self._journal = journal
        self._metrics = metrics
        # optional HostStagingRing: the default (no-collate) batch assembly
        # fills a reusable per-geometry buffer instead of np.stack+pad_batch
        # allocating per dispatch; the buffer is committed against the step's
        # device output (output ready ⟹ the input transfer was consumed), so
        # it is never rewritten while the device may still read it. Collate
        # specs (flow) stage into the ring themselves.
        self._staging = staging
        self._pending: Dict[tuple, List[_Slot]] = {}
        self._open: Dict[str, FeatureAssembly] = {}
        self._finished: List[FeatureAssembly] = []
        # per shape key: ring of (slots, row_of, fetchable) unfetched
        # batches, oldest first — depth 1 bucketed, PackSpec.pages_in_flight
        # under paged dispatch
        self._inflight: Dict[tuple, deque] = {}
        # per-attempt monotonic video ids (the row table's first column);
        # a retry's begin() assigns a fresh id
        self._video_ids: Dict[str, int] = {}
        self._vid_seq = 0
        # per shape key: videos-finished count when its queue last became
        # non-empty (anti-starvation age base)
        self._queue_born: Dict[tuple, int] = {}
        self._videos_finished = 0
        self.real_slots = 0  # clips dispatched
        self.dispatched_slots = 0  # clips + padding/boundary slots dispatched
        self.staged_bytes = 0  # host bytes staged per dispatched device batch
        self.pages_dispatched = 0  # paged-mode dispatches (stats)
        self.segments = 0  # token pages: table rows (segments) dispatched
        self.max_in_flight = 0  # deepest observed in-flight ring (any key)
        self.video_clips: Dict[str, int] = {}  # per finished video
        # per shape key: {"real_slots", "dispatched_slots", "stale_flushes"}
        self._bucket_stats: Dict[tuple, Dict[str, int]] = {}
        # device failures contained by the anti-starvation flush barrier,
        # failed-flush causes (anti-starvation or corpus-end), keyed by shape
        # bucket — the run loop attributes each drained victim only its own
        # buckets' causes
        self.flush_errors: Dict[tuple, List[str]] = {}
        # per open/finished video: the shape keys its slots were queued
        # under (cause attribution for stale-flush failures)
        self._video_keys: Dict[str, set] = {}

    # --- model registry ------------------------------------------------------

    def register_model(self, model: Optional[str], spec: PackSpec) -> None:
        """Co-locate another feature type's spec on this packer.

        Each model keeps its own step callable, batch size, and
        ``(model, geometry)`` bucket keys; nothing co-packs ACROSS models
        (their rows are different programs) — co-residency keeps the device
        fed when any one model's queue drains."""
        self._specs[model] = spec

    @property
    def models(self) -> Tuple[Optional[str], ...]:
        return tuple(self._specs)

    def _spec_for(self, key: tuple) -> PackSpec:
        return self._specs[key[0]]

    @staticmethod
    def _bucket_name(key: tuple) -> str:
        model, shape = key
        dims = "x".join(str(d) for d in shape)
        return dims if model is None else f"{model}:{dims}"

    # --- per-video lifecycle -------------------------------------------------

    def begin(self, path: str, info: dict,
              model: Optional[str] = None) -> None:
        """Open a fresh attempt for ``path`` (replacing any failed prior one).

        ``model`` routes the video's clips to that registered spec's
        ``(model, geometry)`` buckets; None is the single-spec default."""
        if model not in self._specs:
            raise KeyError(f"model {model!r} is not registered with this "
                           f"packer (have: {sorted(map(str, self._specs))})")
        self.discard(path)
        self._video_model[path] = model
        self._vid_seq += 1
        self._video_ids[path] = self._vid_seq
        self._open[path] = FeatureAssembly(path, info)

    def add(self, path: str, clip: np.ndarray) -> None:
        """Queue one clip; dispatches device batches when queues fill."""
        asm = self._open[path]
        slot = _Slot(asm, asm.reserve(), clip, vid=self._video_ids[path])
        model = self._video_model[path]
        tokens = self._specs[model].page_tokens
        # token pages: one queue whatever the documents' lengths
        key = (model, ("tokens", tokens) if tokens else clip.shape)
        self._video_keys.setdefault(path, set()).add(key)
        queue = self._pending.setdefault(key, [])
        # a bucket receiving slots is being fed, not stranded: age counts
        # from its last activity (slot arrival or dispatch), so a slowly
        # filling common bucket is never padded-flushed mid-corpus
        self._queue_born[key] = self._videos_finished
        queue.append(slot)
        self._pump()

    @staticmethod
    def _paged(spec: PackSpec) -> bool:
        """Paged dispatch is active for a spec that ships a paged step and
        does not collate (window chaining owns its own dispatch shape)."""
        return spec.paged_step is not None and spec.collate is None

    def _batch_rows(self, spec: PackSpec) -> int:
        """Rows per dispatched batch: the page size under paged dispatch,
        the padded batch size bucketed."""
        if self._paged(spec):
            return spec.page_rows or spec.batch_size
        return spec.batch_size

    def _full(self, key: tuple) -> bool:
        queue = self._pending.get(key)
        if not queue:
            return False
        spec = self._spec_for(key)
        if spec.page_tokens:
            # a token page goes when the queued documents would fill
            # PAGES_QUEUED pages: fit_documents then has that much to choose
            # from (what it leaves waits for the next page). A function of
            # the arrival order alone, whatever the ring holds
            return (sum(len(s.clip.ids) for s in queue)
                    >= PAGES_QUEUED * spec.page_tokens
                    or sum(len(s.clip.segment_ends) for s in queue)
                    >= PAGES_QUEUED * self._batch_rows(spec))
        return len(queue) >= self._batch_rows(spec)

    def _pump(self) -> None:
        """Dispatch every full queue, one batch per key per round,
        round-robin across models between rounds.

        Single-model this is the old ``while full: dispatch`` loop (a
        collate may consume fewer than batch_size slots per dispatch — flow
        windows burn a frame position per video boundary — so the queue can
        stay full across rounds). Multi-model, whenever several models have
        full queues at once, the round order starts after the last-served
        model so one model's deep backlog cannot dispatch twice before
        another model's ready batch dispatches once."""
        while True:
            ready = [k for k in self._pending if self._full(k)]
            if not ready:
                return
            for key in self._one_per_model(ready):
                if self._full(key):
                    self._dispatch(key)

    def _rr_order(self, keys: List[tuple]) -> List[tuple]:
        """``keys`` ordered round-robin by model starting after the last
        dispatched model (deterministic string order within a model)."""
        models = sorted({k[0] for k in keys}, key=str)
        start = 0
        if self._rr_last is not None:
            for i, m in enumerate(models):
                if str(m) > str(self._rr_last):
                    start = i
                    break
        order = {m: i for i, m in enumerate(models[start:] + models[:start])}
        return sorted(keys, key=lambda k: (order[k[0]], str(k)))

    def _one_per_model(self, ready: List[tuple]) -> List[tuple]:
        """One ready key PER MODEL, round-robin ordered — the dispatch round
        shape: with several models ready, each round serves each model one
        batch, so no model's multi-bucket backlog dispatches twice before
        another model's ready batch dispatches once."""
        out, seen = [], set()
        for key in self._rr_order(ready):
            if key[0] not in seen:
                seen.add(key[0])
                out.append(key)
        return out

    def finish(self, path: str) -> None:
        """Mark ``path``'s stream complete; it finalizes once all rows land."""
        asm = self._open.pop(path)
        asm.finish()
        self.video_clips[path] = asm.expected or 0
        self._finished.append(asm)
        self._videos_finished += 1
        self._flush_stale()

    def forget(self, path: str) -> None:
        """Drop a COMPLETED video's bookkeeping (clip counts, bucket keys).

        Batch runs keep these for the end-of-run stats; the serving daemon
        calls this after each video's output lands so the per-video dicts
        stay bounded over an unbounded request stream (the soak test in
        tests/test_service.py pins this)."""
        self.video_clips.pop(path, None)
        self._video_keys.pop(path, None)
        self._video_model.pop(path, None)
        self._video_ids.pop(path, None)

    def discard(self, path: str) -> None:
        """Drop every trace of ``path``'s current attempt (failure/retry).

        Pending slots are unlinked; slots already dispatched (including the
        in-flight batches) still hold the dead attempt's assembly and scatter
        harmlessly into it — slot-level attribution needs no batch rollback.
        """
        asm = self._open.pop(path, None)
        self.video_clips.pop(path, None)
        self._video_keys.pop(path, None)
        self._video_model.pop(path, None)
        self._video_ids.pop(path, None)
        self._finished = [a for a in self._finished if a.video != path]
        if asm is None:
            return
        for queue in self._pending.values():
            queue[:] = [s for s in queue if s.assembly is not asm]

    # --- dispatch ------------------------------------------------------------

    def _dispatch(self, key: tuple) -> None:
        spec = self._spec_for(key)
        paged = self._paged(spec)
        queue = self._pending[key]
        batch_size = self._batch_rows(spec)
        candidates = queue[:batch_size]
        page = self._page_seq  # the running page number: this dispatch's id
        self._page_seq += 1
        with self._span("stage", page=page) as staged:
            if spec.collate is not None:
                batch, n_used, row_of = spec.collate(
                    [s.clip for s in candidates],
                    [(id(s.assembly), s.idx) for s in candidates])
                slots = candidates[:n_used]
                del queue[:n_used]  # in place: flush() iterates this same list
            elif spec.page_tokens:
                sizes = [(len(s.clip.ids), len(s.clip.segment_ends))
                         for s in queue]
                take = fit_documents(sizes, spec.page_tokens, batch_size)
                if not take:
                    raise ValueError(
                        f"a document of {len(queue[0].clip.ids)} tokens and "
                        f"{len(queue[0].clip.segment_ends)} segments fits no "
                        f"page of {spec.page_tokens} tokens and {batch_size} rows")
                slots = [queue[i] for i in take]
                taken = set(take)
                queue[:] = [s for i, s in enumerate(queue) if i not in taken]
                batch, table, row_of = self._stage_token_page(
                    slots, spec.page_tokens, batch_size)
                # what the page holds, for whoever reads the span records
                # (the benchmark's readers count attention's work from it)
                staged.ids["documents"] = [len(s.clip.ids) for s in slots]
            else:
                slots = candidates
                del queue[:batch_size]
                batch = self._stage_batch([s.clip for s in slots], batch_size)
                row_of = range(len(slots))
        # depth-k ring: resolve this bucket's OLDEST unfetched batch only
        # when the ring is full, so scatter of batch k overlaps the device
        # chewing on k+1..k+depth (bucketed depth is 1 — the original
        # one-batch-in-flight behavior, scatter-then-step)
        depth = spec.pages_in_flight if paged else 1
        ring = self._inflight.setdefault(key, deque())
        while len(ring) >= max(1, depth):
            self._scatter_oldest(key)
        # mid-batch chaos seam (docs/reliability.md): a `kill` here dies with
        # a full batch assembled but never stepped — recovery must replay
        # every co-packed video of every admitted request
        fault_point("device", str(key))
        if paged and not spec.page_tokens:
            with self._span("stage", page=page):
                table = self._stage_table(slots, batch_size)
        # host time of the step call: the extractor's puts (their own spans,
        # inside this one), tracing, and a cache load or compile on a first
        # shape
        with self._span("launch", page=page):
            if paged:
                out = spec.paged_step(batch, table)
                fetchable = out[0]  # device rows; the donated table out is dropped
            else:
                out = spec.step(batch)
                fetchable = out
        self._rr_last = key[0]  # round-robin seed: the model just served
        if self._staging is not None:
            # no-op for batches the ring does not own (collate specs commit
            # their own buffers at device_put time, inside step)
            self._staging.commit(batch, out)
            if paged:
                self._staging.commit(table, out)
        self.staged_bytes += int(getattr(batch, "nbytes", 0))
        ring.append((slots, row_of, fetchable, page))
        self.max_in_flight = max(self.max_in_flight, len(ring))
        # a bucket being served is not starving: age counts from its last
        # activity (dispatch here, slot arrival in add())
        self._queue_born[key] = self._videos_finished
        real = len(slots)
        if spec.page_tokens:  # a token page's slots are its token slots
            real = sum(len(s.clip.ids) for s in slots)
            self.segments += sum(len(s.clip.segment_ends) for s in slots)
            batch_size = spec.page_tokens
        self.real_slots += real
        self.dispatched_slots += batch_size
        stats = self._bucket_stats.setdefault(
            key, {"real_slots": 0, "dispatched_slots": 0, "stale_flushes": 0,
                  "pages_dispatched": 0})
        stats.setdefault("pages_dispatched", 0)
        stats["real_slots"] += real
        stats["dispatched_slots"] += batch_size
        if paged:
            stats["pages_dispatched"] += 1
            self.pages_dispatched += 1
        if self._clock is not None:
            self._clock.add_units("packed_slots", batch_size)
            self._clock.add_units("packed_clips", real)
        if self._journal is not None:
            self._journal.emit("dispatch", bucket=self._bucket_name(key),
                               real_slots=real, batch_slots=batch_size,
                               paged=paged, inflight=len(ring), page=page)
        if self._metrics is not None:
            occ = round(stats["real_slots"] / stats["dispatched_slots"], 4)
            self._metrics.set_gauge("bucket_occupancy", occ,
                                    bucket=self._bucket_name(key))
            if paged:
                # the page-level win (real rows / page rows, cumulative per
                # bucket): pad waste beyond the final partial page shows here
                self._metrics.set_gauge("page_occupancy", occ,
                                        bucket=self._bucket_name(key))

    def _stage_table(self, slots: List[_Slot], page_rows: int) -> np.ndarray:
        """Row table for one page — (video id, clip idx, valid) per row,
        filled into a reusable staging-ring buffer when a ring is wired
        (the table rides the wire next to its page; the ring guards both
        until the step's device values resolve)."""
        entries = [(s.vid, s.idx) for s in slots]
        if self._staging is None:
            return build_row_table(entries, page_rows)
        buf = self._staging.acquire((page_rows, TABLE_COLS), np.int32)
        return build_row_table(entries, page_rows, out=buf)

    def _stage_token_page(self, slots: List[_Slot], page_tokens: int,
                          page_rows: int):
        """One token page and its row table (staging-ring buffers when a
        ring is wired) from whole documents → (page, table, each slot's
        slice of the output rows)."""
        shapes = ((TOKEN_PLANES, page_tokens), (page_rows, TABLE_COLS))
        if self._staging is None:
            page, table = (np.empty(shape, np.int32) for shape in shapes)
        else:
            page, table = (self._staging.acquire(shape, np.int32)
                           for shape in shapes)
        row_of = build_token_page(
            [(s.vid, s.clip.ids, s.clip.segment_ends) for s in slots],
            page, table)
        return page, table, row_of

    def _stage_batch(self, clips: List[np.ndarray],
                     batch_size: int) -> np.ndarray:
        """Default batch assembly: clips stacked (zero-padded to the static
        batch shape) into a reusable staging-ring buffer when a ring is
        wired, else the original fresh ``np.stack`` + ``pad_batch``. Dtype
        follows the clips — uint8 frame slots stay uint8 on the wire."""
        if self._staging is None:
            return pad_batch(np.stack(clips), batch_size)
        return self._staging.stage(clips, batch_size)

    def _scatter_inflight(self, key: Optional[tuple] = None) -> None:
        """Resolve EVERY unfetched batch of ``key`` (or of every key),
        oldest first — the flush-time drain of the depth-k rings."""
        keys = [key] if key is not None else list(self._inflight)
        for k in keys:
            ring = self._inflight.get(k)
            while ring:
                self._scatter_oldest(k)

    def _scatter_oldest(self, key: tuple) -> None:
        """Fetch and scatter one key's oldest unfetched batch. A fetch
        failure drops only that batch's rows (its entry was popped) — the
        younger in-flight entries still resolve at the flush arms."""
        ring = self._inflight.get(key)
        if not ring:
            return
        slots, row_of, fetchable, page = ring.popleft()
        host = self._fetch_batch(key, fetchable, page)
        for i, slot in enumerate(slots):
            slot.assembly.put(slot.idx, host[row_of[i]])

    def _fetch_batch(self, key: tuple, out, page: int) -> np.ndarray:
        """Fetch one batch's device output inside ONE 'device' span: its
        duration is what the 'device_wait' stage, the journal's pair, the
        span record and the ``device_batch_seconds`` histogram all get
        (labeled by model — the per-BATCH device distribution; per-video
        device attribution does not exist under packing, where a batch mixes
        videos)."""
        with self._span("device", stage="device_wait",
                        bucket=self._bucket_name(key), page=page) as sp:
            host = self._wait(out)
        if self._metrics is not None:
            model = key[0] if key[0] is not None else "default"
            self._metrics.observe("device_batch_seconds", sp.seconds,
                                  model=model)
        return host

    def _flush_stale(self) -> None:
        """Anti-starvation: dispatch (and resolve) buckets whose partial
        queues sat idle (no slot arrival, no dispatch) for ``flush_age``
        video completions — latency over overlap for geometries too rare to
        fill their own batches."""
        if not self._flush_age:
            return
        stale = [key for key, queue in self._pending.items()
                 if queue and (self._videos_finished - self._queue_born[key]
                               >= self._flush_age)]
        if not stale:
            return
        failed = set()
        while True:
            # same one-batch-per-model rounds as _pump/flush: several
            # models' stale buckets interleave instead of one model
            # draining its whole backlog first
            ready = [k for k in stale
                     if k not in failed and self._pending.get(k)]
            if not ready:
                break
            for key in self._one_per_model(ready):
                if not self._pending.get(key):
                    continue
                try:
                    self._dispatch(key)
                except KeyboardInterrupt:
                    raise
                except Exception as e:  # noqa: BLE001 — fault-barrier: the stale-flush arm of the per-video isolation point — the flushed batch may hold ZERO slots of the video whose finish() triggered it, so letting this escape would retry/fail the wrong (healthy) video; victims resolve via drain_incomplete with this cause
                    self._record_stale_failure(key, e)
                    failed.add(key)
        for key in stale:
            if key in failed:
                continue
            try:
                self._scatter_inflight(key)  # rare bucket: complete now
            except KeyboardInterrupt:
                raise
            except Exception as e:  # noqa: BLE001 — fault-barrier: the scatter arm of the stale flush — same victim attribution as the dispatch arm above
                self._record_stale_failure(key, e)
                continue
            self._bucket_stats[key]["stale_flushes"] += 1
            if self._journal is not None:
                self._journal.emit("stale_flush",
                                   bucket=self._bucket_name(key))
            if self._metrics is not None:
                self._metrics.inc("stale_flushes_total",
                                  bucket=self._bucket_name(key))

    def _record_stale_failure(self, key: tuple, e: BaseException) -> None:
        msg = (f"anti-starvation flush of bucket "
               f"{self._bucket_name(key)} failed: {e}")
        self.flush_errors.setdefault(key, []).append(msg)
        print(f"[pack] {msg}; its videos will be failed (retryable) "
              "when the corpus drains", file=sys.stderr)

    def flush(self) -> None:
        """Dispatch every partial shape queue (padded) and resolve in-flight.

        Per-bucket fault isolation: one bucket's device failure must not
        abort the other buckets' dispatch/scatter — healthy buckets still
        resolve, and the failed bucket's contributors drain incomplete
        wearing only their own bucket's recorded cause.

        Multi-model packers drain ROUND-ROBIN across models, one batch per
        key per round, so one model's deep backlog cannot monopolize the
        device while another model's ready tail waits.
        """
        keys = set(self._pending) | set(self._inflight)
        failed = set()
        while True:
            ready = [k for k in keys
                     if k not in failed and self._pending.get(k)]
            if not ready:
                break
            for key in self._one_per_model(ready):
                if not self._pending.get(key):
                    continue
                try:
                    self._dispatch(key)
                except KeyboardInterrupt:
                    raise
                except Exception as e:  # noqa: BLE001 — fault-barrier: the corpus-flush arm of the per-video isolation point — a tail batch holds rows of whichever videos' slots it packed, so letting one bucket's failure escape would fail every other bucket's (healthy) pending videos with the wrong cause; victims resolve via drain_incomplete with this cause
                    self._record_flush_failure(key, e)
                    failed.add(key)
        for key in sorted(keys - failed, key=str):
            try:
                self._scatter_inflight(key)
            except KeyboardInterrupt:
                raise
            except Exception as e:  # noqa: BLE001 — fault-barrier: the scatter arm of the corpus flush — same per-bucket containment as the dispatch arm above
                self._record_flush_failure(key, e)

    def _record_flush_failure(self, key: tuple, e: BaseException) -> None:
        msg = f"corpus flush of bucket {self._bucket_name(key)} failed: {e}"
        self.flush_errors.setdefault(key, []).append(msg)
        print(f"[pack] {msg}; its videos will be failed (retryable)",
              file=sys.stderr)

    # --- results -------------------------------------------------------------

    def pop_completed(self, model: Optional[str] = None
                      ) -> List[FeatureAssembly]:
        """Assemblies whose stream finished AND whose every row has landed.

        ``model`` scopes the pop to one registered model's videos (each
        multi-model session finalizes with its OWN spec); the single-spec
        default None matches everything a single-spec packer holds."""
        done = [a for a in self._finished
                if a.complete and self._video_model.get(a.video) == model]
        if done:
            popped = set(map(id, done))
            self._finished = [a for a in self._finished
                              if id(a) not in popped]
        return done

    def drain_incomplete(self, model: Optional[str] = None
                         ) -> List[FeatureAssembly]:
        """Finished-stream videos still missing rows after :meth:`flush` —
        their slots were lost to a co-packed batch's device failure; the run
        loop fails them explicitly so they land in the failure manifest.
        ``model`` scopes the drain exactly like :meth:`pop_completed`."""
        out = [a for a in self._finished
               if not a.complete and self._video_model.get(a.video) == model]
        drained = set(map(id, out))
        self._finished = [a for a in self._finished if id(a) not in drained]
        return out

    def clear_flush_causes(self) -> None:
        """Reset recorded flush failures once their victims were attributed.

        A long-lived packer (the serving daemon) must not blame a video that
        joins a bucket *tomorrow* with a flush failure that already failed
        its victims today."""
        self.flush_errors.clear()

    def has_pending(self) -> bool:
        """True while any slot is queued or any dispatched batch is unfetched
        — the daemon's 'an idle flush would do work' signal."""
        return (any(self._pending.values())
                or any(self._inflight.values()))

    def flush_causes(self, path: str) -> List[str]:
        """Flush-failure messages (anti-starvation or corpus-end) for the
        buckets ``path``'s slots were queued under — a drained victim is
        blamed only with its own buckets' causes, never a co-resident
        healthy bucket's."""
        keys = self._video_keys.get(path, ())
        return [msg for key in sorted(keys, key=str)
                for msg in self.flush_errors.get(key, [])]

    @property
    def occupancy(self) -> float:
        """Real clips / dispatched device slots (1.0 = no padding dispatched)."""
        if not self.dispatched_slots:
            return 0.0
        return self.real_slots / self.dispatched_slots

    @property
    def stale_flushes(self) -> int:
        # list() snapshots atomically (C-level, under the GIL): the serve
        # socket's stats op reads this from the API thread while the daemon
        # thread registers new buckets — Python-level iteration over the
        # live dict could raise "changed size during iteration"
        return sum(s["stale_flushes"] for s in list(self._bucket_stats.values()))

    def bucket_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-shape-key occupancy accounting (JSON-friendly keys).

        Safe to call from the serve socket's API thread concurrently with
        the packing thread: both dict levels are snapshotted with atomic
        C-level copies before any Python-level iteration.
        """
        out: Dict[str, Dict[str, float]] = {}
        for key, live in sorted(dict(self._bucket_stats).items(), key=str):
            s = dict(live)
            out[self._bucket_name(key)] = {
                "real_slots": s["real_slots"],
                "dispatched_slots": s["dispatched_slots"],
                "occupancy": round(
                    s["real_slots"] / s["dispatched_slots"], 4)
                if s["dispatched_slots"] else 0.0,
                "stale_flushes": s["stale_flushes"],
                "pages_dispatched": s.get("pages_dispatched", 0),
            }
        return out

    def model_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-model occupancy rollup of :meth:`bucket_stats` (the serve
        stats op's ``packing.models`` section — operators watch one model's
        queue starving without decoding bucket names). Same atomic-snapshot
        discipline: safe from the API thread."""
        agg: Dict[str, Dict[str, int]] = {}
        for key, live in sorted(dict(self._bucket_stats).items(), key=str):
            s = dict(live)
            name = key[0] if key[0] is not None else "default"
            a = agg.setdefault(name, {"real_slots": 0, "dispatched_slots": 0,
                                      "stale_flushes": 0})
            a["real_slots"] += s["real_slots"]
            a["dispatched_slots"] += s["dispatched_slots"]
            a["stale_flushes"] += s["stale_flushes"]
        return {
            name: {**a, "occupancy":
                   round(a["real_slots"] / a["dispatched_slots"], 4)
                   if a["dispatched_slots"] else 0.0}
            for name, a in agg.items()
        }

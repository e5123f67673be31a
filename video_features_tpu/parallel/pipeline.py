"""Host→device feeding: async prefetch and multi-host work sharding.

Replaces the reference's synchronous per-stack ``.to(device)`` copies
(``/root/reference/models/i3d/extract_i3d.py:140``) with double-buffered
``device_put``: while the device chews on batch *k*, the host decodes and transfers
batch *k+1*. Dispatch in JAX is async already; the prefetcher simply keeps a bounded
queue of in-flight device buffers so decode, PCIe/ICI transfer, and compute overlap.

Multi-host: the reference shards work across *jobs* by splitting file lists
(``gen_file_list.py:6-21``). Here each process takes a deterministic round-robin
shard of the video list — same semantics, no coordinator, resumable per host.
"""

from __future__ import annotations

import collections
import os
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..reliability import fault_point
from ..utils.metrics import span as bare_span


def maybe_initialize_distributed() -> bool:
    """Join a multi-host JAX job when one is configured; no-op otherwise.

    The reference has no multi-host story beyond manually split file lists
    (``gen_file_list.py``); the TPU runtime's DCN mechanism is
    ``jax.distributed.initialize`` (SURVEY.md §2.3/§5). Trigger: ``VFT_MULTIHOST=1``
    (values from the standard JAX env vars / TPU metadata) or an explicit
    coordinator address in ``JAX_COORDINATOR_ADDRESS``. Must run before the first
    device access. Returns True when running multi-process.
    """
    # NB: must not touch jax.process_count()/jax.devices() before deciding —
    # any backend-initializing call makes a later jax.distributed.initialize()
    # raise. Detect an already-initialized service via the distributed client.
    try:
        from jax._src import distributed  # noqa: PLC2701 — no public probe exists

        already = distributed.global_state.client is not None
    except Exception:  # fault-barrier: private-API probe; absence means "not initialized"
        already = False
    if already:
        return jax.process_count() > 1
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if os.environ.get("VFT_MULTIHOST") == "1" or coord:
        # On TPU pods initialize() self-configures from the metadata service;
        # elsewhere (and in the loopback test) the standard JAX env vars name
        # the job shape, but this jax version only auto-reads them for known
        # cluster environments — pass them through explicitly when set.
        kwargs = {}
        if coord:
            kwargs["coordinator_address"] = coord
        n_proc = os.environ.get("JAX_NUM_PROCESSES")
        proc_id = os.environ.get("JAX_PROCESS_ID")
        if bool(n_proc) != bool(proc_id):
            # a half-specified pair makes initialize() fail or hang with no
            # hint at the cause; fail fast with the fix instead
            raise RuntimeError(
                "JAX_NUM_PROCESSES and JAX_PROCESS_ID must be set together "
                f"(got JAX_NUM_PROCESSES={n_proc!r}, JAX_PROCESS_ID={proc_id!r})"
            )
        if n_proc:
            kwargs["num_processes"] = int(n_proc)
            kwargs["process_id"] = int(proc_id)
        jax.distributed.initialize(**kwargs)
        return jax.process_count() > 1
    return False


def shard_video_list(
    paths: Sequence[str],
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
) -> List[str]:
    """Round-robin shard of ``paths`` owned by this process (DCN axis).

    Round-robin (not contiguous) matches ``gen_file_list.py`` and balances mixed
    video lengths across hosts.
    """
    if process_index is None:
        process_index = jax.process_index()
    if process_count is None:
        process_count = jax.process_count()
    return list(paths[process_index::process_count])


def _item_bytes(item) -> int:
    """Approximate host bytes of one queued frame item (``(rgb, pos)``)."""
    if isinstance(item, tuple):
        return sum(int(getattr(x, "nbytes", 0)) for x in item)
    return int(getattr(item, "nbytes", 0))


class DecodePrefetcher:
    """Cross-video decode parallelism: background threads decode upcoming
    videos while the device chews on the current one.

    The reference gets decode parallelism implicitly — one Python thread per
    GPU, each running its own decode loop (``/root/reference/main.py:43-47``).
    The SPMD design centralizes devices behind one process, so when decode is
    slower than compute (the common case: one cv2 stream decodes a few hundred
    fps, the mesh consumes thousands), extra decode streams must be explicit.
    cv2/ffmpeg/PIL release the GIL in their C cores, so threads parallelize.

    ``open_fn(path) -> (meta, frames_iter)``; each worker drains one video's
    iterator into a bounded queue, and :meth:`get` hands back
    ``(meta, iterator)`` draining that queue. The buffer is bounded TWICE and
    the tighter bound governs: ``max_buffered`` caps the frame COUNT (the
    right bound for small frames, where per-item overhead dominates) and
    ``max_buffered_bytes`` caps the payload BYTES — without it a mixed
    corpus's 1080p videos (~6 MB/frame) could pin ``workers × 512`` frames
    ≈ tens of GB of host RAM under the count bound alone. Paths are
    scheduled by the run loop at most ``workers`` ahead of the consume cursor,
    so the totals stay ≤ workers · bound. Decode errors are re-raised at
    consume time — the per-video fault barrier sees them exactly as inline
    decode would.
    """

    _DONE = object()

    def __init__(self, open_fn: Callable, workers: int, max_buffered: int = 512,
                 max_buffered_bytes: int = 512 << 20, span=bare_span):
        if workers < 1:
            raise ValueError("decode workers must be >= 1")
        self._open = open_fn
        self._max = max_buffered
        self._max_bytes = max_buffered_bytes
        # the extractor's one span call (``Extractor._span``; the bare
        # ``utils.metrics.span`` without one): each worker wraps its video in
        # a 'decode' span — a record, a trace annotation and the journal's
        # pair (a non-blocking queue put, never the decode path's problem).
        # The span covers the worker's full occupancy of a decode slot: open
        # + frame production, INCLUDING time blocked on a full buffer
        # (consumer backpressure) — it answers "what was this decode slot
        # doing", not "how fast is cv2".
        self._span = span
        self._slots: dict = {}  # scheduled, not yet consumed
        self._handed: dict = {}  # handed to a consumer via get(), not released
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._sem = threading.Semaphore(workers)
        # live resize (serve/autoscale.py): permits added via release(),
        # removed by non-blocking acquires — shortfall becomes _debt that
        # finishing workers absorb instead of re-releasing their permit
        self._workers = workers
        self._resize_lock = threading.Lock()
        self._debt = 0
        # segmented intra-video decode (io/video.py plan_segments): a long
        # video may occupy several permits, one per segment worker. Extras
        # beyond the video's baseline permit are reserved NON-blockingly at
        # schedule time under the invariant extras ≤ free − pending_baselines,
        # so a segment can never consume the permit an already-scheduled
        # video's baseline worker is entitled to (that blocking acquire is
        # today's liveness guarantee and stays untouched).
        self._planner = None  # optional (path, max_segments) -> SegmentPlan
        self._segment_open = None  # optional (plan, index) -> frames iter
        self._busy = 0  # permits acquired or reserved
        self._pending_baselines = 0  # scheduled slots whose worker has not acquired yet
        self._videos_segmented = 0  # videos decoded as >1 segment (stats)
        self._segments_decoded = 0  # segment workers finished clean (stats)

    @property
    def workers(self) -> int:
        """Current concurrency target (also the run loops' schedule window)."""
        return self._workers

    def set_opener(self, open_fn: Callable) -> None:
        """Replace the per-path decode callable.

        The multi-model serving layer (``extractors/base.py``) shares ONE
        pool across co-resident models and reroutes it through a path→model
        router so each scheduled video decodes with its own model's host
        transform. Must be called before any :meth:`schedule` whose decode
        should route — workers read the opener at decode start."""
        self._open = open_fn

    def set_segmenter(self, planner: Callable, open_segment: Callable) -> None:
        """Enable segmented intra-video decode through this pool.

        ``planner(path, max_segments) -> SegmentPlan | None`` decides whether
        (and how finely) to split a video — None means decode sequentially.
        ``open_segment(plan, index) -> frames_iter`` decodes one segment
        (``io.video.open_video_segment`` with the extractor's transform).
        Like :meth:`set_opener`, the multi-model layer reroutes both per path.
        """
        self._planner = planner
        self._segment_open = open_segment

    def spare_permits(self) -> int:
        """Permits neither held by a worker nor owed to a scheduled video.

        This is the headroom segmentation may consume, and the signal the
        autoscaler reads to prefer segmenting the current video over growing
        the pool (idle permits mean width is not the bottleneck).
        """
        with self._resize_lock:
            return max(0, self._workers - self._busy - self._pending_baselines)

    def segment_stats(self) -> Tuple[int, int]:
        """(videos decoded segmented, segment workers completed clean)."""
        with self._resize_lock:
            return self._videos_segmented, self._segments_decoded

    def resize(self, workers: int) -> None:
        """Grow or shrink the concurrent-decode budget without a restart.

        Growing releases permits immediately; shrinking takes free permits
        now and records the remainder as debt consumed as busy workers
        finish (a mid-decode video is never cancelled by a shrink).
        """
        if workers < 1:
            raise ValueError("decode workers must be >= 1")
        with self._resize_lock:
            delta = workers - self._workers
            self._workers = workers
            if delta > 0:
                for _ in range(delta):
                    if self._debt:
                        self._debt -= 1
                    else:
                        self._sem.release()
            else:
                for _ in range(-delta):
                    if not self._sem.acquire(blocking=False):
                        self._debt += 1

    def _release_permit(self) -> None:
        with self._resize_lock:
            self._busy -= 1
            if self._debt:
                self._debt -= 1
            else:
                self._sem.release()

    def _acquire_baseline(self) -> None:
        """Blocking acquire of a scheduled video's one guaranteed permit."""
        self._sem.acquire()  # at most `workers` decode streams concurrently
        with self._resize_lock:
            self._busy += 1
            self._pending_baselines -= 1

    def _reserve_permits(self, want: int) -> int:
        """Non-blockingly reserve up to ``want`` SPARE permits for segments.

        Never takes a permit a pending baseline worker is entitled to — a
        segmented video only forms when the WHOLE split (all k workers) fits
        in genuinely idle headroom, so every earlier-scheduled video keeps
        its one-permit entitlement by counting and the consumer draining
        videos in schedule order can always make progress (deadlock-free:
        permit holders are only ever workers of videos at or before the
        consumer's cursor, or of videos some independent loop is draining).
        """
        got = 0
        with self._resize_lock:
            spare = self._workers - self._busy - self._pending_baselines
            while got < min(want, max(0, spare)):
                if not self._sem.acquire(blocking=False):
                    break
                got += 1
            self._busy += got
        return got

    def _new_slot(self, maxsize: int, max_bytes: int) -> dict:
        slot = {
            "q": queue.Queue(maxsize=maxsize),
            "meta": None,
            "err": None,
            "bytes": 0,  # buffered payload bytes (max_buffered_bytes bound)
            # per-slot share of the byte budget: a segmented video's k slots
            # split the video's budget so its TOTAL buffered payload honors
            # the same bound as an unsegmented decode
            "max_bytes": max_bytes,
            # guards the bytes counter (vftlint GUARDED_BY: slot['bytes']
            # under the 'slot' lock)
            "lock": threading.Lock(),
            "ready": threading.Event(),
            "stop": threading.Event(),  # per-video cancel (release())
        }
        return slot

    @staticmethod
    def _group_slots(slot: dict) -> List[dict]:
        """The per-queue slots behind one scheduled path (1 or k segments)."""
        return slot["segments"] if "segments" in slot else [slot]

    def schedule(self, path: str) -> None:
        """Start decoding ``path`` in the background (no-op if scheduled).

        When a segmenter is installed (:meth:`set_segmenter`) and spare
        permits exist, the video may be split into seek-aligned segments
        decoded concurrently — planning runs on the calling thread (header
        probe only) and any planner failure falls back to sequential decode:
        scheduling never raises, the real open classifies bad containers.
        """
        if path in self._slots or path in self._handed or self._stop.is_set():
            return
        self._threads = [t for t in self._threads if t.is_alive()]
        plan = self._plan_for(path)
        if plan is not None and self._schedule_segments(path, plan):
            return
        self._schedule_single(path)

    def _schedule_single(self, path: str) -> None:
        slot = self._new_slot(self._max, self._max_bytes)
        self._slots[path] = slot
        with self._resize_lock:
            self._pending_baselines += 1
        t = threading.Thread(
            target=self._pump,
            args=(path, slot, lambda: self._open(path), False, None, None),
            daemon=True)
        self._threads.append(t)
        t.start()

    def _plan_for(self, path: str):
        if self._planner is None or self._segment_open is None:
            return None
        with self._resize_lock:
            spare = self._workers - self._busy - self._pending_baselines
        if spare < 2:
            return None  # a split needs at least two wholly-idle permits
        try:
            plan = self._planner(path, spare)
        except Exception:  # noqa: BLE001 — fault-barrier: planning must never fail a video
            return None
        if plan is None or len(plan.bounds) < 2:
            return None
        return plan

    def _schedule_segments(self, path: str, plan) -> bool:
        # every segment worker's permit — INCLUDING segment 0's — is secured
        # up front: a segmented video must never block on the baseline
        # semaphore while its own sibling segments hold permits waiting for
        # the consumer to reach them (that cycle is a deadlock)
        got = self._reserve_permits(len(plan.bounds))
        if got < 2:
            for _ in range(got):
                self._release_permit()
            return False  # the headroom evaporated since planning
        if got < len(plan.bounds):
            plan = plan.narrow(got)
            if plan is None or len(plan.bounds) < 2 or len(plan.bounds) > got:
                for _ in range(got):
                    self._release_permit()
                return False
            for _ in range(got - len(plan.bounds)):
                self._release_permit()
                got -= 1
        k = len(plan.bounds)
        subs = [self._new_slot(max(1, self._max // k),
                               max(1, self._max_bytes // k)) for _ in range(k)]
        group = {"segments": subs, "meta": plan.meta, "plan": plan}
        self._slots[path] = group
        with self._resize_lock:
            self._videos_segmented += 1  # stats counter (segment_stats)
        for j, sub in enumerate(subs):
            t = threading.Thread(
                target=self._pump,
                args=(path, sub,
                      (lambda p=plan, i=j: (p.meta, self._segment_open(p, i))),
                      True, j, k),
                daemon=True)
            self._threads.append(t)
            t.start()
        return True

    def _pump(self, path: str, slot: dict, produce: Callable, reserved: bool,
              segment: Optional[int], segments: Optional[int]) -> None:
        """Worker body shared by whole-video and segment decode streams.

        ``produce() -> (meta, frames_iter)``; ``reserved`` workers arrived
        with a permit pre-reserved at schedule time (segmented videos secure
        every segment's permit up front), others perform the normal blocking
        baseline acquire. ``segment``/``segments`` tag a segment stream's
        journal span and completion counter.
        """

        def stopped() -> bool:
            return self._stop.is_set() or slot["stop"].is_set()

        if not reserved:
            self._acquire_baseline()
        clean = False
        try:
            # 'decode' span: full occupancy of this decode slot
            with self._span("decode", video=path, segment=segment,
                            segments=segments):
                try:
                    if stopped():
                        return
                    # crash-injection seam: a worker dying HERE (not inside
                    # open_fn) must still surface a classified error at consume
                    # time instead of deadlocking the drain — tests prove it
                    fault_point("pool_worker", path)
                    meta, frames = produce()
                    slot["meta"] = meta  # thread-shared-state: published by the ready Event set below
                    slot["ready"].set()
                    for item in frames:
                        nbytes = _item_bytes(item)
                        # byte bound: wait for buffered-payload room (the frame
                        # COUNT bound is the queue's maxsize below; the tighter
                        # of the two governs). An empty buffer always admits one
                        # item, so a single frame larger than the cap still flows.
                        while not stopped():
                            with slot["lock"]:
                                fits = (slot["bytes"] == 0
                                        or slot["bytes"] + nbytes <= slot["max_bytes"])
                            if fits:
                                break
                            time.sleep(0.05)
                        if stopped():
                            return
                        while not stopped():
                            try:
                                slot["q"].put(item, timeout=0.2)
                                with slot["lock"]:
                                    slot["bytes"] += nbytes  # thread-shared-state: guarded by slot['lock'] (consumer decrements under the same lock)
                                break
                            except queue.Full:
                                continue
                        if stopped():
                            return
                    clean = not stopped()
                except Exception as e:  # noqa: BLE001 — fault-barrier: re-raised classified at consume time
                    slot["err"] = e  # thread-shared-state: published by the ready Event / _DONE sentinel in finally
                finally:
                    slot["ready"].set()
                    while not stopped():
                        try:
                            slot["q"].put(self._DONE, timeout=0.2)
                            break
                        except queue.Full:  # consumer will drain; retry
                            continue
        finally:
            if clean and segment is not None:
                with self._resize_lock:
                    self._segments_decoded += 1  # thread-shared-state: guarded by the 'resize' lock (stats counter, segment_stats reads under it)
            # a shrink may have pre-claimed this permit as debt; the helper
            # settles debt before returning the permit to the pool
            self._release_permit()

    def get(self, path: str):
        """(meta, frames_iter) for ``path`` — prefetched if scheduled, else
        decoded inline. Pair every get() with :meth:`release` (the run loop
        does this in its per-video ``finally``): an abandoned iterator — e.g.
        the per-video fault barrier caught a compute error mid-drain — would
        otherwise pin its worker thread and semaphore permit forever.
        """
        slot = self._slots.pop(path, None)
        if slot is None:
            return self._open(path)
        self._handed[path] = slot
        if "segments" in slot:
            # segmented video: in-order reassembly — stream segment j's queue
            # to the consumer while segments j+1..k-1 keep decoding into
            # theirs. A poisoned segment's error surfaces mid-generator,
            # exactly where a sequential decode error would.
            def reassemble() -> Iterator[Tuple[np.ndarray, float]]:
                for sub in slot["segments"]:
                    for item in self._drain(sub):
                        yield item

            return slot["meta"], reassemble()
        slot["ready"].wait()
        if slot["err"] is not None and slot["meta"] is None:
            raise slot["err"]
        return slot["meta"], self._drain(slot)

    def _drain(self, slot: dict) -> Iterator[Tuple[np.ndarray, float]]:
        while True:
            try:
                item = slot["q"].get(timeout=0.2)
            except queue.Empty:
                # release()/shutdown() with a full queue can drop their
                # _DONE sentinel while the stopped worker never enqueues
                # one — without this check a late consumer blocks forever.
                # A stored worker error must still surface on this exit
                # path (the dropped sentinel would otherwise swallow it).
                if slot["stop"].is_set() or self._stop.is_set():
                    if slot["err"] is not None:
                        raise slot["err"]
                    return
                continue
            if item is self._DONE:
                if slot["err"] is not None:
                    raise slot["err"]
                return
            with slot["lock"]:
                # release the byte budget as soon as the item leaves the
                # buffer (once yielded it is the consumer's memory)
                slot["bytes"] -= _item_bytes(item)
            yield item

    def release(self, path: str) -> None:
        """Cancel/forget a video's decode (no-op for finished or unknown ones).

        For a segmented video the cancel fans out to EVERY segment worker —
        each sub-slot gets its stop flag and a drain-unblocking sentinel.
        """
        slot = self._handed.pop(path, None) or self._slots.pop(path, None)
        if slot is None:
            return
        for sub in self._group_slots(slot):
            sub["stop"].set()
            try:  # a consumer mid-drain must not hang on an exiting worker
                sub["q"].put_nowait(self._DONE)
            except queue.Full:
                pass

    def shutdown(self) -> None:
        self._stop.set()
        for slot in list(self._slots.values()) + list(self._handed.values()):
            for sub in self._group_slots(slot):
                try:  # unblock any drain() consumers
                    sub["q"].put_nowait(self._DONE)
                except queue.Full:
                    pass  # consumer has items to drain before it can block
        for t in self._threads:
            t.join(timeout=2.0)
        self._slots.clear()
        self._handed.clear()


def pad_batch(arr: np.ndarray, batch_size: int) -> np.ndarray:
    """Zero-pad the leading axis to ``batch_size`` (static shapes: one XLA compile
    per geometry instead of one per partial tail batch)."""
    n = arr.shape[0]
    if n == batch_size:
        return arr
    if n > batch_size:
        raise ValueError(f"batch of {n} exceeds batch_size {batch_size}")
    pad = np.zeros((batch_size - n,) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


class HostStagingRing:
    """Reusable host staging buffers for ``device_put`` sources.

    Every frame-path device batch used to be assembled into a FRESH
    ``np.stack(...)`` (historically ``.astype(np.float32)``) allocation —
    per-batch host memory churn on exactly the hot path where the uint8 wire
    format just quartered the bytes. The ring hands out a small per-geometry
    set of preallocated buffers instead: callers :meth:`acquire` a
    ``(shape, dtype)`` buffer, fill it in place, ``device_put`` it, and
    :meth:`commit` it back with the resulting device value.

    Discipline (the ``AsyncOutputWriter``'s bounded-ring idea applied to H2D
    staging): a buffer is never rewritten while its ``device_put`` may still
    be reading it. JAX transfers are asynchronous — the sharded CPU path
    copies lazily and TPU DMA reads the host buffer after dispatch returns —
    so :meth:`acquire` blocks on the committed device value's
    ``block_until_ready`` before handing the same buffer out again. That wait
    is the transfer pipe's backpressure and is surfaced through ``on_wait``
    (the extractors attribute it to the 'transfer' stage).

    Single-threaded by design: acquire/fill/commit all run on the run-loop
    thread (like the corpus packer), so the ring needs no locks and vftlint's
    thread-shared-state table gains no entries. Slots never leave their ring
    — a dispatch failure between acquire and commit just leaves the slot's
    previous (already-awaited) device value cleared, so error paths cannot
    leak buffers.

    Memory bound: at most ``max_geometries`` per-geometry rings are kept —
    acquiring a new geometry past the cap evicts the least-recently-acquired
    ring (its pending transfers awaited first), so a long-lived caller (the
    ``--serve`` daemon staging an open-ended mix of video geometries, or
    ``--device_resize`` shipping native-resolution frames) holds at most
    ``max_geometries × depth`` buffers instead of growing forever — the ring
    analogue of ``packer.forget``'s long-run bound. A corpus cycling through
    more concurrent geometries than the cap just re-allocates for the
    evicted ones (correctness unaffected). ``DEFAULT_MAX_GEOMETRIES`` is the
    single-model budget; a multi-model daemon (``--serve_models``) scales it
    by the loaded model count, since each co-resident model brings its own
    working set of batch geometries and would otherwise thrash the shared
    ring's eviction.
    """

    DEFAULT_MAX_GEOMETRIES = 8

    def __init__(self, depth: int = 3, on_wait: Optional[Callable] = None,
                 max_geometries: int = DEFAULT_MAX_GEOMETRIES):
        if depth < 1:
            raise ValueError("staging ring depth must be >= 1")
        if max_geometries < 1:
            raise ValueError("staging ring max_geometries must be >= 1")
        self._depth = depth
        self._on_wait = on_wait
        self._max_geometries = max_geometries
        # (shape, dtype-str) -> deque of {"buf", "dev"} slots, oldest first
        self._rings: dict = {}
        self._last_acquire: dict = {}  # key -> tick of last acquire (LRU)
        self._tick = 0
        self.allocated = 0  # buffers ever allocated (reuse observability)
        self.acquires = 0
        self.evicted_geometries = 0
        self.wait_seconds = 0.0  # cumulative blocked-on-transfer time

    @staticmethod
    def _key(shape, dtype) -> tuple:
        return (tuple(shape), np.dtype(dtype).str)

    def _await(self, slot: dict) -> None:
        """Block until the slot's committed transfer finished (accounted)."""
        if slot["dev"] is None:
            return
        t0 = time.perf_counter()
        for leaf in jax.tree_util.tree_leaves(slot["dev"]):
            ready = getattr(leaf, "block_until_ready", None)
            if ready is not None:
                ready()
        waited = time.perf_counter() - t0
        self.wait_seconds += waited
        if self._on_wait is not None:
            self._on_wait(waited)
        slot["dev"] = None

    def _evict_lru_geometry(self) -> None:
        key = min(self._rings, key=lambda k: self._last_acquire.get(k, 0))
        for slot in self._rings.pop(key):
            # a pending lazy copy may still read the buffer we are about to
            # drop our last reference to — await it before freeing
            self._await(slot)
        self._last_acquire.pop(key, None)
        self.evicted_geometries += 1

    def acquire(self, shape, dtype) -> np.ndarray:
        """A writable staging buffer of ``(shape, dtype)``.

        Allocates until the ring holds ``depth`` buffers for this geometry,
        then recycles the least-recently-acquired one — blocking first until
        its committed transfer has completed (never rewrite a buffer a
        pending ``device_put`` may still read).
        """
        key = self._key(shape, dtype)
        if key not in self._rings and len(self._rings) >= self._max_geometries:
            self._evict_lru_geometry()  # long-run bound: ≤ cap geometries
        ring = self._rings.setdefault(key, collections.deque())
        self.acquires += 1
        self._tick += 1
        self._last_acquire[key] = self._tick
        if len(ring) < self._depth:
            slot = {"buf": np.empty(shape, dtype), "dev": None}
            self.allocated += 1
        else:
            slot = ring.popleft()
            self._await(slot)
        ring.append(slot)  # stays in the ring: error paths cannot leak it
        return slot["buf"]

    def stage(self, rows, total: Optional[int] = None) -> np.ndarray:
        """Stack equal-shape host ``rows`` into an acquired buffer, zero-
        padded to ``total`` leading entries (default ``len(rows)``) — the one
        shared fill discipline for every batch-staging caller
        (``Extractor._stage_rows``, the packer's default batch assembly).
        Dtype follows the rows: uint8 frames stay uint8 on the wire."""
        n = len(rows)
        if total is None:
            total = n
        buf = self.acquire((total,) + rows[0].shape, rows[0].dtype)
        for i, row in enumerate(rows):
            buf[i] = row
        if n < total:
            buf[n:] = 0
        return buf

    def commit(self, buf: np.ndarray, device_value) -> None:
        """Record ``device_value`` (a jax array or pytree of them) as the
        in-flight transfer reading ``buf``; the slot is not recycled until it
        is ready. A ``buf`` the ring does not own is a no-op — callers may
        pass every dispatched batch through here without tracking which ones
        were ring-staged (e.g. a zero-padded tail batch from ``pad_batch``,
        or the frame-sharded I3D path's (frames, last) view tuples).
        """
        if not isinstance(buf, np.ndarray):
            return
        ring = self._rings.get(self._key(buf.shape, buf.dtype))
        if ring is None:
            return
        for slot in ring:
            if slot["buf"] is buf:
                slot["dev"] = device_value
                return


def prefetch_to_device(
    arrays: Iterable[np.ndarray],
    sharding=None,
    depth: int = 2,
    span=None,
    commit: Optional[Callable] = None,
) -> Iterator[jax.Array]:
    """Iterate device arrays with ``depth`` transfers in flight.

    ``sharding``: optional NamedSharding for the transfer target (mesh-sharded
    batches); default puts on the default device. Items may be pytrees
    (e.g. the frame-sharded I3D flow step's (frames, last_frame) pairs) with
    ``sharding`` a matching pytree of shardings — ``jax.device_put`` accepts
    both.

    ``span``: the extractor's one span call (``Extractor._span``) — each put
    is a ``put`` span: the dispatch time and the staged payload bytes land on
    the 'transfer' stage.
    ``commit(host, dev)``: optional hook called right after each put — the
    extractors pass :meth:`HostStagingRing.commit` so ring-staged batches are
    guarded against rewrite until their transfer completes.
    """
    if depth < 1:
        raise ValueError("prefetch depth must be >= 1")
    queue: collections.deque = collections.deque()
    it = iter(arrays)

    def put(host):
        if span is None:
            return jax.device_put(host, sharding)
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree_util.tree_leaves(host))
        with span("put", stage="transfer", nbytes=nbytes):
            return jax.device_put(host, sharding)

    def enqueue() -> bool:
        try:
            host = next(it)
        except StopIteration:
            return False
        dev = put(host)
        queue.append(dev)
        if commit is not None:
            commit(host, dev)
        return True

    for _ in range(depth):
        if not enqueue():
            break
    while queue:
        out = queue.popleft()
        enqueue()
        yield out

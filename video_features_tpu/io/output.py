"""Output actions: what happens to a finished feature dict.

Reproduces ``action_on_extraction`` (``utils/utils.py:45-74``) including the
``<stem>_<key>.npy`` naming and the per-feature-type output subdirectory the reference
extractors join before calling it (e.g. ``extract_i3d.py:78``). Adds a done-manifest so
interrupted jobs can resume (the reference reruns everything — SURVEY.md §5).

Writes are atomic (tmp + ``os.replace``): a SIGKILL mid-save must never leave a
truncated ``.npy`` that a later ``--resume`` counts as done. Filesystem failures
raise :class:`~..reliability.OutputError` (transient — disk/NFS pressure clears).

:class:`AsyncOutputWriter` (default, ``--sync_writer`` reverts) moves the
save + mark-done pair onto a bounded single-writer thread so serialization
overlaps the next video's compute; ordering and atomicity are unchanged.
"""

from __future__ import annotations

import json
import os
import pathlib
import queue
import sys
import threading
from typing import Dict, Mapping, Optional

import numpy as np

from ..reliability import OutputError, VideoTimeoutError, fault_point
from ..reliability.retry import RetryPolicy, retry_call
from ..reliability.manifest import read_jsonl
from ..utils.metrics import span as bare_span

MANIFEST_NAME = ".done_manifest.jsonl"


def feature_output_dir(output_path: str, feature_type: str) -> str:
    """Features land in ``<output_path>/<feature_type>/`` (reference extract_*.py)."""
    return os.path.join(output_path, feature_type)


def atomic_write_bytes(path: str, data: bytes) -> None:
    """Publish ``data`` at ``path`` via tmp + ``os.replace`` — the shared
    crash-safety discipline (:func:`_atomic_save`, request results, the
    feature cache's CAS entries in ``cache/store.py``): a kill at any point
    leaves either no visible file or a complete one. Raises
    :class:`~..reliability.OutputError` on filesystem failure."""
    tmp = path + ".tmp"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except OSError as e:
        raise OutputError(f"failed to write {path}: {e}") from e
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _atomic_save(fpath: str, value: np.ndarray) -> None:
    """Write ``value`` to ``fpath`` via tmp + rename; never a truncated final file.

    ``np.save`` appends ``.npy`` to *names*, not file objects, so the tmp file
    is written through an explicit handle. A crash between write and rename
    leaves only ``<file>.npy.tmp`` — invisible to loaders and to ``--resume``.
    """
    tmp = fpath + ".tmp"
    try:
        with open(tmp, "wb") as f:
            np.save(f, value)
        fault_point("save", fpath)
        os.replace(tmp, fpath)
    except OSError as e:
        raise OutputError(f"failed to write {fpath}: {e}") from e
    finally:
        # on success the replace consumed tmp; on ANY failure (including an
        # injected fault) remove it — only a hard kill may leave one behind
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def action_on_extraction(
    feats_dict: Mapping[str, np.ndarray],
    video_path: str,
    output_path: str,
    on_extraction: str = "print",
) -> Dict[str, str]:
    """Print or save each array in ``feats_dict``.

    ``print`` dumps the array plus a ``max/mean/min`` stats line (the reference's
    numeric smoke test, ``utils/utils.py:57-61``); ``save_numpy`` writes
    ``<stem>_<key>.npy`` under ``output_path``. Returns ``{key: saved_path}`` for
    ``save_numpy`` (empty for ``print``).
    """
    saved: Dict[str, str] = {}
    for key, value in feats_dict.items():
        value = np.asarray(value)
        if on_extraction == "print":
            print(key)
            print(value)
            print(f"max: {value.max():.8f}; mean: {value.mean():.8f}; min: {value.min():.8f}")
            print()
        elif on_extraction == "save_numpy":
            try:
                os.makedirs(output_path, exist_ok=True)
            except OSError as e:
                raise OutputError(f"cannot create output dir {output_path}: {e}") from e
            fname = f"{output_stem(video_path)}_{key}.npy"
            fpath = os.path.join(output_path, fname)
            if value.ndim > 0 and len(value) == 0:
                print(f"Warning: the value is empty for {key} @ {fpath}")
            _atomic_save(fpath, value)
            saved[key] = fpath
        else:
            raise NotImplementedError(f"on_extraction: {on_extraction} is not implemented")
    return saved


def write_outputs(feats_dict: Mapping[str, np.ndarray], video_path: str,
                  output_path: str, on_extraction: str = "save_numpy",
                  cancelled: Optional[threading.Event] = None) -> None:
    """One video's complete output sequence — THE single implementation of
    the write-before-done / cancellation contract, shared by the inline
    path (:meth:`Extractor._process_one`) and the async writer thread:

    1. re-check the watchdog cancel event before touching disk;
    2. ``action_on_extraction`` (atomic per-array tmp+rename saves);
    3. re-check the cancel event — features may exist, but a cancelled
       attempt must NOT be marked done;
    4. append the done-manifest record.
    """

    def check_cancelled(stage: str) -> None:
        if cancelled is not None and cancelled.is_set():
            raise VideoTimeoutError(
                f"{video_path}: attempt was cancelled by the watchdog; {stage}")

    check_cancelled("discarding features before any write")
    action_on_extraction(feats_dict, video_path, output_path, on_extraction)
    if on_extraction == "save_numpy":
        # write-before-done ordering: the record lands only after every
        # .npy of this video has been atomically renamed into place
        check_cancelled("features written but NOT marked done")
        mark_done(output_path, video_path, feats_dict.keys())


class FeatureAssembly:
    """Out-of-order per-video feature assembly for the corpus packer.

    With ``--pack_corpus`` a video's clips ride in device batches shared with
    other videos, so its per-clip feature rows arrive in whatever order those
    batches dispatch — and videos complete out of submission order (a short
    video co-packed behind a long one finishes first). This buffer collects
    rows by clip index and rebuilds the in-order feature array once the clip
    stream has finished and every reserved row has landed; only then does the
    run loop hand the assembled output to the (order-preserving) writer.
    Single-threaded: owned and touched only by the packed run loop's thread.
    """

    __slots__ = ("video", "info", "expected", "_reserved", "_rows")

    def __init__(self, video: str, info: dict):
        self.video = video
        self.info = info  # per-video stream metadata (fps, timestamps, …)
        self.expected: Optional[int] = None  # clip count, known at finish()
        self._reserved = 0
        self._rows: Dict[int, np.ndarray] = {}

    def reserve(self) -> int:
        """Claim the next clip index (stream order)."""
        idx = self._reserved
        self._reserved += 1
        return idx

    def put(self, idx: int, row: np.ndarray) -> None:
        self._rows[idx] = row

    def finish(self) -> None:
        """The clip stream ended cleanly; every reserved row is now expected."""
        self.expected = self._reserved

    @property
    def complete(self) -> bool:
        return self.expected is not None and len(self._rows) == self.expected

    def stacked(self, empty_row_shape, dtype=np.float32) -> np.ndarray:
        """The video's features in clip order; a typed empty for zero clips."""
        if not self.expected:
            return np.zeros((0,) + tuple(empty_row_shape), dtype)
        return np.stack([self._rows[i] for i in range(self.expected)])

    def release(self) -> None:
        """Drop the per-clip row buffers once :meth:`stacked` was consumed.

        Each row is a VIEW into the device batch's fetched host array, so a
        lingering assembly pins whole ``(batch_size, …)`` batches — on a
        long-lived serving daemon that is unbounded growth. The run loop
        releases every assembly right after finalize (success or failure);
        :meth:`stacked`'s ``np.stack`` copied the data, so outputs are safe.
        """
        self._rows.clear()


def output_stem(video_path: str) -> str:
    """``<stem>`` of a video's output files: the file name without its
    extension, and without the ``.tokens`` of a transcript's ``.tokens.npz``."""
    return pathlib.Path(video_path).stem.removesuffix(".tokens")


def feats_nbytes(feats_dict: Mapping[str, np.ndarray]) -> int:
    """Payload bytes of one video's feature dict (the ``write`` span's
    ``bytes`` and the ``write_bytes`` counter)."""
    return sum(int(getattr(v, "nbytes", 0)) for v in feats_dict.values())


class WriteHandle:
    """Completion token for one video's asynchronous output write."""

    __slots__ = ("video", "nbytes", "_done", "_error")

    def __init__(self, video: str, nbytes: int = 0):
        self.video = video
        self.nbytes = nbytes  # payload bytes of the job (the write counters)
        self._done = threading.Event()
        self._error: Optional[BaseException] = None

    def ok(self) -> bool:
        """The write completed without an error."""
        return self._done.is_set() and self._error is None

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the write completed; re-raises its classified error.

        Returns False if ``timeout`` expired with the write still pending.
        """
        if not self._done.wait(timeout):
            return False
        if self._error is not None:
            raise self._error
        return True

    def done(self) -> bool:
        return self._done.is_set()


class AsyncOutputWriter:
    """Bounded single-writer thread: overlap feature serialization with the
    next video's compute.

    ``action_on_extraction`` + ``mark_done`` previously ran inside the
    per-video loop, serializing multi-GB dense-flow ``.npy`` writes against
    device compute; here they run on one background thread while the loop
    moves on. The PR-1 reliability invariants are preserved by construction
    (pinned by tests/test_async_writer.py + tests/test_fault_injection.py):

    - jobs run strictly in submission order (one queue, one thread);
    - within a job, features are written first (atomic tmp+rename,
      :func:`_atomic_save`) and the done-manifest record appended AFTER —
      a kill at any point leaves either no visible output or a complete one;
    - a failed job surfaces its classified :class:`OutputError` on that
      job's :class:`WriteHandle` only, optionally after transient retries
      (``retry``), never on another video's handle;
    - the queue is bounded: a slow disk backpressures :meth:`submit` (the
      extraction loop) instead of pinning every finished video's features in
      host memory.
    """

    def __init__(self, depth: int = 2, retry: Optional[RetryPolicy] = None,
                 span=bare_span):
        self._q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._retry = retry
        # the extractor's one span call (``Extractor._span``): each job runs
        # inside a ``write`` span on this thread
        self._span = span
        # counters, handed out in the run's ``_pack_stats`` (all kept on
        # the submitting thread, from the handles: the writer thread stores
        # nothing but a handle's outcome). ``_live`` holds the handles not yet
        # counted; the unfinished among them are the backlog — the queue plus
        # the job in hand — sampled at every submit
        self._live: list = []
        self.backlog_max = 0
        self.videos_written = 0
        self.write_bytes = 0
        self._closed = False
        self._thread = threading.Thread(target=self._drain, daemon=True,
                                        name="output-writer")
        self._thread.start()

    def submit(self, feats_dict: Mapping[str, np.ndarray], video_path: str,
               output_path: str, on_extraction: str = "save_numpy",
               cancelled: Optional[threading.Event] = None) -> WriteHandle:
        """Enqueue one video's output job; blocks when the queue is full.

        ``cancelled``: the attempt's watchdog cancellation event. The job
        re-checks it before touching disk and again between the feature
        writes and the done record — the same two points the inline path
        checks — so an attempt whose timeout fires in the check-to-submit
        window (or mid-write) can never leave a done-manifest record for a
        video the run counted as failed.
        """
        if self._closed:
            raise OutputError("output writer is closed")
        if not self._thread.is_alive():
            raise OutputError("output writer thread died")
        handle = WriteHandle(video_path, feats_nbytes(feats_dict))
        self.counters()
        self._live.append(handle)
        self.backlog_max = max(self.backlog_max, len(self._live))
        self._q.put((handle, feats_dict, video_path, output_path, on_extraction,
                     cancelled))
        return handle

    def counters(self) -> Dict[str, int]:
        """``writer_backlog_max``, ``videos_written``, ``write_bytes``: counts
        the handles that finished since the last call (the submitting
        thread's to call)."""
        finished = [h for h in self._live if h.done()]
        self._live = [h for h in self._live if not h.done()]
        for h in finished:
            if h.ok():
                self.videos_written += 1
                self.write_bytes += h.nbytes
        return {"writer_backlog_max": self.backlog_max,
                "videos_written": self.videos_written,
                "write_bytes": self.write_bytes}

    _run_one = staticmethod(write_outputs)  # one write-contract implementation

    def _drain(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            handle, *job = item
            retries = []
            try:
                with self._span("write", video=handle.video,
                                bytes=handle.nbytes) as sp:
                    try:
                        if self._retry is not None:
                            # OutputError is transient (disk/NFS pressure
                            # clears); retrying here re-runs idempotent steps
                            # only — atomic saves overwrite, duplicate done
                            # records collapse into the load_done_set set
                            retry_call(lambda: self._run_one(*job), self._retry,  # noqa: B023
                                       on_retry=lambda *a: retries.append(a))  # noqa: B023
                        else:
                            self._run_one(*job)
                    finally:
                        sp.ids.update(retries=len(retries))
            except Exception as e:  # noqa: BLE001 — fault-barrier: stored on the handle, re-raised classified at the run loop's per-video write reap
                handle._error = e  # thread-shared-state: set before the _done Event; wait() reads after it
            finally:
                handle._done.set()

    def close(self, wait: bool = True) -> None:
        """Stop accepting jobs; by default drain queued jobs first.

        ``wait=True`` joins the thread after it finishes everything already
        queued — on interrupts the physical writes (and their ordering) still
        complete even if the caller no longer collects the handles.
        """
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        if wait:
            self._thread.join()


def request_result_path(notify_dir: str, request_id: str) -> str:
    """Completion-notification file for one service request
    (:mod:`..serve`): submitters poll for it instead of tailing logs."""
    return os.path.join(notify_dir, f"{request_id}.result.json")


def write_request_result(notify_dir: str, request_id: str,
                         record: Mapping) -> str:
    """Atomically write a request's per-request done/failed manifest.

    One JSON document per request: terminal state, the per-video ``done``
    list and classified ``failed`` records. Written via tmp + ``os.replace``
    like every other output — a submitter that sees the file sees a complete
    record. Returns the path written.
    """
    path = request_result_path(notify_dir, request_id)
    tmp = path + ".tmp"
    try:
        os.makedirs(notify_dir, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(dict(record), f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        raise OutputError(
            f"failed to write request result {path}: {e}") from e
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return path


def manifest_path(output_path: str) -> str:
    return os.path.join(output_path, MANIFEST_NAME)


def mark_done(output_path: str, video_path: str, keys) -> None:
    """Append a completion record for ``video_path`` to the done-manifest."""
    record = {"video": os.path.abspath(video_path), "keys": sorted(keys)}
    try:
        os.makedirs(output_path, exist_ok=True)
        with open(manifest_path(output_path), "a") as f:
            f.write(json.dumps(record) + "\n")
    except OSError as e:
        raise OutputError(f"cannot append to done-manifest in {output_path}: {e}") from e


def load_done_set(output_path: str) -> set:
    """Absolute video paths already completed according to the manifest.

    Corrupt/undecodable lines (a crash mid-append, manual edits) are counted
    and warned about, not silently skipped: every dropped line is a video that
    ``--resume`` will re-extract, and the operator should know why.
    """
    done = set()
    path = manifest_path(output_path)
    records, corrupt = read_jsonl(path)
    for record in records:
        if "video" in record:
            done.add(record["video"])
        else:
            corrupt += 1
    if corrupt:
        print(
            f"warning: ignored {corrupt} corrupt line(s) in {path}; "
            "the affected videos will be re-extracted on --resume",
            file=sys.stderr,
        )
    return done

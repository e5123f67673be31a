"""A profiler trace of a short slice of the window, taken by the benchmark
itself (never through the program's ``--profile_dir``, which wraps the whole
run), and placed by the window's own progress: no guess of the program's
speed is in it."""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

POLL_S = 0.05


def start_device_trace(directory: str) -> None:
    import jax

    # device events only: the Python and host tracers made the traced window
    # a quarter slower and the trace a thousand times larger (1.4 M host
    # events for 4 s) for nothing a metric reads
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)


def stop_device_trace() -> None:
    import jax

    jax.profiler.stop_trace()


class SliceTracer:
    """``arm(progressed)`` at the window's start. One thread asks
    ``progressed()`` every ``POLL_S``: the generator's answer is None until
    the window has finished its first unit of work (a video written), then
    the Unix time at which it did. By then the pipeline is full. The slice
    starts at once and lasts ``seconds`` of the configuration's
    ``trace_slice`` or until ``finish()``, whichever is first; ``finish()``
    returns when the trace is on disk. A window that closes before its first
    unit was seen leaves ``started`` false and no trace."""

    def __init__(self, directory: str, params: dict):
        self.directory = directory
        self.length = float(params["seconds"])
        self.started = False
        self.slice_seconds = 0.0
        self.armed_at = 0.0  # Unix s, like the two below
        self.progress_at: Optional[float] = None  # the generator's word
        self.seen_lag_s = self.start_lag_s = 0.0   # after progress_at
        self._closed = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None

    def arm(self, progressed: Callable[[], Optional[float]]) -> None:
        self.armed_at = time.time()
        self._thread = threading.Thread(target=self._run, args=(progressed,),
                                        name="slice-tracer", daemon=True)
        self._thread.start()

    def _run(self, progressed) -> None:
        try:
            at = progressed()
            while at is None:
                if self._closed.wait(POLL_S):
                    return
                at = progressed()
            self.progress_at = at
            self.seen_lag_s = time.time() - at
            start_device_trace(self.directory)
            self.start_lag_s = time.time() - at
            t0 = time.perf_counter()
            self.started = True
            self._closed.wait(self.length)
            self.slice_seconds = time.perf_counter() - t0
            stop_device_trace()
        except Exception as e:  # noqa: BLE001 — handed to finish(), which raises it
            self._error = e

    def finish(self) -> None:
        self._closed.set()
        if self._thread is not None:
            self._thread.join()
        if self._error is not None:
            raise self._error

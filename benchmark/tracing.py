"""A profiler trace of a short slice of the window, taken by the benchmark
itself from a timer thread (never through the program's ``--profile_dir``,
which wraps the whole run)."""

from __future__ import annotations

import threading
import time


class SliceTracer:
    """``arm()`` at the window's start; the slice starts ``start_fraction`` of
    the nominal window in (so the pipeline is full) and lasts ``seconds``.
    ``finish()`` stops a slice the window outran."""

    def __init__(self, directory: str, params: dict, window_seconds: float):
        self.directory = directory
        self.delay = float(params.get("start_fraction", 0.3)) * window_seconds
        self.length = min(float(params.get("seconds", 4.0)), 0.5 * window_seconds)
        self.slice_seconds = 0.0
        self._lock = threading.Lock()
        self._started_at = None
        self._done = False
        self._timers = []

    def arm(self) -> None:
        t = threading.Timer(self.delay, self._start)
        t.daemon = True
        t.start()
        self._timers.append(t)

    def _start(self) -> None:
        import jax

        with self._lock:
            if self._done:
                return
            # device events only: the Python and host tracers made the
            # traced window a quarter slower and the trace a thousand times
            # larger (1.4 M host events for 4 s) for nothing a metric reads
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self._started_at = time.perf_counter()
        t = threading.Timer(self.length, self._stop)
        t.daemon = True
        t.start()
        self._timers.append(t)

    def _stop(self) -> None:
        import jax

        with self._lock:
            if self._started_at is None or self._done:
                self._done = True
                return
            self.slice_seconds = time.perf_counter() - self._started_at
            self._done = True
            jax.profiler.stop_trace()

    def finish(self) -> None:
        for t in self._timers:
            t.cancel()
        self._stop()

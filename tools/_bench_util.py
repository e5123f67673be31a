"""Shared timing for the stage profilers (profile_raft / profile_i3d).

The methodology of record lives in bench.py (unique inputs per call, one
forced host read that data-depends on every output leaf, sync-latency
subtraction, iteration auto-raise against the noise floor); this module
re-exports it so the profilers and the bench can never drift apart.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as _bench  # noqa: E402 — repo-root bench.py
from video_features_tpu.parallel.mesh import enable_compilation_cache  # noqa: E402, F401

force = _bench._force
timeit = _bench._timeit


def time_fn(name, fn, mk_inputs, iters=4, repeats=3):
    """Median seconds/iteration via bench._time_step (auto-raised iterations);
    prints one line, flagging measurements still under 3× the sync latency."""
    sec, sync, iters_run = _bench._time_step(fn, mk_inputs, iters, repeats)
    flag = "  [noise-limited]" if iters_run * sec < 3 * sync else ""
    print(f"{name:>16}: {sec * 1e3:9.2f} ms/iter  "
          f"(sync {sync * 1e3:.0f} ms, iters {iters_run}){flag}", flush=True)
    return sec

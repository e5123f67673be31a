"""The comparison that decides ``correct``.

After the window has closed and the program's device state is freed, a sample
of the videos the window itself finished (drawn from the seed, the longest
clip among them; the generator says which) is put through the
configuration's plain reference, once per distinct clip, and each ``.npy``
the window wrote is compared with it:

- feature rows by their **gap**: the worst row's ``||program - reference||_2``
  over ``max(||reference row||_2, median row norm)``, one number per output
  key, each with its own limit from the configuration's file (``limits``);
- everything else (frame rate, time stamps, the number of rows) exactly:
  the count of values that differ, limit 0.

A sampled video without its outputs counts as a mismatch: it never came.
"""

from __future__ import annotations

import importlib
import os
import sys
from typing import Dict, List, Tuple

import numpy as np


def row_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: ``||got - want||_2 / max(||want||_2, median row norm)``;
    infinite where a row is not finite."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    norms = np.linalg.norm(want, axis=1)
    floor = max(float(np.median(norms)) if len(norms) else 0.0, 1e-30)
    diff = np.linalg.norm(got - want, axis=1)
    return np.where(np.isfinite(diff), diff / np.maximum(norms, floor), np.inf)


def compare(ctx, gen, window: dict, flat_weights: Dict[str, dict]) -> Tuple[bool, List[dict]]:
    from weights import unflatten

    ref = importlib.import_module("reference." + ctx.conf["reference"])
    limits = ctx.conf["limits"]
    answer = ref.make_answer_fn({k: unflatten(v) for k, v in flat_weights.items()})
    sample = gen.check_sample(ctx, window)
    gaps = {k: 0.0 for k in ref.FEATURE_KEYS}
    mismatches = 0 if sample else 1
    rows = 0
    answers: Dict[str, dict] = {}
    for path in sample:
        key = os.stat(path).st_ino  # window entries are hard links to the clips
        if key not in answers:
            answers[key] = answer(path)
        want, got = answers[key], gen.read_outputs(window, path)
        for k in ref.FEATURE_KEYS:
            if k not in got or got[k].shape != want[k].shape:
                mismatches += 1
                continue
            g = row_gaps(got[k], want[k])
            gaps[k] = max(gaps[k], float(g.max()))
            print(f"[check] {os.path.basename(path)} {k}: rows {len(g)} gap max {g.max():.3e} "
                  f"median {np.median(g):.3e} min {g.min():.3e}", file=sys.stderr)
            rows += len(want[k])
        for k in ref.EXACT_KEYS:
            if k not in got or got[k].shape != want[k].shape:
                mismatches += 1
            else:
                mismatches += int(np.sum(np.asarray(got[k]) != np.asarray(want[k])))
    numbers = [{"name": "gap." + k, "value": gaps[k], "limit": limits["gap." + k]}
               for k in ref.FEATURE_KEYS]
    numbers.append({"name": "exact_mismatches", "value": mismatches, "limit": 0})
    correct = all(n["value"] <= n["limit"] for n in numbers)
    numbers.append({"name": "compared_videos", "value": len(sample), "limit": None})
    numbers.append({"name": "compared_rows", "value": rows, "limit": None})
    return correct, numbers

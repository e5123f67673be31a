"""Traffic generator ``corpus_run``: a batch job over a corpus of trimmed clips.

Set-up writes ``clips`` distinct clips from the seed: each a contiguous range
(seeded start, a length of an even spread over ``min_frames``..``max_frames``)
of one of ``sources`` long enough to hold it, decoded with OpenCV and written
again with ``fourcc`` at the source's own size and frame rate. Every seed
gives the same multiset of (length, source) in another order and other start
frames, with the longest clip first (``clip_plan`` says why), so the work of
a window does not change with the seed.

The window is ONE call of ``get_extractor(cfg).run(paths)`` with
``on_extraction=save_numpy``, the call ``run.main`` makes, over
``window_videos`` paths: the clips cycled, each entry a hard link under a stem
of its own. ``window_videos`` is fixed work that the configuration's file
states for a run of ``run_seconds`` (a multiple of ``clips``, so that every
seed has the same frames); how long it lasts is the program's business.
Another ``--seconds`` scales it, never under ``min_window_videos``.
The extractor is the one the warm-up pass compiled. A traced run's slice
starts when the window's first output file is there (``first_written``).
"""

from __future__ import annotations

import glob
import os
import time
from typing import Dict, List, Optional

import cv2
import numpy as np


def clip_plan(traffic: dict, seed: int, source_frames: List[int]) -> List[dict]:
    """Which range of which source each clip is. Lengths are an even spread
    over min..max; a length takes its source by rule (by its rank among those
    long enough to hold it), so no length is ever cut and every seed has the
    same multiset of (frames, source). The seed gives the order and the start
    frames, but not the first clip: the consumer takes the window's first
    entry alone for its first page, so that clip's decode is the fill, and a
    seeded one made the window swing by 0.2 s with the seed (PERF.md section
    6, PR 32). The longest goes first for every seed; the rest are permuted."""
    rng = np.random.default_rng([int(seed), 0xC11F5])
    k = int(traffic["clips"])
    lengths = np.linspace(traffic["min_frames"], traffic["max_frames"], k).round().astype(int)
    if lengths.max() > max(source_frames):
        raise ValueError(f"max_frames {lengths.max()} exceeds every source {source_frames}")
    order = [k - 1] + [int(r) for r in rng.permutation(k - 1)]
    plan = []
    for i, rank in enumerate(order):
        n = int(lengths[rank])
        fits = [j for j, total in enumerate(source_frames) if total >= n]
        src = fits[rank % len(fits)]
        start = int(rng.integers(0, source_frames[src] - n + 1))
        plan.append({"clip": i, "source": src, "start": start, "frames": n})
    return plan


def _read_all(path: str):
    cap = cv2.VideoCapture(path)
    if not cap.isOpened():
        raise IOError(f"cannot open {path}")
    fps = cap.get(cv2.CAP_PROP_FPS)
    frames = []
    while True:
        ok, bgr = cap.read()
        if not ok:
            break
        frames.append(bgr)
    cap.release()
    return frames, fps


def write_corpus(traffic: dict, seed: int, root: str, out_dir: str) -> List[str]:
    os.makedirs(out_dir, exist_ok=True)
    decoded = [_read_all(os.path.join(root, s)) for s in traffic["sources"]]
    plan = clip_plan(traffic, seed, [len(f) for f, _ in decoded])
    paths = []
    for item in plan:
        frames, fps = decoded[item["source"]]
        h, w = frames[0].shape[:2]
        path = os.path.join(out_dir, f"clip{item['clip']}.mp4")
        vw = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*traffic["fourcc"]), fps, (w, h))
        if not vw.isOpened():
            raise IOError(f"cannot write {path}")
        for bgr in frames[item["start"]:item["start"] + item["frames"]]:
            vw.write(bgr)
        vw.release()
        paths.append(path)
    return paths


WINDOW_PREFIX = "w"  # no clip's own name starts with it: the warm-up's outputs do not match


def window_paths(clips: List[str], n: int, out_dir: str) -> List[str]:
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for i in range(n):
        src = clips[i % len(clips)]
        dst = os.path.join(out_dir, f"{WINDOW_PREFIX}{i:05d}_{os.path.basename(src)}")
        os.link(src, dst)
        paths.append(dst)
    return paths


def window_count(conf: dict, traffic: dict, seconds: float, run_seconds: float) -> int:
    """Entries of one window: the configuration's ``window_videos`` at
    ``run_seconds``, in proportion at another ``--seconds``, never under the
    mix's ``min_window_videos``."""
    return max(int(traffic["min_window_videos"]),
               int(round(int(conf["window_videos"]) * seconds / run_seconds)))


def build_extractor(ctx):
    """The program's own construction: ``get_extractor(ExtractionConfig)``,
    weights through its checkpoint directory."""
    from video_features_tpu.config import ExtractionConfig
    from video_features_tpu.extractors import get_extractor
    from video_features_tpu.parallel.mesh import enable_compilation_cache

    enable_compilation_cache()
    fields = dict(ctx.conf["extraction"])
    if ctx.variant:
        fields.update(ctx.conf["variants"][ctx.variant]["extraction"])
    fields.update(
        feature_type=ctx.conf["feature_type"], on_extraction="save_numpy",
        num_devices=ctx.chips,
        output_path=os.path.join(ctx.scratch, "out"),
        tmp_path=os.path.join(ctx.scratch, "tmp"))
    return get_extractor(ExtractionConfig(**fields))


def output_files(output_dir: str, path: str) -> Dict[str, str]:
    """``<stem>_<key>.npy`` files of one video → {key: file}."""
    stem = os.path.splitext(os.path.basename(path))[0]
    return {os.path.basename(f)[len(stem) + 1:-4]: f
            for f in glob.glob(os.path.join(output_dir, stem + "_*.npy"))}


def first_written(output_dir: str) -> Optional[float]:
    """When the window finished its first video: the modification time (Unix
    seconds) of the oldest ``.npy`` of a window entry in ``output_dir``, None
    while there is none. The writer publishes a file by ``os.replace``, so one
    that is listed is whole."""
    times = [e.stat().st_mtime for e in os.scandir(output_dir)
             if e.name.startswith(WINDOW_PREFIX) and e.name.endswith(".npy")]
    return min(times) if times else None


def run_window(ctx) -> dict:
    """Set up, warm, measure. Returns the facts every later step reads."""
    traffic, conf = ctx.traffic, ctx.conf
    clips = write_corpus(traffic, ctx.seed, ctx.root, os.path.join(ctx.scratch, "corpus"))
    ex = build_extractor(ctx)
    # warm-up: one pass over the first `warmup_clips` clips (more than one
    # page of either configuration) compiles or loads the one page program
    # the window uses; the same extractor object then takes the window
    warm = list(clips[:int(traffic["warmup_clips"])])
    warm_ok = ex.run(warm)
    if warm_ok != len(warm):
        raise RuntimeError(f"warm-up: {warm_ok}/{len(warm)} clips succeeded")
    n = window_count(conf, traffic, ctx.seconds, ctx.run_seconds)
    paths = window_paths(clips, n, os.path.join(ctx.scratch, "window"))
    if ctx.trace:
        os.environ["VFT_METRICS"] = "1"  # fills StageClock; traced run only
    ctx.before_window(lambda: first_written(ex.output_dir))
    t0 = time.perf_counter()
    ok = ex.run(paths)
    t1 = time.perf_counter()
    ctx.after_window()
    os.environ.pop("VFT_METRICS", None)
    wall = t1 - t0
    stats = dict(ex._pack_stats or {})
    # a video counts when run() says it succeeded and its files are there
    finished = [p for p in paths if output_files(ex.output_dir, p)]
    failed = n - min(ok, len(finished))
    return {
        "extractor": ex, "output_dir": ex.output_dir, "clips": clips,
        "finished": finished, "attempted": n, "failed": failed, "wall_s": wall,
        "rows": int(stats.get("real_slots", 0)),
        "stats": stats,
        "end_to_end": {"videos_per_s": (n - failed) / wall,
                       "setup_s": t0 - ctx.t_start},
    }


def release(window: dict) -> None:
    """Free the program's device state before the reference runs."""
    import gc

    import jax

    ex = window.pop("extractor", None)
    if ex is not None:
        for name in ("params", "i3d_params", "flow_params", "_paged_programs"):
            if hasattr(ex, name):
                setattr(ex, name, None)
    del ex
    gc.collect()
    jax.clear_caches()


def check_sample(ctx, window: dict) -> List[str]:
    """Which finished videos are compared: ``check_videos`` of them drawn
    from the seed, the longest clip's first finished entry always among
    them."""
    finished = window["finished"]
    if not finished:
        return []
    rng = np.random.default_rng([int(ctx.seed), 0x5A3F1E])
    by_clip = {}
    for p in finished:
        by_clip.setdefault(os.path.basename(p).split("_", 1)[1], []).append(p)
    frames = {os.path.basename(c): int(cv2.VideoCapture(c).get(cv2.CAP_PROP_FRAME_COUNT))
              for c in window["clips"]}
    longest = max((c for c in frames if c in by_clip), key=lambda c: frames[c])
    sample = [by_clip[longest][0]]
    rest = [p for p in finished if p != sample[0]]
    k = min(int(ctx.conf["check_videos"]) - 1, len(rest))
    if k > 0:
        sample += [rest[i] for i in rng.choice(len(rest), size=k, replace=False)]
    return sample


def read_outputs(window: dict, path: str) -> Dict[str, np.ndarray]:
    return {key: np.load(f) for key, f in output_files(window["output_dir"], path).items()}

"""Traffic generator ``corpus_tokens``: a batch job over a corpus of token
transcripts, the text stream's ``corpus_run``.

Set-up writes ``documents`` distinct transcripts from the seed
(``<name>.tokens.npz``: ``ids``, cumulative ``segment_ends``, ``start_ms``,
``end_ms``). Lengths are a geometric spread over ``min_tokens``..``max_tokens``,
the same multiset for every seed; the longest goes first (it fills the
window's first page alone, as ``corpus_run``'s longest clip is the fill) and
the seed permutes the rest. Whole documents fill a page first-fit in arrival
order, so an order decides how many pages a pass over the corpus makes and how
many tiles of keys the full-attention layers walk (documents that start inside
a tile share it); a cell's work must not change with the seed, so the seed's
permutations are drawn until one packs as the traffic's ``equal_work`` states
(``document_plan``). Token ids are uniform over the vocabulary, segments
``min_segment_tokens``..``max_segment_tokens`` long, both from the seed.

The window is ONE call of ``get_extractor(cfg).run(paths)`` with
``on_extraction=save_numpy`` over ``window_videos`` paths: the documents
cycled, each entry a hard link under a stem of its own; closed loop, all the
program sustains. ``window_videos`` is fixed work stated by the
configuration's file for a run of ``run_seconds``, scaled by ``--seconds`` and
never under ``min_window_videos``. The extractor is the one the warm-up pass
compiled. ``rows`` is the real tokens the window's pages held. A traced run's
slice starts when the window's first output file is there.
"""

from __future__ import annotations

import gc
import glob
import os
import time
from typing import Dict, List

import numpy as np

from generators.corpus_run import (build_extractor, first_written,  # noqa: F401 — first_written is the slice's start
                                   window_count, window_paths)
# the transcript format is the program's; a program without one stops here,
# before any weights are drawn
from video_features_tpu.io.transcript import SUFFIX


def document_lengths(traffic: dict) -> List[int]:
    k, lo, hi = int(traffic["documents"]), traffic["min_tokens"], traffic["max_tokens"]
    return [int(round(lo * (hi / lo) ** (i / (k - 1)))) for i in range(k)]


def pack_pass(order: List[int], page_tokens: int) -> List[List[int]]:
    """The pages one pass over the corpus makes, by the program's rule: a page
    goes when the queued documents fill or overflow it, and holds every queued
    document that still fits what the ones before it left (first-fit). A pass
    starts with the longest document, a page of its own, so every pass of a
    window packs alike."""
    queue, pages = [], []
    for n in order + [page_tokens]:  # the next pass's first document closes this one
        queue.append(n)
        while sum(queue) >= page_tokens:
            page, rest = [], []
            for m in queue:
                (page if sum(page) + m <= page_tokens else rest).append(m)
            pages.append(page)
            queue = rest
    return pages[:-1]


def attention_tiles(pages: List[List[int]], page_tokens: int, tile: int) -> int:
    """Tiles of keys a full-attention layer walks over ``pages``: each tile of
    queries goes from the tile in which its first query's document starts (the
    pad run counts as one document) up to its own."""
    total = 0
    for page in pages:
        starts = np.cumsum([0] + page)
        for t in range(0, page_tokens, tile):
            start = starts[np.searchsorted(starts, t, side="right") - 1]
            total += t // tile - start // tile + 1
    return total


def document_plan(traffic: dict, seed: int) -> List[int]:
    """The documents' lengths in corpus order: the longest first, the rest
    permuted by the seed — the first of the seed's permutations whose pass
    makes ``equal_work``'s pages and attention tiles (about one in 150 does;
    PERF.md section 6, PR 34, says what an order left free costs)."""
    lengths = document_lengths(traffic)
    work = traffic["equal_work"]
    page_tokens, tile = int(work["page_tokens"]), int(work["attention_tile"])
    if lengths[-1] != page_tokens:
        raise ValueError(f"equal_work: the longest document ({lengths[-1]} tokens) must fill a page "
                         f"({page_tokens}) alone, or a pass's pages run into the next pass's")
    rng = np.random.default_rng([int(seed), 0xD0C5])
    k = len(lengths)
    for _ in range(int(work["max_draws"])):
        order = [lengths[k - 1]] + [lengths[int(r)] for r in rng.permutation(k - 1)]
        pages = pack_pass(order, page_tokens)
        if (len(pages) == int(work["pages_per_pass"])
                and attention_tiles(pages, page_tokens, tile) == int(work["attention_tiles_per_pass"])):
            return order
    raise RuntimeError(f"no permutation of seed {seed} packs as equal_work states "
                       f"in {work['max_draws']} draws")


def make_document(traffic: dict, rng: np.random.Generator, tokens: int) -> Dict[str, np.ndarray]:
    lo, hi = int(traffic["min_segment_tokens"]), int(traffic["max_segment_tokens"])
    sizes = rng.integers(lo, hi + 1, size=tokens // lo + 1)
    ends = np.cumsum(sizes)
    ends = ends[:int(np.searchsorted(ends, tokens))]  # every segment that ends before the last token
    if len(ends) and tokens - ends[-1] < lo:
        ends = ends[:-1]                              # a short tail joins the segment before it
    ends = np.append(ends, tokens).astype(np.int32)
    ms = int(traffic["ms_per_token"])
    return {"ids": rng.integers(0, int(traffic["vocab_size"]), size=tokens).astype(np.int32),
            "segment_ends": ends,
            "start_ms": np.concatenate([[0], ends[:-1]]).astype(np.int64) * ms,
            "end_ms": ends.astype(np.int64) * ms}


def write_corpus(traffic: dict, seed: int, out_dir: str) -> List[str]:
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng([int(seed), 0x70C5])
    paths = []
    for i, tokens in enumerate(document_plan(traffic, seed)):
        path = os.path.join(out_dir, f"doc{i}{SUFFIX}")
        np.savez(path, **make_document(traffic, rng, tokens))
        paths.append(path)
    return paths


def stem_of(path: str) -> str:
    return os.path.basename(path)[:-len(SUFFIX)]


def output_files(output_dir: str, path: str) -> Dict[str, str]:
    """``<stem>_<key>.npy`` files of one transcript → {key: file}."""
    stem = stem_of(path)
    return {os.path.basename(f)[len(stem) + 1:-4]: f
            for f in glob.glob(os.path.join(output_dir, stem + "_*.npy"))}


def run_window(ctx) -> dict:
    """Set up, warm, measure. Returns the facts every later step reads."""
    traffic, conf = ctx.traffic, ctx.conf
    docs = write_corpus(traffic, ctx.seed, os.path.join(ctx.scratch, "corpus"))
    ex = build_extractor(ctx)
    # warm-up: the first documents (more than one page) compile or load the
    # one page program; the same extractor object then takes the window
    warm = list(docs[:int(traffic["warmup_documents"])])
    warm_ok = ex.run(warm)
    if warm_ok != len(warm):
        raise RuntimeError(f"warm-up: {warm_ok}/{len(warm)} documents succeeded")
    n = window_count(conf, traffic, ctx.seconds, ctx.run_seconds)
    paths = window_paths(docs, n, os.path.join(ctx.scratch, "window"))
    if ctx.trace:
        os.environ["VFT_METRICS"] = "1"  # fills the span records; traced run only
    ctx.before_window(lambda: first_written(ex.output_dir))
    t0 = time.perf_counter()
    ok = ex.run(paths)
    t1 = time.perf_counter()
    ctx.after_window()
    os.environ.pop("VFT_METRICS", None)
    wall = t1 - t0
    stats = dict(ex._pack_stats or {})
    finished = [p for p in paths if output_files(ex.output_dir, p)]
    failed = n - min(ok, len(finished))
    return {
        "extractor": ex, "output_dir": ex.output_dir, "clips": docs,
        "finished": finished, "attempted": n, "failed": failed, "wall_s": wall,
        "rows": int(stats.get("real_slots", 0)),  # real tokens in the window's pages
        "stats": stats,
        "end_to_end": {"videos_per_s": (n - failed) / wall,
                       "setup_s": t0 - ctx.t_start},
    }


def release(window: dict) -> None:
    """Free the program's device state (6.3 GB of weights) before the
    reference runs."""
    import jax

    ex = window.pop("extractor", None)
    if ex is not None:
        for name in ("params", "_paged_programs", "_moe_counters"):
            setattr(ex, name, None)
    del ex
    gc.collect()
    jax.clear_caches()


def check_sample(ctx, window: dict) -> List[str]:
    """Which finished transcripts are compared: ``check_videos`` of them
    drawn from the seed, the longest document's first finished entry always
    among them (it is ``doc0``, a page of its own, every position of the
    attention kernel's reach)."""
    finished = window["finished"]
    if not finished:
        return []
    rng = np.random.default_rng([int(ctx.seed), 0x5A3F1E])
    longest = [p for p in finished if os.path.basename(p).split("_", 1)[1] == "doc0" + SUFFIX]
    sample = longest[:1] or finished[:1]
    rest = [p for p in finished if p != sample[0]]
    k = min(int(ctx.conf["check_videos"]) - 1, len(rest))
    if k > 0:
        sample += [rest[i] for i in rng.choice(len(rest), size=k, replace=False)]
    return sample


def read_outputs(window: dict, path: str) -> Dict[str, np.ndarray]:
    return {key: np.load(f) for key, f in output_files(window["output_dir"], path).items()}

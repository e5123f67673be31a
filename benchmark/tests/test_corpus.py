"""The corpus generator: the same seed gives the same bytes, another seed
other clips, and every seed the same multiset of lengths."""

import hashlib
import json
import os

from conftest import BENCH, ROOT
from generators import corpus_run


def _traffic():
    with open(os.path.join(BENCH, "traffic", "corpus_clips.json")) as f:
        t = json.load(f)
    t.update(clips=3, min_frames=12, max_frames=20)
    return t


def _digest(paths):
    return [hashlib.sha256(open(p, "rb").read()).hexdigest() for p in paths]


def test_same_seed_same_bytes_other_seed_other_clips(tmp_path):
    t = _traffic()
    a = corpus_run.write_corpus(t, 2147483659, ROOT, str(tmp_path / "a"))
    b = corpus_run.write_corpus(t, 2147483659, ROOT, str(tmp_path / "b"))
    c = corpus_run.write_corpus(t, 5, ROOT, str(tmp_path / "c"))
    assert _digest(a) == _digest(b)
    assert set(_digest(a)) != set(_digest(c))


def test_every_seed_has_the_same_lengths():
    t = _traffic()
    t.update(clips=8, min_frames=260, max_frames=420)
    plans = [corpus_run.clip_plan(t, seed, [355, 420]) for seed in (1, 2, 2**31 + 11)]
    lengths = [sorted(p["frames"] for p in plan) for plan in plans]
    assert lengths[0] == lengths[1] == lengths[2]
    assert lengths[0][0] == 260 and lengths[0][-1] == 420
    for plan in plans:
        for item in plan:
            assert item["start"] + item["frames"] <= [355, 420][item["source"]]
    assert [p["frames"] for p in plans[0]] != [p["frames"] for p in plans[1]]


def test_window_paths_are_hard_links_under_their_own_stems(tmp_path):
    t = _traffic()
    clips = corpus_run.write_corpus(t, 7, ROOT, str(tmp_path / "corpus"))
    paths = corpus_run.window_paths(clips, 7, str(tmp_path / "window"))
    assert len({os.path.basename(p) for p in paths}) == 7
    assert os.stat(paths[0]).st_ino == os.stat(clips[0]).st_ino
    assert os.stat(paths[3]).st_ino == os.stat(clips[0]).st_ino

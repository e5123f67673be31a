"""The corpus generator: the same seed gives the same bytes, another seed
other clips, and every seed the same multiset of lengths and sources with the
same first clip; the window's fixed
work, and what counts as its first written video."""

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


def test_every_seed_has_the_same_lengths_and_sources_and_the_same_first_clip():
    t = _traffic()
    t.update(clips=8, min_frames=260, max_frames=420)
    plans = [corpus_run.clip_plan(t, seed, [355, 420]) for seed in (1, 2, 2**31 + 11)]
    work = [sorted((p["frames"], p["source"]) for p in plan) for plan in plans]
    assert work[0] == work[1] == work[2]
    assert work[0][0] == (260, 0) and work[0][-1] == (420, 1)
    assert sorted(src for _n, src in work[0]) == [0, 0, 0, 1, 1, 1, 1, 1]
    for plan in plans:
        assert [p["clip"] for p in plan] == list(range(8))
        # the first clip is the fill: the same range of the same source for every seed
        assert plan[0] == {"clip": 0, "source": 1, "start": 0, "frames": 420}
        for item in plan:
            assert item["start"] + item["frames"] <= [355, 420][item["source"]]
    assert [p["frames"] for p in plans[0]] != [p["frames"] for p in plans[1]]
    assert [p["start"] for p in plans[0]] != [p["start"] for p in plans[1]]


def test_window_paths_are_hard_links_under_their_own_stems(tmp_path):
    t = _traffic()
    clips = corpus_run.write_corpus(t, 7, ROOT, str(tmp_path / "corpus"))
    paths = corpus_run.window_paths(clips, 7, str(tmp_path / "window"))
    assert len({os.path.basename(p) for p in paths}) == 7
    assert os.stat(paths[0]).st_ino == os.stat(clips[0]).st_ino
    assert os.stat(paths[3]).st_ino == os.stat(clips[0]).st_ino


def test_a_window_is_the_configurations_fixed_work_and_never_under_the_mix_floor():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run_seconds = bench["run_seconds"]
    seen = {}
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        if traffic["generator"] != "corpus_run":
            continue  # another generator sizes its window by its own rule
        with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
            conf = json.load(f)
        n = corpus_run.window_count(conf, traffic, run_seconds, run_seconds)
        assert n == conf["window_videos"]
        assert n % traffic["clips"] == 0  # every seed has the same frames
        floor = traffic["min_window_videos"]
        for seconds in (0.5, 1, 5, 10, 20, 39, 40, 51):
            assert corpus_run.window_count(conf, traffic, seconds, run_seconds) >= floor
        assert corpus_run.window_count(conf, traffic, run_seconds / 2, run_seconds) == max(
            floor, round(n / 2))
        assert corpus_run.window_count(conf, traffic, 2 * run_seconds, run_seconds) == 2 * n
        seen[cell["name"]] = n
    assert seen["i3d_pwc_fp32.corpus_clips"] == 16  # two passes over its 8 clips (PR 32)


def test_first_written_is_the_oldest_whole_output_of_a_window_entry(tmp_path):
    out = tmp_path / "out"
    out.mkdir()
    assert corpus_run.first_written(str(out)) is None
    (out / "clip0_rgb.npy").write_bytes(b"x")            # the warm-up's
    (out / "w00000_clip0_rgb.npy.tmp").write_bytes(b"x")  # not published yet
    assert corpus_run.first_written(str(out)) is None
    (out / "w00001_clip1_flow.npy").write_bytes(b"x")
    os.utime(out / "w00001_clip1_flow.npy", (1000.0, 1000.0))
    (out / "w00000_clip0_rgb.npy").write_bytes(b"x")
    os.utime(out / "w00000_clip0_rgb.npy", (1002.5, 1002.5))
    assert corpus_run.first_written(str(out)) == 1000.0

"""bench.py record-keeping helpers: the baseline reader and the code revision
stamped on every entry."""
# fast-registry: default tier — drives jitted extractor paths; compile-heavy for the fast pre-commit tier

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # imports nothing heavy at module scope
    return mod


def test_read_baseline_matches_headline_math(bench):
    baseline, measured = bench._read_baseline()
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        raw = json.load(f)["measured"]
    assert measured == raw
    assert baseline == float(raw["i3d_rgb_clips_per_sec"])


def test_git_rev_is_short_hex_or_none_outside_a_checkout(bench):
    """The chip tool copies the tree without ``.git``; the revision is then
    ``None``, never an exception."""
    rev = bench._git_rev()
    if rev is None:
        assert not os.path.exists(os.path.join(REPO, ".git"))
        return
    assert 6 <= len(rev) <= 16
    int(rev, 16)  # hex

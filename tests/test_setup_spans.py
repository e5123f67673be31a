"""The program's set-up on the span call (docs/observability.md "Set-up"):
one process-wide recorder, kept whatever the recording switch says, holds the
extractor's ``construct``, each checkpoint's ``load_weights`` with its
``place_wait``, every compile or cache load (``compile``, ``trace``,
``lower``, from JAX's own events) and one ``run`` a run; each run hands it
out in ``_pack_stats["setup"]``. A tiny text extractor on random weights,
its Pallas kernels in the interpreter."""

# fast-registry: page program compiles (Pallas kernels in the interpreter)

import os

import numpy as np
import pytest

import jax
from jax._src import monitoring  # the listener lists' getters are not public

from video_features_tpu.config import ExtractionConfig
from video_features_tpu.extractors import get_extractor
from video_features_tpu.extractors import laguna as laguna_extractor
from video_features_tpu.extractors import token_pages
from video_features_tpu.models import laguna as model
from video_features_tpu.utils import metrics
from video_features_tpu.weights.store import load_weights

TINY = model.LagunaConfig(vocab_size=256, hidden_size=32, intermediate_size=64,
                          num_key_value_heads=2, head_dim=16, heads_full=2, heads_sliding=2,
                          sliding_window=24, num_experts=4, num_experts_per_tok=2,
                          moe_intermediate_size=16, shared_expert_intermediate_size=16,
                          yarn_original_max_position_embeddings=64)
PAGE_TOKENS = 128
LENGTHS = (100, 37, 60, 120, 20)  # three pages a pass


@pytest.fixture
def fresh(monkeypatch):
    """A set-up recorder of this test's own (the process's has the earlier
    tests' compiles), tiny widths, random weights, recording switched off."""
    recorder = metrics.SpanRecorder(metrics.SETUP_LIMIT)
    monkeypatch.setattr(metrics, "_SETUP", recorder)
    monkeypatch.setattr(model, "PUBLISHED", TINY)
    monkeypatch.setattr(token_pages, "ATTENTION_BLOCK", 16)
    monkeypatch.setattr(laguna_extractor.ExtractLaguna, "random_layers", 2)
    monkeypatch.setattr(laguna_extractor.ExtractLaguna, "random_experts", 4)
    monkeypatch.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")
    monkeypatch.delenv("VFT_CHECKPOINT_DIR", raising=False)
    monkeypatch.delenv("VFT_METRICS", raising=False)
    return recorder


def build(tmp_path):
    return get_extractor(ExtractionConfig(
        feature_type="laguna", on_extraction="save_numpy", page_tokens=PAGE_TOKENS,
        output_path=str(tmp_path / "out"), tmp_path=str(tmp_path / "t")))


def corpus(tmp_path, lengths):
    rng = np.random.default_rng(0)
    paths = []
    for i, n in enumerate(lengths):
        ends = np.arange(12, n + 12, 12).clip(max=n).astype(np.int32)
        path = str(tmp_path / f"d{i}.tokens.npz")
        np.savez(path, ids=rng.integers(0, TINY.vocab_size, n).astype(np.int32),
                 segment_ends=ends, start_ms=np.concatenate([[0], ends[:-1]]).astype(np.int64),
                 end_ms=ends.astype(np.int64))
        paths.append(path)
    return paths


def test_setup_records_of_a_run_with_the_switch_off(tmp_path, fresh):
    """Built, then run twice with recording off: the second run's
    ``_pack_stats["setup"]`` holds the construction with its checkpoint load
    (its ``leaves`` and ``bytes_placed`` those of the tree), the compiles with
    their ``program`` and ``cache``, and the two runs, in order on
    ``time.time_ns()``; the second run, of more pages, added one record."""
    ex = build(tmp_path)
    assert ex.run(corpus(tmp_path, LENGTHS)) == len(LENGTHS)
    first = ex._pack_stats
    assert "spans" not in first and first["pages_dispatched"] == 3
    n_first = len(first["setup"]["records"])
    twice = corpus(tmp_path, LENGTHS * 2)
    assert ex.run(twice) == len(twice)
    stats = ex._pack_stats
    assert "spans" not in stats and stats["pages_dispatched"] > 3
    setup = stats["setup"]
    assert setup["clock"] == "time_ns" and setup["dropped"] == 0
    records = setup["records"]
    assert len(records) == n_first + 1  # the second run's own record, nothing per page

    by_name = {}
    for i, r in enumerate(records):
        by_name.setdefault(r["name"], []).append(i)
        assert r["end"] is not None and r["start"] <= r["end"]
    (c,) = by_name["construct"]
    assert records[c]["parent"] is None and records[c]["ids"]["model"] == "laguna"
    (w,) = by_name["load_weights"]
    load = records[w]
    assert load["parent"] == c and load["ids"]["checkpoint"] == "laguna"
    leaves = jax.tree.leaves(ex.params)
    assert load["ids"]["leaves"] == len(leaves)
    assert load["ids"]["bytes_placed"] == sum(x.nbytes for x in leaves)
    assert load["ids"]["bytes_read"] > 0 and load["ids"]["read_s"] > 0
    (p,) = by_name["place_wait"]
    assert records[p]["parent"] == w
    assert load["start"] <= records[p]["start"] <= records[p]["end"] <= load["end"]

    compiles = [records[i] for i in by_name["compile"]]
    assert compiles and all(r["ids"]["program"] and r["ids"]["cache"] in ("hit", "miss")
                            for r in compiles)
    assert any(r["parent"] is not None and records[r["parent"]]["name"] == "load_weights"
               for r in compiles)  # the checkpoint's casts and stacks
    runs = [records[i] for i in by_name["run"]]
    assert len(runs) == 2 and all(r["parent"] is None for r in runs)
    assert records[c]["end"] <= runs[0]["start"] <= runs[0]["end"] <= runs[1]["start"]


def test_compile_records_lie_inside_a_construct_or_a_run(tmp_path, fresh):
    """On the one clock, each ``compile``, ``trace`` and ``lower`` record of
    the process lies inside its ``construct`` or a ``run``, and its parent
    is the set-up span that was open on its thread."""
    ex = build(tmp_path)
    assert ex.run(corpus(tmp_path, LENGTHS)) == len(LENGTHS)
    records = ex._pack_stats["setup"]["records"]
    outer = [r for r in records if r["name"] in ("construct", "run")]
    compiling = [r for r in records if r["name"] in ("compile", "trace", "lower")]
    assert {r["name"] for r in compiling} == {"compile", "trace", "lower"}
    for r in compiling:
        assert any(o["start"] <= r["start"] and r["end"] <= o["end"] for o in outer), r
        assert r["parent"] is not None
        assert records[r["parent"]]["name"] in ("construct", "load_weights", "run")


def test_listeners_registered_once(tmp_path, fresh):
    """Two extractors built in one process: each of the set-up recorder's
    ``jax.monitoring`` listeners is registered once, and each construction
    is a ``construct`` record."""
    build(tmp_path)
    build(tmp_path)
    assert monitoring.get_event_time_span_listeners().count(metrics._on_time_span) == 1
    assert monitoring.get_event_duration_listeners().count(metrics._on_duration) == 1
    assert monitoring.get_event_listeners().count(metrics._on_event) == 1
    assert monitoring.get_scalar_listeners().count(metrics._on_scalar) == 1
    assert [r["name"] for r in fresh.records].count("construct") == 2


def test_load_weights_divides_read_host_and_place_wait(fresh):
    """``load_weights``: the read's seconds and bytes summed onto its ids,
    one ``place_wait`` child, the placed tree's ``leaves`` and
    ``bytes_placed``; and the stage report's set-up line."""
    tree = {"a": np.ones((4, 8), np.float32), "b": {"c": np.zeros(3, np.float32)}}
    with metrics.setup_span("construct", model="t"):
        with load_weights("t") as load:
            host = load.read(lambda: tree)
            read = load.leaves({"x": np.ones(5, np.float32)}.__getitem__)
            assert read("x").shape == (5,)
            placed = load.place(jax.device_put(host))
    names = [r["name"] for r in fresh.records]
    assert names[:3] == ["construct", "load_weights", "place_wait"]
    ids = fresh.records[1]["ids"]
    assert ids["bytes_read"] == 4 * 8 * 4 + 3 * 4 + 5 * 4 and ids["read_s"] >= 0
    assert ids["leaves"] == 2 and ids["bytes_placed"] == 4 * 8 * 4 + 3 * 4
    assert jax.tree.leaves(placed)[0].shape == (4, 8)
    line = metrics.setup_report(fresh.export())
    assert line.startswith("set-up: construct ") and "weights 1 checkpoint(s)" in line
    assert "0.00 GB placed" in line and "compiled" in line
    assert metrics.setup_report({"records": []}) == "set-up: no construction recorded"


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(pytest.main([os.path.abspath(__file__), "-q"]))

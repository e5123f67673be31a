"""``lfm2_24b_a2b_bf16``'s benchmark pieces on the CPU, by hand: the configuration
as the harness reads it, the operation counter against a count made another
way, what the reference reads of the program, the two new readers on a
hand-built reduction (``test_laguna_cpu.py``'s planes, with this model's
scopes), and the readings script's sweep with both planted faults at a size a
test can hold."""

import glob
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from flops import lfm2 as counter
from layer_metrics import (_laguna, _spans, attention_pct, moe_dispatch_pct, short_conv_pct,
                           short_conv_roofline)
from reference import lfm2 as ref
from run import find_cell, load_json, metrics_for
from test_laguna_cpu import PEAKS, build  # the hand-built trace

CELL = "lfm2_24b_a2b_bf16.corpus_transcripts_64k"
SCOPES = {1: "jit(paged)/paged/lfm2/L3/attn/short_conv/core/multiply",
          2: "jit(paged)/paged/lfm2/L3/attn/short_conv/proj/dot_general",
          3: "jit(paged)/paged/lfm2/L3/moe/while/body/moe/experts/gmm",
          4: "jit(paged)/paged/lfm2/L2/attn/core/segment_attention_full",
          5: "jit(paged)/paged/lfm2/L3/moe/while/body/moe/combine/moe_combine",
          6: "jit(paged)/paged/lfm2/L2/moe/route/dot_general",
          9: ""}


def test_the_harness_reads_the_configuration_and_the_cell():
    bench = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cell = find_cell(bench, CELL)
    conf = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert cell["chips"] == 1 and conf["reference"] == conf["flops"] == "lfm2"
    assert set(conf["limits"]) == {"gap." + k for k in ref.FEATURE_KEYS}
    assert conf["extraction"]["page_tokens"] == traffic["equal_work"]["page_tokens"]
    assert conf["window_videos"] % traffic["documents"] == 0  # whole passes: the counter is exact
    assert traffic["vocab_size"] == ref.PUBLISHED["vocab_size"]
    assert "fixed_weights" not in conf  # every leaf from --seed
    names = {m["name"] for m in metrics_for(bench["per_layer"], CELL)}
    assert {"short_conv_pct", "short_conv_roofline"} <= names
    assert all(m["moves"] == "videos_per_s" for m in bench["per_layer"]
               if m["name"] in ("short_conv_pct", "short_conv_roofline"))


def test_counter_against_a_count_made_another_way():
    """Attention pairs by enumeration, the operations per token written out as
    one sum over the published matrices, the core's bytes as its operands."""
    for n in (1, 7, 511, 512, 513):
        assert counter.attention_pairs(n) == sum(i + 1 for i in range(n))
    assert counter.attention_core_flops([3, 5], 2) == 4 * 64 * 32 * (6 + 15)
    assert counter.attention_core_flops([3, 5], 3) == 0  # a conv layer has no pairs
    assert counter.CONV_LAYERS == (0, 1, 3, 4, 5)
    assert counter.core_bytes(1) == 16_384 and counter.core_flops(1) == 7 * 2048
    ops = 0
    for layer in range(6):
        if layer == 2:
            ops += 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
        else:
            ops += 2 * (2048 * 6144 + 2048 * 2048) + 7 * 2048
        ops += 6 * 2048 * 11776 if layer < 2 else 2 * 2048 * 64 + 4 * 6 * 2048 * 1536
    assert counter.product_flops_per_token() == ops
    docs = counter.document_lengths()
    pairs = sum(n * (n + 1) // 2 for n in docs)
    assert counter.flops_per_row() == pytest.approx(ops + 4 * 64 * 32 * pairs / sum(docs))
    # the parameters the configuration's file states: every matrix once
    spec = ref.weight_specs()["lfm2"]
    assert sum(int(np.prod(s)) for s in spec.values()) == 2_789_794_176
    # a full page's least core time is memory's, a third of a millisecond a layer
    assert counter.core_bytes(16384) / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == pytest.approx(
        0.33e-3, rel=0.01)


def test_every_traffic_counted_by_this_counter_has_its_lengths():
    bench = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    configs = {os.path.basename(f)[:-5] for f in glob.glob(os.path.join(BENCH, "configs", "*.json"))
               if json.load(open(f)).get("flops") == "lfm2"}
    cells = [w for w in bench["workloads"] if w["config"] in configs]
    assert [w["name"] for w in cells] == [CELL]
    for w in cells:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        assert sorted(counter.document_lengths(path)) == sorted(counter.document_lengths())


def test_the_reference_reads_nothing_of_the_programs_models():
    source = open(ref.__file__).read()
    assert "video_features_tpu.models" not in source and "video_features_tpu.ops" not in source
    assert [l for l in source.splitlines() if "video_features_tpu" in l and "import" in l] == [
        "from video_features_tpu.config import FEATURE_TYPES"]


@pytest.fixture
def built(monkeypatch):
    space, trace, stats = build()
    plane = space["devices"]["/device:TPU:0"]
    plane["metadata"] = {m: (f"%op.{m}", s) for m, s in SCOPES.items()}
    monkeypatch.setattr(_spans, "load", lambda path=None: space)
    monkeypatch.setattr(_laguna, "load", lambda path=None: space)
    return trace, stats, {"device_kind": "TPU v5 lite", "peaks": PEAKS, "chips": 1}


def test_the_new_readers_on_a_planted_trace(built):
    """Four executions of 100 ms, the first and last cut by the slice; in
    each: the conv core 30 ms, the conv's in-projection 10, the experts 20,
    attention's core 5, the combine 5, the route 10."""
    trace, stats, facts = built
    bandwidth = PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    # whole executions are the 2nd and 3rd: pages 11 ([4096, 1024]) and 12 ([8192])
    nbytes = 5 * 16_384 * (4096 + 1024 + 8192)
    assert short_conv_roofline.read(trace, stats, facts) == pytest.approx(
        100 * nbytes / bandwidth / 0.060)
    ops_page, bytes_page = short_conv_roofline.work_of_page([8192])
    assert (ops_page, bytes_page) == (5 * 7 * 2048 * 8192, 5 * 16_384 * 8192)
    assert bytes_page / bandwidth > 10 * ops_page / PEAKS["TPU v5 lite"]["bf16_flops_per_s"]
    assert short_conv_pct.read(trace, stats, facts) == pytest.approx(40.0)  # core + proj
    assert attention_pct.read(trace, stats, facts) == pytest.approx(45.0)  # and attention's core
    assert moe_dispatch_pct.read(trace, stats, facts) == pytest.approx(15.0)  # combine + route


def test_a_program_without_the_scopes_reads_nothing(monkeypatch):
    """The parent's program has no ``short_conv`` scope: both readers leave
    their metric out and do not raise."""
    space, trace, stats = build()
    plane = space["devices"]["/device:TPU:0"]
    plane["metadata"] = {m: (n, "jit(paged)/i3d/page/x" if s else "") for m, (n, s) in plane["metadata"].items()}
    for r in stats["spans"]["records"]:
        r["ids"].pop("documents", None)
    monkeypatch.setattr(_spans, "load", lambda path=None: space)
    monkeypatch.setattr(_laguna, "load", lambda path=None: space)
    facts = {"device_kind": "TPU v5 lite", "peaks": PEAKS, "chips": 1}
    for reader in (short_conv_roofline, short_conv_pct):
        assert reader.read(trace, stats, facts) is None
        assert reader.read(dict(trace, path=None), {}, facts) is None


def test_the_sweep_reading_dry(monkeypatch, capsys):
    """``lfm2_readings.py sweep`` off the chip at tiny widths (the
    interpreter): two pages, the program against the rounded reference, both
    planted faults over it, all-zero features at 1."""
    import lfm2_readings as readings
    from video_features_tpu.models import lfm2 as model

    widths = dict(vocab_size=512, hidden_size=64, num_hidden_layers=6, intermediate_size=96,
                  num_attention_heads=4, num_key_value_heads=2, num_experts=8,
                  moe_intermediate_size=32)
    rng = np.random.default_rng(1)

    def document(n):
        return rng.integers(0, 512, n).astype(np.int32), np.append(np.arange(12, n, 12), n).astype(np.int32)

    pages = [[document(128)], [document(50), document(40), document(30)]]
    monkeypatch.setattr(readings, "SWEEP", (model.Lfm2Config(**widths), dict(ref.PUBLISHED, **widths),
                                            128, pages))
    readings.sweep(CELL, [7])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["pages"] == [[128], [50, 40, 30]] and len(line["program"]["medians"]) == 4
    assert line["program"]["gap"] < 0.05
    assert line["restart"]["gap"] > 5 * line["program"]["gap"]
    assert line["restart"]["medians"][:2] == line["program"]["medians"][:2]  # a page's first document
    # the harness's bias (std 0.05) moves a weight by a tenth of a score: farther
    # than bfloat16's rounding at these widths, by less than the missed restart
    assert line["bias"]["gap"] > 2 * line["program"]["gap"]
    assert line["zeros"]["gap"] == pytest.approx(1.0)

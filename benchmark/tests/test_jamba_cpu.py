"""``jamba2_3b_bf16``'s benchmark pieces on the CPU, by hand: the operation
counter against a count made another way, the reference's scan against the
equations worked in numpy and its two planted faults, what the reference reads
of the program, the two new readers on a hand-built reduction
(``test_laguna_cpu.py``'s planes, with this model's scopes), and the readings
script's ``scan`` and planted faults at a size a test can hold."""

import glob
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from flops import jamba as counter
from layer_metrics import _laguna, _spans, attention_pct, mamba_pct, ssm_scan_roofline
from reference import jamba as ref
from run import load_json
from test_laguna_cpu import PEAKS, build  # the hand-built trace

SCOPES = {1: "jit(paged)/paged/jamba/L1/attn/mamba/scan/selective_scan",
          2: "jit(paged)/paged/jamba/L1/attn/mamba/proj/dot_general",
          3: "jit(paged)/paged/jamba/L1/mlp/dot_general",
          4: "jit(paged)/paged/jamba/L7/attn/core/segment_attention_full",
          5: "jit(paged)/paged/jamba/L1/attn/mamba/conv/multiply",
          6: "jit(paged)/paged/jamba/pool/dot_general",
          9: ""}


def test_counter_against_a_count_made_another_way():
    """Attention pairs by enumeration, the products per token written out as
    one sum over the published matrices, the scan's bytes as its operands."""
    for n in (1, 7, 511, 512, 513):
        assert counter.attention_pairs(n) == sum(i + 1 for i in range(n))
    assert counter.attention_core_flops([3, 5], 7) == 4 * 128 * 20 * (6 + 15)
    assert counter.attention_core_flops([3, 5], 0) == 0  # a Mamba layer has no pairs
    assert counter.MAMBA_LAYERS == tuple(l for l in range(28) if l not in (7, 21))
    assert counter.scan_bytes(1) == 51_328 and counter.scan_flops(1) == 7 * 81_920
    matrices = 0
    for layer in range(28):
        if layer in (7, 21):
            matrices += 2 * 2560 * 2560 + 2 * 2560 * 128
        else:
            matrices += 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
        matrices += 3 * 2560 * 8192
    assert counter.product_flops_per_token() == 2 * matrices
    docs = counter.document_lengths()
    pairs = sum(n * (n + 1) // 2 for n in docs)
    assert counter.flops_per_row() == pytest.approx(
        counter.product_flops_per_token() + 2 * 4 * 128 * 20 * pairs / sum(docs))
    # the parameters the configuration's file states: every matrix once
    spec = ref.weight_specs()["jamba"]
    assert sum(int(np.prod(s)) for s in spec.values()) == 3_029_337_472
    conf = load_json(BENCH, "configs", "jamba2_3b_bf16.json")
    assert "3,029.3 M" in conf["parameters"] and "6.06 GB" in conf["parameters"]
    # a full page's least scan time is memory's, about a millisecond a layer
    assert counter.scan_bytes(16384) / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == pytest.approx(
        1.03e-3, rel=0.01)


def test_every_traffic_counted_by_this_counter_has_its_lengths():
    bench = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    configs = {os.path.basename(f)[:-5] for f in glob.glob(os.path.join(BENCH, "configs", "*.json"))
               if json.load(open(f)).get("flops") == "jamba"}
    cells = [w for w in bench["workloads"] if w["config"] in configs]
    assert [w["name"] for w in cells] == ["jamba2_3b_bf16.corpus_transcripts_64k"]
    for w in cells:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        assert sorted(counter.document_lengths(path)) == sorted(counter.document_lengths())
    traffic = load_json(BENCH, "traffic", "corpus_transcripts_64k.json")
    assert traffic["vocab_size"] == ref.PUBLISHED["vocab_size"]
    conf = load_json(BENCH, "configs", "jamba2_3b_bf16.json")
    assert conf["extraction"]["page_tokens"] == traffic["equal_work"]["page_tokens"]
    assert conf["window_videos"] % traffic["documents"] == 0  # whole passes: the counter is exact


def test_the_reference_reads_nothing_of_the_programs_models():
    source = open(ref.__file__).read()
    assert "video_features_tpu.models" not in source and "video_features_tpu.ops" not in source
    assert [l for l in source.splitlines() if "video_features_tpu" in l and "import" in l] == [
        "from video_features_tpu.config import FEATURE_TYPES"]


def test_the_scan_by_hand_and_its_faults(monkeypatch):
    """The reference's step 5 on a few tokens and channels against the
    equations worked with numpy; ``carry`` drops the state at every
    ``FAULT_CHUNK``-th token; ``reset`` is the caller's (the state it starts
    from)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    n, channels, state = 6, 3, 4
    u, dt = rng.standard_normal((2, n, channels)).astype(np.float32)
    dt = np.log1p(np.exp(dt))
    b, c = rng.standard_normal((2, n, state)).astype(np.float32)
    a = -rng.uniform(0.5, 2.0, (channels, state)).astype(np.float32)
    d = rng.uniform(0.8, 1.2, channels).astype(np.float32)
    h, want = np.zeros((channels, state)), []
    for t in range(n):
        h = np.exp(dt[t][:, None] * a) * h + np.outer(dt[t] * u[t], b[t])
        want.append(h @ c[t] + d * u[t])
    args = [jnp.asarray(x) for x in (u, dt, b, c, a, d)]
    zero = jnp.zeros((channels, state), jnp.float32)
    got, last = ref.selective_scan(*args, zero)
    np.testing.assert_allclose(np.asarray(got), np.stack(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(last), h, atol=1e-5)
    monkeypatch.setattr(ref, "FAULT_CHUNK", 3)
    dropped, _ = ref.selective_scan(*args, zero, fault="carry")
    np.testing.assert_allclose(np.asarray(dropped)[:3], np.stack(want)[:3], atol=1e-5)
    assert np.abs(np.asarray(dropped)[3] - want[3]).max() > 1e-3
    carried, _ = ref.selective_scan(*args, jnp.asarray(h, jnp.float32))
    assert np.abs(np.asarray(carried)[0] - want[0]).max() > 1e-3


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
    each: the scan 30 ms, the in-projection 10, the dense unit 20, attention's
    core 5, the convolution 5, the pool 10."""
    trace, stats, facts = built
    bandwidth = PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    # whole executions are the 2nd and 3rd: pages 11 ([4096, 1024]) and 12 ([8192])
    nbytes = 26 * 51_328 * (4096 + 1024 + 8192)
    assert ssm_scan_roofline.read(trace, stats, facts) == pytest.approx(100 * nbytes / bandwidth / 0.060)
    ops_page, bytes_page = ssm_scan_roofline.work_of_page([8192])
    assert (ops_page, bytes_page) == (26 * 7 * 81_920 * 8192, 26 * 51_328 * 8192)
    assert bytes_page / bandwidth > 10 * ops_page / PEAKS["TPU v5 lite"]["bf16_flops_per_s"]
    assert mamba_pct.read(trace, stats, facts) == pytest.approx(45.0)  # scan + proj + conv
    assert attention_pct.read(trace, stats, facts) == pytest.approx(50.0)  # and attention's core


def test_a_program_without_the_scopes_reads_nothing(monkeypatch):
    space, trace, stats = build()
    plane = space["devices"]["/device:TPU:0"]
    plane["metadata"] = {m: (n, "jit(paged)/i3d/page/x" if s else "") for m, (n, s) in plane["metadata"].items()}
    for r in stats["spans"]["records"]:
        r["ids"].pop("documents", None)
    monkeypatch.setattr(_spans, "load", lambda path=None: space)
    monkeypatch.setattr(_laguna, "load", lambda path=None: space)
    facts = {"device_kind": "TPU v5 lite", "peaks": PEAKS, "chips": 1}
    for reader in (ssm_scan_roofline, mamba_pct):
        assert reader.read(trace, stats, facts) is None
        assert reader.read(dict(trace, path=None), {}, facts) is None


def test_the_scan_reading_dry_and_the_planted_faults(monkeypatch, capsys):
    """``jamba_readings.py scan`` off the chip at a small page (the
    interpreter), and the ``pos`` planes the two faults give the scan."""
    import jax.numpy as jnp

    import jamba_readings as readings
    from video_features_tpu.models import jamba as model

    monkeypatch.setattr(readings, "SCAN_TOKENS", 256)
    monkeypatch.setattr(readings, "SCAN_WIDTH", 512)
    assert readings.scan(readings.CELL, 7) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["inside"] and out["mixed"]["documents"] == [64, 1, 32, 125]

    pos = jnp.asarray(np.concatenate([np.arange(300), np.arange(200), np.zeros(12)]).astype(np.int32))
    reset = np.asarray(readings.fault_pos("reset", pos))
    assert reset[0] == 0 and (reset[1:] > 0).all()
    carry = np.asarray(readings.fault_pos("carry", pos))
    assert list(np.flatnonzero(carry == 0)) == [0, 256, 300, 500] + list(range(501, 512))
    real = model.selective_scan
    with readings.planted("carry"):
        assert model.selective_scan is not real
    assert model.selective_scan is real
    with pytest.raises(SystemExit):
        with readings.planted("delta"):
            pass


def test_the_sweep_reading_dry(monkeypatch, capsys):
    """``jamba_readings.py sweep`` off the chip at tiny widths (the
    interpreter): two pages, the program against the rounded reference, the
    missed restart far over it, all-zero features at 1, a cut after one layer."""
    import jamba_readings as readings
    from video_features_tpu.models import jamba as model

    widths = dict(vocab_size=512, hidden_size=64, num_hidden_layers=4, intermediate_size=96,
                  attn_layer_period=4, attn_layer_offset=2, num_attention_heads=4,
                  num_key_value_heads=1, mamba_d_state=16, mamba_dt_rank=8)
    rng = np.random.default_rng(1)

    def document(n):
        return rng.integers(0, 512, n).astype(np.int32), np.append(np.arange(12, n, 12), n).astype(np.int32)

    pages = [[document(128)], [document(50), document(40), document(30)]]
    monkeypatch.setattr(readings, "SWEEP", (model.JambaConfig(**widths), dict(ref.PUBLISHED, **widths),
                                            128, pages))
    monkeypatch.setattr(readings, "DEPTHS", (1,))
    readings.sweep(readings.CELL, [7])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["pages"] == [[128], [50, 40, 30]] and len(line["program"]["medians"]) == 4
    assert line["program"]["gap"] < 0.05 and line["depths"]["1"] < line["program"]["gap"]
    assert line["reset"]["gap"] > 5 * line["program"]["gap"]
    assert line["zeros"]["gap"] == pytest.approx(1.0)

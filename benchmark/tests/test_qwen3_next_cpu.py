"""``qwen3_next_80b_bf16``'s benchmark pieces on the CPU, by hand: the operation
counter against a count made another way, the reference's share rule and what
it reads of the program, the two new readers on a hand-built reduction
(``test_laguna_cpu.py``'s planes, with this model's scopes), and the readings
script's ``core`` and planted faults at a size a test can hold."""

import glob
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from flops import qwen3_next as counter
from layer_metrics import (_laguna, _spans, attention_pct, expert_load_max_over_mean,
                           gdn_core_roofline, gdn_pct, moe_dispatch_pct)
from reference import qwen3_next as ref
from run import load_json
from test_laguna_cpu import PEAKS, build  # the hand-built trace

SCOPES = {1: "jit(paged)/paged/qwen3_next/L1/attn/gdn/core/gated_delta_chunk",
          2: "jit(paged)/paged/qwen3_next/L1/attn/gdn/proj/dot_general",
          3: "jit(paged)/paged/qwen3_next/L1/moe/experts/gmm",
          4: "jit(paged)/paged/qwen3_next/L1/moe/dispatch/sort",
          5: "jit(paged)/paged/qwen3_next/L3/attn/core/segment_attention_full",
          6: "jit(paged)/paged/qwen3_next/L1/attn/gdn/conv/multiply",
          9: ""}


def test_counter_against_a_count_made_another_way():
    """Attention pairs by enumeration at a small size, the products per token
    written out as one sum over the published matrices, the delta rule as its
    three 128 x 128 products a token and value head."""
    for n in (1, 7, 511, 512, 513):
        assert counter.attention_pairs(n) == sum(i + 1 for i in range(n))
    assert counter.attention_core_flops([3, 5], 3) == 4 * 256 * 16 * (6 + 15)
    assert counter.attention_core_flops([3, 5], 0) == 0  # a linear layer has no pairs
    assert counter.delta_rule_flops(1) == 3 * 2 * 128 * 128 * 32 == 3_145_728
    assert counter.delta_rule_bytes(1) == 24_832
    matrices = 0
    for layer in range(4):
        if layer == 3:
            matrices += 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
        else:
            matrices += 2048 * 12288 + 2048 * 64 + 4096 * 2048
        matrices += 2048 * 512 + 3 * 2048 * 512 + 2048 + 2.5 * 3 * 2048 * 512  # router, shared, its gate, 2.5 routed
    assert counter.product_flops_per_token() == pytest.approx(2 * matrices + 3 * 3_145_728)
    docs = counter.document_lengths()
    pairs = sum(n * (n + 1) // 2 for n in docs)
    assert counter.flops_per_row() == pytest.approx(
        counter.product_flops_per_token() + 4 * 256 * 16 * pairs / sum(docs))
    assert counter.flops_per_row() == pytest.approx(0.36256e9 + 0.07711e9, rel=1e-4)  # 440 MFLOP a real token
    assert counter.expert_flops(320) == 320 * 3 * 2 * 2048 * 512
    # the parameters the configuration's file states: every matrix once
    spec = ref.weight_specs()["qwen3_next"]
    assert sum(int(np.prod(s)) for s in spec.values()) == 2_067_000_384
    conf = load_json(BENCH, "configs", "qwen3_next_80b_bf16.json")
    assert "2,067 M" in conf["parameters"] and "4.13 GB" in conf["parameters"]


def test_every_traffic_counted_by_this_counter_has_its_lengths_and_the_cut_is_the_references():
    assert counter.LAYERS == ref.LAYERS == (0, 1, 2, 3)
    assert counter.EXPERTS_HELD == len(ref.EXPERTS) == 128 and ref.EXPERTS == tuple(range(128))
    assert [counter.is_full(l) for l in counter.LAYERS] == [ref.is_full(ref.PUBLISHED, l) for l in ref.LAYERS]
    bench = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    configs = {os.path.basename(f)[:-5] for f in glob.glob(os.path.join(BENCH, "configs", "*.json"))
               if json.load(open(f)).get("flops") == "qwen3_next"}
    cells = [w for w in bench["workloads"] if w["config"] in configs]
    assert [w["name"] for w in cells] == ["qwen3_next_80b_bf16.corpus_transcripts"]
    for w in cells:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        assert sorted(counter.document_lengths(path)) == sorted(counter.document_lengths())
    traffic = load_json(BENCH, "traffic", "corpus_transcripts.json")
    assert traffic["vocab_size"] <= ref.PUBLISHED["vocab_size"]
    conf = load_json(BENCH, "configs", "qwen3_next_80b_bf16.json")
    assert conf["extraction"]["page_tokens"] == traffic["equal_work"]["page_tokens"]
    assert conf["window_videos"] % traffic["documents"] == 0  # whole passes: the counter is exact


def test_the_share_rule_and_what_the_reference_reads_of_the_program():
    """The router keeps all its outputs; the held experts' part alone is
    summed; the shared expert is gated token by token; the reference's source
    names no model or op of the program."""
    import jax.numpy as jnp

    cfg = dict(ref.PUBLISHED, hidden_size=16, num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=8)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in ((8, 16, 8), (8, 16, 8), (8, 8, 16))]
    whole = ref.routed_part(cfg, h, router, *mats, jnp.arange(8))
    parts = [ref.routed_part(cfg, h, router, *(m[ids] for m in mats), jnp.asarray(ids))
             for ids in (np.array([0, 1, 2]), np.array([3, 4, 5, 6, 7]))]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]), np.asarray(whole), atol=1e-5)
    weights, ids = ref.routing(cfg, h, router)
    logits = np.asarray(h @ router)
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), 1), np.sort(np.argsort(-probs, 1)[:, :2], 1))
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-6)
    source = open(ref.__file__).read()
    assert "video_features_tpu.models" not in source and "video_features_tpu.ops" not in source
    assert [l for l in source.splitlines() if "video_features_tpu" in l and "import" in l] == [
        "from video_features_tpu.config import FEATURE_TYPES"]


def test_the_token_recurrence_by_hand():
    """The reference's step 5 on three tokens and one head against the
    equations worked with numpy, and its two planted faults."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    q, k, v = rng.standard_normal((3, 3, 1, 4)).astype(np.float32)
    g = -rng.uniform(0.1, 1.0, (3, 1)).astype(np.float32)
    beta = rng.uniform(0.2, 0.9, (3, 1)).astype(np.float32)
    state, want = np.zeros((4, 4)), []
    for t in range(3):
        state = state * np.exp(g[t, 0])
        delta = beta[t, 0] * (v[t, 0] - state.T @ k[t, 0])
        state = state + np.outer(k[t, 0], delta)
        want.append(state.T @ q[t, 0])
    got = np.asarray(ref.delta_rule(*(jnp.asarray(a) for a in (q, k, v, g, beta))))
    np.testing.assert_allclose(got[:, 0], np.stack(want), atol=1e-5)
    no_delta = np.asarray(ref.delta_rule(*(jnp.asarray(a) for a in (q, k, v, g, beta)), fault="delta"))
    assert np.abs(no_delta - got).max() > 1e-2
    np.testing.assert_allclose(no_delta[0], got[0], atol=1e-6)  # the first token has nothing to correct


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
    each: the delta rule 30 ms, projections 10, experts 20, dispatch 5, full
    attention's core 5, the convolution 10."""
    trace, stats, facts = built
    bandwidth = PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    # whole executions are the 2nd and 3rd: pages 11 ([4096, 1024]) and 12 ([8192])
    nbytes = 3 * 24_832 * (4096 + 1024 + 8192)
    assert gdn_core_roofline.read(trace, stats, facts) == pytest.approx(100 * nbytes / bandwidth / 0.060)
    ops_page, bytes_page = gdn_core_roofline.work_of_page([8192])
    assert (ops_page, bytes_page) == (3 * 3_145_728 * 8192, 3 * 24_832 * 8192)
    assert bytes_page / bandwidth > 1.8 * ops_page / PEAKS["TPU v5 lite"]["bf16_flops_per_s"]  # memory bounds it
    assert gdn_pct.read(trace, stats, facts) == pytest.approx(50.0)  # core + proj + conv
    assert attention_pct.read(trace, stats, facts) == pytest.approx(55.0)  # and the full layer's core
    assert moe_dispatch_pct.read(trace, stats, facts) == pytest.approx(5.0)
    assert expert_load_max_over_mean.read(trace, stats, facts) == pytest.approx(1.5)


def test_a_program_without_the_scopes_reads_nothing(monkeypatch):
    space, trace, stats = build()
    plane = space["devices"]["/device:TPU:0"]
    plane["metadata"] = {m: (n, "jit(paged)/i3d/page/x" if s else "") for m, (n, s) in plane["metadata"].items()}
    for r in stats["spans"]["records"]:
        r["ids"].pop("documents", None)
    monkeypatch.setattr(_spans, "load", lambda path=None: space)
    monkeypatch.setattr(_laguna, "load", lambda path=None: space)
    facts = {"device_kind": "TPU v5 lite", "peaks": PEAKS, "chips": 1}
    for reader in (gdn_core_roofline, gdn_pct):
        assert reader.read(trace, stats, facts) is None
        assert reader.read(dict(trace, path=None), {}, facts) is None


def test_the_core_reading_dry_and_the_planted_faults_differ(monkeypatch, capsys):
    """``qwen3_next_readings.py core`` off the chip at a small page (the
    interpreter), and the two faults it plants in the program against the
    kernel they alter."""
    import jax.numpy as jnp

    import qwen3_next_readings as readings
    from video_features_tpu.models import qwen3_next as model
    from video_features_tpu.ops import gated_delta as op

    monkeypatch.setattr(readings, "CORE_TOKENS", 256)
    assert readings.core(readings.CELL, 7) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["inside"] and out["mixed"]["documents"] == [64, 1, 32, 125]

    rng = np.random.default_rng(0)
    tokens, kh, vh, d = 128, 2, 4, 16
    q, k = (rng.standard_normal((tokens, kh, d)).astype(np.float32) * 1.5 for _ in range(2))
    v = rng.standard_normal((tokens, vh * d)).astype(np.float32)
    g = -np.log1p(np.exp(rng.standard_normal((tokens, vh)))).astype(np.float32) * 0.3
    beta = (1 / (1 + np.exp(-rng.standard_normal((tokens, vh))))).astype(np.float32)
    doc = np.repeat(np.arange(2), (100, 28)).astype(np.int32)
    qkv = np.concatenate([q.reshape(tokens, -1), k.reshape(tokens, -1), v], axis=1)
    args = [jnp.asarray(a) for a in (qkv, g, beta, doc)]
    sound = np.asarray(op.gated_delta(*args, key_heads=kh, chunk=32, interpret=True))
    with readings.planted("carry"):
        dropped = np.asarray(op.gated_delta.__wrapped__(*args, key_heads=kh, chunk=32, interpret=True))
    assert np.abs(dropped[:32] - sound[:32]).max() < 1e-6   # the first chunk carries nothing
    assert np.abs(dropped[32:100] - sound[32:100]).max() > 1e-2
    with readings.planted("delta"):
        assert model.gated_delta is readings.decayed_linear_attention
    assert model.gated_delta is op.gated_delta
    qu = q / np.sqrt(np.sum(q * q, -1, keepdims=True) + 1e-6) / 4
    ku = k / np.sqrt(np.sum(k * k, -1, keepdims=True) + 1e-6)
    want = np.concatenate([np.asarray(ref.delta_rule(*(jnp.asarray(a) for a in (
        np.repeat(qu[sl], 2, 1), np.repeat(ku[sl], 2, 1), v[sl].reshape(-1, vh, d), g[sl], beta[sl])),
        fault="delta")) for sl in (slice(0, 100), slice(100, 128))]).reshape(tokens, -1)
    got = np.asarray(readings.decayed_linear_attention(*args, key_heads=kh, chunk=32))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(got - sound).max() > 1e-2

"""``sarvam_105b_bf16``'s benchmark pieces on the CPU, by hand: the operation
counter against a count made another way, the reference's share rule and what
it reads of the program, and the two new readers on a hand-built reduction
(``test_laguna_cpu.py``'s planes, with this model's scopes)."""

import glob
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from flops import sarvam as counter
from layer_metrics import (_laguna, _spans, attention_pct, expert_load_max_over_mean,
                           mla_core_roofline, mla_latent_pct, moe_dispatch_pct)
from reference import sarvam as ref
from run import load_json
from test_laguna_cpu import MS, PEAKS, T0, build  # noqa: F401 — the hand-built trace

SCOPES = {1: "jit(paged)/paged/sarvam/L1/attn/core/segment_attention_latent",
          2: "jit(paged)/paged/sarvam/L1/attn/latent/dot_general",
          3: "jit(paged)/paged/sarvam/L1/moe/experts/gmm",
          4: "jit(paged)/paged/sarvam/L1/moe/dispatch/sort",
          5: "jit(paged)/paged/sarvam/L1/attn/up/dot_general",
          6: "jit(paged)/paged/sarvam/L1/attn/rope/multiply",
          9: ""}


def test_counter_against_a_count_made_another_way():
    """Attention pairs by enumeration at a small size, and the products per
    token written out as one sum over the published matrices."""
    for n in (1, 7, 511, 512, 513):
        assert counter.attention_pairs(n) == sum(i + 1 for i in range(n))
    # a pair costs a head 2 x 192 for its score and 2 x 128 for the weighted sum
    assert counter.attention_core_flops([3, 5], 2) == (2 * 192 + 2 * 128) * 64 * (6 + 15)
    matrices = 0
    for layer in range(5):
        matrices += 4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + 64 * 128 * 4096
        if layer == 0:
            matrices += 3 * 4096 * 16384
        else:
            matrices += 4096 * 128 + 3 * 4096 * 2048 + 1.0 * 3 * 4096 * 2048  # router, shared, 1 routed
    assert counter.product_flops_per_token() == pytest.approx(2 * matrices)
    assert counter.flops_per_row() == pytest.approx(1.75584e9 + 0.96387e9, rel=1e-4)
    assert counter.expert_flops(1024) == 1024 * 3 * 2 * 4096 * 2048
    # the parameters the configuration's file states: every matrix once
    spec = ref.weight_specs()["sarvam"]
    assert sum(int(np.prod(s)) for s in spec.values()) == 3_461_659_648


def test_every_traffic_counted_by_this_counter_has_its_lengths_and_the_cut_is_the_references():
    assert counter.LAYERS == ref.LAYERS == (0, 1, 2, 3, 4)
    assert counter.EXPERTS_HELD == len(ref.EXPERTS) == 16 and ref.EXPERTS == tuple(range(16))
    bench = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    configs = {os.path.basename(f)[:-5] for f in glob.glob(os.path.join(BENCH, "configs", "*.json"))
               if json.load(open(f)).get("flops") == "sarvam"}
    cells = [w for w in bench["workloads"] if w["config"] in configs]
    assert [w["name"] for w in cells] == ["sarvam_105b_bf16.corpus_transcripts"]
    for w in cells:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        assert sorted(counter.document_lengths(path)) == sorted(counter.document_lengths())
    # the traffic's ids fit the whole embedding the share keeps
    traffic = load_json(BENCH, "traffic", "corpus_transcripts.json")
    assert traffic["vocab_size"] <= ref.PUBLISHED["vocab_size"]
    conf = load_json(BENCH, "configs", "sarvam_105b_bf16.json")
    assert conf["extraction"]["page_tokens"] == traffic["equal_work"]["page_tokens"]
    assert conf["window_videos"] % traffic["documents"] == 0  # whole passes: the counter is exact


def test_the_share_rule_and_what_the_reference_reads_of_the_program():
    """The router keeps all 128 outputs and its bias; the held experts'
    part alone is summed; the reference's source names no model or op of the
    program."""
    import jax.numpy as jnp

    cfg = dict(ref.PUBLISHED, hidden_size=16, num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=8)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8) * 0.5, jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in ((8, 16, 8), (8, 16, 8), (8, 8, 16))]
    whole = ref.routed_part(cfg, h, router, bias, *mats, jnp.arange(8))
    parts = [ref.routed_part(cfg, h, router, bias, *(m[ids] for m in mats), jnp.asarray(ids))
             for ids in (np.array([0, 1, 2]), np.array([3, 4, 5, 6, 7]))]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]), np.asarray(whole), atol=1e-5)
    weights, ids = ref.routing(cfg, h, router, bias)
    scores = 1 / (1 + np.exp(-np.asarray(h @ router)))
    np.testing.assert_array_equal(np.sort(np.asarray(ids), 1),
                                  np.sort(np.argsort(-(scores + np.asarray(bias)), 1)[:, :2], 1))
    np.testing.assert_allclose(np.asarray(weights).sum(1), 2.5, rtol=1e-6)
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
    each: core 30 ms, latent 10, experts 20, dispatch 5, up 5, rope 10."""
    trace, stats, facts = built
    peak = PEAKS["TPU v5 lite"]["bf16_flops_per_s"]
    # whole executions are the 2nd and 3rd: pages 11 ([4096, 1024]) and 12 ([8192])
    ops = sum(counter.attention_core_flops(d, l) for d in ([4096, 1024], [8192]) for l in counter.LAYERS)
    assert mla_core_roofline.read(trace, stats, facts) == pytest.approx(100 * ops / peak / 0.060)
    ops_page, nbytes = mla_core_roofline.work_of_page([8192])
    assert nbytes == 2 * 8192 * (64 * (128 + 64 + 128 + 128 + 128) + 64) * 5
    assert ops_page / peak > 9 * nbytes / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]  # compute bounds it
    assert mla_latent_pct.read(trace, stats, facts) == pytest.approx(25.0)  # latent + up + rope
    assert attention_pct.read(trace, stats, facts) == pytest.approx(55.0)
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
    for reader in (mla_core_roofline, mla_latent_pct):
        assert reader.read(trace, stats, facts) is None
        assert reader.read(dict(trace, path=None), {}, facts) is None

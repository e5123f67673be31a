"""The text stream's third model (``--feature_type qwen3_next``: Gated DeltaNet
linear-attention layers, gated full attention every fourth layer, a gated
shared expert) at tiny widths on the CPU: the program against the benchmark's
plain reference through ``Extractor.run``, the chunked delta rule against the
token recurrence, the convolution, the partial rope, the per-head norms and
the output gate, the router, the expert share and the weight table. The three
Pallas kernels are the chip's, run in the Pallas interpreter. Arithmetic is
checked in float32 (``models.text_layers.DTYPE`` patched); the bfloat16 path is
run once and held loosely. What the models share (pages, packing, the grouped
product, the daemon's session) is ``tests/test_laguna.py``'s.
"""

# fast-registry: page program compiles (three Pallas kernels in the interpreter)

import collections
import functools
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from check import row_gaps  # noqa: E402
from reference import qwen3_next as ref  # noqa: E402
from weights import make_leaf, make_weights, unflatten, write_npz  # noqa: E402

from video_features_tpu.config import ExtractionConfig  # noqa: E402
from video_features_tpu.extractors import get_extractor  # noqa: E402
from video_features_tpu.extractors import token_pages as extractor_module  # noqa: E402
from video_features_tpu.models import qwen3_next as model  # noqa: E402
from video_features_tpu.models import text_layers  # noqa: E402
from video_features_tpu.ops import moe  # noqa: E402
from video_features_tpu.ops.gated_delta import CHUNK, chunk_edges, gated_delta  # noqa: E402

# two key heads serving four value heads of 16, four query heads over two
# key/value heads of 32 (8 of them rotated), sixteen experts of which a chip
# holds four
WIDTHS = dict(vocab_size=512, hidden_size=64, linear_num_key_heads=2, linear_num_value_heads=4,
              linear_key_head_dim=16, linear_value_head_dim=16, num_attention_heads=4,
              num_key_value_heads=2, head_dim=32, num_experts=16, num_experts_per_tok=4,
              moe_intermediate_size=32, shared_expert_intermediate_size=32)
TINY = model.Qwen3NextConfig(**WIDTHS)
REF_TINY = dict(ref.PUBLISHED, **WIDTHS)
LAYERS = (0, 1, 2, 3)
HELD = (0, 1, 2, 3)  # a quarter of the 16 experts
PAGE_TOKENS, BLOCK = 128, 16
# pages in this order: {100} once 256 tokens wait, then at the flush {37, 60, 20},
# {120}: with chunks of 64, a document that crosses a chunk's edge and one that
# starts inside a chunk
LENGTHS = (100, 37, 60, 120, 20)


def transcript(path, rng, tokens, lo=8, hi=14):
    sizes = []
    while sum(sizes) < tokens:
        sizes.append(min(tokens - sum(sizes), int(rng.integers(lo, hi))))
    ends = np.cumsum(sizes).astype(np.int32)
    np.savez(path, ids=rng.integers(0, TINY.vocab_size, tokens).astype(np.int32),
             segment_ends=ends, start_ms=(ends - sizes).astype(np.int64) * 300,
             end_ms=ends.astype(np.int64) * 300)
    return path


def read_out(out_dir, path):
    stem = os.path.basename(path)[:-len(".tokens.npz")]
    return {k: np.load(os.path.join(out_dir, "qwen3_next", f"{stem}_{k}.npy"))
            for k in ("qwen3_next", "timestamps_ms", "tokens")}


@pytest.fixture
def tiny(monkeypatch):
    """The published shape at tiny widths, attention in blocks of 16."""
    monkeypatch.setattr(model, "PUBLISHED", TINY)
    monkeypatch.setattr(extractor_module, "ATTENTION_BLOCK", BLOCK)


@pytest.fixture
def float32(monkeypatch):
    monkeypatch.setattr(text_layers, "DTYPE", jnp.float32)


def pre_rounded(flat):
    """Matrices as both sides round them (bfloat16); ``…/bias`` leaves are
    float32 on both."""
    import ml_dtypes

    return {k: v if k.endswith("/bias") else v.astype(ml_dtypes.bfloat16).astype(np.float32)
            for k, v in flat.items()}


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Seeded weights by the reference's own table, through the benchmark's
    generator and the program's checkpoint directory."""
    spec = ref.weight_specs(REF_TINY, layers=LAYERS, experts=HELD)
    flat = {name: pre_rounded(make_weights(s, 7, name)) for name, s in spec.items()}
    directory = str(tmp_path_factory.mktemp("weights"))
    write_npz(directory, "qwen3_next", flat["qwen3_next"])
    return directory, flat


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    rng = np.random.default_rng(0)
    d = tmp_path_factory.mktemp("corpus")
    return [transcript(str(d / f"v{i}.tokens.npz"), rng, n) for i, n in enumerate(LENGTHS)]


def page_documents(stats):
    """Each token page's documents (their lengths), page by page: what its
    ``stage`` span recorded."""
    return [r["ids"]["documents"] for r in stats["spans"]["records"] if "documents" in r["ids"]]


def extractor(tmp_path, sub, checkpoint_dir, monkeypatch, **kw):
    monkeypatch.setenv("VFT_CHECKPOINT_DIR", checkpoint_dir)
    return get_extractor(ExtractionConfig(
        feature_type="qwen3_next", on_extraction="save_numpy", page_tokens=PAGE_TOKENS,
        output_path=str(tmp_path / sub), tmp_path=str(tmp_path / "t"), **kw))


def test_program_matches_reference_and_packing_keeps_rows(tmp_path, tiny, float32, checkpoint,
                                                          corpus, monkeypatch):
    """Through ``Extractor.run`` on a corpus whose documents share pages, the
    ``.npy`` files against the plain reference; then the same documents one a
    page, and in the order that packs them into other pages: the same rows (a
    document packed mid-page equals the document alone). Two planted faults are held apart: the reference with its state
    dropped every 16 tokens, and with ``δ = βv``, are each far from the
    program."""
    directory, flat = checkpoint
    monkeypatch.setenv("VFT_METRICS", "1")  # the stage records say what each page held
    ex = extractor(tmp_path, "packed", directory, monkeypatch)
    assert ex.cfg.pack_corpus and ex.cfg.num_devices == 1
    assert ex.share == model.Share(LAYERS, HELD)
    assert ex.run(corpus) == len(corpus)
    stats = ex._pack_stats
    assert stats["pages_dispatched"] == 3 and stats["real_slots"] == sum(LENGTHS)
    assert page_documents(stats) == [[100], [37, 60, 20], [120]]
    assert stats["routed_total"] == TINY.num_experts_per_tok * sum(LENGTHS) * len(LAYERS)
    assert stats["routed_held"] == int(np.sum(stats["expert_rows"])) < stats["routed_total"]
    assert np.asarray(stats["expert_rows"]).shape == (len(LAYERS), len(HELD))
    assert stats["expert_chunks"] >= stats["expert_chunk_calls"] == len(LAYERS) * 3
    # three linear layers walk two chunks of 64 in each of three pages; every
    # chunk holds a start or pads: {100}: both (the start, the pads),
    # {37, 60, 20}: both (two starts; a start and the pads), {120}: both
    assert stats["gdn_chunks"] == 3 * 2 * 3
    assert stats["gdn_boundary_chunks"] == 3 * (2 + 2 + 2)

    tree = {k: unflatten(v) for k, v in flat.items()}
    answer = ref.make_answer_fn(tree, REF_TINY)
    monkeypatch.setattr(ref, "FAULT_CHUNK", 16)
    faulty = {fault: ref.make_forward(ref.round_weights(unflatten(flat["qwen3_next"])), REF_TINY,
                                      fault=fault) for fault in ("carry", "delta")}
    packed = {}
    for path in corpus:
        want, got = answer(path), read_out(str(tmp_path / "packed"), path)
        assert got["qwen3_next"].dtype == np.float32
        assert got["qwen3_next"].shape == (len(want["tokens"]), TINY.hidden_size)
        assert row_gaps(got["qwen3_next"], want["qwen3_next"]).max() < 2e-5
        for k in ref.EXACT_KEYS:
            np.testing.assert_array_equal(got[k], want[k])
        with np.load(path) as z:
            for fault, features in faulty.items():
                far = row_gaps(features(z["ids"], z["segment_ends"]), got["qwen3_next"]).max()
                assert far > 1e-2, (fault, far)
        packed[path] = got["qwen3_next"]

    for path in corpus:  # one document a page
        assert ex.run([path]) == 1
        assert ex._pack_stats["pages_dispatched"] == 1
        alone = read_out(str(tmp_path / "packed"), path)["qwen3_next"]
        assert row_gaps(alone, packed[path]).max() < 2e-5

    # the other way round the same documents pack as {20, 100}, {120}, {60, 37} (the first page
    # passes 60 + 37 over for 100): a document's rows do not depend on the company it keeps
    assert ex.run(corpus[::-1]) == len(corpus)
    assert page_documents(ex._pack_stats) == [[20, 100], [120], [60, 37]]
    for path in corpus:
        turned = read_out(str(tmp_path / "packed"), path)["qwen3_next"]
        assert row_gaps(turned, packed[path]).max() < 2e-5


def test_bfloat16_path(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """The arithmetic the type really runs, held loosely at this width (a
    bfloat16 rounding is 0.4 % of a value here and a router's near-tie flips
    on it)."""
    directory, flat = checkpoint
    ex = extractor(tmp_path, "bf16", directory, monkeypatch)
    assert ex.run(corpus[:3]) == 3
    answer = ref.make_answer_fn({k: unflatten(v) for k, v in flat.items()}, REF_TINY)
    gaps = np.concatenate([row_gaps(read_out(str(tmp_path / "bf16"), p)["qwen3_next"],
                                    answer(p)["qwen3_next"]) for p in corpus[:3]])
    assert np.isfinite(gaps).all() and np.median(gaps) < 0.1


def test_daemon_serves_the_type(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """``--serve`` with ``qwen3_next``: a request goes through the daemon's
    ``PackedSession`` to the same files."""
    from video_features_tpu.serve.daemon import ExtractionService

    directory, _flat = checkpoint
    spool = tmp_path / "spool"
    spool.mkdir()
    ex = extractor(tmp_path, "served", directory, monkeypatch, serve=True, spool_dir=str(spool),
                   idle_flush_sec=0.0, serve_models=("qwen3_next",))
    svc = ExtractionService(ex, poll_interval=0.001)
    request = svc.submit({"videos": corpus[:2]})
    svc.request_drain()
    assert svc.run() == 0 and request.state == "done"
    svc.close()
    for path in corpus[:2]:
        got = read_out(str(tmp_path / "served"), path)
        assert got["qwen3_next"].shape == (len(got["tokens"]), TINY.hidden_size)
        assert np.isfinite(got["qwen3_next"]).all() and np.abs(got["qwen3_next"]).max() > 0


# --- the chunked delta rule ---------------------------------------------------

def rule_inputs(rng, tokens, key_heads, per_key, width):
    """Queries and keys as the convolution leaves them (any length), values,
    ``g ≤ 0`` and ``β`` in (0, 1)."""
    heads = key_heads * per_key
    q, k = rng.standard_normal((2, tokens, key_heads, width)).astype(np.float32) * 1.7
    v = rng.standard_normal((tokens, heads, width)).astype(np.float32)
    g = (-np.log1p(np.exp(1.4 * rng.standard_normal((tokens, heads))))).astype(np.float32)
    beta = (1 / (1 + np.exp(-1.4 * rng.standard_normal((tokens, heads))))).astype(np.float32)
    return q, k, v, g, beta


def run_kernel(q, k, v, g, beta, doc, **kw):
    tokens = len(doc)
    qkv = np.concatenate([a.reshape(tokens, -1) for a in (q, k, v)], axis=1)
    return np.asarray(gated_delta(*(jnp.asarray(a) for a in (qkv, g, beta, doc)),
                                  key_heads=q.shape[1], interpret=True, **kw)).reshape(v.shape)


def token_recurrence(q, k, v, g, beta, sl, per_key):
    """Steps 4 and 5 token by token on one document ALONE (the reference's
    scan over unit rows made here)."""
    width = q.shape[-1]
    q = q[sl] / np.sqrt(np.sum(q[sl] ** 2, -1, keepdims=True) + 1e-6) / np.sqrt(width)
    k = k[sl] / np.sqrt(np.sum(k[sl] ** 2, -1, keepdims=True) + 1e-6)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.delta_rule(*(jnp.asarray(a) for a in (
            np.repeat(q, per_key, 1), np.repeat(k, per_key, 1), v[sl], g[sl], beta[sl]))))


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("lengths,per_key", [
    ((20,), 2), ((40, 7, 50, 20), 2), ((128,), 2), ((5, 16, 33, 1, 60), 2), ((1,), 2),
    ((63, 1, 64), 1), ((3, 1, 1, 70), 4)],
    ids=["one_short", "mixed", "whole_page", "starts_mid_chunk_and_one_token", "one_token",
         "ends_at_an_edge", "four_value_heads_a_key_head"])
def test_chunked_core_against_the_token_recurrence(lengths, per_key, chunk, rng):
    """Equal to the recurrence run on each document ALONE, for documents that
    begin and end anywhere inside a chunk, a document of one token, two or
    more value heads a key head; the trailing pads (document -1) stay finite."""
    tokens, key_heads, width = 128, 2, 16
    doc = np.full(tokens, -1, np.int32)
    doc[:sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
    q, k, v, g, beta = rule_inputs(rng, tokens, key_heads, per_key, width)
    got = run_kernel(q, k, v, g, beta, doc, chunk=chunk)
    assert np.isfinite(got).all()  # the pads too
    at = 0
    for n in lengths:
        sl = slice(at, at + n)
        at += n
        np.testing.assert_allclose(got[sl], token_recurrence(q, k, v, g, beta, sl, per_key),
                                   atol=2e-6)


def test_strong_decay_and_a_long_chunk_stay_finite_and_exact(rng):
    """Decays far below float32's smallest ratio inside one chunk (a
    cumulative ``g`` of -300 and beyond): differences are taken for earlier
    tokens only, so nothing overflows and nothing is ``inf - inf``."""
    tokens, key_heads, per_key, width = 128, 1, 2, 16
    q, k, v, g, beta = rule_inputs(rng, tokens, key_heads, per_key, width)
    g = g * 8.0 - 1.0
    assert np.cumsum(g, 0).min() < -300
    doc = np.repeat(np.arange(2), 64).astype(np.int32)
    got = run_kernel(q, k, v, g, beta, doc)
    for sl in (slice(0, 64), slice(64, 128)):
        np.testing.assert_allclose(got[sl], token_recurrence(q, k, v, g, beta, sl, per_key),
                                   atol=2e-6)


def test_one_key_many_times_and_weak_decay_stay_exact(rng):
    """A page's pads are one token id many times: one key, one ``β``, one
    ``g``, and where that ``g`` is near 0 the triangular system is dense with
    entries near 1. Inverted by halves it stays exact over many chunks; the
    product ``(I − L)(I + L²)(I + L⁴)…`` would not (its powers reach 1e17 and
    float32 cancels to garbage, which a carried state then multiplies chunk
    after chunk to ``inf``: read on the chip, PERF.md section 6, PR 40)."""
    tokens, key_heads, per_key, width = 512, 1, 2, 16
    q, k, v, _g, _beta = rule_inputs(rng, 1, key_heads, per_key, width)
    q, k, v = (np.tile(a, (tokens, 1, 1)) for a in (q, k, v))
    g = np.full((tokens, 2), -0.01, np.float32)
    beta = np.full((tokens, 2), 0.98, np.float32)
    doc = np.full(tokens, -1, np.int32)
    got = run_kernel(q, k, v, g, beta, doc)
    want = token_recurrence(q, k, v, g, beta, slice(0, tokens), per_key)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the shorter-looking product on the same system, in float32: far off
    i = np.arange(64)
    lower = np.where(i[:, None] > i[None, :], 0.98 * np.exp(-0.01 * (i[:, None] - i[None, :])), 0.0)
    true = np.linalg.inv(np.eye(64) + lower)
    power = (-lower).astype(np.float32)
    product = np.eye(64, dtype=np.float32) + power
    for _ in range(5):
        power = power @ power
        product = product + product @ power
    assert np.abs(product - true).max() > 1e3 and np.abs(true).max() <= 1.0


@pytest.mark.parametrize("heads,step_rows", [(4, 128), (16, 64)], ids=["one_step", "two_steps_of_heads"])
def test_gated_norm_against_numpy(heads, step_rows, rng, monkeypatch):
    """``RMSNorm(o) · w · silu(z)`` head by head, ``z`` read from inside a
    wider array at a column offset."""
    from video_features_tpu.ops import gated_delta as op

    monkeypatch.setattr(op, "NORM_ROWS", step_rows)
    tokens, width = 128, 16
    o = rng.standard_normal((tokens, heads * width)).astype(np.float32) * 3
    wide = rng.standard_normal((tokens, 3 * heads * width)).astype(np.float32)
    w = rng.uniform(0.5, 1.5, width).astype(np.float32)
    column = 2 * heads * width
    got = np.asarray(op.gated_norm.__wrapped__(jnp.asarray(o), jnp.asarray(wide), jnp.asarray(w), heads=heads,
                                               gate_column=column, eps=1e-6, interpret=True))
    oh = o.reshape(tokens, heads, width).astype(np.float64)
    z = wide[:, column:].reshape(tokens, heads, width).astype(np.float64)
    want = oh / np.sqrt(np.mean(oh * oh, -1, keepdims=True) + 1e-6) * w * z / (1 + np.exp(-z))
    np.testing.assert_allclose(got, want.reshape(tokens, -1), atol=2e-5)
    with pytest.raises(ValueError, match="from column 8"):
        op.gated_norm(jnp.asarray(o), jnp.asarray(wide), jnp.asarray(w), heads=heads, gate_column=8)


def test_both_kernels_cross_lower_for_tpu_at_the_published_shapes():
    """jaxpr → Mosaic MLIR at the page's own shapes (16 key and 32 value heads
    of 128, 16,384 tokens, bfloat16) with no TPU present; the Mosaic compile
    itself is the chip's (``benchmark/sizing_token_pages.py`` makes it here by
    hand for a described v5e)."""
    from video_features_tpu.ops.gated_delta import gated_norm

    tokens, key_heads, heads, width = 16384, 16, 32, 128
    qkv = jax.ShapeDtypeStruct((tokens, (2 * key_heads + heads) * width), jnp.bfloat16)
    gates = jax.ShapeDtypeStruct((tokens, heads), jnp.float32)
    doc = jax.ShapeDtypeStruct((tokens,), jnp.int32)
    core = jax.export.export(jax.jit(functools.partial(gated_delta, key_heads=key_heads)),
                             platforms=["tpu"])(qkv, gates, gates, doc)
    assert "tpu_custom_call" in core.mlir_module() and "gated_delta_chunk" in core.mlir_module()
    o = jax.ShapeDtypeStruct((tokens, heads * width), jnp.bfloat16)
    wide = jax.ShapeDtypeStruct((tokens, (2 * key_heads + 2 * heads) * width), jnp.bfloat16)
    norm = jax.export.export(
        jax.jit(functools.partial(gated_norm, heads=heads, gate_column=(2 * key_heads + heads) * width)),
        platforms=["tpu"])(o, wide, jax.ShapeDtypeStruct((width,), jnp.bfloat16))
    assert "tpu_custom_call" in norm.mlir_module() and "gated_norm" in norm.mlir_module()


def test_chunk_edges_and_page_counters():
    doc = np.full(256, -1, np.int32)
    doc[:200] = np.repeat(np.arange(3), (70, 58, 72))
    edges = np.asarray(chunk_edges(jnp.asarray(doc), 64))
    np.testing.assert_array_equal(edges, [[-2, 0, 1, 2], [0, 1, 2, -1]])
    share = model.Share((0, 1, 2, 3, 4), ())
    # chunks 0 (a start), 1 (a start at 70, another at 128... inside chunk 2), 3 (pads)
    counted = np.asarray(model.page_counters(TINY, share, jnp.asarray(doc)))
    assert CHUNK == 64
    np.testing.assert_array_equal(counted, [4 * 4, 4 * 4])  # layers 0, 1, 2, 4 are linear
    whole = np.zeros(256, np.int32)
    np.testing.assert_array_equal(np.asarray(model.page_counters(TINY, share, jnp.asarray(whole))),
                                  [16, 4])  # only the page's first chunk holds a start
    with pytest.raises(ValueError, match="chunks of 48"):
        gated_delta(jnp.zeros((96, 128)), jnp.zeros((96, 4)), jnp.zeros((96, 4)),
                    jnp.zeros((96,), jnp.int32), key_heads=2, chunk=48)


# --- the layers around it -------------------------------------------------------

@pytest.mark.parametrize("with_bias", [False, True], ids=["no_bias", "bias"])
def test_the_convolution_does_not_cross_a_documents_start(with_bias, rng):
    """``text_layers.causal_conv``, the stream's one convolution: Qwen3-Next
    calls it with no bias, Jamba with one added to every token."""
    lengths, channels = (5, 1, 2, 40, 3), 24
    tokens = 64
    u = rng.standard_normal((tokens, channels)).astype(np.float32)
    w = rng.standard_normal((4, channels)).astype(np.float32)
    bias = rng.standard_normal(channels).astype(np.float32) if with_bias else np.zeros(channels, np.float32)
    pos = np.zeros(tokens, np.int32)
    at = 0
    for n in lengths:
        pos[at:at + n] = np.arange(n)
        at += n
    pos[at:] = np.arange(tokens - at)  # pads count on from 0: anything finite
    got = np.asarray(text_layers.causal_conv(jnp.asarray(u), jnp.asarray(w), jnp.asarray(pos),
                                             jnp.asarray(bias) if with_bias else None))
    at = 0
    for n in lengths:
        alone = np.asarray(ref.causal_conv(jnp.asarray(u[at:at + n]), jnp.asarray(w))) + bias
        np.testing.assert_allclose(got[at:at + n], alone, atol=1e-5)
        by_hand = sum(w[3 - s] * (u[at + n - 1 - s] if n - 1 - s >= 0 else 0) for s in range(4))
        np.testing.assert_allclose(got[at + n - 1], by_hand + bias, atol=1e-5)
        at += n
    # token 10 is its document's third: three taps, the fourth would be the neighbour's
    np.testing.assert_allclose(got[10], u[10] * w[3] + u[9] * w[2] + u[8] * w[1] + bias, atol=1e-5)
    # and it does reach back inside a document: the first tap matters
    assert np.abs(got[20] - (u[20] * w[3] + u[19] * w[2] + u[18] * w[1] + bias)).max() > 1e-3


def test_partial_rope_against_float64_formula():
    """Rope over the first 64 of a head's 256 at the PUBLISHED parameters
    (``rope_theta`` 1e7, dimension ``i`` with ``i + 32``, no scaling) against
    the formula written out in float64; the other 192 pass untouched."""
    cfg = model.PUBLISHED
    pos = np.array([0, 1, 5, 511, 512, 4097, 16383], np.int64)
    rot = 64
    inv = 1e7 ** (-np.arange(0, rot, 2) / rot)
    np.testing.assert_allclose(model.rope_inv_freq(cfg), inv, rtol=1e-14)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((len(pos), 3, 256))
    angle = pos[:, None] * inv[None, :]
    cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
    a, b = x[..., :32], x[..., 32:64]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., 64:]], axis=-1)
    got = np.asarray(text_layers.apply_rope(jnp.asarray(x, jnp.float32), jnp.asarray(pos, jnp.int32),
                                            model.rope_inv_freq(cfg), 1.0))
    np.testing.assert_allclose(got[:4], want[:4], atol=1e-4)
    np.testing.assert_allclose(got, want, atol=0.02)  # float32 angles at 16,383
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:].astype(np.float32))
    rc, rs = ref.rope_tables(ref.PUBLISHED, pos, dtype=np.float64)
    np.testing.assert_allclose(rc[:, None], cos, atol=1e-12)
    np.testing.assert_allclose(rs[:, None], sin, atol=1e-12)
    np.testing.assert_allclose(np.asarray(ref.rotate(jnp.asarray(x[:4], jnp.float32),
                                                     rc[:4].astype(np.float32),
                                                     rs[:4].astype(np.float32))), want[:4], atol=1e-4)


def layer_params(layer, seed=3):
    """One layer's leaves at tiny widths, pre-rounded → (the program's tree,
    the reference's)."""
    spec = ref.weight_specs(REF_TINY, layers=(layer,), experts=HELD)["qwen3_next"]
    flat = pre_rounded(make_weights(spec, seed, "qwen3_next"))
    params, _share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    return params["layers"][0], ref.round_weights(unflatten(flat))["layers"][str(layer)], flat


def test_per_head_norms_and_the_element_wise_output_gate(float32, rng):
    """The full layer against its equations written out in numpy: per-head
    RMSNorm on queries and keys (scales of the head's width), rope on a
    quarter of the head, scores over ``sqrt(d)``, the output times
    ``sigmoid(gate)`` ELEMENT by element (a per-head gate would differ)."""
    p, w, flat = layer_params(3)
    tokens, heads, kv, d = 32, 4, 2, 32
    x = rng.standard_normal((tokens, TINY.hidden_size)).astype(np.float32)
    doc = np.zeros(tokens, np.int32)
    pos = np.arange(tokens, dtype=np.int32)
    got = np.asarray(model.full_attention(TINY, p, jnp.asarray(x), jnp.asarray(doc),
                                          jnp.asarray(pos), BLOCK, interpret=True))

    def norm(a, scale):
        return a / np.sqrt(np.mean(a * a, -1, keepdims=True) + 1e-6) * scale

    f = {k: np.asarray(v, np.float64) for k, v in flat.items()}
    h = norm(x.astype(np.float64), f["layers/3/attn_norm/scale"])
    qg = (h @ f["layers/3/q_proj"]).reshape(tokens, heads, 2 * d)
    q, gate = norm(qg[..., :d], f["layers/3/q_norm/scale"]), qg[..., d:]
    k = norm((h @ f["layers/3/k_proj"]).reshape(tokens, kv, d), f["layers/3/k_norm/scale"])
    v = (h @ f["layers/3/v_proj"]).reshape(tokens, kv, d)
    inv = 1e7 ** (-np.arange(0, 8, 2) / 8)
    angle = pos[:, None] * inv[None, :]
    cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]

    def rope(a):
        return np.concatenate([a[..., :4] * cos - a[..., 4:8] * sin,
                               a[..., 4:8] * cos + a[..., :4] * sin, a[..., 8:]], axis=-1)

    q, k = rope(q), rope(k)
    o = np.zeros((tokens, heads, d))
    for a in range(heads):
        s = q[:, a] @ k[:, a // 2].T / np.sqrt(d)
        s = np.where(np.tril(np.ones((tokens, tokens), bool)), s, -np.inf)
        prob = np.exp(s - s.max(1, keepdims=True))
        o[:, a] = prob / prob.sum(1, keepdims=True) @ v[:, a // 2]
    want = x + (o / (1 + np.exp(-gate))).reshape(tokens, -1) @ f["layers/3/o_proj"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    per_head = x + (o / (1 + np.exp(-gate.mean(-1, keepdims=True)))).reshape(tokens, -1) @ f["layers/3/o_proj"]
    assert np.abs(per_head - want).max() > 1e-3
    with jax.default_matmul_precision("highest"):
        rc, rs = (jnp.asarray(t) for t in ref.rope_tables(REF_TINY, pos))
        np.testing.assert_allclose(np.asarray(ref.full_attention(REF_TINY, w, jnp.asarray(x), rc, rs)),
                                   want, atol=2e-5)


def test_a_gated_delta_net_layer_against_the_reference(float32, rng):
    """One linear layer, a page of three documents and pads, against the
    reference's layer on each document alone: projections, the convolution,
    the decay from ``A_log`` and ``dt_bias``, the unit rows (queries over
    ``sqrt(128)``'s tiny twin), the gated norm with its weight as published."""
    p, w, _flat = layer_params(1)
    lengths, tokens = (50, 1, 70), 128
    x = rng.standard_normal((tokens, TINY.hidden_size)).astype(np.float32)
    doc, pos = np.full(tokens, -1, np.int32), np.zeros(tokens, np.int32)
    at = 0
    for i, n in enumerate(lengths):
        doc[at:at + n], pos[at:at + n] = i, np.arange(n)
        at += n
    got = np.asarray(model.gated_delta_net(TINY, p, jnp.asarray(x), jnp.asarray(doc),
                                           jnp.asarray(pos), interpret=True))
    assert np.isfinite(got).all()
    at = 0
    with jax.default_matmul_precision("highest"):
        for n in lengths:
            want = np.asarray(ref.gated_delta_net(REF_TINY, w, jnp.asarray(x[at:at + n])))
            np.testing.assert_allclose(got[at:at + n], want, atol=2e-5)
            at += n
    assert p["a_log"].dtype == p["dt_bias"].dtype == jnp.float32


def test_the_router_and_the_gated_shared_expert(float32, rng):
    """Softmax over all experts, the top-k renormalised to sum 1, factor 1;
    the shared expert's output times ``sigmoid(h · w_s)`` token by token."""
    p, w, flat = layer_params(0)
    h = jnp.asarray(rng.standard_normal((24, TINY.hidden_size)), jnp.float32)
    weights, experts = (np.asarray(a) for a in model.route(TINY, p, h))
    probs = np.asarray(jax.nn.softmax(h @ p["router"], axis=-1))
    np.testing.assert_array_equal(experts, np.argsort(-probs, 1)[:, :4])
    picked = np.take_along_axis(probs, experts, axis=1)
    np.testing.assert_allclose(weights, picked / picked.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(1), 1.0, rtol=1e-6)
    rw, re = (np.asarray(a) for a in ref.routing(REF_TINY, h, w["router"]))
    np.testing.assert_array_equal(re, experts)
    np.testing.assert_allclose(rw, weights, rtol=1e-5)

    assert p["shared_gate"].shape == (TINY.hidden_size, 1)
    nobody = jnp.full((TINY.num_experts,), -1, jnp.int32)  # no expert held: the shared part alone
    y, (_total, held, _rows, _trips, _runs) = text_layers.expert_layer(
        p, h, jnp.ones((24,), bool), nobody.at[0].set(0), 1, functools.partial(model.route, TINY),
        interpret=True)
    ungated = {k: v for k, v in p.items() if k != "shared_gate"}
    y0, _ = text_layers.expert_layer(ungated, h, jnp.ones((24,), bool), nobody.at[0].set(0), 1,
                                     functools.partial(model.route, TINY), interpret=True)
    shared = np.asarray(text_layers.gated_mlp(h, p["shared_gate_up"], p["shared_down"]))
    gate = 1 / (1 + np.exp(-np.asarray(h) @ flat["layers/0/shared_gate"]))
    assert gate.shape == (24, 1) and 0.05 < gate.min() < gate.max() < 0.95
    np.testing.assert_allclose(np.asarray(y) - np.asarray(y0), (gate - 1) * shared, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(ref.shared_part(h, w["shared"], w["shared_gate"])),
                                   gate * shared, atol=1e-5)


def test_four_shares_and_the_gated_shared_expert_once_make_the_uncut_layer(float32):
    """The share ties to the model: what the four chips of a stage each give
    for their own quarter of the experts, plus the gated shared expert counted
    once, is the uncut reference's sparse unit."""
    tokens, all_experts = 48, tuple(range(TINY.num_experts))
    spec = ref.weight_specs(REF_TINY, layers=(1,), experts=all_experts)["qwen3_next"]
    flat = pre_rounded(make_weights(spec, 11, "qwen3_next"))
    w = ref.round_weights(unflatten(flat))["layers"]["1"]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((tokens, TINY.hidden_size)), jnp.float32)
    stack = lambda m, ids: jnp.stack([w["experts"][str(e)][m] for e in ids])  # noqa: E731
    with jax.default_matmul_precision("highest"):
        gated_shared = ref.shared_part(h, w["shared"], w["shared_gate"])
        uncut = (ref.routed_part(REF_TINY, h, w["router"],
                                 *(stack(m, all_experts) for m in ("gate_proj", "up_proj", "down_proj")),
                                 jnp.asarray(all_experts)) + gated_shared)
    valid = jnp.ones((tokens,), bool)
    total, held_rows = None, 0
    for rank in range(4):
        ids = all_experts[rank::4]  # any four-way split of the experts
        names = [n for n in flat if "/experts/" not in n or int(n.split("/")[3]) in ids]
        params, share = model.stack_checkpoint(TINY, names, flat.__getitem__)
        assert share.experts == tuple(sorted(ids)) and share.layers == (1,)
        slot_of = np.full((TINY.num_experts,), -1, np.int32)
        slot_of[list(share.experts)] = np.arange(len(ids))
        p = params["layers"][0]
        y, (routed_total, routed_held, rows, _chunks, _runs) = text_layers.expert_layer(
            p, h, valid, jnp.asarray(slot_of), len(ids), functools.partial(model.route, TINY),
            interpret=True)
        shared = (jax.nn.sigmoid(text_layers.dot(h, p["shared_gate"]))
                  * text_layers.gated_mlp(h, p["shared_gate_up"], p["shared_down"]))
        routed = y - shared  # every chip computes the gated shared expert alike
        total = routed if total is None else total + routed
        held_rows += int(routed_held)
        assert int(routed_total) == tokens * TINY.num_experts_per_tok
        assert int(np.sum(rows)) == int(routed_held)
    total = total + gated_shared
    assert held_rows == tokens * TINY.num_experts_per_tok  # every assignment on exactly one chip
    assert row_gaps(np.asarray(total), np.asarray(uncut)).max() < 1e-5


def primitives(jaxpr, into=None):
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list)) else (value,)):
                if hasattr(inner, "jaxpr"):
                    primitives(inner.jaxpr, into)
                elif hasattr(inner, "eqns"):
                    primitives(inner, into)
    return into


def test_an_expert_layer_without_a_shared_gate_traces_as_it_did_at_the_parent():
    """Laguna's and sarvam's checkpoints have no ``shared_gate`` leaf: their
    routed layer traces to the operations it had before this model came (read
    at the parent commit: the same function traced on the same shapes), and
    the leaf adds one product, one sigmoid and one multiply."""
    tokens, hid, experts, held, width = 64, 32, 8, 2, 16
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    p = {"router": shape(hid, experts), "shared_gate_up": shape(hid, 2 * width),
         "shared_down": shape(width, hid), "experts_gate_up": shape(held, hid, 2 * width),
         "experts_down": shape(held, width, hid)}
    slot_of = jnp.asarray([0, 1] + [-1] * (experts - held), jnp.int32)

    def layer(p, h, valid):
        return text_layers.expert_layer(p, h, valid, slot_of, held,
                                        lambda p, h: moe.route(h, p["router"], 2, 2.5),
                                        interpret=True)

    args = (shape(tokens, hid), jax.ShapeDtypeStruct((tokens,), jnp.bool_))
    traced = jax.make_jaxpr(layer)(p, *args)
    plain = primitives(traced.jaxpr)
    gated = primitives(jax.make_jaxpr(layer)(dict(p, shared_gate=shape(hid, 1)), *args).jaxpr)
    assert gated - plain == collections.Counter({"dot_general": 1, "logistic": 1, "mul": 1})
    assert not plain - gated
    equations, digest = PARENT_EXPERT_LAYER
    assert sum(plain.values()) == equations
    # two grouped products and the combine's kernel; the chunk loop and the combine's two loops over slabs and groups
    assert plain["pallas_call"] == 3 and plain["while"] == 3
    assert hashlib.sha256(str(traced).encode()).hexdigest()[:16] == digest


# the same trace where the combine became the ``moe_combine`` kernel (PR 43;
# 1279, "53af1bbd5b1d63b0" at 5874d32): equations with the kernels' own
# counted in, and the first 16 hex digits of sha256(str(jaxpr))
PARENT_EXPERT_LAYER = (1643, "c5e8d57c686ded9b")


# --- the weight table and the configuration's file -------------------------------

def test_weight_specs_and_the_checkpoints_layouts():
    spec = ref.weight_specs()["qwen3_next"]
    assert spec["layers/2/experts/7/gate_proj"] == (2048, 512)
    assert spec["layers/2/experts/7/down_proj"] == (512, 2048)
    assert spec["layers/1/router"] == (2048, 512) and spec["layers/1/shared_gate"] == (2048, 1)
    assert spec["layers/0/q_proj"] == spec["layers/0/k_proj"] == (2048, 2048)
    assert spec["layers/0/v_proj"] == spec["layers/0/z_proj"] == (2048, 4096)
    assert spec["layers/0/b_proj"] == spec["layers/0/a_proj"] == (2048, 32)
    assert spec["layers/0/conv"] == (4, 8192) and spec["layers/0/out_proj"] == (4096, 2048)
    assert spec["layers/0/dt/bias"] == spec["layers/0/a_log/bias"] == (32,)
    assert spec["layers/0/gdn_norm/scale"] == (128,)
    assert spec["layers/3/q_proj"] == (2048, 16 * 512) and spec["layers/3/k_proj"] == (2048, 512)
    assert spec["layers/3/q_norm/scale"] == spec["layers/3/k_norm/scale"] == (256,)
    assert spec["layers/3/o_proj"] == (4096, 2048) and "layers/3/conv" not in spec
    assert "layers/2/o_proj" not in spec and "layers/0/mlp/gate_proj" not in spec
    assert spec["embed/embedding"] == (151936, 2048)
    assert not any(len(shape) > 2 for shape in spec.values())  # nothing stacked: fan-in is rows
    total = sum(int(np.prod(s)) for s in spec.values())
    assert 2.066e9 < total < 2.068e9
    assert {int(n.split("/")[3]) for n in spec if "/experts/" in n} == set(range(128))
    assert model.leaf_shapes(model.PUBLISHED, range(4), range(128)) == spec
    for name in ("layers/0/dt/bias", "layers/0/a_log/bias"):
        assert 0.02 < float(make_leaf(np.random.default_rng(0), name, (32,)).std()) < 0.08

    # stack_checkpoint's layouts: a full layer's queries then gates, by head
    tiny_spec = model.leaf_shapes(TINY, (0, 3), (0, 1))
    flat = {n: np.arange(int(np.prod(s)), dtype=np.float32).reshape(s) % 251 for n, s in tiny_spec.items()}
    params, share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    assert share == model.Share((0, 3), (0, 1))
    linear, full = params["layers"]
    heads, d = 4, 32
    q = flat["layers/3/q_proj"].reshape(64, heads, 2 * d)
    np.testing.assert_array_equal(
        np.asarray(full["wqgkv"], np.float32),
        np.concatenate([q[..., :d].reshape(64, -1), q[..., d:].reshape(64, -1),
                        flat["layers/3/k_proj"], flat["layers/3/v_proj"]], axis=-1))
    np.testing.assert_array_equal(
        np.asarray(linear["wqkvz"], np.float32),
        np.concatenate([flat[f"layers/0/{m}_proj"] for m in "qkvz"], axis=-1))
    np.testing.assert_array_equal(
        np.asarray(linear["wba"], np.float32),
        np.concatenate([flat["layers/0/b_proj"], flat["layers/0/a_proj"]], axis=-1))
    assert linear["conv"].shape == (4, 2 * 32 + 64) and "conv" not in full
    assert set(linear) >= {"shared_gate", "router", "experts_gate_up", "gdn_norm", "wo"}


def test_configuration_file_keeps_every_published_number():
    root = os.path.dirname(BENCH)
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b_bf16.json")) as f:
        conf = json.load(f)
    cfg = model.PUBLISHED
    for key in ("vocab_size", "hidden_size", "rms_norm_eps", "full_attention_interval",
                "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim", "num_attention_heads",
                "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size"):
        assert conf[key] == getattr(cfg, key) == ref.PUBLISHED[key], key
    # the catalog row's `config`, every number under the same key
    catalog = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
               "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
               "linear_key_head_dim": 128, "linear_num_key_heads": 16,
               "linear_num_value_heads": 32, "linear_value_head_dim": 128,
               "max_position_embeddings": 262144, "moe_intermediate_size": 512,
               "num_attention_heads": 16, "num_experts_per_tok": 10, "num_key_value_heads": 2,
               "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_theta": 10000000,
               "shared_expert_intermediate_size": 512, "vocab_size": 151936}
    for key, value in catalog.items():
        assert conf[key] == value, key
    assert conf["mlp_only_layers"] == [] and conf["rope_scaling"] is None
    assert conf["norm_topk_prob"] is True and conf["model_type"] == "qwen3_next"
    assert conf["reduced"] == ["num_hidden_layers", "num_experts"]
    assert conf["num_experts"] == len(ref.EXPERTS) == 128
    assert conf["num_hidden_layers"] == len(ref.LAYERS) == 4
    assert conf["published"]["num_experts"] == cfg.num_experts == ref.PUBLISHED["num_experts"] == 512
    assert conf["published"]["num_hidden_layers"] == 48
    assert [cfg.is_full(l) for l in ref.LAYERS] == [ref.is_full(ref.PUBLISHED, l) for l in ref.LAYERS] \
        == [False, False, False, True]
    assert conf["feature_type"] == "qwen3_next" and conf["reference"] == conf["flops"] == "qwen3_next"
    assert conf["extraction"]["page_tokens"] % CHUNK == 0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert any(c["file"] == "benchmark/configs/qwen3_next_80b_bf16.json"
               and c["reduced"] == conf["reduced"] for c in bench["configs"])
    cell = [w for w in bench["workloads"] if w["config"] == "qwen3_next_80b_bf16"]
    assert [(w["name"], w["chips"], w["traffic"]) for w in cell] == [
        ("qwen3_next_80b_bf16.corpus_transcripts", 1, "corpus_transcripts")]
    listed = [m["name"] for m in bench["per_layer"] if cell[0]["name"] in m.get("workloads", ())]
    assert len(listed) == 21 and {"gdn_core_roofline", "gdn_pct", "setup_weights_s", "setup_compile_s",
                                  "setup_compiles", "setup_program_s"} <= set(listed)
    assert "attn_core_roofline" not in listed and "moe_experts_roofline" not in listed

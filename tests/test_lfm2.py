"""The text stream's fifth model (``--feature_type lfm2``: gated short-convolution
mixers in three layers of four, QK-normed attention over 64-wide heads, a
sigmoid router choosing 4 of 64 experts with no shared expert) at tiny widths
on the CPU: the program against the benchmark's plain reference through
``Extractor.run``, the two planted faults far from it, the attention kernel's
paired 64-wide heads against a plain softmax, the routed layer without a
shared expert against a plain loop, the renormalisation's ``eps``, the share,
the scopes the benchmark's readers match, and the weight table. The Pallas
kernels are the chip's, run in the Pallas interpreter. Arithmetic is checked
in float32 (``models.text_layers.DTYPE`` patched); the bfloat16 path is run
once and held loosely. What the models share (pages, packing, the daemon's
session) is ``tests/test_laguna.py``'s.
"""

# fast-registry: page program compiles (three Pallas kernels in the interpreter)

import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from check import row_gaps  # noqa: E402
from lfm2_readings import planted, unrestarted  # noqa: E402 — the planted faults, as the chip reads them
from reference import lfm2 as ref  # noqa: E402
from weights import make_weights, unflatten, write_npz  # noqa: E402

from video_features_tpu.config import ExtractionConfig  # noqa: E402
from video_features_tpu.extractors import get_extractor  # noqa: E402
from video_features_tpu.extractors import token_pages as extractor_module  # noqa: E402
from video_features_tpu.models import lfm2 as model  # noqa: E402
from video_features_tpu.models import text_layers  # noqa: E402
from video_features_tpu.ops import moe  # noqa: E402
from video_features_tpu.ops.segment_attention import segment_attention  # noqa: E402
from video_features_tpu.parallel.pages import build_token_page  # noqa: E402

# 4 query heads of 16 over 2 key/value heads (a grid step holds both), 8
# experts of 32 (top 4), the published layer_types: layers 0-5 are conv, conv,
# attention, conv, conv, conv; 0-1 dense
WIDTHS = dict(vocab_size=512, hidden_size=64, num_hidden_layers=6, intermediate_size=96,
              num_attention_heads=4, num_key_value_heads=2, num_experts=8,
              moe_intermediate_size=32)
TINY = model.Lfm2Config(**WIDTHS)
REF_TINY = dict(ref.PUBLISHED, **WIDTHS)
LAYERS = tuple(range(6))
EXPERTS = tuple(range(8))
PAGE_TOKENS, BLOCK = 128, 16
# pages in this order: {100, 28} (the second document ends at the page's last
# slot), then {37, 60} and {120} with pads
LENGTHS = (100, 28, 37, 60, 120)


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
    return {k: np.load(os.path.join(out_dir, "lfm2", f"{stem}_{k}.npy"))
            for k in ("lfm2", "timestamps_ms", "tokens")}


@pytest.fixture
def tiny(monkeypatch):
    """The published shape at tiny widths, attention in blocks of 16."""
    monkeypatch.setattr(model, "PUBLISHED", TINY)
    monkeypatch.setattr(extractor_module, "ATTENTION_BLOCK", BLOCK)


@pytest.fixture
def float32(monkeypatch):
    monkeypatch.setattr(text_layers, "DTYPE", jnp.float32)


def pre_rounded(flat):
    """Matrices as both sides round them (bfloat16); the expert bias is
    float32 on both."""
    import ml_dtypes

    return {k: v if k.endswith("/bias") else v.astype(ml_dtypes.bfloat16).astype(np.float32)
            for k, v in flat.items()}


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Seeded weights by the reference's own table, through the benchmark's
    generator and the program's checkpoint directory; the expert bias drawn
    wide enough to move choices."""
    spec = ref.weight_specs(REF_TINY, layers=LAYERS, experts=EXPERTS)
    flat = {name: pre_rounded(make_weights(s, 7, name)) for name, s in spec.items()}
    for n in flat["lfm2"]:
        if n.endswith("/choice/bias"):
            flat["lfm2"][n] = flat["lfm2"][n] * 4
    directory = str(tmp_path_factory.mktemp("weights"))
    write_npz(directory, "lfm2", flat["lfm2"])
    return directory, flat


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    rng = np.random.default_rng(0)
    d = tmp_path_factory.mktemp("corpus")
    return [transcript(str(d / f"v{i}.tokens.npz"), rng, n) for i, n in enumerate(LENGTHS)]


def page_documents(stats):
    return [r["ids"]["documents"] for r in stats["spans"]["records"] if "documents" in r["ids"]]


def extractor(tmp_path, sub, checkpoint_dir, monkeypatch, **kw):
    monkeypatch.setenv("VFT_CHECKPOINT_DIR", checkpoint_dir)
    return get_extractor(ExtractionConfig(
        feature_type="lfm2", on_extraction="save_numpy", page_tokens=PAGE_TOKENS,
        output_path=str(tmp_path / sub), tmp_path=str(tmp_path / "t"), **kw))


def test_program_matches_reference_and_packing_keeps_rows(tmp_path, tiny, float32, checkpoint,
                                                          corpus, monkeypatch):
    """Through ``Extractor.run`` on a corpus whose documents share pages, the
    ``.npy`` files against the plain reference; every assignment is held (all
    experts here); then the same documents one a page: the same rows (a
    document packed mid-page equals the document alone)."""
    directory, flat = checkpoint
    monkeypatch.setenv("VFT_METRICS", "1")  # the stage records say what each page held
    ex = extractor(tmp_path, "packed", directory, monkeypatch)
    assert ex.cfg.pack_corpus and ex.cfg.num_devices == 1
    assert ex.share == model.Share(LAYERS, EXPERTS)
    assert ex.run(corpus) == len(corpus)
    stats = ex._pack_stats
    assert page_documents(stats) == [[100, 28], [37, 60], [120]]
    assert stats["pages_dispatched"] == 3 and stats["real_slots"] == sum(LENGTHS)
    assert stats["routed_total"] == stats["routed_held"] == 4 * 4 * sum(LENGTHS)
    assert stats["expert_chunks"] == stats["expert_chunk_calls"] == 4 * 3  # one chunk a page and layer
    assert np.asarray(stats["expert_rows"]).shape == (4, 8)

    answer = ref.make_answer_fn({k: unflatten(v) for k, v in flat.items()}, REF_TINY,
                                activations="float32")
    packed = {}
    for path in corpus:
        want, got = answer(path), read_out(str(tmp_path / "packed"), path)
        assert got["lfm2"].dtype == np.float32
        assert got["lfm2"].shape == (len(want["tokens"]), TINY.hidden_size)
        assert row_gaps(got["lfm2"], want["lfm2"]).max() < 2e-5
        for k in ref.EXACT_KEYS:
            np.testing.assert_array_equal(got[k], want[k])
        packed[path] = got["lfm2"]

    for path in corpus:  # one document a page
        assert ex.run([path]) == 1
        assert ex._pack_stats["pages_dispatched"] == 1
        alone = read_out(str(tmp_path / "packed"), path)["lfm2"]
        assert row_gaps(alone, packed[path]).max() < 2e-5


def page_of(docs, tokens=PAGE_TOKENS):
    page = np.zeros((4, tokens), np.int32)
    table = np.zeros((tokens // extractor_module.SEGMENT_TOKENS_MIN, 3), np.int32)
    slices = build_token_page([(0, ids, ends) for ids, ends in docs], page, table)
    return jnp.asarray(page), slices


@pytest.mark.parametrize("kind", ["restart", "bias"])
def test_both_planted_faults_are_far_from_the_reference(kind, float32, checkpoint, corpus):
    """On the page of two documents: the short convolution not restarted at
    the second document, and the expert bias in the weights as well as the
    choice, each read over 1e-2 against the reference where the program reads
    under 2e-5. The restart shows on the second document alone."""
    _directory, flat = checkpoint
    params, share = model.stack_checkpoint(TINY, list(flat["lfm2"]), flat["lfm2"].__getitem__)
    docs = []
    for path in corpus[:2]:
        with np.load(path) as z:
            docs.append((z["ids"], z["segment_ends"]))
    page, slices = page_of(docs)
    features = ref.make_forward(ref.round_weights(unflatten(flat["lfm2"])), REF_TINY,
                                activations="float32")
    want = [features(ids, ends) for ids, ends in docs]

    def gaps():
        forward = jax.jit(functools.partial(model.forward, TINY, share, PAGE_TOKENS // 8, BLOCK,
                                            interpret=True))
        rows = np.asarray(forward(params, page)[0])
        return [float(row_gaps(rows[s], w).max()) for s, w in zip(slices, want)]

    sound = gaps()
    assert max(sound) < 2e-5
    with planted(kind):
        fault = gaps()
    if kind == "restart":
        assert fault[0] < 2e-5 and fault[1] > 1e-2, fault
    else:
        assert min(fault) > 1e-2, fault


def test_bfloat16_path(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """The arithmetic the type really runs, against the reference rounded
    where the program rounds (as ``correct`` takes it) and against the same
    reference with float32 activations, which reads farther."""
    directory, flat = checkpoint
    ex = extractor(tmp_path, "bf16", directory, monkeypatch)
    assert ex.run(corpus[:2]) == 2
    tree = {k: unflatten(v) for k, v in flat.items()}
    gaps = {act: np.concatenate([row_gaps(read_out(str(tmp_path / "bf16"), p)["lfm2"],
                                          answer(p)["lfm2"]) for p in corpus[:2]])
            for act, answer in ((act, ref.make_answer_fn(tree, REF_TINY, activations=act))
                                for act in ("bfloat16", "float32"))}
    assert np.isfinite(gaps["bfloat16"]).all()
    assert gaps["bfloat16"].max() < 0.05 and np.median(gaps["bfloat16"]) < 0.02
    assert np.median(gaps["float32"]) > 2 * np.median(gaps["bfloat16"])


# --- the attention kernel's paired 64-wide heads ----------------------------------

def plain_attention(q, k, v, doc, heads, kv, d):
    """Causal softmax within each document, query head ``a`` over key/value
    head ``a // (heads / kv)``, in float64."""
    n = q.shape[0]
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    seen = (doc[:, None] == doc[None, :]) & np.tril(np.ones((n, n), bool))
    o = np.zeros((n, heads * d))
    for a in range(heads):
        b = a // (heads // kv)
        s = q[:, a * d:(a + 1) * d] @ k[:, b * d:(b + 1) * d].T
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(1, keepdims=True))
        o[:, a * d:(a + 1) * d] = p / p.sum(1, keepdims=True) @ v[:, b * d:(b + 1) * d]
    return o


@pytest.mark.parametrize("heads,kv", [(16, 4), (8, 8), (4, 1)],
                         ids=["paired_4_to_1", "paired_one_to_one", "one_key_value_head"])
def test_64_wide_heads_against_a_plain_softmax(heads, kv, rng):
    """At ``head_dim`` 64 a grid step holds two key/value heads (a lane row of
    ``k`` and ``v``) and their query heads; one key/value head of 64 is a block
    the array's own width. Documents start mid-block; pads at the end."""
    d, tokens, block = 64, 256, 64
    doc = np.full(tokens, -1, np.int32)
    doc[:40], doc[40:41], doc[41:200] = 0, 1, 2
    q, k, v = (rng.standard_normal((tokens, n * d)).astype(np.float32) * 0.5
               for n in (heads, kv, kv))
    got = np.asarray(segment_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                       jnp.asarray(doc), kv_heads=kv, head_dim=d, block=block,
                                       interpret=True))
    want = plain_attention(q, k, v, doc, heads, kv, d)
    real = doc >= 0
    np.testing.assert_allclose(got[real], want[real], atol=2e-5, rtol=1e-5)


def test_the_published_attention_call_pairs_its_heads():
    """What LFM2's attention layer calls, at the published shape (32 query
    heads over 8 key/value heads of 64): a grid of 4 key/value steps, each a
    block of 128 lanes of ``k`` and ``v`` and 512 of ``q``; 128-wide heads keep
    one key/value head a step."""
    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for value in eqn.params.values():
                if hasattr(value, "jaxpr"):
                    yield from pallas_calls(value.jaxpr)

    def grid_and_blocks(heads, kv, d):
        tokens, block = 16384, 512
        q = jax.ShapeDtypeStruct((tokens, heads * d), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((tokens, kv * d), jnp.bfloat16)
        doc = jax.ShapeDtypeStruct((tokens,), jnp.int32)
        traced = jax.make_jaxpr(functools.partial(segment_attention, kv_heads=kv, head_dim=d,
                                                  block=block))(q, k, k, doc)
        (call,) = pallas_calls(traced.jaxpr)
        mapping = call.params["grid_mapping"]
        blocks = [tuple(int(getattr(b, "block_size", b)) for b in m.block_shape)
                  for m in mapping.block_mappings]
        return mapping.grid, blocks[:3]

    assert grid_and_blocks(32, 8, 64) == ((4, 32, 32), [(512, 512), (512, 128), (512, 128)])
    assert grid_and_blocks(48, 8, 128) == ((8, 32, 32), [(512, 768), (512, 128), (512, 128)])


# --- the routed layer: no shared expert, the renormalisation's eps, the share ------

def test_route_takes_an_eps_and_leaves_the_default_alone(rng):
    h = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8) * 0.2, jnp.float32)
    plain, ids = moe.route(h, w, 4, 1.0, scoring="sigmoid", bias=bias)
    with_eps, ids_eps = moe.route(h, w, 4, 1.0, scoring="sigmoid", bias=bias, eps=0.5)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_eps))
    top = np.asarray(jax.nn.sigmoid(h @ w))[np.arange(12)[:, None], np.asarray(ids)]
    np.testing.assert_allclose(np.asarray(plain), top / top.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(with_eps), top / (top.sum(1, keepdims=True) + 0.5),
                               rtol=1e-6)
    # eps 0 adds nothing to the program: the three routed cells compile as before
    text = jax.jit(lambda h: moe.route(h, w, 4, 1.0)).lower(h).as_text()
    assert text == jax.jit(lambda h: moe.route(h, w, 4, 1.0, eps=0.0)).lower(h).as_text()


def layer_weights(seed=11, experts=EXPERTS):
    spec = ref.weight_specs(REF_TINY, layers=(2,), experts=experts)["lfm2"]
    flat = make_weights(spec, seed, "lfm2")
    flat["layers/2/choice/bias"] = flat["layers/2/choice/bias"] * 4  # wide enough to move choices
    return flat


def test_the_routed_layer_without_a_shared_expert_against_a_plain_loop(float32):
    """``expert_layer`` with no ``shared_gate_up``: the sum starts at zero, and
    is each token's chosen experts' SwiGLU times its weight, written out token
    by token in numpy; a pad is routed nowhere and reads zero."""
    tokens = 40
    flat = layer_weights()
    params, share = model.stack_checkpoint(TINY, list(flat), pre_rounded(flat).__getitem__)
    p = params["layers"][0]
    assert "shared_gate_up" not in p and "shared_down" not in p
    h = np.random.default_rng(3).standard_normal((tokens, TINY.hidden_size)).astype(np.float32)
    valid = np.arange(tokens) < 36
    y, (routed_total, routed_held, rows, chunks, _runs) = text_layers.expert_layer(
        p, jnp.asarray(h), jnp.asarray(valid), jnp.arange(8, dtype=jnp.int32), 8,
        functools.partial(model.route, TINY), interpret=True)
    assert int(routed_total) == int(routed_held) == 36 * 4 and int(chunks) == 1
    f = {k: v.astype(np.float64) for k, v in pre_rounded(flat).items()}
    want = np.zeros((tokens, TINY.hidden_size))
    for t in range(36):
        s = 1 / (1 + np.exp(-(h[t] @ f["layers/2/router"])))
        chosen = np.argsort(-(s + f["layers/2/choice/bias"]), kind="stable")[:4]
        g = s[chosen] / (s[chosen].sum() + 1e-6)
        for e, ge in zip(chosen, g):
            pre = f"layers/2/experts/{e}"
            a = h[t] @ f[f"{pre}/gate_proj"]
            want[t] += ge * ((a / (1 + np.exp(-a))) * (h[t] @ f[f"{pre}/up_proj"])) @ f[f"{pre}/down_proj"]
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=1e-5)
    assert int(np.sum(rows)) == 36 * 4


def test_two_halves_of_the_experts_make_the_uncut_layer(float32):
    """The share ties to the model: what two chips each give for their own
    half of the experts (no shared expert to count once) adds up to the uncut
    reference's routed layer (sigmoid router, biased choice, eps)."""
    tokens = 48
    flat = layer_weights()
    w = ref.round_weights(unflatten(flat))["layers"]["2"]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((tokens, TINY.hidden_size)), jnp.float32)
    stack = lambda m, ids: jnp.stack([w["experts"][str(e)][m] for e in ids])  # noqa: E731
    bias = w["choice"]["bias"]
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_part(REF_TINY, h, w["router"], bias,
                                *(stack(m, EXPERTS) for m in ("gate_proj", "up_proj", "down_proj")),
                                jnp.asarray(EXPERTS), ref.rounder("float32"))
        unbiased = ref.routing(REF_TINY, h, w["router"], jnp.zeros_like(bias))[1]
        biased = ref.routing(REF_TINY, h, w["router"], bias)[1]
    assert (np.sort(np.asarray(unbiased), 1) != np.sort(np.asarray(biased), 1)).any()
    valid = jnp.ones((tokens,), bool)
    total, held_rows = 0.0, 0
    for ids in (EXPERTS[0::2], EXPERTS[1::2]):
        names = [n for n in flat if "/experts/" not in n or int(n.split("/")[3]) in ids]
        params, share = model.stack_checkpoint(TINY, names, pre_rounded(flat).__getitem__)
        assert share.experts == ids and share.layers == (2,)
        slot_of = np.full((TINY.num_experts,), -1, np.int32)
        slot_of[list(ids)] = np.arange(len(ids))
        y, (routed_total, routed_held, _rows, _chunks, _runs) = text_layers.expert_layer(
            params["layers"][0], h, valid, jnp.asarray(slot_of), len(ids),
            functools.partial(model.route, TINY), interpret=True)
        total = total + np.asarray(y)
        held_rows += int(routed_held)
        assert int(routed_total) == tokens * 4
    assert held_rows == tokens * 4  # every assignment on exactly one chip
    assert row_gaps(total, np.asarray(uncut)).max() < 2e-5


# --- the layers, the scopes, the table ---------------------------------------------

def test_the_short_convolution_restarts_at_each_document(float32, rng):
    """One conv layer over a page of three documents and pads against the
    reference's layer on each document alone: ``[B | C | u]``, ``B ⊙ u``, the
    three taps with zeros before a document's first token, ``C ⊙ w``."""
    spec = ref.weight_specs(REF_TINY, layers=(3,), experts=EXPERTS)["lfm2"]
    flat = make_weights(spec, 5, "lfm2")
    params, _share = model.stack_checkpoint(TINY, list(flat), pre_rounded(flat).__getitem__)
    w = ref.round_weights(unflatten(flat))["layers"]["3"]
    lengths, tokens = (50, 1, 2, 70), 128
    x = rng.standard_normal((tokens, TINY.hidden_size)).astype(np.float32)
    pos = np.zeros(tokens, np.int32)
    at = 0
    for n in lengths:
        pos[at:at + n] = np.arange(n)
        at += n
    got = np.asarray(model.short_conv(TINY, params["layers"][0], jnp.asarray(x), jnp.asarray(pos)))
    at = 0
    with jax.default_matmul_precision("highest"):
        for n in lengths:
            want = np.asarray(ref.short_conv(REF_TINY, w, jnp.asarray(x[at:at + n])))
            assert row_gaps(got[at:at + n], want).max() < 2e-5
            at += n
    # the taps reach two tokens back: without the restart the next document reads otherwise
    moved = np.asarray(model.short_conv(TINY, params["layers"][0], jnp.asarray(x),
                                        unrestarted(jnp.asarray(pos))))
    assert np.abs(moved[50:53] - got[50:53]).max() > 1e-3
    np.testing.assert_array_equal(moved[:50], got[:50])


def test_the_readers_scopes_survive_the_compile(tiny):
    """The benchmark's readers find the conv mixers by ``/attn/short_conv/``
    and their core by ``/attn/short_conv/core``; attention's scopes, the dense
    units under ``…/mlp/`` and the routed layers under ``…/moe/`` with no
    ``shared`` scope. A renamed scope fails here and not in a traced run."""
    shapes = model.leaf_shapes(TINY, LAYERS, EXPERTS)
    flat = {n: np.zeros(s, np.float32) for n, s in shapes.items()}
    params, share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    page = np.zeros((4, PAGE_TOKENS), np.int32)

    def forward(params, page):
        return model.forward(TINY, share, 16, BLOCK, params, page, interpret=True)

    hlo = jax.jit(forward).lower(params, jnp.asarray(page)).compile().as_text()
    names = {line.split('op_name="', 1)[1].split('"', 1)[0] for line in hlo.splitlines()
             if 'op_name="' in line}
    for layer in (0, 1, 3, 4, 5):
        for scope in ("proj", "core", "out"):
            assert any(f"lfm2/L{layer}/attn/short_conv/{scope}" in n for n in names), (layer, scope)
    for scope in ("qkv", "norm", "rope", "core", "out"):
        assert any(f"lfm2/L2/attn/{scope}" in n for n in names), scope
    assert not any("lfm2/L2/attn/short_conv" in n for n in names)
    for layer in (0, 1):
        assert any(f"lfm2/L{layer}/mlp/" in n for n in names) and not any(
            f"lfm2/L{layer}/moe/" in n for n in names), layer
    for layer in (2, 3, 4, 5):
        for scope in ("route", "dispatch", "experts", "combine"):
            assert any(f"lfm2/L{layer}/moe/" in n and f"moe/{scope}" in n for n in names), (layer, scope)
    assert not any("/moe/shared" in n for n in names)
    assert any("lfm2/embed" in n for n in names) and any("lfm2/pool" in n for n in names)


def test_weight_specs_and_the_checkpoints_layouts():
    spec = ref.weight_specs()["lfm2"]
    assert [l for l in range(40) if ref.is_attention(ref.PUBLISHED, l)] == list(range(2, 40, 4))
    assert [l for l in range(40) if model.PUBLISHED.is_attention(l)] == list(range(2, 40, 4))
    assert spec["layers/0/in_proj"] == (2048, 6144) and spec["layers/0/out_proj"] == (2048, 2048)
    assert spec["layers/0/conv/kernel"] == (3, 2048)
    assert spec["layers/2/q_proj"] == spec["layers/2/o_proj"] == (2048, 2048)
    assert spec["layers/2/k_proj"] == spec["layers/2/v_proj"] == (2048, 512)
    assert spec["layers/2/q_norm/scale"] == spec["layers/2/k_norm/scale"] == (64,)
    assert spec["layers/1/mlp/gate_proj"] == (2048, 11776) and "layers/1/router" not in spec
    assert spec["layers/5/router"] == (2048, 64) and spec["layers/5/choice/bias"] == (64,)
    assert spec["layers/5/experts/63/down_proj"] == (1536, 2048)
    assert not any("/shared" in n for n in spec)
    assert spec["embed/embedding"] == (65536, 2048)
    count = lambda pre: sum(int(np.prod(s)) for n, s in spec.items() if n.startswith(pre))  # noqa: E731
    assert count("layers/0/") == 89_139_200 and count("layers/2/") == 614_600_896
    assert count("layers/3/") == 620_898_368
    total = sum(int(np.prod(s)) for s in spec.values())
    assert total == 2_789_794_176  # 5.58 GB in bfloat16
    assert model.leaf_shapes(model.PUBLISHED, range(6), range(64)) == spec

    tiny_spec = model.leaf_shapes(TINY, (1, 2), EXPERTS)
    flat = {n: np.arange(int(np.prod(s)), dtype=np.float32).reshape(s) % 251 for n, s in tiny_spec.items()}
    params, share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    assert share == model.Share((1, 2), EXPERTS)
    conv_layer, attention_layer = params["layers"]
    np.testing.assert_array_equal(
        np.asarray(attention_layer["wqkv"], np.float32),
        np.concatenate([flat[f"layers/2/{m}_proj"] for m in "qkv"], axis=-1))
    assert attention_layer["router_bias"].dtype == jnp.float32
    assert set(conv_layer) == {"attn_norm", "mlp_norm", "w_in", "conv", "w_out", "w_gate_up", "w_down"}
    assert attention_layer["experts_gate_up"].shape == (8, 64, 64)


def test_configuration_file_keeps_every_published_number():
    root = os.path.dirname(BENCH)
    with open(os.path.join(BENCH, "configs", "lfm2_24b_a2b_bf16.json")) as f:
        conf = json.load(f)
    cfg = model.PUBLISHED
    # the catalog row's `config`, every number under the same key but the one cut
    catalog = {"conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 11776,
               "max_position_embeddings": 128000, "moe_intermediate_size": 1536, "norm_eps": 1e-05,
               "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
               "num_experts_per_tok": 4, "num_key_value_heads": 8, "routed_scaling_factor": 1,
               "vocab_size": 65536}
    for key, value in catalog.items():
        assert conf[key] == value, key
        if key in ref.PUBLISHED:
            assert ref.PUBLISHED[key] == value and getattr(cfg, key) == value, key
    assert conf["conv_bias"] is False and conf["norm_topk_prob"] is True
    assert conf["use_expert_bias"] is True and conf["model_type"] == "lfm2_moe"
    assert conf["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert cfg.rope_theta == ref.PUBLISHED["rope_theta"] == 1000000
    assert tuple(conf["layer_types"]) == cfg.layer_types == ref.PUBLISHED["layer_types"]
    assert len(conf["layer_types"]) == conf["published"]["num_hidden_layers"] == 40
    assert conf["reduced"] == ["num_hidden_layers"] and conf["num_hidden_layers"] == len(ref.LAYERS) == 6
    assert conf["feature_type"] == "lfm2" and conf["reference"] == conf["flops"] == "lfm2"
    assert "2,789,794,176" in conf["parameters"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b_bf16"][0]["reduced"] == [
        "num_hidden_layers"]
    cell = [w for w in bench["workloads"] if w["config"] == "lfm2_24b_a2b_bf16"]
    assert [(w["name"], w["chips"], w["traffic"]) for w in cell] == [
        ("lfm2_24b_a2b_bf16.corpus_transcripts_64k", 1, "corpus_transcripts_64k")]
    listed = {m["name"] for m in bench["per_layer"] if cell[0]["name"] in m.get("workloads", ())}
    assert {"short_conv_pct", "short_conv_roofline", "attention_pct", "moe_dispatch_pct",
            "expert_load_max_over_mean", "step_mfu", "device_idle_pct", "setup_weights_s",
            "setup_compile_s", "setup_compiles", "setup_program_s"} <= listed
    assert not listed & {"mamba_pct", "gdn_pct", "moe_experts_roofline", "attn_core_roofline"}
    assert conf["window_videos"] % 16 == 0

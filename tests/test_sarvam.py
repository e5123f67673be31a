"""The text stream's second model (``--feature_type sarvam``: latent attention
with a shared rope key, a sigmoid router with a choice bias) at tiny widths on
the CPU: the program against the benchmark's plain reference through
``Extractor.run``, the attention kernel's shared-key term, the rope, the
router, the expert share and the weight table. The two Pallas kernels are the
chip's, run in the Pallas interpreter. Arithmetic is checked in float32
(``models.text_layers.DTYPE`` patched); the bfloat16 path is run once and held
loosely. What the two models share (pages, packing, the grouped product, the
daemon's session) is ``tests/test_laguna.py``'s.
"""

# fast-registry: page program compiles (both Pallas kernels in the interpreter)

import functools
import json
import math
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
from reference import sarvam as ref  # noqa: E402
from weights import make_leaf, make_weights, unflatten, write_npz  # noqa: E402

from video_features_tpu.config import ExtractionConfig  # noqa: E402
from video_features_tpu.extractors import get_extractor  # noqa: E402
from video_features_tpu.extractors import token_pages as extractor_module  # noqa: E402
from video_features_tpu.models import sarvam as model  # noqa: E402
from video_features_tpu.models import text_layers  # noqa: E402
from video_features_tpu.ops import moe  # noqa: E402
from video_features_tpu.ops.segment_attention import segment_attention  # noqa: E402

# four heads of 16 + 32 rotated (a lane row of shared-part queries a step), a
# latent of 32, sixteen experts of which a chip holds two
WIDTHS = dict(vocab_size=512, hidden_size=64, intermediate_size=128, num_attention_heads=4,
              qk_nope_head_dim=16, qk_rope_head_dim=32, v_head_dim=16, kv_lora_rank=32,
              num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32)
TINY = model.SarvamConfig(yarn_original_max_position_embeddings=64, **WIDTHS)
REF_TINY = dict(ref.PUBLISHED, **WIDTHS)
REF_TINY["rope_scaling"] = dict(ref.PUBLISHED["rope_scaling"], original_max_position_embeddings=64)
LAYERS = (0, 1, 2, 3, 4)
HELD = (0, 1)  # an eighth of the 16 experts
PAGE_TOKENS, BLOCK = 128, 16
LENGTHS = (100, 37, 60, 120, 20)  # pages in this order: {100} once 256 tokens wait, then at the flush {37, 60, 20}, {120}


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
    return {k: np.load(os.path.join(out_dir, "sarvam", f"{stem}_{k}.npy"))
            for k in ("sarvam", "timestamps_ms", "tokens")}


@pytest.fixture
def tiny(monkeypatch):
    """The published shape at tiny widths, attention in blocks of 16."""
    monkeypatch.setattr(model, "PUBLISHED", TINY)
    monkeypatch.setattr(extractor_module, "ATTENTION_BLOCK", BLOCK)


@pytest.fixture
def float32(monkeypatch):
    monkeypatch.setattr(text_layers, "DTYPE", jnp.float32)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Seeded weights by the reference's own table, through the benchmark's
    generator and the program's checkpoint directory; matrices pre-rounded to
    bfloat16 so that the float32 check sees the weights both sides round to
    (the expert bias is float32 on both)."""
    import ml_dtypes

    spec = ref.weight_specs(REF_TINY, layers=LAYERS, experts=HELD)
    flat = {name: {k: v if k.endswith("/bias") else v.astype(ml_dtypes.bfloat16).astype(np.float32)
                   for k, v in make_weights(s, 7, name).items()} for name, s in spec.items()}
    directory = str(tmp_path_factory.mktemp("weights"))
    write_npz(directory, "sarvam", flat["sarvam"])
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
        feature_type="sarvam", on_extraction="save_numpy", page_tokens=PAGE_TOKENS,
        output_path=str(tmp_path / sub), tmp_path=str(tmp_path / "t"), **kw))


def test_program_matches_reference_and_packing_keeps_rows(tmp_path, tiny, float32, checkpoint,
                                                          corpus, monkeypatch):
    """Through ``Extractor.run`` on a corpus whose documents share pages, the
    ``.npy`` files against the plain reference; then the same documents one a
    page, and in the order that packs them into other pages: the same rows.
    The reference WITHOUT the shared rope term is far from both: the
    comparison sees MLA's own fault."""
    directory, flat = checkpoint
    monkeypatch.setenv("VFT_METRICS", "1")  # the stage records say what each page held
    ex = extractor(tmp_path, "packed", directory, monkeypatch)
    assert ex.cfg.pack_corpus and ex.cfg.num_devices == 1
    assert ex.share == model.Share(LAYERS, HELD)
    assert ex.run(corpus) == len(corpus)
    stats = ex._pack_stats
    assert stats["pages_dispatched"] == 3 and stats["real_slots"] == sum(LENGTHS)
    assert page_documents(stats) == [[100], [37, 60, 20], [120]]
    sparse = sum(1 for l in LAYERS if not TINY.is_dense(l))
    assert stats["routed_total"] == TINY.num_experts_per_tok * sum(LENGTHS) * sparse
    assert stats["routed_held"] == int(np.sum(stats["expert_rows"])) < stats["routed_total"]
    assert np.asarray(stats["expert_rows"]).shape == (sparse, len(HELD))
    # one chunk a routed layer and page unless a page held more than a chunk's rows (ops/moe.py)
    assert stats["expert_chunks"] >= stats["expert_chunk_calls"] == sparse * stats["pages_dispatched"]

    tree = {k: unflatten(v) for k, v in flat.items()}
    answer = ref.make_answer_fn(tree, REF_TINY)
    without = ref.make_forward(ref.round_weights(unflatten(flat["sarvam"])), REF_TINY,
                               rope_term=False)
    packed = {}
    for path in corpus:
        want, got = answer(path), read_out(str(tmp_path / "packed"), path)
        assert got["sarvam"].dtype == np.float32
        assert got["sarvam"].shape == (len(want["tokens"]), TINY.hidden_size)
        assert row_gaps(got["sarvam"], want["sarvam"]).max() < 2e-5
        for k in ref.EXACT_KEYS:
            np.testing.assert_array_equal(got[k], want[k])
        with np.load(path) as z:
            assert row_gaps(without(z["ids"], z["segment_ends"]), want["sarvam"]).max() > 1e-2
        packed[path] = got["sarvam"]

    for path in corpus:  # one document a page
        assert ex.run([path]) == 1
        assert ex._pack_stats["pages_dispatched"] == 1
        alone = read_out(str(tmp_path / "packed"), path)["sarvam"]
        assert row_gaps(alone, packed[path]).max() < 2e-5

    # the other way round the same documents pack as {20, 100}, {120}, {60, 37} (the first page
    # passes 60 + 37 over for 100): a document's rows do not depend on the company it keeps
    assert ex.run(corpus[::-1]) == len(corpus)
    assert page_documents(ex._pack_stats) == [[20, 100], [120], [60, 37]]
    for path in corpus:
        turned = read_out(str(tmp_path / "packed"), path)["sarvam"]
        assert row_gaps(turned, packed[path]).max() < 2e-5


def test_bfloat16_path(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """The arithmetic the type really runs, held loosely at this width (a
    bfloat16 rounding is 0.4 % of a value here and a router's near-tie flips
    on it)."""
    directory, flat = checkpoint
    ex = extractor(tmp_path, "bf16", directory, monkeypatch)
    assert ex.run(corpus[:3]) == 3
    answer = ref.make_answer_fn({k: unflatten(v) for k, v in flat.items()}, REF_TINY)
    gaps = np.concatenate([row_gaps(read_out(str(tmp_path / "bf16"), p)["sarvam"],
                                    answer(p)["sarvam"]) for p in corpus[:3]])
    assert np.isfinite(gaps).all() and np.median(gaps) < 0.1


def test_daemon_serves_the_type(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """``--serve`` with ``sarvam``: a request goes through the daemon's
    ``PackedSession`` to the same files."""
    from video_features_tpu.serve.daemon import ExtractionService

    directory, _flat = checkpoint
    spool = tmp_path / "spool"
    spool.mkdir()
    ex = extractor(tmp_path, "served", directory, monkeypatch, serve=True, spool_dir=str(spool),
                   idle_flush_sec=0.0, serve_models=("sarvam",))
    svc = ExtractionService(ex, poll_interval=0.001)
    request = svc.submit({"videos": corpus[:2]})
    svc.request_drain()
    assert svc.run() == 0 and request.state == "done"
    svc.close()
    for path in corpus[:2]:
        got = read_out(str(tmp_path / "served"), path)
        assert got["sarvam"].shape == (len(got["tokens"]), TINY.hidden_size)
        assert np.isfinite(got["sarvam"]).all() and np.abs(got["sarvam"]).max() > 0


# --- the kernel's shared-key term -----------------------------------------------

def naive_latent(q, k, v, qs, ks, doc, heads, d, r):
    t = np.arange(len(doc))
    seen = (doc[:, None] == doc[None, :]) & (t[None, :] <= t[:, None])
    out = np.zeros_like(v)
    for h in range(heads):
        cols = slice(h * d, (h + 1) * d)
        s = q[:, cols] @ k[:, cols].T + qs[:, h * r:(h + 1) * r] @ ks.T
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(1, keepdims=True))
        out[:, cols] = p / p.sum(1, keepdims=True) @ v[:, cols]
    return out


@pytest.mark.parametrize("lengths", [(20,), (40, 7, 50, 20), (128,), (5, 16, 33, 1, 60)],
                         ids=["one_short", "mixed", "whole_page", "starts_mid_tile"])
@pytest.mark.parametrize("heads,r", [(4, 32), (16, 64), (2, 64)], ids=["4x32", "16x64", "2x64"])
def test_shared_key_term_against_a_plain_einsum(lengths, heads, r, rng):
    """``q·kᵀ + q_shared·k_sharedᵀ`` per head against materialised scores:
    documents that start inside a tile of keys, pads, several grid steps of
    heads (16 heads in steps of 8) and fewer heads than a step holds."""
    tokens, d = 128, 16
    doc = np.full(tokens, -1, np.int32)
    doc[:sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
    q, k, v = (rng.standard_normal((tokens, heads * d)).astype(np.float32) for _ in range(3))
    qs = rng.standard_normal((tokens, heads * r)).astype(np.float32)
    ks = rng.standard_normal((tokens, r)).astype(np.float32)
    got = np.asarray(segment_attention(
        *(jnp.asarray(a) for a in (q, k, v, doc)), kv_heads=heads, head_dim=d, block=BLOCK,
        interpret=True, q_shared=jnp.asarray(qs), k_shared=jnp.asarray(ks)))
    real = doc >= 0
    want = naive_latent(q, k, v, qs, ks, doc, heads, d, r)
    np.testing.assert_allclose(got[real], want[real], atol=3e-5)
    plain = np.asarray(segment_attention(*(jnp.asarray(a) for a in (q, k, v, doc)),
                                         kv_heads=heads, head_dim=d, block=BLOCK, interpret=True))
    assert np.abs(plain[real] - got[real]).max() > 1e-2  # the term is there


def pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            if hasattr(value, "jaxpr"):
                yield from pallas_calls(value.jaxpr)


@pytest.mark.parametrize("window,name,heads,kv,d", [
    (None, "segment_attention_full", 48, 8, 128), (512, "segment_attention_window", 72, 8, 128),
    (None, "segment_attention_full", 16, 2, 256)], ids=["full", "window", "full_256_wide"])
def test_a_laguna_call_lowers_to_the_parents_kernel(window, name, heads, kv, d):
    """What Laguna's layers call, at the published shapes, traces to the
    ``pallas_call`` the parent commit's kernel file made (read there: name,
    grid, operands, blocks); the latent call is another name with two more
    operands; Qwen3-Next's full layer (eight 256-wide query heads to one
    key/value head) is the same kernel under the same name at another
    ``head_dim`` and ``group``."""
    tokens, block = 16384, 512
    q = jax.ShapeDtypeStruct((tokens, heads * d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((tokens, kv * d), jnp.bfloat16)
    doc = jax.ShapeDtypeStruct((tokens,), jnp.int32)
    traced = jax.make_jaxpr(functools.partial(segment_attention, kv_heads=kv, head_dim=d,
                                              window=window, block=block))(q, k, k, doc)
    (call,) = pallas_calls(traced.jaxpr)
    group = heads // kv
    assert call.params["name"] == name
    assert [v.aval.shape for v in call.invars] == [
        (32,), (tokens, heads * d), (tokens, kv * d), (tokens, kv * d), (tokens, 1), (1, tokens)]
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (kv, 32, 32 if window is None else 2)
    blocks = [tuple(int(getattr(b, "block_size", b)) for b in m.block_shape)
              for m in mapping.block_mappings]
    assert blocks == [(block, group * d), (block, d), (block, d), (block, 1), (1, block),
                      (block, group * d)]
    assert [v.aval.shape for v in call.outvars] == [(tokens, heads * d)]

    heads, r, d = 64, 64, 128
    q = jax.ShapeDtypeStruct((tokens, heads * d), jnp.bfloat16)
    latent = jax.make_jaxpr(functools.partial(segment_attention, kv_heads=heads, head_dim=d,
                                              block=block))(
        q, q, q, doc, q_shared=jax.ShapeDtypeStruct((tokens, heads * r), jnp.bfloat16),
        k_shared=jax.ShapeDtypeStruct((tokens, r), jnp.bfloat16))
    (call,) = pallas_calls(latent.jaxpr)
    assert call.params["name"] == "segment_attention_latent"
    shapes = [v.aval.shape for v in call.invars]
    assert shapes[4:6] == [(tokens, heads * r), (tokens, 256)]  # the ONE key, a lane row a place
    assert len(shapes) == 8


# --- rope, router, share -------------------------------------------------------

def test_rope_against_float64_formula():
    """YaRN over the 64 rotated of a head's 192 at the PUBLISHED parameters
    against the formula written out in float64, pairs ``(2i, 2i + 1)``; the
    program's halves are the same rotation in another column order, so the
    scores agree; ``mscale²`` in the scale and 1 on cos and sin."""
    cfg = model.PUBLISHED
    pos = np.array([0, 1, 5, 511, 512, 4097, 16383], np.int64)
    rot, theta, orig, factor = 64, 10000.0, 4096, 40.0
    inv = theta ** (-np.arange(0, rot, 2) / rot)
    dim = lambda turns: rot * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
    low, high = math.floor(dim(32.0)), math.ceil(dim(1.0))
    assert 0 < low < high < rot // 2  # the blend is a real one at the published numbers
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    inv_p, factor_p = model.rope_inv_freq(cfg)
    np.testing.assert_allclose(inv_p, inv, rtol=1e-14)
    assert factor_p == 1.0
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(40.0) + 1.0) ** 2)
    assert cfg.softmax_scale == pytest.approx(0.072169 * 1.873854, rel=1e-5)
    assert ref.softmax_scale(ref.PUBLISHED) == pytest.approx(cfg.softmax_scale, rel=1e-12)

    rng = np.random.default_rng(1)
    x, y = rng.standard_normal((2, len(pos), 3, rot))
    angle = pos[:, None] * inv[None, :]
    cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]

    def pairs(z):
        a, b = z[..., 0::2], z[..., 1::2]
        return np.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(z.shape)

    rc, rs = ref.rope_tables(ref.PUBLISHED, pos, dtype=np.float64)
    np.testing.assert_allclose(rc[:, None], cos, atol=1e-12)
    np.testing.assert_allclose(rs[:, None], sin, atol=1e-12)
    np.testing.assert_allclose(np.asarray(ref.rotate(jnp.asarray(x[:4], jnp.float32),
                                                     rc[:4].astype(np.float32),
                                                     rs[:4].astype(np.float32))),
                               pairs(x)[:4], atol=1e-4)
    halves = np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2)])  # stack_checkpoint's
    got_x, got_y = (np.asarray(text_layers.apply_rope(
        jnp.asarray(z[..., halves], jnp.float32), jnp.asarray(pos, jnp.int32), inv_p, factor_p))
        for z in (x, y))
    want = np.einsum("phd,pgd->phg", pairs(x), pairs(y))
    got = np.einsum("phd,pgd->phg", got_x, got_y)
    np.testing.assert_allclose(got[:4], want[:4], atol=2e-4)
    np.testing.assert_allclose(got, want, atol=0.15)  # float32 angles at 16,383


def test_bias_moves_the_choice_and_not_the_weights_and_pads_are_not_routed():
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.2, jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(h @ router))
    w0, e0 = (np.asarray(a) for a in moe.route(h, router, 4, 2.5, scoring="sigmoid"))
    np.testing.assert_array_equal(np.sort(e0, 1), np.sort(np.argsort(-scores, 1)[:, :4], 1))
    bias = np.zeros(16, np.float32)
    bias[5] = 10.0   # expert 5 is always chosen
    w1, e1 = (np.asarray(a) for a in moe.route(h, router, 4, 2.5, scoring="sigmoid",
                                               bias=jnp.asarray(bias)))
    assert (e1 == 5).any(axis=1).all() and not (e0 == 5).any(axis=1).all()
    picked = np.take_along_axis(scores, e1, axis=1)  # the unbiased scores of the biased choice
    np.testing.assert_allclose(w1, 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w1.sum(1), 2.5, rtol=1e-6)
    rw, re = (np.asarray(a) for a in ref.routing(dict(num_experts_per_tok=4,
                                                      routed_scaling_factor=2.5),
                                                 h, router, jnp.asarray(bias)))
    np.testing.assert_array_equal(re, e1)
    np.testing.assert_allclose(rw, w1, rtol=1e-6)
    # softmax stays what it was: the default, no bias
    ws, es = moe.route(h, router, 4, 2.5)
    probs = np.asarray(jax.nn.softmax(h @ router, axis=-1))
    np.testing.assert_array_equal(np.asarray(es), np.argsort(-probs, 1)[:, :4])
    with pytest.raises(ValueError, match="scoring"):
        moe.route(h, router, 4, 2.5, scoring="tanh")
    valid = jnp.arange(32) < 20
    d = moe.dispatch(jnp.asarray(e1), valid, jnp.arange(16, dtype=jnp.int32), 16)
    assert int(d.group_sizes.sum()) == 20 * 4
    assert not bool(d.held[20:].any()) and bool(d.held[:20].all())


def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer(float32):
    """The share ties to the model: what the eight chips of a stage each give
    for their own eighth of the experts, plus the shared expert counted once,
    is the uncut reference's expert layer (sigmoid router, biased choice)."""
    tokens, all_experts = 48, tuple(range(TINY.num_experts))
    spec = ref.weight_specs(REF_TINY, layers=(1,), experts=all_experts)["sarvam"]
    flat = make_weights(spec, 11, "sarvam")
    flat["layers/1/choice/bias"] = flat["layers/1/choice/bias"] * 4  # wide enough to move choices
    w = ref.round_weights(unflatten(flat))["layers"]["1"]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((tokens, TINY.hidden_size)), jnp.float32)
    stack = lambda m, ids: jnp.stack([w["experts"][str(e)][m] for e in ids])  # noqa: E731
    bias = w["choice"]["bias"]
    uncut = (ref.routed_part(REF_TINY, h, w["router"], bias,
                             *(stack(m, all_experts) for m in ("gate_proj", "up_proj", "down_proj")),
                             jnp.asarray(all_experts)) + ref.shared_part(h, w["shared"]))
    unbiased = ref.routing(REF_TINY, h, w["router"], jnp.zeros_like(bias))[1]
    assert (np.sort(np.asarray(unbiased), 1)
            != np.sort(np.asarray(ref.routing(REF_TINY, h, w["router"], bias)[1]), 1)).any()
    valid = jnp.ones((tokens,), bool)
    total, held_rows = None, 0
    for rank in range(8):
        ids = all_experts[rank::8]  # any eight-way split of the experts
        names = [n for n in flat if "/experts/" not in n or int(n.split("/")[3]) in ids]

        def read(n):
            a = np.asarray(flat[n])
            return a if n.endswith("/bias") else a.astype(jnp.bfloat16).astype(np.float32)

        params, share = model.stack_checkpoint(TINY, names, read)
        assert share.experts == tuple(sorted(ids)) and share.layers == (1,)
        slot_of = np.full((TINY.num_experts,), -1, np.int32)
        slot_of[list(share.experts)] = np.arange(len(ids))
        p = params["layers"][0]
        assert p["router_bias"].dtype == jnp.float32
        y, (routed_total, routed_held, rows, _chunks, _runs) = text_layers.expert_layer(
            p, h, valid, jnp.asarray(slot_of), len(ids), functools.partial(model.route, TINY),
            interpret=True)
        routed = y - text_layers.gated_mlp(h, p["shared_gate_up"], p["shared_down"])
        total = routed if total is None else total + routed
        held_rows += int(routed_held)
        assert int(routed_total) == tokens * TINY.num_experts_per_tok
        assert int(np.sum(rows)) == int(routed_held)
    total = total + ref.shared_part(h, w["shared"])
    assert held_rows == tokens * TINY.num_experts_per_tok  # every assignment on exactly one chip
    assert row_gaps(np.asarray(total), np.asarray(uncut)).max() < 1e-5


def test_weight_specs_and_the_checkpoints_layouts():
    spec = ref.weight_specs()["sarvam"]
    assert spec["layers/3/experts/7/gate_proj"] == (4096, 2048)
    assert spec["layers/3/experts/7/down_proj"] == (2048, 4096)
    assert spec["layers/1/router"] == (4096, 128) and spec["layers/1/choice/bias"] == (128,)
    assert spec["layers/2/q_proj"] == (4096, 64 * 192) and spec["layers/2/kv_a_proj"] == (4096, 576)
    assert spec["layers/2/kv_b_proj"] == (512, 64 * 256) and spec["layers/2/o_proj"] == (8192, 4096)
    assert spec["layers/0/mlp/gate_proj"] == (4096, 16384) and "layers/0/router" not in spec
    assert spec["embed/embedding"] == (262144, 4096)
    assert not any(len(shape) > 2 for shape in spec.values())  # nothing stacked: fan-in is rows
    total = sum(int(np.prod(s)) for s in spec.values())
    assert 3.461e9 < total < 3.462e9
    assert {int(n.split("/")[3]) for n in spec if "/experts/" in n} == set(range(16))
    assert model.leaf_shapes(model.PUBLISHED, range(5), range(16)) == spec
    bias = make_leaf(np.random.default_rng(0), "layers/1/choice/bias", (128,))
    assert 0.02 < float(bias.std()) < 0.08  # drawn as a bias is: a small normal

    # stack_checkpoint regroups the published layouts: a head's columns by kind
    tiny_spec = model.leaf_shapes(TINY, (1,), (0, 1))
    flat = {n: np.arange(int(np.prod(s)), dtype=np.float32).reshape(s) % 251 for n, s in tiny_spec.items()}
    params, _share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    p, heads, dn, dr = params["layers"][0], 4, 16, 32
    q = flat["layers/1/q_proj"].reshape(64, heads, dn + dr)
    wq = np.asarray(p["wq"], np.float32)
    np.testing.assert_array_equal(wq[:, :heads * dn], q[..., :dn].reshape(64, -1))
    rot = q[..., dn:]
    np.testing.assert_array_equal(wq[:, heads * dn:].reshape(64, heads, dr),
                                  np.concatenate([rot[..., 0::2], rot[..., 1::2]], axis=-1))
    kvb = flat["layers/1/kv_b_proj"].reshape(32, heads, 2 * dn)
    np.testing.assert_array_equal(np.asarray(p["wkvb"], np.float32),
                                  np.concatenate([kvb[..., :dn].reshape(32, -1),
                                                  kvb[..., dn:].reshape(32, -1)], axis=-1))
    kva = flat["layers/1/kv_a_proj"]
    np.testing.assert_array_equal(np.asarray(p["wkva"], np.float32),
                                  np.concatenate([kva[:, :32], kva[:, 32::2], kva[:, 33::2]], axis=-1))


def test_configuration_file_keeps_every_published_number():
    root = os.path.dirname(BENCH)
    with open(os.path.join(BENCH, "configs", "sarvam_105b_bf16.json")) as f:
        conf = json.load(f)
    cfg = model.PUBLISHED
    for key in ("vocab_size", "hidden_size", "intermediate_size", "num_attention_heads",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "first_k_dense_replace", "rms_norm_eps", "num_experts_per_tok",
                "moe_intermediate_size", "num_shared_experts", "routed_scaling_factor",
                "rope_theta"):
        assert conf[key] == getattr(cfg, key) == ref.PUBLISHED[key], key
    assert conf["q_head_dim"] == cfg.q_head_dim == 192
    assert conf["head_dim"] == cfg.kv_lora_rank + cfg.qk_rope_head_dim == 576
    assert conf["moe_router_enable_expert_bias"] is True and conf["use_qk_norm"] is True
    scaling = conf["rope_scaling"]
    assert scaling == {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                       "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                       "type": "deepseek_yarn"}
    assert (scaling["factor"], scaling["original_max_position_embeddings"]) == (
        cfg.yarn_factor, cfg.yarn_original_max_position_embeddings)
    for key in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale",
                "mscale_all_dim"):
        assert ref.PUBLISHED["rope_scaling"][key] == scaling[key]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts"]
    assert conf["num_experts"] == len(ref.EXPERTS) == 16
    assert conf["num_hidden_layers"] == len(ref.LAYERS) == 5
    assert conf["published"]["num_experts"] == cfg.num_experts == ref.PUBLISHED["num_experts"]
    assert conf["published"]["num_hidden_layers"] == 32
    assert set(conf["assumed"]) >= {"A1", "A2", "A3", "A4", "A5"}
    assert conf["feature_type"] == "sarvam" and conf["reference"] == conf["flops"] == "sarvam"
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert any(c["file"] == "benchmark/configs/sarvam_105b_bf16.json"
               and c["reduced"] == conf["reduced"] for c in bench["configs"])
    cell = [w for w in bench["workloads"] if w["config"] == "sarvam_105b_bf16"]
    assert [(w["name"], w["chips"]) for w in cell] == [("sarvam_105b_bf16.corpus_transcripts", 1)]

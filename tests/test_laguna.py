"""The text stream (``--feature_type laguna``) at tiny widths on the CPU: the
program against the benchmark's plain reference through ``Extractor.run``,
packing, both attention kinds, both ropes, the expert share, the routing
counters and the weight table. The two Pallas kernels (attention, the grouped
product) are the chip's, run in the Pallas interpreter. Arithmetic is checked in float32 (``models.text_layers.DTYPE``
patched): at a width of 64 the program's bfloat16 would swamp a misplaced
mask or a wrong rope; the bfloat16 path itself is run once and held loosely.
"""

# fast-registry: page program compiles (both Pallas kernels in the interpreter)

import functools
import itertools
import json
import math
import os
import sys
import types

import numpy as np
import pytest

import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from check import row_gaps  # noqa: E402
from reference import laguna as ref  # noqa: E402
from weights import make_leaf, make_weights, unflatten, write_npz  # noqa: E402

from video_features_tpu.config import ExtractionConfig  # noqa: E402
from video_features_tpu.extractors import get_extractor  # noqa: E402
from video_features_tpu.extractors import token_pages as extractor_module  # noqa: E402
from video_features_tpu.models import laguna as model  # noqa: E402
from video_features_tpu.models import text_layers  # noqa: E402
from video_features_tpu.ops import moe  # noqa: E402
from video_features_tpu.ops.segment_attention import first_key_block, segment_attention  # noqa: E402
from video_features_tpu.parallel.packer import CorpusPacker, PackSpec  # noqa: E402
from video_features_tpu.parallel.pages import (DOC, IDS, PAGES_QUEUED, POS, SEG,  # noqa: E402
                                               build_token_page, fit_documents)
from video_features_tpu.reliability import load_failures  # noqa: E402

WIDTHS = dict(vocab_size=512, hidden_size=64, intermediate_size=128, num_key_value_heads=2,
              head_dim=16, heads_full=4, heads_sliding=6, sliding_window=24, num_experts=16,
              num_experts_per_tok=4, moe_intermediate_size=32,
              shared_expert_intermediate_size=32)
TINY = model.LagunaConfig(yarn_original_max_position_embeddings=64, **WIDTHS)
REF_TINY = dict(ref.PUBLISHED, **WIDTHS)
REF_TINY["full_rope"] = dict(ref.PUBLISHED["full_rope"], original_max_position_embeddings=64)
LAYERS = (0, 1, 2, 3, 4)
HELD = (0, 1, 2, 3)  # a quarter of the 16 experts
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
    return {k: np.load(os.path.join(out_dir, "laguna", f"{stem}_{k}.npy"))
            for k in ("laguna", "timestamps_ms", "tokens")}


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
    generator and the program's checkpoint directory; values pre-rounded to
    bfloat16 so that the float32 check sees the weights both sides round to."""
    import ml_dtypes

    spec = ref.weight_specs(REF_TINY, layers=LAYERS, experts=HELD)
    flat = {name: {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
                   for k, v in make_weights(s, 7, name).items()} for name, s in spec.items()}
    directory = str(tmp_path_factory.mktemp("weights"))
    write_npz(directory, "laguna", flat["laguna"])
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
        feature_type="laguna", on_extraction="save_numpy", page_tokens=PAGE_TOKENS,
        output_path=str(tmp_path / sub), tmp_path=str(tmp_path / "t"), **kw))


def test_program_matches_reference_and_packing_keeps_rows(tmp_path, tiny, float32, checkpoint,
                                                          corpus, monkeypatch):
    """Through ``Extractor.run`` on a corpus whose documents share pages, the
    ``.npy`` files against the plain reference; then the same documents one a
    page (one run each on the same program), and in the order that packs them
    into other pages: the same rows."""
    directory, flat = checkpoint
    monkeypatch.setenv("VFT_METRICS", "1")  # the stage records say what each page held
    ex = extractor(tmp_path, "packed", directory, monkeypatch)
    assert ex.cfg.pack_corpus and ex.share == model.Share(LAYERS, HELD)
    assert ex.run(corpus) == len(corpus)
    stats = ex._pack_stats
    assert stats["pages_dispatched"] == 3 and stats["real_slots"] == sum(LENGTHS)
    assert page_documents(stats) == [[100], [37, 60, 20], [120]]
    assert stats["dispatched_slots"] == 3 * PAGE_TOKENS
    # routing counts: top-k assignments per REAL token and no pad token routed
    sparse = sum(1 for l in LAYERS if not TINY.is_dense(l))
    assert stats["routed_total"] == TINY.num_experts_per_tok * sum(LENGTHS) * sparse
    assert stats["routed_held"] == int(np.sum(stats["expert_rows"])) < stats["routed_total"]
    assert np.asarray(stats["expert_rows"]).shape == (sparse, len(HELD))
    # one chunk a routed layer and page unless a page held more than a chunk's rows (ops/moe.py)
    assert stats["expert_chunks"] >= stats["expert_chunk_calls"] == sparse * stats["pages_dispatched"]

    answer = ref.make_answer_fn({k: unflatten(v) for k, v in flat.items()}, REF_TINY)
    packed = {}
    for path in corpus:
        want, got = answer(path), read_out(str(tmp_path / "packed"), path)
        assert got["laguna"].dtype == np.float32
        assert row_gaps(got["laguna"], want["laguna"]).max() < 2e-5
        assert stats["segments"] >= len(want["tokens"])
        for k in ref.EXACT_KEYS:
            np.testing.assert_array_equal(got[k], want[k])
        packed[path] = got["laguna"]

    for path in corpus:  # one document a page
        assert ex.run([path]) == 1
        assert ex._pack_stats["pages_dispatched"] == 1
        alone = read_out(str(tmp_path / "packed"), path)["laguna"]
        assert row_gaps(alone, packed[path]).max() < 2e-5

    # the other way round the same documents pack as {20, 100}, {120}, {60, 37} (the first page
    # passes 60 + 37 over for 100): a document's rows do not depend on the company it keeps
    assert ex.run(corpus[::-1]) == len(corpus)
    assert page_documents(ex._pack_stats) == [[20, 100], [120], [60, 37]]
    for path in corpus:
        turned = read_out(str(tmp_path / "packed"), path)["laguna"]
        assert row_gaps(turned, packed[path]).max() < 2e-5


def test_bfloat16_path_and_a_transcript_too_long(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """The arithmetic the type really runs, held loosely at this width (a
    bfloat16 rounding is 0.4 % of a value here and a router's near-tie flips
    on it), and a transcript longer than a page: a permanent error of that
    video alone, in the manifest."""
    directory, flat = checkpoint
    rng = np.random.default_rng(3)
    long = transcript(str(tmp_path / "long.tokens.npz"), rng, PAGE_TOKENS + 1)
    ex = extractor(tmp_path, "bf16", directory, monkeypatch, retries=0)
    assert ex.run(corpus[:3] + [long]) == 3
    failures = load_failures(os.path.join(str(tmp_path / "bf16"), "laguna"))
    assert list(failures) == [os.path.abspath(long)]
    record = failures[os.path.abspath(long)]
    assert record["error_class"] == "DecodeError" and not record["transient"]
    answer = ref.make_answer_fn({k: unflatten(v) for k, v in flat.items()}, REF_TINY)
    gaps = np.concatenate([row_gaps(read_out(str(tmp_path / "bf16"), p)["laguna"],
                                    answer(p)["laguna"]) for p in corpus[:3]])
    assert np.isfinite(gaps).all() and np.median(gaps) < 0.1


def naive_attention(q, k, v, doc, heads, kv, d, window):
    t = np.arange(len(doc))
    seen = (doc[:, None] == doc[None, :]) & (t[None, :] <= t[:, None])
    if window:
        seen &= t[:, None] - t[None, :] < window
    out = np.zeros_like(q)
    for h in range(heads):
        g = h // (heads // kv)
        s = np.where(seen, q[:, h * d:(h + 1) * d] @ k[:, g * d:(g + 1) * d].T, -np.inf)
        p = np.exp(s - s.max(1, keepdims=True))
        out[:, h * d:(h + 1) * d] = p / p.sum(1, keepdims=True) @ v[:, g * d:(g + 1) * d]
    return out


@pytest.mark.parametrize("lengths", [(20,), (40, 7, 50, 20), (128,)],
                         ids=["under_window", "mixed", "whole_page"])
def test_window_layer_against_full_layer(lengths, rng):
    """Documents under the window: the window layer's mask is the full
    layer's. Over it: each against the materialised scores, and the blocks a
    window or a document's start puts out of reach are not visited."""
    tokens, kv, group, d, window = 128, 2, 3, 16, 24
    doc = np.full(tokens, -1, np.int32)
    doc[:sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
    q, k, v = (rng.standard_normal((tokens, w)).astype(np.float32)
               for w in (kv * group * d, kv * d, kv * d))
    run = lambda w: np.asarray(segment_attention(  # noqa: E731
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(doc), kv_heads=kv,
        head_dim=d, window=w, block=BLOCK, interpret=True))
    full, windowed = run(None), run(window)
    real = doc >= 0
    np.testing.assert_allclose(full[real], naive_attention(q, k, v, doc, kv * group, kv, d, None)[real], atol=2e-5)
    np.testing.assert_allclose(windowed[real], naive_attention(q, k, v, doc, kv * group, kv, d, window)[real], atol=2e-5)
    if max(lengths) <= window:
        np.testing.assert_array_equal(full[real], windowed[real])
    else:
        assert np.abs(full[real] - windowed[real]).max() > 1e-3
    first = np.asarray(first_key_block(jnp.asarray(doc), BLOCK, window))
    blocks = np.arange(tokens // BLOCK)
    assert (first <= blocks).all() and (first >= blocks - math.ceil((window - 1) / BLOCK)).all()
    starts = np.concatenate([[0], np.cumsum(lengths)])[:-1]
    for b in blocks:  # no block before the document that the block's first query is in
        inside = starts[starts <= b * BLOCK]
        if real[b * BLOCK]:
            assert first[b] >= inside.max() // BLOCK


@pytest.mark.parametrize("full", [True, False], ids=["yarn_half_head", "plain_whole_head"])
def test_rope_against_float64_formula(full):
    """Both ropes at the PUBLISHED parameters against the formula written out
    in float64; the reference's tables against the same."""
    cfg, d = model.PUBLISHED, 128
    pos = np.array([0, 1, 5, 511, 512, 4097], np.int64)
    if full:
        rot, theta, orig, factor = 64, 500000.0, 8192, 128.0
        inv = theta ** (-np.arange(0, rot, 2) / rot)
        dim = lambda turns: rot * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
        low, high = math.floor(dim(32.0)), math.ceil(dim(1.0))
        ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        scale = 1.4852030263919618
        assert 0 < low < high < rot // 2  # the blend is a real one at the published numbers
    else:
        rot, scale = 128, 1.0
        inv = 10000.0 ** (-np.arange(0, rot, 2) / rot)
    x = np.random.default_rng(1).standard_normal((len(pos), 3, d))
    angle = pos[:, None] * inv[None, :]
    cos, sin = np.cos(angle)[:, None] * scale, np.sin(angle)[:, None] * scale
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)
    inv_p, factor_p = model.rope_inv_freq(cfg, full)
    got = model.apply_rope(jnp.asarray(x, jnp.float32), jnp.asarray(pos, jnp.int32), inv_p, factor_p)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-3 * scale)  # float32 angles at 4,097
    np.testing.assert_allclose(np.asarray(got)[:4], want[:4], atol=1e-4)
    rc, rs, rrot = ref.rope_tables(ref.PUBLISHED, full, pos, dtype=np.float64)
    assert rrot == rot
    np.testing.assert_allclose(rc[:, None], cos, atol=1e-12)
    np.testing.assert_allclose(rs[:, None], sin, atol=1e-12)


def test_four_shares_and_the_shared_expert_once_make_the_uncut_layer(float32):
    """The share ties to the model: what the four chips of a stage each give
    for their own quarter of the experts, plus the shared expert counted once,
    is the uncut reference's expert layer."""
    tokens, all_experts = 48, tuple(range(TINY.num_experts))
    spec = ref.weight_specs(REF_TINY, layers=(1,), experts=all_experts)["laguna"]
    flat = make_weights(spec, 11, "laguna")
    w = ref.round_weights(unflatten(flat))["layers"]["1"]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((tokens, TINY.hidden_size)), jnp.float32)
    stack = lambda m, ids: jnp.stack([w["experts"][str(e)][m] for e in ids])  # noqa: E731
    uncut = (ref.routed_part(REF_TINY, h, w["router"], *(stack(m, all_experts) for m in
                                                         ("gate_proj", "up_proj", "down_proj")),
                             jnp.asarray(all_experts)) + ref.shared_part(h, w["shared"]))
    valid = jnp.ones((tokens,), bool)
    total, held_rows = None, 0
    for rank in range(4):
        ids = all_experts[rank::4]  # any four-way split of the experts
        names = [n for n in flat if "/experts/" not in n
                 or int(n.split("/")[3]) in ids]
        params, share = model.stack_checkpoint(TINY, names, lambda n: np.asarray(flat[n]).astype(
            jnp.bfloat16).astype(np.float32))
        assert share.experts == tuple(sorted(ids))
        slot_of = np.full((TINY.num_experts,), -1, np.int32)
        slot_of[list(share.experts)] = np.arange(len(ids))
        p = params["layers"][0]
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


def test_pad_tokens_are_not_routed(float32):
    h = jnp.asarray(np.random.default_rng(2).standard_normal((32, 64)), jnp.float32)
    router = jnp.asarray(np.random.default_rng(3).standard_normal((64, 16)), jnp.float32)
    _w, experts = moe.route(h, router, 4, 2.5)
    valid = jnp.arange(32) < 20
    d = moe.dispatch(experts, valid, jnp.arange(16, dtype=jnp.int32), 16)
    assert int(d.group_sizes.sum()) == 20 * 4
    assert not bool(d.held[20:].any()) and bool(d.held[:20].all())
    assert (np.asarray(d.token_of_row)[:80] < 20).all()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2.0 ** -8)],
                         ids=["float32", "bfloat16"])
def test_grouped_product_against_a_loop_over_groups(dtype, tol, rng):
    """The one grouped product (the kernel the chip runs, here in the
    interpreter): uneven groups, an empty one, rows past the groups, tiles
    smaller than the operands."""
    sizes = np.array([5, 0, 17, 1, 9], np.int32)
    lhs = rng.standard_normal((48, 24)).astype(np.float32)
    rhs = rng.standard_normal((5, 24, 40)).astype(np.float32)
    lhs, rhs = (np.asarray(jnp.asarray(a, dtype).astype(jnp.float32)) for a in (lhs, rhs))
    got = np.asarray(moe.grouped_matmul(jnp.asarray(lhs, dtype), jnp.asarray(rhs, dtype),
                                        jnp.asarray(sizes), interpret=True).astype(jnp.float32))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    want = np.concatenate([lhs[lo:hi] @ rhs[g] for g, (lo, hi) in enumerate(zip(bounds, bounds[1:]))])
    assert row_gaps(got[:bounds[-1]], want).max() <= tol


def test_weight_specs_give_every_expert_matrix_its_true_fan_in():
    spec = ref.weight_specs()["laguna"]
    assert spec["layers/3/experts/17/gate_proj"] == (3072, 1024)
    assert spec["layers/3/experts/17/down_proj"] == (1024, 3072)
    assert spec["layers/1/router"] == (3072, 256) and spec["layers/0/g_proj"] == (3072, 48)
    assert spec["layers/2/q_proj"] == (3072, 72 * 128) and "layers/0/router" not in spec
    assert not any(len(shape) > 2 for shape in spec.values())  # nothing stacked: fan-in is rows
    total = sum(int(np.prod(s)) for s in spec.values())
    assert 3.15e9 < total < 3.16e9
    held = {int(n.split("/")[3]) for n in spec if "/experts/" in n}
    assert held == set(range(64))
    assert model.leaf_shapes(model.PUBLISHED, range(5), range(64)) == spec
    leaf = make_leaf(np.random.default_rng(0), "layers/3/experts/17/gate_proj", (3072, 1024))
    assert abs(float(leaf.std()) / math.sqrt(2.0 / 3072) - 1.0) < 0.01
    scale = make_leaf(np.random.default_rng(0), "layers/3/attn_norm/scale", (3072,))
    assert 0.8 <= scale.min() and scale.max() <= 1.2


def first_fit(sizes, page_tokens, page_rows):
    """The oracle of arrival order alone: each document that still fits what
    the ones before it left (the packer's rule before PR 41)."""
    take, tokens, rows = [], 0, 0
    for i, (n, s) in enumerate(sizes):
        if tokens + n <= page_tokens and rows + s <= page_rows:
            take.append(i)
            tokens += n
            rows += s
    return take


def test_token_pages_fit_and_planes():
    sizes = [(100, 9), (37, 4), (60, 6), (20, 2), (8, 1)]
    assert fit_documents(sizes, 128, 16) == [0, 3, 4]       # the oldest, then 20 + 8: a full page
    assert fit_documents(sizes[1:], 128, 16) == [0, 1, 2, 3]
    assert fit_documents(sizes, 128, 10) == [0, 4]          # the table's rows bound a page too
    assert fit_documents([(129, 3)], 128, 16) == []
    # where arrival order would take 37 + 20 + 8 beside the oldest, the best fit is 60 + 8
    late = [(60, 6), (37, 4), (20, 2), (8, 1), (60, 2)]
    assert first_fit(late, 128, 16) == [0, 1, 2, 3] and fit_documents(late, 128, 16) == [0, 3, 4]
    assert fit_documents([(10, 1), (5, 1), (5, 1), (5, 1)], 20, 9) == [0, 1, 2]  # a tie: the newest waits
    # 6 + 4 fills the page with fewer documents and one row too many
    assert fit_documents([(10, 1), (6, 3), (4, 1), (3, 1), (3, 1)], 20, 4) == [0, 2, 3, 4]
    page, table = np.empty((4, 32), np.int32), np.empty((6, 3), np.int32)
    docs = [(7, np.arange(10, 22, dtype=np.int32), np.array([5, 12], np.int32)),
            (9, np.arange(3, dtype=np.int32), np.array([3], np.int32))]
    rows = build_token_page(docs, page, table)
    assert rows == [slice(0, 2), slice(2, 3)]
    np.testing.assert_array_equal(page[IDS, :15], list(range(10, 22)) + [0, 1, 2])
    np.testing.assert_array_equal(page[DOC, :16], [0] * 12 + [1] * 3 + [-1])
    np.testing.assert_array_equal(page[POS, :15], list(range(12)) + [0, 1, 2])
    np.testing.assert_array_equal(page[SEG, :16], [0] * 5 + [1] * 7 + [2] * 3 + [-1])
    assert (page[:, 15:] == np.array([[0], [-1], [0], [-1]])).all()
    np.testing.assert_array_equal(table, [[7, 0, 1], [7, 1, 1], [9, 0, 1]] + [[-1, -1, 0]] * 3)


@pytest.mark.parametrize("seed", range(12))
def test_fit_documents_against_every_subset(seed):
    """Seeded queues small enough to try every subset that holds the first
    document: the rule takes the first, keeps both bounds, no subset fits
    fuller, and of the fullest it takes the one whose latest documents are
    the earliest."""
    rng = np.random.default_rng([seed, 0xF17])
    for _ in range(20):
        tokens, rows = int(rng.integers(16, 200)), int(rng.integers(3, 12))
        few_rows = bool(rng.integers(2))  # half the queues: rows that bind
        sizes = [(int(rng.integers(0, tokens + 8)), int(rng.integers(0, rows + 2 if few_rows else 3)))
                 for _ in range(int(rng.integers(1, 10)))]
        take = fit_documents(sizes, tokens, rows)
        if sizes[0][0] > tokens or sizes[0][1] > rows:
            assert take == []
            continue
        assert take[0] == 0 and take == sorted(set(take))
        fits = {}  # every subset with the first document, within both bounds: its fill
        for k in range(len(sizes)):
            for others in itertools.combinations(range(1, len(sizes)), k):
                subset = (0,) + others
                fill = sum(sizes[i][0] for i in subset)
                if fill <= tokens and sum(sizes[i][1] for i in subset) <= rows:
                    fits[subset] = fill
        assert tuple(take) in fits
        fullest = [s for s, fill in fits.items() if fill == max(fits.values())]
        assert tuple(take) == min(fullest, key=lambda s: s[::-1])


def test_packer_fills_token_pages_from_two_pages_of_documents():
    """A ``CorpusPacker`` with a token spec over the transcript corpus' 16
    lengths, five seeded orders cycled three times each: every document goes
    out once, no page goes before two pages' worth is queued unless ``flush()``
    sends it, and a pass makes 6.2 pages at most in the mean (arrival order
    alone makes 7 to 8; 5.62 pages hold the tokens)."""
    page_tokens, page_rows, cycles = 16384, 2048, 3
    lengths = [int(round(1024 * 16 ** (i / 15))) for i in range(16)]
    pages_made, chosen = [], []
    for seed in range(5):
        order = [lengths[i] for i in np.random.default_rng([seed, 0xD0C5]).permutation(16)] * cycles
        sent, flushing = [], [False]

        def paged_step(page, table):
            left = [int(s.clip.ids[0]) for s in packer._pending[key]]  # the documents still queued
            sent.append((page.copy(), table.copy(), flushing[0], left))
            return table[:, :1].astype(np.float32), table

        packer = CorpusPacker(PackSpec(
            batch_size=page_rows, empty_row_shape=(1,), open_clips=None, step=None, finalize=None,
            paged_step=paged_step, page_rows=page_rows, page_tokens=page_tokens), wait=np.asarray)
        key = (None, ("tokens", page_tokens))
        for v, n in enumerate(order):
            ends = np.arange(32, n + 32, 32).clip(max=n).astype(np.int32)
            packer.begin(f"v{v}", {})
            packer.add(f"v{v}", types.SimpleNamespace(ids=np.full(n, v, np.int32), segment_ends=ends))
            packer.finish(f"v{v}")
        flushing[0] = True
        packer.flush()

        done = {a.video: a for a in packer.pop_completed()}
        assert sorted(done) == sorted(f"v{v}" for v in range(len(order)))
        slots = np.concatenate([page[IDS][page[DOC] >= 0] for page, *_ in sent])
        np.testing.assert_array_equal(np.bincount(slots), order)  # each document's tokens, once
        for v, n in enumerate(order):  # and its rows came back from the page that held it
            rows = done[f"v{v}"].stacked((1,))[0]
            assert rows.shape == (-(-n // 32), 1) and (rows == packer._video_ids[f"v{v}"]).all()
        n_chosen = 0
        for page, _table, flushed, left in sent:
            real = int((page[DOC] >= 0).sum())
            assert flushed or real + sum(order[v] for v in left) >= PAGES_QUEUED * page_tokens
            # the queue at the dispatch, in arrival order: the page's documents and those left
            held = sorted(int(v) for v in np.unique(page[IDS][page[DOC] >= 0]))
            queue = sorted(held + left)
            sizes = [(order[v], -(-order[v] // 32)) for v in queue]
            take = [queue.index(v) for v in held]
            assert take == fit_documents(sizes, page_tokens, page_rows)
            n_chosen += take != first_fit(sizes, page_tokens, page_rows)
        assert packer.pages_dispatched == len(sent) and packer.real_slots == sum(order)
        pages_made.append(len(sent))
        chosen.append(n_chosen)  # some orders need no choice: two pages' worth in order is enough
    assert sum(pages_made) / (5 * cycles) <= 6.2, pages_made
    assert all(c <= n for c, n in zip(chosen, pages_made)) and sum(chosen) > 5


def test_daemon_serves_the_type(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """``--serve`` with ``laguna``: ``serve_models`` validates, and a request
    goes through the daemon's ``PackedSession`` to the same files."""
    from video_features_tpu.serve.daemon import ExtractionService

    directory, _flat = checkpoint
    spool = tmp_path / "spool"
    spool.mkdir()
    ex = extractor(tmp_path, "served", directory, monkeypatch, serve=True, spool_dir=str(spool),
                   idle_flush_sec=0.0, serve_models=("laguna",))
    svc = ExtractionService(ex, poll_interval=0.001)
    request = svc.submit({"videos": corpus[:3]})
    svc.request_drain()
    assert svc.run() == 0 and request.state == "done"
    svc.close()
    for path in corpus[:3]:
        got = read_out(str(tmp_path / "served"), path)
        assert got["laguna"].shape == (len(got["tokens"]), TINY.hidden_size)
        assert np.isfinite(got["laguna"]).all() and np.abs(got["laguna"]).max() > 0


def test_configuration_file_keeps_every_published_number():
    root = os.path.dirname(BENCH)
    with open(os.path.join(BENCH, "configs", "laguna_s21_bf16.json")) as f:
        conf = json.load(f)
    cfg = model.PUBLISHED
    for key in ("vocab_size", "hidden_size", "intermediate_size", "num_key_value_heads",
                "head_dim", "sliding_window", "rms_norm_eps", "num_experts_per_tok",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "moe_routed_scaling_factor"):
        assert conf[key] == getattr(cfg, key) == ref.PUBLISHED[key], key
    assert conf["reduced"] == ["num_hidden_layers", "num_experts"]
    assert conf["num_experts"] == len(ref.EXPERTS) and conf["num_hidden_layers"] == len(ref.LAYERS)
    assert conf["published"]["num_experts"] == cfg.num_experts == ref.PUBLISHED["num_experts"]
    for layer in ref.LAYERS:
        assert conf["num_attention_heads_per_layer"][layer] == cfg.heads(layer) == ref.heads_of(ref.PUBLISHED, layer)
        assert (conf["layer_types"][layer] == "full_attention") == cfg.is_full(layer) == ref.is_full(layer)
        assert (conf["mlp_layer_types"][layer] == "dense") == cfg.is_dense(layer)
    full = conf["rope_parameters"]["full_attention"]
    assert (full["rope_theta"], full["factor"], full["attention_factor"]) == (
        cfg.full_rope_theta, cfg.yarn_factor, cfg.yarn_attention_factor)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert any(c["file"] == "benchmark/configs/laguna_s21_bf16.json"
               and c["reduced"] == conf["reduced"] for c in bench["configs"])


def test_the_package_and_the_other_types_do_not_load_the_new_modules():
    """``import video_features_tpu`` and building another type's extractor
    load neither the model nor a Pallas library: the text stream costs the
    other cells' set-up nothing."""
    import subprocess

    code = (
        "import sys, video_features_tpu\n"
        "from video_features_tpu.config import ExtractionConfig\n"
        "from video_features_tpu.extractors import get_extractor\n"
        "import video_features_tpu.extractors.base, video_features_tpu.parallel.packer\n"
        "bad = [m for m in sys.modules if m.startswith(('video_features_tpu.models.laguna',"
        " 'video_features_tpu.models.sarvam', 'video_features_tpu.models.text_layers',"
        " 'video_features_tpu.models.qwen3_next', 'video_features_tpu.extractors.qwen3_next',"
        " 'video_features_tpu.models.jamba', 'video_features_tpu.extractors.jamba',"
        " 'video_features_tpu.ops.selective_scan',"
        " 'video_features_tpu.extractors.laguna', 'video_features_tpu.extractors.sarvam',"
        " 'video_features_tpu.extractors.token_pages', 'video_features_tpu.ops.moe',"
        " 'video_features_tpu.ops.gated_delta',"
        " 'video_features_tpu.ops.segment_attention', 'jax.experimental.pallas'))]\n"
        "assert not bad, bad\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                          cwd=os.path.dirname(BENCH))
    assert done.returncode == 0, done.stderr[-2000:]

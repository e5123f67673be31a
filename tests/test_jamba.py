"""The text stream's fourth model (``--feature_type jamba``: Mamba-1 selective-scan
layers with rope-free attention over one key/value head every fourteenth layer,
a dense feed-forward in every layer) at tiny widths on the CPU: the program
against the benchmark's plain reference through ``Extractor.run``, the
selective scan against a plain loop, the Mamba and attention layers, the scopes
the benchmark's readers match, and the weight table. The two Pallas kernels
are the chip's, run in the Pallas interpreter. Arithmetic is checked in float32
(``models.text_layers.DTYPE`` patched); the bfloat16 path is run once and held
loosely. What the models share (pages, packing, the daemon's session) is
``tests/test_laguna.py``'s.
"""

# fast-registry: page program compiles (both Pallas kernels in the interpreter)

import functools
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
from reference import jamba as ref  # noqa: E402
from weights import make_leaf, make_weights, unflatten, write_npz  # noqa: E402

from video_features_tpu.config import ExtractionConfig  # noqa: E402
from video_features_tpu.extractors import get_extractor  # noqa: E402
from video_features_tpu.extractors import token_pages as extractor_module  # noqa: E402
from video_features_tpu.models import jamba as model  # noqa: E402
from video_features_tpu.models import text_layers  # noqa: E402
from video_features_tpu.ops.selective_scan import EXCHANGE, GROUP, selective_scan  # noqa: E402

# 4 query heads of 16 over one key/value head, 128 inner channels of 16
# states, rank 8; layers 0-3 with attention at layer 2 (period 4, offset 2)
WIDTHS = dict(vocab_size=512, hidden_size=64, num_hidden_layers=4, intermediate_size=96,
              attn_layer_period=4, attn_layer_offset=2, num_attention_heads=4,
              num_key_value_heads=1, mamba_d_state=16, mamba_dt_rank=8)
TINY = model.JambaConfig(**WIDTHS)
REF_TINY = dict(ref.PUBLISHED, **WIDTHS)
LAYERS = (0, 1, 2, 3)
PAGE_TOKENS, BLOCK = 128, 16
# pages in this order: {100, 28} (the second document ends at the page's last
# slot), then {37, 60} and {120} with pads; every document after a page's first
# starts mid-way through the scan's chunk
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
    return {k: np.load(os.path.join(out_dir, "jamba", f"{stem}_{k}.npy"))
            for k in ("jamba", "timestamps_ms", "tokens")}


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
    spec = ref.weight_specs(REF_TINY, layers=LAYERS)
    flat = {name: pre_rounded(make_weights(s, 7, name)) for name, s in spec.items()}
    directory = str(tmp_path_factory.mktemp("weights"))
    write_npz(directory, "jamba", flat["jamba"])
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
        feature_type="jamba", on_extraction="save_numpy", page_tokens=PAGE_TOKENS,
        output_path=str(tmp_path / sub), tmp_path=str(tmp_path / "t"), **kw))


def test_program_matches_reference_and_packing_keeps_rows(tmp_path, tiny, float32, checkpoint,
                                                          corpus, monkeypatch):
    """Through ``Extractor.run`` on a corpus whose documents share pages, the
    ``.npy`` files against the plain reference; then the same documents one a
    page: the same rows (a document packed mid-page equals the document
    alone). Both planted faults of the reference are far from the program:
    the state not restarted between documents run back to back, and the state
    dropped every 16 tokens."""
    directory, flat = checkpoint
    monkeypatch.setenv("VFT_METRICS", "1")  # the stage records say what each page held
    ex = extractor(tmp_path, "packed", directory, monkeypatch)
    assert ex.cfg.pack_corpus and ex.cfg.num_devices == 1
    assert ex.share == model.Share(LAYERS, ())
    assert ex.run(corpus) == len(corpus)
    stats = ex._pack_stats
    assert page_documents(stats) == [[100, 28], [37, 60], [120]]
    assert stats["pages_dispatched"] == 3 and stats["real_slots"] == sum(LENGTHS)
    assert stats["routed_total"] == stats["routed_held"] == stats["expert_chunk_calls"] == 0

    tree = {k: unflatten(v) for k, v in flat.items()}
    answer = ref.make_answer_fn(tree, REF_TINY, activations="float32")
    monkeypatch.setattr(ref, "FAULT_CHUNK", 16)
    carry, reset = (ref.make_forward(ref.round_weights(unflatten(flat["jamba"])), REF_TINY,
                                     fault=fault, activations="float32") for fault in ("carry", "reset"))
    packed = {}
    for i, path in enumerate(corpus):
        want, got = answer(path), read_out(str(tmp_path / "packed"), path)
        assert got["jamba"].dtype == np.float32
        assert got["jamba"].shape == (len(want["tokens"]), TINY.hidden_size)
        assert row_gaps(got["jamba"], want["jamba"]).max() < 2e-5
        for k in ref.EXACT_KEYS:
            np.testing.assert_array_equal(got[k], want[k])
        with np.load(path) as z:
            assert row_gaps(carry(z["ids"], z["segment_ends"]), got["jamba"]).max() > 1e-2
            # back to back: the first document starts from zero as the program's does
            far = row_gaps(reset(z["ids"], z["segment_ends"]), got["jamba"]).max()
            assert (far < 2e-5) if i == 0 else (far > 1e-2), (i, far)
        packed[path] = got["jamba"]

    for path in corpus:  # one document a page
        assert ex.run([path]) == 1
        assert ex._pack_stats["pages_dispatched"] == 1
        alone = read_out(str(tmp_path / "packed"), path)["jamba"]
        assert row_gaps(alone, packed[path]).max() < 2e-5


def test_bfloat16_path(tmp_path, tiny, checkpoint, corpus, monkeypatch):
    """The arithmetic the type really runs, against the reference rounded
    where the program rounds (as ``correct`` takes it) and against the same
    reference with float32 activations, which reads farther."""
    directory, flat = checkpoint
    ex = extractor(tmp_path, "bf16", directory, monkeypatch)
    assert ex.run(corpus[:2]) == 2
    tree = {k: unflatten(v) for k, v in flat.items()}
    gaps = {act: np.concatenate([row_gaps(read_out(str(tmp_path / "bf16"), p)["jamba"],
                                          answer(p)["jamba"]) for p in corpus[:2]])
            for act, answer in ((act, ref.make_answer_fn(tree, REF_TINY, activations=act))
                                for act in ("bfloat16", "float32"))}
    assert np.isfinite(gaps["bfloat16"]).all()
    assert gaps["bfloat16"].max() < 0.03 and np.median(gaps["bfloat16"]) < 0.01
    assert np.median(gaps["float32"]) > 3 * np.median(gaps["bfloat16"])


def test_the_references_rounding_is_not_a_cast_pair():
    """The reference rounds with ``reduce_precision``, which a compiler keeps;
    a cast to bfloat16 and back is dropped inside a TPU fusion. Its values
    are bfloat16's, and float32 rounds nothing."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal(4096), jnp.float32)
    r = ref.rounder("bfloat16")
    assert "reduce_precision" in jax.jit(r).lower(x).as_text()
    np.testing.assert_array_equal(np.asarray(jax.jit(r)(x)),
                                  np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    assert np.abs(np.asarray(r(x)) - np.asarray(x)).max() > 0
    np.testing.assert_array_equal(np.asarray(ref.rounder("float32")(x)), np.asarray(x))


# --- the selective scan ---------------------------------------------------------

def scan_inputs(rng, tokens, width, state=16):
    u = rng.standard_normal((tokens, width)).astype(np.float32)
    dt = np.log1p(np.exp(1.4 * rng.standard_normal((tokens, width)))).astype(np.float32)
    b, c = rng.standard_normal((2, tokens, state)).astype(np.float32)
    a = (-np.exp(0.5 * rng.standard_normal((state, width)))).astype(np.float32)
    d = rng.uniform(0.8, 1.2, width).astype(np.float32)
    return u, dt, b, c, a, d


def plain_loop(u, dt, b, c, a, d, z, pos):
    """The recurrence token by token in float64, the state zeroed where a
    document starts."""
    h = np.zeros(a.shape)
    y = np.zeros(u.shape)
    for t in range(len(u)):
        if pos[t] == 0:
            h[:] = 0.0
        h = np.exp(a * dt[t]) * h + b[t][:, None] * (dt[t] * u[t])[None, :]
        y[t] = (c[t][:, None] * h).sum(0) + d * u[t]
    z = z.astype(np.float64)
    return y * z / (1 + np.exp(-z))


@pytest.mark.parametrize("chunk,channels,width", [(32, 32, 128), (16, 64, 128), (128, 128, 128),
                                                 (32, 1024, 3072)],
                         ids=["four_chunks_four_blocks", "eight_chunks_two_blocks", "one_step",
                              "three_blocks_of_1024"])
@pytest.mark.parametrize("lengths", [(128,), (5, 40, 1, 50), (16, 17, 15, 32, 30), (24, 104),
                                     (12, 116), (15, 113)],
                         ids=["whole_page", "mid_chunk_starts_and_pads", "starts_at_and_beside_edges",
                              "start_first_of_exchange", "start_middle_of_exchange",
                              "start_last_of_exchange"])
def test_selective_scan_against_a_plain_loop(lengths, chunk, channels, width, rng):
    """Across chunk edges and channel blocks, a document that starts
    mid-chunk or at an edge, a document of one token, trailing pads (``pos``
    0: they restart at every token and stay finite); ``z`` read from inside a
    wider array at a column offset of whole blocks. A document starts at the
    first, the middle and the last token of the kernel's in-VMEM exchange of
    ``EXCHANGE`` tokens (24 is also mid-way through a group of ``GROUP``), and
    one width is three blocks of 1,024 channels (eight rows of 128 a token)."""
    tokens = 128
    pos = np.zeros(tokens, np.int32)
    at = 0
    for n in lengths:
        pos[at:at + n] = np.arange(n)
        at += n
    u, dt, b, c, a, d = scan_inputs(rng, tokens, width)
    gate = rng.standard_normal((tokens, 3 * width)).astype(np.float32)
    got = np.asarray(selective_scan(*(jnp.asarray(x) for x in (u, dt, b, c, a, d, gate, pos)),
                                    gate_column=width, chunk=chunk, channels=channels,
                                    interpret=True))
    assert np.isfinite(got).all()
    want = plain_loop(u, dt, b, c, a, d, gate[:, width:2 * width], pos)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # the restart matters: one document across the page reads otherwise
    if len(lengths) > 1:
        alone = plain_loop(u, dt, b, c, a, d, gate[:, width:2 * width], np.arange(tokens))
        assert np.abs(alone[lengths[0]:lengths[0] + 4] - want[lengths[0]:lengths[0] + 4]).max() > 1e-2


def test_selective_scan_refuses_what_it_cannot_tile(rng):
    u, dt, b, c, a, d = scan_inputs(rng, 96, 64)
    args = [jnp.asarray(x) for x in (u, dt, b, c, a, d, u, np.zeros(96, np.int32))]
    with pytest.raises(ValueError, match="chunks of 40"):
        selective_scan(*args, chunk=40, interpret=True)
    with pytest.raises(ValueError, match="from column 8"):
        selective_scan(*args, gate_column=8, channels=32, interpret=True)
    assert (GROUP, EXCHANGE) == (16, 8)  # what the plain-loop test's document starts are placed for


def test_the_scan_kernel_lowers_for_tpu_at_the_published_shape():
    """jaxpr → Mosaic MLIR at the page's own shape (16,384 tokens, 5,120
    channels, 16 states, bfloat16 ``u`` and ``z``) with no TPU present; the
    Mosaic compile itself is the chip's (``benchmark/sizing_token_pages.py``
    makes it here by hand for a described v5e). The exchange between tiles of
    tokens and a token's rows of channels stays inside the kernel: outside the
    ``tpu_custom_call`` no operation transposes anything, and none takes or
    makes an array of the page's ``u``, ``Δ`` or ``z`` (``(16384, 5120)``, the
    gate's ``(16384, 10240)``, or either cut into rows of 128): on the TPU such
    a relayout is a pass over the array, not a bitcast."""
    tokens, width, state = 16384, 5120, 16
    s = jax.ShapeDtypeStruct
    scan = jax.export.export(jax.jit(functools.partial(selective_scan, gate_column=width)),
                             platforms=["tpu"])(
        s((tokens, width), jnp.bfloat16), s((tokens, width), jnp.float32),
        s((tokens, state), jnp.float32), s((tokens, state), jnp.float32),
        s((state, width), jnp.float32), s((width,), jnp.bfloat16),
        s((tokens, 2 * width), jnp.bfloat16), s((tokens,), jnp.int32))
    text = scan.mlir_module()
    assert "tpu_custom_call" in text and "selective_scan" in text
    outside = [line for line in text.splitlines()
               if "= stablehlo." in line and "stablehlo.custom_call" not in line]
    assert outside and not [line for line in outside if "stablehlo.transpose" in line]
    page = (f"tensor<{tokens}x{width}x", f"tensor<{tokens}x{2 * width}x",
            f"tensor<{tokens}x{width // 128}x128x", f"tensor<{tokens}x{2 * width // 128}x128x")
    assert not [line for line in outside if any(p in line for p in page)]


# --- the layers around it -------------------------------------------------------

def layer_params(layer, seed=3):
    """One layer's leaves at tiny widths, pre-rounded → (the program's tree,
    the reference's)."""
    spec = ref.weight_specs(REF_TINY, layers=(layer,))["jamba"]
    flat = pre_rounded(make_weights(spec, seed, "jamba"))
    params, _share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    return params["layers"][0], ref.round_weights(unflatten(flat))["layers"][str(layer)], flat


def page_planes(lengths, tokens):
    doc, pos = np.full(tokens, -1, np.int32), np.zeros(tokens, np.int32)
    at = 0
    for i, n in enumerate(lengths):
        doc[at:at + n], pos[at:at + n] = i, np.arange(n)
        at += n
    return doc, pos


def test_a_mamba_layer_against_the_reference(float32, rng):
    """One Mamba layer, a page of three documents and pads, against the
    reference's layer on each document alone: the in-projection, the
    convolution with its bias, the three inner norms, ``Δ`` from ``b_dt``,
    ``A = −exp(A_log)``, the scan with ``D``, the gate and the out-projection."""
    p, w, _flat = layer_params(1)
    lengths, tokens = (50, 1, 70), 128
    x = rng.standard_normal((tokens, TINY.hidden_size)).astype(np.float32)
    doc, pos = page_planes(lengths, tokens)
    got = np.asarray(model.mamba(TINY, p, jnp.asarray(x), jnp.asarray(pos), interpret=True))
    assert np.isfinite(got).all()
    at = 0
    zero = jnp.zeros((TINY.inner, TINY.mamba_d_state), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for n in lengths:
            want, _last = ref.mamba(REF_TINY, w, jnp.asarray(x[at:at + n]), zero)
            assert row_gaps(got[at:at + n], np.asarray(want)).max() < 2e-5
            at += n
    assert p["a_log"].shape == (16, TINY.inner) and p["a_log"].dtype == jnp.float32
    assert p["dt_bias"].dtype == p["conv_bias"].dtype == jnp.float32
    # the convolution's bias is there: without it the layer reads otherwise
    unbiased = dict(p, conv_bias=jnp.zeros_like(p["conv_bias"]))
    moved = np.asarray(model.mamba(TINY, unbiased, jnp.asarray(x), jnp.asarray(pos), interpret=True))
    assert np.abs(moved - got).max() > 1e-3


def test_attention_without_rope_over_one_key_value_head(float32, rng):
    """The attention layer against its equations in numpy: every query head
    over the ONE key and value, scores over ``sqrt(d)``, causal within a
    document, no positional encoding (so a document's rows do not depend on
    where in the page it sits)."""
    p, w, flat = layer_params(2)
    tokens, heads, d = 32, 4, 16
    x = rng.standard_normal((tokens, TINY.hidden_size)).astype(np.float32)
    doc = np.zeros(tokens, np.int32)
    got = np.asarray(model.attention(TINY, p, jnp.asarray(x), jnp.asarray(doc), BLOCK,
                                     interpret=True))

    f = {k: np.asarray(v, np.float64) for k, v in flat.items()}
    h = x / np.sqrt(np.mean(x.astype(np.float64) ** 2, -1, keepdims=True) + 1e-6) \
        * f["layers/2/attn_norm/scale"]
    q = (h @ f["layers/2/q_proj"]).reshape(tokens, heads, d)
    k, v = h @ f["layers/2/k_proj"], h @ f["layers/2/v_proj"]
    o = np.zeros((tokens, heads, d))
    for a in range(heads):
        s = q[:, a] @ k.T / np.sqrt(d)
        s = np.where(np.tril(np.ones((tokens, tokens), bool)), s, -np.inf)
        prob = np.exp(s - s.max(1, keepdims=True))
        o[:, a] = prob / prob.sum(1, keepdims=True) @ v
    want = x + o.reshape(tokens, -1) @ f["layers/2/o_proj"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(ref.attention(REF_TINY, w, jnp.asarray(x))), want,
                                   atol=2e-5)
    # the same document behind another in the page: the same rows
    doc2 = np.concatenate([np.zeros(16, np.int32), np.ones(tokens, np.int32)])
    x2 = np.concatenate([rng.standard_normal((16, TINY.hidden_size)).astype(np.float32), x])
    later = np.asarray(model.attention(TINY, p, jnp.asarray(x2), jnp.asarray(doc2), BLOCK,
                                       interpret=True))
    np.testing.assert_allclose(later[16:], got, atol=2e-5)


def test_the_readers_scopes_survive_the_compile(tiny):
    """The benchmark's readers find the Mamba layers by ``/attn/mamba/`` and
    the scan by ``/attn/mamba/scan`` in an operation's ``op_name``; the
    kernel, ``selective_scan``, sits under ``jamba/L<k>/attn/mamba/scan``. A
    renamed scope fails here and not in a traced benchmark run."""
    shapes = model.leaf_shapes(TINY, LAYERS)
    flat = {n: np.zeros(s, np.float32) for n, s in shapes.items()}
    params, share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    page = np.zeros((4, PAGE_TOKENS), np.int32)

    def forward(params, page):
        return model.forward(TINY, share, 16, BLOCK, params, page, interpret=True)

    hlo = jax.jit(forward).lower(params, jnp.asarray(page)).compile().as_text()
    names = {line.split('op_name="', 1)[1].split('"', 1)[0] for line in hlo.splitlines()
             if 'op_name="' in line}
    for layer in (0, 1, 3):
        for scope in ("proj", "conv", "ssm_params", "scan", "out"):
            assert any(f"jamba/L{layer}/attn/mamba/{scope}" in n for n in names), (layer, scope)
        assert any(f"jamba/L{layer}/attn/mamba/scan/" in n and "/selective_scan" in n
                   for n in names), layer
        assert any(f"jamba/L{layer}/mlp/" in n for n in names), layer
    for scope in ("qkv", "core", "out"):
        assert any(f"jamba/L2/attn/{scope}" in n for n in names), scope
    assert not any("jamba/L2/attn/mamba" in n for n in names)
    assert not any("/moe/" in n for n in names)
    assert any("jamba/embed" in n for n in names) and any("jamba/pool" in n for n in names)


# --- the weight table and the configuration's file -------------------------------

def test_weight_specs_and_the_checkpoints_layouts():
    spec = ref.weight_specs()["jamba"]
    assert [l for l in range(28) if ref.is_attention(ref.PUBLISHED, l)] == [7, 21]
    assert [l for l in range(28) if model.PUBLISHED.is_attention(l)] == [7, 21]
    assert spec["layers/0/in_proj"] == (2560, 10240) and spec["layers/0/out_proj"] == (5120, 2560)
    assert spec["layers/0/x_proj"] == (5120, 192) and spec["layers/0/dt_proj/kernel"] == (160, 5120)
    assert spec["layers/0/a_log/bias"] == (5120, 16) and spec["layers/0/d/scale"] == (5120,)
    assert spec["layers/0/conv/kernel"] == (4, 5120) and spec["layers/0/conv/bias"] == (5120,)
    assert spec["layers/7/q_proj"] == spec["layers/7/o_proj"] == (2560, 2560)
    assert spec["layers/7/k_proj"] == spec["layers/7/v_proj"] == (2560, 128)
    assert "layers/7/in_proj" not in spec and "layers/6/q_proj" not in spec
    assert spec["layers/27/mlp/gate_proj"] == (2560, 8192)
    assert spec["embed/embedding"] == (65536, 2560)
    mamba = sum(int(np.prod(s)) for n, s in spec.items() if n.startswith("layers/0/")
                and "/mlp" not in n and n != "layers/0/attn_norm/scale")
    assert mamba == 41_241_792  # 41.24 M a Mamba mixer
    total = sum(int(np.prod(s)) for s in spec.values())
    assert total == 3_029_337_472  # 3,029.3 M, 6.06 GB in bfloat16
    assert model.leaf_shapes(model.PUBLISHED, range(28)) == spec
    # the harness draws by a leaf's last name: A_log a small normal (A near -1), D near 1
    rng = np.random.default_rng(0)
    assert 0.02 < float(make_leaf(rng, "layers/0/a_log/bias", (5120, 16)).std()) < 0.08
    assert 0.8 <= float(make_leaf(rng, "layers/0/d/scale", (5120,)).min())

    tiny_spec = model.leaf_shapes(TINY, (1, 2))
    flat = {n: np.arange(int(np.prod(s)), dtype=np.float32).reshape(s) % 251 for n, s in tiny_spec.items()}
    params, share = model.stack_checkpoint(TINY, list(flat), flat.__getitem__)
    assert share == model.Share((1, 2), ())
    mamba_layer, attention_layer = params["layers"]
    np.testing.assert_array_equal(
        np.asarray(attention_layer["wqkv"], np.float32),
        np.concatenate([flat[f"layers/2/{m}_proj"] for m in "qkv"], axis=-1))
    np.testing.assert_array_equal(np.asarray(mamba_layer["a_log"]), flat["layers/1/a_log/bias"].T)
    assert set(mamba_layer) >= {"w_in", "conv", "conv_bias", "w_x", "w_dt", "dt_bias", "d", "w_out",
                                "w_gate_up", "w_down"}
    assert "router" not in mamba_layer and "w_in" not in attention_layer


def test_configuration_file_keeps_every_published_number():
    root = os.path.dirname(BENCH)
    with open(os.path.join(BENCH, "configs", "jamba2_3b_bf16.json")) as f:
        conf = json.load(f)
    cfg = model.PUBLISHED
    # the catalog row's `config`, every number under the same key
    catalog = {"attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
               "expert_layer_period": 2, "hidden_size": 2560, "intermediate_size": 8192,
               "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
               "max_position_embeddings": 262144, "num_attention_heads": 20, "num_experts": 1,
               "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1,
               "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "vocab_size": 65536}
    for key, value in catalog.items():
        assert conf[key] == value, key
        if key in ref.PUBLISHED:
            assert ref.PUBLISHED[key] == value and getattr(cfg, key) == value, key
    assert conf["mamba_conv_bias"] is True and conf["mamba_proj_bias"] is False
    assert conf["sliding_window"] is None and conf["tie_word_embeddings"] is True
    assert conf["hidden_act"] == "silu" and conf["model_type"] == "jamba"
    assert conf["reduced"] == [] and conf["num_hidden_layers"] == len(ref.LAYERS) == 28
    assert conf["feature_type"] == "jamba" and conf["reference"] == conf["flops"] == "jamba"
    assert conf["check_videos"] == 2 and conf["trace_slice"]["seconds"] == 8.0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c for c in bench["configs"] if c["name"] == "jamba2_3b_bf16"][0]["reduced"] == []
    cell = [w for w in bench["workloads"] if w["config"] == "jamba2_3b_bf16"]
    assert [(w["name"], w["chips"], w["traffic"]) for w in cell] == [
        ("jamba2_3b_bf16.corpus_transcripts_64k", 1, "corpus_transcripts_64k")]
    listed = {m["name"] for m in bench["per_layer"] if cell[0]["name"] in m.get("workloads", ())}
    assert {"mamba_pct", "ssm_scan_roofline", "attention_pct", "step_mfu", "device_idle_pct",
            "setup_weights_s", "setup_compile_s", "setup_compiles", "setup_program_s"} <= listed
    assert not listed & {"moe_dispatch_pct", "expert_load_max_over_mean", "gdn_pct"}
    with open(os.path.join(BENCH, "traffic", "corpus_transcripts_64k.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "traffic", "corpus_transcripts.json")) as f:
        shared = json.load(f)
    assert traffic["vocab_size"] == cfg.vocab_size
    assert {k: v for k, v in traffic.items() if k != "vocab_size"} == \
        {k: v for k, v in shared.items() if k != "vocab_size"}

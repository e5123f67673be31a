"""Fast direct tests of the TPU kernel lowerings (default test subset).

Covers the production paths a slow-marked file would hide from the default
run: the spatially tiled Pallas cost-volume kernel (interpret mode), the
TapConv3D lowering every bias-free I3D conv takes, in both dtypes, and the text
stream's attention kernel (its softmax state's layout, and pages against a
plain softmax).
"""
# fast-registry: default tier — kernel parity vs torch mirrors

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

def test_corr81_pallas_tiled_matches_xla():
    """The spatially tiled kernel (interpret mode on CPU) must match the XLA
    formulation at sizes beyond the 16² single-block cap, including non-/16
    sizes exercising the pad-and-slice path."""
    from video_features_tpu.ops.pallas_corr import corr81_pallas_tiled, corr81_xla

    rng = np.random.default_rng(7)
    for h, w, c in ((32, 32, 8), (24, 40, 4), (18, 23, 5)):
        f1 = jnp.asarray(rng.standard_normal((2, h, w, c)).astype(np.float32))
        f2 = jnp.asarray(rng.standard_normal((2, h, w, c)).astype(np.float32))
        ref = np.asarray(corr81_xla(f1, f2))
        out = np.asarray(corr81_pallas_tiled(f1, f2, interpret=True))
        assert out.shape == ref.shape
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# I3D's real shape classes: the two stems (3 and 2 input channels), the widest
# and the narrowest 3×3×3 of mixed_3b, a 1×1×1, and odd sizes for the pads
@pytest.mark.parametrize("kernel,stride,cin,cout,thw", [
    pytest.param((7, 7, 7), (2, 2, 2), 3, 64, (8, 16, 16), id="stem7x7x7-rgb"),
    pytest.param((7, 7, 7), (2, 2, 2), 2, 64, (8, 16, 16), id="stem7x7x7-flow"),
    pytest.param((3, 3, 3), (1, 1, 1), 96, 128, (4, 7, 7), id="3x3x3-96to128"),
    pytest.param((3, 3, 3), (1, 1, 1), 16, 32, (4, 7, 7), id="3x3x3-16to32"),
    pytest.param((1, 1, 1), (1, 1, 1), 192, 64, (4, 7, 7), id="1x1x1-192to64"),
    pytest.param((7, 7, 7), (2, 2, 2), 4, 6, (8, 12, 12), id="7x7x7-small"),
    pytest.param((3, 3, 3), (1, 1, 1), 4, 6, (7, 13, 13), id="3x3x3-odd-sizes"),
])
def test_tap_conv3d_matches_direct_conv(kernel, stride, cin, cout, thw):
    """The tap lowering must equal nn.Conv's conv3d (same kernel, same
    TF-SAME pads) in float32, where equality is tight (bfloat16 only
    reassociates further)."""
    import flax.linen as fnn

    from video_features_tpu.models.layers import TapConv3D, tf_same_pads

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, *thw, cin)).astype(np.float32))
    tap = TapConv3D(cout, kernel, stride, dtype=jnp.float32)
    params = tap.init(jax.random.PRNGKey(0), x)
    assert params["params"]["kernel"].shape == (*kernel, cin, cout)
    out = tap.apply(params, x)
    ref = fnn.Conv(cout, kernel, strides=stride,
                   padding=tf_same_pads(kernel, stride), use_bias=False,
                   dtype=jnp.float32).apply(params, x)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _conv_ranks(fn, *args):
    """Operand rank of every ``conv_general_dilated`` in ``fn``'s jaxpr."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "conv_general_dilated":
                yield eqn.invars[0].aval.ndim
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kernel,stride,cin", [
    pytest.param((7, 7, 7), (2, 2, 2), 3, id="7x7x7s2"),
    pytest.param((3, 3, 3), (1, 1, 1), 16, id="3x3x3"),
    pytest.param((1, 1, 1), (1, 1, 1), 16, id="1x1x1"),
])
def test_unit3d_lowers_every_shape_class_as_temporal_taps(kernel, stride, cin, dtype):
    """One lowering per shape class, chosen from nothing but the kernel's
    shape, the same for float32 and bfloat16 and on every backend: a
    bias-free I3D convolution is ``kt`` conv2ds over (N·T, H, W, C) and no
    conv3d (PERF.md §6, PR 37: the chip preferred it for every class). The
    parameter stays ``conv3d/kernel`` in nn.Conv's layout, so converted
    checkpoints load as before."""
    from video_features_tpu.models.i3d import Unit3D

    unit = Unit3D(8, kernel, stride, dtype=dtype)
    x = jnp.ones((1, 8, 12, 12, cin), jnp.float32)
    params = unit.init(jax.random.PRNGKey(0), x)
    assert params["params"]["conv3d"]["kernel"].shape == (*kernel, cin, 8)
    assert params["params"]["conv3d"]["kernel"].dtype == jnp.float32
    assert _conv_ranks(lambda p, v: unit.apply(p, v), params, x) == [4] * kernel[0]


def test_i3d_biased_logits_head_keeps_the_direct_conv():
    """The one biased convolution (the logits head, outside the feature
    path) is nn.Conv's conv3d with its bias, as the checkpoint has it."""
    from video_features_tpu.models.i3d import Unit3D

    head = Unit3D(5, use_bn=False, use_bias=True, relu=False)
    x = jnp.ones((1, 3, 1, 1, 16), jnp.float32)
    params = head.init(jax.random.PRNGKey(0), x)
    assert set(params["params"]["conv3d"]) == {"kernel", "bias"}
    assert _conv_ranks(lambda p, v: head.apply(p, v), params, x) == [5]


@pytest.mark.parametrize("modality,cin", [("rgb", 3), ("flow", 2)])
def test_i3d_bf16_features_are_the_all_taps_composition(modality, cin):
    """``--dtype bfloat16`` took the taps for every convolution before
    float32 did, and PR 37 must not move its features: the model is 101
    conv2ds and no conv3d, its parameter tree is the float32 model's, and the
    stem unit is bit for bit TapConv3D → BatchNorm → ReLU."""
    from video_features_tpu.models.i3d import I3D, Unit3D
    from video_features_tpu.models.layers import TapConv3D, TorchBatchNorm

    x = jnp.asarray(np.random.default_rng(4).uniform(-1, 1, (1, 16, 32, 32, cin))
                    .astype(np.float32))
    shapes = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        model = I3D(modality=modality, dtype=dtype)
        tree = jax.eval_shape(lambda r, v: model.init(r, v, features=True),
                              jax.random.PRNGKey(0), x)
        shapes[name] = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)
        ranks = _conv_ranks(lambda p, v: model.apply(p, v, features=True), tree, x)
        # 7 stem taps + 1 + 3 + nine blocks of four 1×1×1 and two 3×3×3
        assert ranks == [4] * (7 + 1 + 3 + 9 * (4 + 2 * 3))
    assert shapes["float32"] == shapes["bfloat16"]
    assert shapes["bfloat16"]["params"]["conv3d_1a_7x7"]["conv3d"]["kernel"][0] == \
        (7, 7, 7, cin, 64)

    unit = Unit3D(64, (7, 7, 7), (2, 2, 2), dtype=jnp.bfloat16)
    params = unit.init(jax.random.PRNGKey(1), x)["params"]
    conv = TapConv3D(64, (7, 7, 7), (2, 2, 2), dtype=jnp.bfloat16).apply(
        {"params": params["conv3d"]}, x)
    by_hand = jax.nn.relu(TorchBatchNorm(dtype=jnp.bfloat16).apply(
        {"params": params["batch3d"]}, conv))
    out = unit.apply({"params": params}, x)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(by_hand.astype(jnp.float32)))


def test_i3d_bf16_tap_path_close_to_fp32():
    """dtype=bfloat16 through the same TapConv3D lowering as float32;
    features must stay near the fp32 model (same params)."""
    from video_features_tpu.models.i3d import I3D
    from video_features_tpu.weights.store import random_params_like

    m32 = I3D(modality="rgb", dtype=jnp.float32)
    mbf = I3D(modality="rgb", dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.default_rng(4).uniform(-1, 1, (1, 16, 64, 64, 3))
                    .astype(np.float32))
    p = random_params_like(lambda r, d: m32.init(r, d, features=True),
                           jax.random.PRNGKey(0), x)["params"]
    f32 = np.asarray(m32.apply({"params": p}, x, features=True))
    fbf = np.asarray(mbf.apply({"params": p}, x, features=True))
    scale = np.abs(f32).max() + 1e-6
    assert np.abs(f32 - fbf).max() <= 0.05 * scale


def test_resolve_corr_impl_auto_switches_on_volume_size(monkeypatch):
    from video_features_tpu.models import raft
    from video_features_tpu.models.raft import resolve_corr_impl

    # 16 pairs at 256²: pyramid 16·(32·32)²·4 B·1.328 ≈ 89 MB → volume
    assert resolve_corr_impl("auto", 16, 256, 256) == "volume"
    # 16 pairs at 1080p: 16·(135·240)²·4 B·1.328 ≈ 89 GB — several times
    # HBM; the GATHER on-demand path is the big-frame choice (ADVICE r5:
    # the matmul remat's FLOPs scale with frame area and its win was only
    # measured at 64×64 on CPU). The remat is never auto's choice: it is
    # asked for by name
    assert resolve_corr_impl("auto", 16, 1080, 1920) == "on_demand"
    # explicit choices pass through untouched
    for impl in ("volume", "volume_gather", "on_demand", "on_demand_matmul"):
        assert resolve_corr_impl(impl, 16, 1080, 1920) == impl
    # bf16 halves the volume: a geometry just past the fp32 budget fits
    monkeypatch.setattr(raft, "_VOLUME_HBM_BUDGET", 16 * (32 * 32) ** 2 * 4)
    assert resolve_corr_impl("auto", 16, 256, 256) == "on_demand"
    # mesh-sharded step: the budget is per DEVICE — 8 devices hold 2 pairs
    # each, so the same global batch fits (advisor round-3 finding)
    assert resolve_corr_impl("auto", 16, 256, 256, n_devices=8) == "volume"
    assert resolve_corr_impl("auto", 16, 256, 256, jnp.bfloat16) == "volume"


def test_raft_forward_accepts_auto():
    from video_features_tpu.models.raft import raft_forward, raft_init_params

    rng = np.random.default_rng(9)
    params = raft_init_params(0)
    x1 = jnp.asarray(rng.uniform(0, 255, (1, 32, 40, 3)).astype(np.float32))
    x2 = jnp.asarray(rng.uniform(0, 255, (1, 32, 40, 3)).astype(np.float32))
    auto = raft_forward(params, x1, x2, iters=2, corr_impl="auto")
    vol = raft_forward(params, x1, x2, iters=2, corr_impl="volume")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(vol))


def test_r21d_bf16_close_to_fp32():
    """R(2+1)D bf16 (direct conv3d — its factored convs are NOT hit by the
    conv3d-bf16 pathology, and the tap lowering measured slower there; see
    models/r21d.py::_conv3d) must stay near the fp32 model on shared params."""
    from video_features_tpu.models.r21d import R2Plus1D18
    from video_features_tpu.weights.store import random_params_like

    m32 = R2Plus1D18(dtype=jnp.float32)
    mbf = R2Plus1D18(dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.default_rng(8).uniform(-2, 2, (1, 4, 56, 56, 3))
                    .astype(np.float32))
    p = random_params_like(lambda r, d: m32.init(r, d, features=True),
                           jax.random.PRNGKey(0), x)["params"]
    f32 = np.asarray(m32.apply({"params": p}, x, features=True))
    fbf = np.asarray(mbf.apply({"params": p}, x, features=True))
    scale = np.abs(f32).max() + 1e-6
    assert np.abs(f32 - fbf).max() <= 0.05 * scale


def _warp_backward_numpy(img, flow):
    """The reference's backward warp (pwc_net.py:23-41) written out: bilinear
    taps at ``base + flow`` with zeros outside the frame, a ones channel
    sampled alongside, and every pixel whose sampled one is <= 0.999 zeroed."""
    img = img.astype(np.float32)
    _, h, w, _ = img.shape
    ys, xs = np.meshgrid(np.arange(h, dtype=np.float32),
                         np.arange(w, dtype=np.float32), indexing="ij")
    x, y = xs[None] + flow[..., 0], ys[None] + flow[..., 1]
    x0, y0 = np.floor(x), np.floor(y)
    fx, fy = x - x0, y - y0
    batch = np.arange(img.shape[0])[:, None, None]
    out = np.zeros(img.shape, np.float32)
    ones = np.zeros(img.shape[:-1], np.float32)
    for yi, wy in ((y0, 1 - fy), (y0 + 1, fy)):
        for xi, wx in ((x0, 1 - fx), (x0 + 1, fx)):
            inside = (yi >= 0) & (yi <= h - 1) & (xi >= 0) & (xi <= w - 1)
            weight = (wy * wx * inside).astype(np.float32)
            taps = img[batch, np.clip(yi, 0, h - 1).astype(int),
                       np.clip(xi, 0, w - 1).astype(int)]
            out += weight[..., None] * taps
            ones += weight
    return out * (ones > 0.999)[..., None]


@pytest.mark.parametrize("case", ["float32", "bfloat16_image", "whole_pixels"])
def test_warp_backward_matches_numpy_reference(case):
    """The one warp lowering (corner gathers) against the warp written in
    NumPy, on flows that leave the frame on every side."""
    from video_features_tpu.ops.warp import warp_backward

    rng = np.random.default_rng(3)
    img = rng.standard_normal((2, 11, 15, 6)).astype(np.float32)
    flow = rng.uniform(-12, 12, (2, 11, 15, 2)).astype(np.float32)
    if case == "whole_pixels":
        # every tap lands on a pixel, the far corner and one past each edge
        # among them: the kept pixels are the shifted image exactly
        flow = np.round(flow)
    device_img = jnp.asarray(img)
    if case == "bfloat16_image":
        device_img = device_img.astype(jnp.bfloat16)
        img = np.asarray(device_img.astype(jnp.float32))
    out = np.asarray(warp_backward(device_img, jnp.asarray(flow)))
    ref = _warp_backward_numpy(img, flow)
    assert out.dtype == np.float32 and out.shape == ref.shape
    zeroed = np.all(ref == 0, axis=-1)
    # both are there to be wrong about: pixels kept and pixels zeroed, and
    # flows past the left, right, top and bottom edges
    assert 0.1 < zeroed.mean() < 0.9
    xs = np.arange(15, dtype=np.float32)[None, None] + flow[..., 0]
    ys = np.arange(11, dtype=np.float32)[None, :, None] + flow[..., 1]
    assert xs.min() < -1 and xs.max() > 15 and ys.min() < -1 and ys.max() > 11
    np.testing.assert_array_equal(np.all(out == 0, axis=-1), zeroed)
    if case == "whole_pixels":
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("size,out_size,channels", [
    ((256, 341), (256, 384), 3),     # PWC at the I3D geometry: height skipped
    ((64, 96), (256, 341), 2),       # PWC's flow back to the frame
    ((96, 128), (128, 171), 3),      # R(2+1)D: a downscale, no antialiasing
    ((720, 1280), (768, 1280), 3),   # PWC alone at 720p: width skipped
    ((37, 53), (37, 53), 3),         # identity: the input's values exactly
    ((1, 9), (4, 20), 1),            # a 1-pixel axis
])
def test_resize_bilinear_matches_torch(size, out_size, channels):
    """ops/warp.resize_bilinear_torch == F.interpolate(mode='bilinear',
    align_corners=False) over the geometries its callers use and their
    edges. The interpolation matrices hold torch's own weights bit for bit
    (the source coordinate is rounded once, as torch's fused multiply-add
    does); what is left is the order of the two lerps."""
    import torch

    from video_features_tpu.ops.warp import resize_bilinear_torch

    x = np.random.default_rng(9).random((2,) + size + (channels,), dtype=np.float32)
    out = np.asarray(resize_bilinear_torch(jnp.asarray(x), *out_size))
    assert out.dtype == np.float32 and out.shape == (2,) + out_size + (channels,)
    if size == out_size:
        np.testing.assert_array_equal(out, x)
        return
    ref = torch.nn.functional.interpolate(
        torch.from_numpy(x).permute(0, 3, 1, 2), size=out_size, mode="bilinear",
        align_corners=False).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


def test_resize_bilinear_is_pinned_contractions_not_gathers():
    """A resize between static geometries holds no gather, one contraction
    per axis that changes, and pins float32 products itself: the CLI's
    default matmul precision (one bfloat16 pass on a TPU) must not reach it."""
    from jax import lax

    from video_features_tpu.ops.warp import resize_bilinear_torch

    x = jnp.asarray(np.random.default_rng(2).random((2, 12, 16, 3), dtype=np.float32))
    for out_size, n_dots in (((24, 31), 2), ((12, 31), 1), ((24, 16), 1), ((12, 16), 0)):
        with jax.default_matmul_precision("bfloat16"):
            jaxpr = jax.make_jaxpr(lambda a: resize_bilinear_torch(a, *out_size))(x)
        prims = [e.primitive.name for e in jaxpr.eqns]
        assert "gather" not in prims and "dynamic_slice" not in prims, prims
        dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
        assert len(dots) == n_dots, (out_size, prims)
        for e in dots:
            assert e.params["precision"] == (lax.Precision.HIGHEST,) * 2
            assert e.params["preferred_element_type"] == jnp.float32
    plain = np.asarray(resize_bilinear_torch(x, 24, 31))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(np.asarray(resize_bilinear_torch(x, 24, 31)), plain)


def test_raft_on_demand_matmul_matches_gather():
    """The gather-free on-demand lookup (per-iteration MXU volume remat +
    one-hot window selection, models/raft._lookup_on_demand impl='matmul')
    must match the gather formulation, incl. OOB windows and the chunked
    query path (chunk < H·W)."""
    from video_features_tpu.models.raft import (
        _build_f2_pyramid, _lookup_on_demand, coords_grid)

    rng = np.random.default_rng(5)
    b, h, w, d = 2, 16, 24, 12
    f1 = jnp.asarray(rng.standard_normal((b, h, w, d)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((b, h, w, d)).astype(np.float32))
    pyr = _build_f2_pyramid(f2)
    # coords: grid + big random flow so plenty of windows leave the image
    coords = coords_grid(b, h, w) + jnp.asarray(
        rng.uniform(-10, 10, (b, h, w, 2)).astype(np.float32))
    ref = np.asarray(_lookup_on_demand(f1, pyr, coords, "gather"))
    out = np.asarray(_lookup_on_demand(f1, pyr, coords, "matmul"))
    assert out.shape == ref.shape == (b, h, w, 4 * 81)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    # forced tiny chunks exercise the scan + tail-pad path
    out_c = np.asarray(_lookup_on_demand(f1, pyr, coords, "matmul",
                                         chunk_budget=h * w * 7))
    np.testing.assert_allclose(out_c, ref, rtol=1e-4, atol=1e-4)


# --- the attention kernel over packed documents ----------------------------------

def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            if hasattr(value, "jaxpr"):
                yield from _pallas_calls(value.jaxpr)


# the published attention of each text model: (query heads, key/value heads,
# head_dim, window, shared key columns) → the query heads a grid step holds
@pytest.mark.parametrize("heads,kv,d,window,shared,heads_step", [
    pytest.param(32, 8, 64, None, 0, 8, id="lfm2_32_over_8_x64"),
    pytest.param(48, 8, 128, None, 0, 6, id="laguna_full_48_over_8_x128"),
    pytest.param(72, 8, 128, 512, 0, 9, id="laguna_window_72_over_8_x128"),
    pytest.param(16, 2, 256, None, 0, 8, id="qwen3_next_16_over_2_x256"),
    pytest.param(20, 1, 128, None, 0, 20, id="jamba_20_over_1_x128"),
    pytest.param(64, 64, 128, None, 64, 8, id="sarvam_latent_64_x128_shared_64"),
])
def test_the_softmax_state_is_lane_replicated_at_every_published_shape(
        heads, kv, d, window, shared, heads_step):
    """The running max and sum are ``(heads_step, block, 128)`` float32, each
    row's value in every lane of its row, beside the ``(block, heads_step *
    head_dim)`` accumulator: the score tile's lane rows take them by whole
    copies, with no lane broadcast per row."""
    from video_features_tpu.ops.segment_attention import segment_attention

    tokens, block = 16384, 512
    arg = lambda w, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (tokens, w) if w else (tokens,), dtype)
    call = functools.partial(segment_attention, kv_heads=kv, head_dim=d, window=window, block=block)
    args = [arg(heads * d), arg(kv * d), arg(kv * d), arg(0, jnp.int32)]
    if shared:
        args += [arg(heads * shared), arg(shared)]
    traced = jax.make_jaxpr(lambda q, k, v, doc, *rest: call(q, k, v, doc, **dict(
        zip(("q_shared", "k_shared"), rest))))(*args)
    (pallas,) = _pallas_calls(traced.jaxpr)
    n = pallas.params["grid_mapping"].num_scratch_operands
    scratch = [(tuple(v.aval.shape), v.aval.dtype) for v in pallas.params["jaxpr"].invars[-n:]]
    assert scratch == [((heads_step, block, 128), jnp.float32)] * 2 + [
        ((block, heads_step * d), jnp.float32)]


def _plain_attention(q, k, v, doc, heads, kv, d, window):
    """Softmax within each document (and fewer than ``window`` back), query
    head ``a`` over key/value head ``a // (heads / kv)``, in float32."""
    n = q.shape[0]
    i = np.arange(n)
    seen = (doc[:, None] == doc[None, :]) & (i[None, :] <= i[:, None])
    if window is not None:
        seen &= i[:, None] - i[None, :] < window
    o = np.zeros((n, heads * d), np.float32)
    for a in range(heads):
        b = a // (heads // kv)
        s = np.where(seen, q[:, a * d:(a + 1) * d] @ k[:, b * d:(b + 1) * d].T, -np.inf)
        p = np.exp(s - s.max(1, keepdims=True))
        o[:, a * d:(a + 1) * d] = p / p.sum(1, keepdims=True) @ v[:, b * d:(b + 1) * d]
    return o


@pytest.mark.parametrize("heads,kv,d,block,window,lengths,tokens", [
    # a document begins at block 0's last query, another ends one short of a block
    pytest.param(4, 1, 32, 16, None, (15, 33, 15), 64, id="document_at_a_blocks_last_token"),
    # the page's last block holds nothing but pads
    pytest.param(4, 2, 64, 16, None, (20, 28), 64, id="last_block_all_pads"),
    # 256-wide heads over a 128-lane state: `alpha` and the sum tiled twice
    pytest.param(4, 2, 256, 128, None, (100, 200), 384, id="heads_256_wide_state_tiled"),
    # 64-wide heads at a lane row's upper half take the state's upper lanes
    pytest.param(8, 2, 64, 128, None, (130, 90), 256, id="heads_64_wide_at_lane_offset"),
    pytest.param(6, 2, 128, 32, 40, (100, 60), 192, id="window_40_over_blocks_of_32"),
])
def test_segment_attention_against_a_plain_softmax(heads, kv, d, block, window, lengths, tokens,
                                                   rng):
    """Pages of several documents and a padded tail in the Pallas interpreter,
    against the softmax written out in float32; pad rows stay finite."""
    from video_features_tpu.ops.segment_attention import segment_attention

    doc = np.full(tokens, -1, np.int32)
    doc[:sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
    q, k, v = (rng.standard_normal((tokens, n * d)).astype(np.float32) * 0.5
               for n in (heads, kv, kv))
    got = np.asarray(segment_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                       jnp.asarray(doc), kv_heads=kv, head_dim=d, window=window,
                                       block=block, interpret=True))
    want = _plain_attention(q, k, v, doc, heads, kv, d, window)
    real = doc >= 0
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[real], want[real], atol=2e-5, rtol=1e-5)

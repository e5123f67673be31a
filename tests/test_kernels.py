"""Fast direct tests of the TPU kernel lowerings (default test subset).

Covers the production paths a slow-marked file would hide from the default
run: the spatially tiled Pallas cost-volume kernel (interpret mode) and the
bf16 TapConv3D lowering every bf16 I3D conv takes.
"""
# fast-registry: default tier — kernel parity vs torch mirrors

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


def test_tap_conv3d_matches_direct_conv():
    """The bf16 tap lowering must equal nn.Conv's conv3d (same TF-SAME pads);
    checked in fp32 where equality is tight (bf16 only reassociates further)."""
    import flax.linen as fnn

    from video_features_tpu.models.layers import TapConv3D, tf_same_pads

    rng = np.random.default_rng(3)
    for kernel, stride in (((7, 7, 7), (2, 2, 2)), ((3, 3, 3), (1, 1, 1)),
                           ((1, 1, 1), (1, 1, 1))):
        x = jnp.asarray(rng.standard_normal((2, 8, 12, 12, 4)).astype(np.float32))
        tap = TapConv3D(6, kernel, stride, dtype=jnp.float32)
        params = tap.init(jax.random.PRNGKey(0), x)
        out = tap.apply(params, x)
        kern = params["params"]["kernel"]
        ref = fnn.Conv(6, kernel, strides=stride,
                       padding=tf_same_pads(kernel, stride), use_bias=False,
                       dtype=jnp.float32).apply({"params": {"kernel": kern}}, x)
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_tap_fp32_flag_routes_joint_extent_only(monkeypatch):
    """VFT_I3D_TAP_FP32=1: fp32 convs with joint spatio-temporal extent take
    the tap lowering (same numerics to ~1e-6); factored kernels stay direct."""
    import flax.linen as fnn

    from video_features_tpu.models.layers import TapConv3D, conv3d_module

    monkeypatch.setenv("VFT_I3D_TAP_FP32", "1")
    pads = ((1, 1), (1, 1), (1, 1))
    joint = conv3d_module(6, (3, 3, 3), (1, 1, 1), pads, jnp.float32, "c")
    assert isinstance(joint, TapConv3D)
    factored = conv3d_module(6, (3, 1, 1), (1, 1, 1),
                             ((1, 1), (0, 0), (0, 0)), jnp.float32, "c")
    assert isinstance(factored, fnn.Conv)
    monkeypatch.delenv("VFT_I3D_TAP_FP32")
    off = conv3d_module(6, (3, 3, 3), (1, 1, 1), pads, jnp.float32, "c")
    assert isinstance(off, fnn.Conv)

    # full-model numerics under the flag: same params, ~fp32-tight agreement
    monkeypatch.setenv("VFT_I3D_TAP_FP32", "1")
    from video_features_tpu.models.i3d import I3D

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.uniform(-1, 1, (1, 16, 32, 32, 3)).astype(np.float32))
    model = I3D(modality="rgb")
    params = model.init(jax.random.PRNGKey(0), x, features=True)
    tap_out = np.asarray(model.apply(params, x, features=True))
    monkeypatch.delenv("VFT_I3D_TAP_FP32")
    ref_out = np.asarray(model.apply(params, x, features=True))
    np.testing.assert_allclose(tap_out, ref_out, rtol=1e-4, atol=1e-5)


def test_tap_conv3d_explicit_pads_match_direct_conv():
    """The explicit-padding branch (torch-style R21D pads, incl. asymmetric)
    at the tight kernel-level tolerance — the end-to-end 5% feature test could
    absorb a boundary-only lo/hi swap."""
    import flax.linen as fnn

    from video_features_tpu.models.layers import TapConv3D

    rng = np.random.default_rng(5)
    cases = (
        ((1, 7, 7), (1, 2, 2), ((0, 0), (3, 3), (3, 3))),  # r21d stem
        ((3, 1, 1), (2, 1, 1), ((1, 1), (0, 0), (0, 0))),  # strided temporal
        ((3, 3, 3), (1, 1, 1), ((0, 1), (1, 2), (2, 0))),  # asymmetric pads
    )
    for kernel, stride, pads in cases:
        x = jnp.asarray(rng.standard_normal((2, 7, 13, 13, 4)).astype(np.float32))
        tap = TapConv3D(6, kernel, stride, dtype=jnp.float32, padding=pads)
        params = tap.init(jax.random.PRNGKey(1), x)
        out = tap.apply(params, x)
        kern = params["params"]["kernel"]
        ref = fnn.Conv(6, kernel, strides=stride, padding=pads, use_bias=False,
                       dtype=jnp.float32).apply({"params": {"kernel": kern}}, x)
        assert out.shape == ref.shape, (kernel, stride, pads)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_i3d_bf16_tap_path_close_to_fp32():
    """dtype=bfloat16 now routes convs through TapConv3D; features must stay
    near the fp32 model (same params)."""
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

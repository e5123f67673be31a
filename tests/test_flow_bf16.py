"""Measured bf16 flow-net drift (--flow_dtype bfloat16) vs the fp32 path.

Round-2 review: fp32-only flow was an *asserted* precision claim
("iterative flow refinement is precision-sensitive") with no measurement.
These tests quantify the drift and pin the bound that makes bf16 flow safe
for the I3D sandwich: the reference quantizes flow to uint8 at 40/255 ≈ 0.157
px per step (``extract_i3d.py:59-72``), so flow errors well under half a step
(~0.078 px) are absorbed or flip at most border pixels by ±1 level.

CPU runs bf16 in emulation — slow but bit-faithful; shapes stay small.
"""
# fast-registry: default tier — bf16 drift measurement over flow compiles

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from video_features_tpu.models.pwc import (
    DEC_CURRENT,
    DENSE_OUT,
    LEVEL_NAMES,
    _decoder,
    pwc_forward,
    pwc_init_params,
)
from video_features_tpu.models.raft import raft_forward, raft_init_params


@pytest.fixture(scope="module")
def frames(rng_mod=np.random.default_rng(21)):
    # smooth synthetic frames + a shifted copy: realistic small flows, not
    # white noise (white noise makes correlation windows degenerate)
    base = rng_mod.uniform(0, 255, (1, 40, 48, 3)).astype(np.float32)
    from scipy.ndimage import gaussian_filter, shift

    base = gaussian_filter(base, sigma=(0, 3, 3, 0))
    nxt = shift(base, (0, 1.3, -0.8, 0), order=1, mode="nearest")
    return jnp.asarray(base), jnp.asarray(nxt)


def test_pwc_bf16_drift_bounded(frames):
    x1, x2 = frames
    params = pwc_init_params(0)
    f32 = np.asarray(pwc_forward(params, x1, x2))
    bf16 = np.asarray(pwc_forward(params, x1, x2, dtype=jnp.bfloat16))
    err = np.abs(bf16 - f32)
    scale = np.abs(f32).max() + 1e-6
    # bf16 has ~3 decimal digits; one conv stack + refiner accumulates to
    # sub-percent relative error in practice — bound at 2% of peak flow
    assert err.max() <= 0.02 * scale + 1e-3, (err.max(), scale)


def test_pwc_bf16_decoder_accumulates_in_float32():
    """The by-source dense block adds six partial convolutions: under
    ``dtype=bfloat16`` each must come out of the MXU as float32 and be added in
    float32, and only the finished sum rounded (the concatenated form rounded
    one float32 sum over all input channels). Read from the jaxpr, so the
    rounding point cannot drift back unseen."""
    p = pwc_init_params(0)[LEVEL_NAMES[6]]
    f = jnp.zeros((1, 4, 6, 196), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda a, b: _decoder(p, 6, a, b, None, "xla"))(f, f)
    convs = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "conv_general_dilated"]
    assert len(convs) == 6
    for eqn in convs:
        assert eqn.invars[0].aval.dtype == jnp.bfloat16  # the products stay bfloat16
        assert eqn.outvars[0].aval.dtype == jnp.float32
    # every sum over feature maps (21 in the block: a bias and up to six
    # partial convolutions for each of six consumers) is a float32 sum
    adds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "add"
            and len(e.outvars[0].aval.shape) == 4]
    assert len(adds) >= 21 and all(e.outvars[0].aval.dtype == jnp.float32 for e in adds)
    feat, flow = jaxpr.out_avals  # a dict's leaves, by key
    assert flow.dtype == feat.dtype == jnp.bfloat16
    assert flow.shape[-1] == 2 and feat.shape[-1] == DEC_CURRENT[6] + sum(DENSE_OUT)


def test_raft_bf16_drift_bounded(frames):
    x1, x2 = frames
    params = raft_init_params(0)
    f32 = np.asarray(raft_forward(params, x1, x2, iters=8))
    bf16 = np.asarray(raft_forward(params, x1, x2, iters=8, dtype=jnp.bfloat16))
    err = np.abs(bf16 - f32)
    scale = np.abs(f32).max() + 1e-6
    # the fp32 coords carry keeps per-iteration bf16 conv noise from
    # compounding multiplicatively; bound at 5% of peak flow for 8 iterations
    assert err.max() <= 0.05 * scale + 1e-3, (err.max(), scale)


def test_bf16_flow_quantizes_like_fp32(frames):
    """The I3D sandwich's uint8 quantization absorbs bf16 flow drift: quantized
    planes agree within ±1 level on ≥99% of pixels."""
    from video_features_tpu.models.i3d import i3d_preprocess_flow

    x1, x2 = frames
    params = pwc_init_params(0)
    f32 = pwc_forward(params, x1, x2)
    bf16 = pwc_forward(params, x1, x2, dtype=jnp.bfloat16)
    q32 = np.asarray(i3d_preprocess_flow(f32[:, None]))
    qbf = np.asarray(i3d_preprocess_flow(bf16[:, None]))
    # levels are 2/255 apart after ScaleTo1_1
    level = 2.0 / 255.0
    diff_levels = np.abs(q32 - qbf) / level
    assert (diff_levels <= 1.0 + 1e-6).mean() >= 0.99, diff_levels.max()


def test_flow_dtype_plumbs_through_extractor(tmp_path, monkeypatch):
    monkeypatch.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")
    from video_features_tpu.config import ExtractionConfig
    from video_features_tpu.extractors.flow import ExtractFlow

    rng = np.random.default_rng(5)
    fr = rng.uniform(0, 255, (4, 40, 48, 3)).astype(np.float32)
    outs = {}
    for fd in ("float32", "bfloat16"):
        cfg = ExtractionConfig(feature_type="pwc", batch_size=3, num_devices=1,
                               flow_dtype=fd,
                               output_path=str(tmp_path / f"o{fd}"),
                               tmp_path=str(tmp_path / f"t{fd}"))
        ex = ExtractFlow(cfg)
        outs[fd] = ex._run_pairs(fr)
    assert outs["float32"].shape == outs["bfloat16"].shape
    # different dtypes must actually change the numerics (plumbing is live)...
    assert not np.array_equal(outs["float32"], outs["bfloat16"])
    # ...but only slightly
    scale = np.abs(outs["float32"]).max() + 1e-6
    assert np.abs(outs["float32"] - outs["bfloat16"]).max() <= 0.05 * scale


def test_raft_on_demand_matmul_bf16_drift_bounded(frames):
    """bf16 on_demand_matmul (bf16 vol-einsum inputs, fp32 accumulation) vs
    the fp32 gather on-demand path: same drift class as the volume path's
    bf16 pyramid storage — one bf16 rounding of the lookup input."""
    x1, x2 = frames
    params = raft_init_params(0)
    f32 = np.asarray(raft_forward(params, x1, x2, iters=8,
                                  corr_impl="on_demand"))
    bf16 = np.asarray(raft_forward(params, x1, x2, iters=8,
                                   corr_impl="on_demand_matmul",
                                   dtype=jnp.bfloat16))
    err = np.abs(bf16 - f32)
    scale = np.abs(f32).max() + 1e-6
    assert err.max() <= 0.05 * scale + 1e-3, (err.max(), scale)
    # the dtype plumbing is LIVE: a direct lookup in bf16 must differ from
    # fp32 (else a silent revert of the bf16 vol-einsum passes the bound
    # above on conv drift alone)
    from video_features_tpu.models.raft import (
        _build_f2_pyramid, _lookup_on_demand, _encoder, coords_grid)

    f1 = _encoder(params["fnet"], 2.0 * (x1 / 255.0) - 1.0, "instance")
    f2 = _encoder(params["fnet"], 2.0 * (x2 / 255.0) - 1.0, "instance")
    pyr = _build_f2_pyramid(f2.astype(jnp.float32))
    coords = coords_grid(*f1.shape[:3])
    a = np.asarray(_lookup_on_demand(f1, pyr, coords, "matmul"))
    b = np.asarray(_lookup_on_demand(f1, pyr, coords, "matmul",
                                     dtype=jnp.bfloat16))
    assert np.abs(a - b).max() > 0, "bf16 vol-einsum plumbing is dead"
    assert np.allclose(a, b, rtol=0.03, atol=0.03 * np.abs(a).max())

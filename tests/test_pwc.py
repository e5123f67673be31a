"""JAX PWC-Net numerical parity vs a torch functional mirror (random weights),
and the decoder's dense block, convolved by source, against the concatenated form."""
# fast-registry: default tier — the dense block's algebra and structure guard the benchmark's hottest scope; whole-model parity stays slow

import os
import re
import sys

import numpy as np
import pytest

slow = pytest.mark.slow  # multi-minute on CPU: whole-model parity / full-video extract


sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import jax
import jax.numpy as jnp
import torch

from torch_mirrors import _pwc_corr, _pwc_warp, pwc_random_state_dict, pwc_torch_forward
from video_features_tpu.models.pwc import (
    DEC_CURRENT,
    DENSE_NAMES,
    DENSE_OUT,
    LEVEL_NAMES,
    _decoder,
    _dense_block,
    correlation_81,
    pwc_forward,
    pwc_init_params,
)
from video_features_tpu.ops.nnf import conv2d, leaky_relu
from video_features_tpu.ops.warp import warp_backward
from video_features_tpu.weights.convert_torch import convert_pwc


@pytest.fixture(scope="module")
def converted():
    sd = pwc_random_state_dict(seed=11)
    return sd, convert_pwc(sd)


@slow
def test_param_tree_matches_init_structure(converted):
    _, params = converted
    init = pwc_init_params(seed=0)
    p1 = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    p2 = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(init)[0]}
    assert p1 == p2


@slow
def test_correlation_matches_torch():
    rng = np.random.default_rng(0)
    f1 = rng.standard_normal((2, 10, 12, 7)).astype(np.float32)
    f2 = rng.standard_normal((2, 10, 12, 7)).astype(np.float32)
    ref = _pwc_corr(torch.from_numpy(f1).permute(0, 3, 1, 2),
                    torch.from_numpy(f2).permute(0, 3, 1, 2)).permute(0, 2, 3, 1).numpy()
    out = np.asarray(correlation_81(jnp.asarray(f1), jnp.asarray(f2)))
    assert out.shape == (2, 10, 12, 81)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@slow
def test_warp_matches_torch():
    rng = np.random.default_rng(1)
    img = rng.standard_normal((2, 8, 9, 5)).astype(np.float32)
    flow = (rng.standard_normal((2, 8, 9, 2)) * 2).astype(np.float32)
    ref = _pwc_warp(torch.from_numpy(img).permute(0, 3, 1, 2),
                    torch.from_numpy(flow).permute(0, 3, 1, 2)).permute(0, 2, 3, 1).numpy()
    out = np.asarray(warp_backward(jnp.asarray(img), jnp.asarray(flow)))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@slow
def test_flow_parity(converted):
    sd, params = converted
    rng = np.random.default_rng(0)
    # non-/64 size exercises both bilinear resizes (in and out)
    img1 = rng.uniform(0, 255, (1, 96, 120, 3)).astype(np.float32)
    img2 = rng.uniform(0, 255, (1, 96, 120, 3)).astype(np.float32)
    ref = pwc_torch_forward(
        sd, torch.from_numpy(img1).permute(0, 3, 1, 2), torch.from_numpy(img2).permute(0, 3, 1, 2)
    ).permute(0, 2, 3, 1).numpy()
    out = np.asarray(pwc_forward(params, jnp.asarray(img1), jnp.asarray(img2)))
    assert out.shape == ref.shape == (1, 96, 120, 2)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=2e-3)
    cos = np.sum(out * ref) / (np.linalg.norm(out) * np.linalg.norm(ref))
    assert cos > 1 - 1e-5


# ---- the dense block: by source against by concatenation -------------------

def _dense_block_concatenated(p, feat):
    """The block as the reference writes it (pwc_net.py:166-187): consumer k
    reads the concatenation of every earlier map, new features in front."""
    for name in DENSE_NAMES[:-1]:
        feat = jnp.concatenate([leaky_relu(conv2d(p[name]["0"], feat, 1, 1)), feat], axis=-1)
    return conv2d(p["moduleSix"]["0"], feat, 1, 1), feat


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("level", [6, 5, 4, 3, 2])
def test_dense_block_by_source_equals_concatenated(level):
    p = pwc_init_params(seed=3)[LEVEL_NAMES[level]]
    x = jnp.asarray(np.random.default_rng(level).standard_normal(
        (2, 6, 7, DEC_CURRENT[level])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        flow_ref, feat_ref = _dense_block_concatenated(p, x)
        flow, feat = _dense_block(p, x)
    assert flow.shape == flow_ref.shape and feat.shape == feat_ref.shape
    assert feat.shape[-1] == DEC_CURRENT[level] + sum(DENSE_OUT)
    assert _gap(flow, flow_ref) <= 2e-6 and _gap(feat, feat_ref) <= 2e-6


def test_dense_block_slices_are_told_apart():
    """Weights in which the (consumer k, source j) slice is the constant
    ``k + j/8`` at the centre tap and 0 elsewhere, no bias, an input of ones:
    every map is then constant, and the maps' values follow from the slices'
    widths alone. A wrong row offset or column order pairs a source with
    another slice's constant, and no such pairing gives these numbers."""
    widths = (DEC_CURRENT[2],) + DENSE_OUT  # of x_0 … x_5
    p = {}
    for k, name in enumerate(DENSE_NAMES, start=1):
        # consumer k's input is [x_{k-1} | … | x_0]: source j's rows start
        # after the maps newer than it
        kernel = np.zeros((3, 3, sum(widths[:k]), (DENSE_OUT + (2,))[k - 1]), np.float32)
        for j in range(k):
            off = sum(widths[j + 1:k])
            kernel[1, 1, off:off + widths[j], :] = (k + j / 8) / 1024
        p[name] = {"0": {"kernel": kernel, "bias": np.zeros(kernel.shape[-1], np.float32)}}
    x = jnp.ones((1, 5, 5, widths[0]), jnp.float32)
    flow, feat = _dense_block(p, x)
    value = [1.0]  # x_0 … x_5, then the flow: positive throughout, so no leak
    for k in range(1, 7):
        value.append(sum((k + j / 8) / 1024 * widths[j] * value[j] for j in range(k)))
    np.testing.assert_allclose(np.asarray(flow), value[6], rtol=1e-5)
    expected = np.concatenate([np.full(widths[j], value[j]) for j in range(5, -1, -1)])
    np.testing.assert_allclose(np.asarray(feat)[0, 2, 2], expected, rtol=1e-5)
    ref_flow, ref_feat = _dense_block_concatenated(p, x)
    np.testing.assert_allclose(np.asarray(flow), np.asarray(ref_flow), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(feat), np.asarray(ref_feat), rtol=1e-5)


def test_level2_decoder_lowers_to_six_wide_convolutions():
    """Structure of the lowered level-2 decoder: the block's six convolutions
    are 450, 322, 194, 98, 34 and 2 columns wide (beside the two transposed
    convolutions that upsample flow and features, 2 columns each), and the
    565-channel map is concatenated at most once, for its readers outside."""
    params = pwc_init_params(seed=0)
    n, h, w = 1, 8, 12
    f1 = jnp.zeros((n, h, w, 32), jnp.float32)
    prev = {"flow": jnp.zeros((n, h // 2, w // 2, 2), jnp.float32),
            "feat": jnp.zeros((n, h // 2, w // 2, DEC_CURRENT[3] + sum(DENSE_OUT)), jnp.float32)}
    text = jax.jit(lambda a, b, c: _decoder(params[LEVEL_NAMES[2]], 2, a, b, c)
                   ).lower(f1, f1, prev).as_text()
    convs = re.findall(r"stablehlo\.convolution.*->\s*tensor<([0-9x]+)xf32>", text)
    assert sorted(int(c.split("x")[-1]) for c in convs) == sorted([450, 322, 194, 98, 34, 2, 2, 2])
    cats = [int(c.split("x")[-1])
            for c in re.findall(r"stablehlo\.concatenate.*->\s*tensor<([0-9x]+)xf32>", text)]
    assert cats.count(565) <= 1
    assert not {245, 373, 469, 533} & set(cats)  # the loop's growing maps are gone

"""Flax I3D numerical parity vs a torch functional mirror (random weights)."""

import os
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # whole-model parity: minutes on CPU

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import jax
import jax.numpy as jnp
import torch

from torch_mirrors import i3d_forward, i3d_random_state_dict
from video_features_tpu.models.i3d import I3D, i3d_preprocess_flow, i3d_preprocess_rgb
from video_features_tpu.weights.convert_torch import convert_i3d

# 224 spatial is what the extractor feeds; tests use 64x64 so CPU runtime stays sane.
# Temporal dim follows the reference's stack geometry scaled down (T=16 -> T'=2 after
# the /8 temporal stride, matching the i3d_net.py:256 comment for T=24).
T, S = 16, 64


@pytest.fixture(scope="module", params=["rgb", "flow"])
def modality(request):
    return request.param


@pytest.fixture(scope="module")
def converted(modality):
    sd = i3d_random_state_dict(modality=modality, seed=5)
    params = convert_i3d(sd)
    return sd, params


def test_param_tree_matches_model(converted, modality):
    sd, params = converted
    c = {"rgb": 3, "flow": 2}[modality]
    model = I3D(modality=modality)
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, S, S, c)), features=False)["params"]
    init_paths = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(init)[0]}
    conv_paths = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert init_paths == conv_paths


def test_features_parity(converted, modality):
    sd, params = converted
    c = {"rgb": 3, "flow": 2}[modality]
    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, (1, T, S, S, c)).astype(np.float32)
    ref = i3d_forward(sd, torch.from_numpy(x).permute(0, 4, 1, 2, 3), features=True).numpy()
    out = np.asarray(I3D(modality=modality).apply({"params": params}, jnp.asarray(x), features=True))
    assert out.shape == ref.shape == (1, 1024)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=5e-4)
    cos = np.sum(out * ref) / (np.linalg.norm(out) * np.linalg.norm(ref))
    assert cos > 1 - 1e-6


def test_logits_parity(converted, modality):
    sd, params = converted
    c = {"rgb": 3, "flow": 2}[modality]
    rng = np.random.default_rng(1)
    x = rng.uniform(-1, 1, (1, T, S, S, c)).astype(np.float32)
    ref_probs, ref_logits = i3d_forward(sd, torch.from_numpy(x).permute(0, 4, 1, 2, 3), features=False)
    probs, logits = I3D(modality=modality).apply({"params": params}, jnp.asarray(x), features=False)
    np.testing.assert_allclose(np.asarray(logits), ref_logits.numpy(), rtol=1e-3, atol=5e-4)
    np.testing.assert_allclose(np.asarray(probs), ref_probs.numpy(), rtol=1e-3, atol=1e-5)


def test_preprocess_rgb_matches_reference():
    u8 = np.arange(0, 256, dtype=np.uint8).reshape(1, 1, 16, 16, 1).repeat(3, -1)
    out = np.asarray(i3d_preprocess_rgb(jnp.asarray(u8)))
    ref = 2 * u8.astype(np.float32) / 255 - 1
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_preprocess_flow_matches_reference():
    # Clamp(-20,20) -> round(128 + 255/40 f) (half-to-even, unclipped) -> 2x/255 - 1
    f = np.array([-25.0, -20.0, -0.1, 0.0, 0.1, 19.9, 20.0, 25.0], np.float32).reshape(1, 1, 1, 4, 2)
    t = torch.from_numpy(f).clamp(-20, 20)
    ref = (2 * (128 + 255 / 40 * t).round() / 255 - 1).numpy()
    out = np.asarray(i3d_preprocess_flow(jnp.asarray(f)))
    np.testing.assert_allclose(out, ref, rtol=0, atol=0)  # must be bit-exact
    assert out.max() > 1.0  # the 256 quirk survives


def test_maxpool_tf_same_matches_torch_ceilmode():
    """Odd input sizes exercise the ceil-mode overhang path."""
    from torch_mirrors import _tf_same_pad_5d
    from video_features_tpu.models.layers import max_pool_tf_same

    rng = np.random.default_rng(2)
    x = rng.standard_normal((1, 7, 9, 11, 4)).astype(np.float32)
    for kernel, stride in [((1, 3, 3), (1, 2, 2)), ((3, 3, 3), (2, 2, 2)), ((2, 2, 2), (2, 2, 2)),
                           ((3, 3, 3), (1, 1, 1))]:
        t = torch.nn.functional.pad(
            torch.from_numpy(x).permute(0, 4, 1, 2, 3), _tf_same_pad_5d(kernel, stride))
        ref = torch.nn.functional.max_pool3d(t, kernel, stride, ceil_mode=True)
        out = np.asarray(max_pool_tf_same(jnp.asarray(x), kernel, stride))
        np.testing.assert_allclose(out, ref.permute(0, 2, 3, 4, 1).numpy(), rtol=1e-6, atol=1e-6)

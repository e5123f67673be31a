"""Inception-3D (I3D, Kinetics-400) in Flax, NDHWC layout.

Behavioral spec — ``/root/reference/models/i3d/i3d_src/i3d_net.py``:
- Unit3D = conv3d (TF-SAME asymmetric padding, no bias) + eval BatchNorm + ReLU
  (``:37-105``); max pools zero-pad TF-SAME then pool with ceil_mode (``:108-120``).
- Stem conv 7³/2, two 1×3×3/1×2×2 pools, conv 1³, conv 3³, then nine Inception
  ``Mixed`` blocks with a 3³/2³ pool between groups (``:179-224``).
- Features head (``features=True``): AvgPool3d (2,7,7) stride 1 → squeeze spatial →
  mean over remaining time → (B, 1024) (``:257-264``).
- Logits head: 1³ conv with bias (no BN/ReLU) → squeeze → time mean → softmax;
  returns (probs, logits) (``:266-274``).
- ``modality``: 'rgb' (3 input channels) or 'flow' (2) (``:170-176``).

TPU design: channel-last NDHWC so every conv lands on the MXU with native tiling;
every bias-free conv3d is a sum of per-temporal-tap conv2ds (``layers.TapConv3D``:
the asymmetric SAME pads are explicit ``lax.conv_general_dilated`` padding in
space and one zero pad in time); the architecture is one spec table walked by
``nn.compact`` — module names match the reference state_dict so checkpoint
conversion is a pure name/layout map.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    TapConv3D,
    TorchBatchNorm,
    avg_pool_valid,
    max_pool_tf_same,
    tf_same_pads,
)

# (branch_0) (branch_1 reduce, branch_1 out) (branch_2 reduce, branch_2 out) (branch_3)
MixedSpec = Tuple[int, int, int, int, int, int]

# name → op spec; walked in order by I3D.__call__
I3D_STEM = (
    ("conv", "conv3d_1a_7x7", 64, (7, 7, 7), (2, 2, 2)),
    ("pool", "maxPool3d_2a_3x3", (1, 3, 3), (1, 2, 2)),
    ("conv", "conv3d_2b_1x1", 64, (1, 1, 1), (1, 1, 1)),
    ("conv", "conv3d_2c_3x3", 192, (3, 3, 3), (1, 1, 1)),
    ("pool", "maxPool3d_3a_3x3", (1, 3, 3), (1, 2, 2)),
    ("mixed", "mixed_3b", (64, 96, 128, 16, 32, 32)),
    ("mixed", "mixed_3c", (128, 128, 192, 32, 96, 64)),
    ("pool", "maxPool3d_4a_3x3", (3, 3, 3), (2, 2, 2)),
    ("mixed", "mixed_4b", (192, 96, 208, 16, 48, 64)),
    ("mixed", "mixed_4c", (160, 112, 224, 24, 64, 64)),
    ("mixed", "mixed_4d", (128, 128, 256, 24, 64, 64)),
    ("mixed", "mixed_4e", (112, 144, 288, 32, 64, 64)),
    ("mixed", "mixed_4f", (256, 160, 320, 32, 128, 128)),
    ("pool", "maxPool3d_5a_2x2", (2, 2, 2), (2, 2, 2)),
    ("mixed", "mixed_5b", (256, 160, 320, 32, 128, 128)),
    ("mixed", "mixed_5c", (384, 192, 384, 48, 128, 128)),
)

NUM_FEATURES = 1024


class Unit3D(nn.Module):
    """conv3d + (optional) BN + (optional) ReLU with reference TF-SAME padding."""

    features: int
    kernel: Sequence[int] = (1, 1, 1)
    stride: Sequence[int] = (1, 1, 1)
    use_bn: bool = True
    use_bias: bool = False
    relu: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if not self.use_bias:
            # every bias-free convolution as temporal taps, in both dtypes:
            # the whole tower is then conv2ds over (N·T, H, W, C), which read
            # 11 % faster end to end than nn.Conv (TapConv3D's table)
            x = TapConv3D(self.features, self.kernel, self.stride,
                          dtype=self.dtype, name="conv3d")(x)
        else:
            x = nn.Conv(
                self.features,
                tuple(self.kernel),
                strides=tuple(self.stride),
                padding=tf_same_pads(self.kernel, self.stride),
                use_bias=True,
                dtype=self.dtype,
                name="conv3d",
            )(x)
        if self.use_bn:
            x = TorchBatchNorm(dtype=self.dtype, name="batch3d")(x)
        if self.relu:
            x = nn.relu(x)
        return x


class Mixed(nn.Module):
    """Inception block: 1³ | 1³→3³ | 1³→3³ | pool→1³, concatenated on channels.

    Submodule names mirror the reference state_dict (``branch_1.0`` etc.,
    ``i3d_net.py:123-157``) so conversion needs no name table.
    """

    spec: MixedSpec
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c0, c1r, c1, c2r, c2, c3 = self.spec
        dt = self.dtype
        b0 = Unit3D(c0, dtype=dt, name="branch_0")(x)
        b1 = Unit3D(c1r, dtype=dt, name="branch_1.0")(x)
        b1 = Unit3D(c1, (3, 3, 3), dtype=dt, name="branch_1.1")(b1)
        b2 = Unit3D(c2r, dtype=dt, name="branch_2.0")(x)
        b2 = Unit3D(c2, (3, 3, 3), dtype=dt, name="branch_2.1")(b2)
        b3 = max_pool_tf_same(x, (3, 3, 3), (1, 1, 1))
        b3 = Unit3D(c3, dtype=dt, name="branch_3.1")(b3)
        return jnp.concatenate([b0, b1, b2, b3], axis=-1)


class I3D(nn.Module):
    """Input NDHWC float in [-1, 1]; (B, T, H, W, 3) rgb or (B, T, H, W, 2) flow."""

    num_classes: int = 400
    modality: str = "rgb"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, features: bool = True):
        expected_c = {"rgb": 3, "flow": 2}[self.modality]
        if x.shape[-1] != expected_c:
            raise ValueError(
                f"{self.modality} I3D expects {expected_c} input channels, got {x.shape[-1]}"
            )
        x = x.astype(self.dtype)
        def walk(x, ops):
            for op, name, *spec in ops:
                if op == "conv":
                    feats, kernel, stride = spec
                    x = Unit3D(feats, kernel, stride, dtype=self.dtype,
                               name=name)(x)
                elif op == "pool":
                    kernel, stride = spec
                    x = max_pool_tf_same(x, kernel, stride)
                else:
                    x = Mixed(spec[0], dtype=self.dtype, name=name)(x)
            return x

        # device scope ``i3d/stem``: everything before the first inception
        # block (flax names each module's operations by its own name besides)
        n_stem = next(i for i, spec in enumerate(I3D_STEM) if spec[0] == "mixed")
        with jax.named_scope("i3d/stem"):
            x = walk(x, I3D_STEM[:n_stem])
        x = walk(x, I3D_STEM[n_stem:])

        # (B, T', 7, 7, 1024) → AvgPool3d((2,7,7), stride 1) → (B, T'-1, 1, 1, 1024).
        # The reference kernel (2,7,7) assumes the 224-crop geometry where the final
        # spatial size is exactly 7×7; the spatial kernel adapts so smaller (test)
        # inputs work — identical numerics at the supported 224 input.
        if x.shape[1] < 2:
            raise ValueError(
                f"input too short for I3D: {x.shape[1]} temporal positions remain "
                f"before the (2,·,·) average pool; use stack_size >= 16"
            )
        x = avg_pool_valid(x.astype(jnp.float32), (2, x.shape[2], x.shape[3]), (1, 1, 1))
        if features:
            return jnp.mean(x[:, :, 0, 0, :], axis=1)  # (B, 1024)

        logits = Unit3D(
            self.num_classes,
            use_bn=False,
            use_bias=True,
            relu=False,
            dtype=jnp.float32,
            name="conv3d_0c_1x1",
        )(x)
        logits = jnp.mean(logits[:, :, 0, 0, :], axis=1)  # (B, num_classes)
        return nn.softmax(logits, axis=-1), logits


def i3d_preprocess_rgb(frames_u8: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """uint8 (B, T, H, W, 3) → [-1, 1] float: the reference ``ScaleTo1_1``
    ((2x/255) − 1, ``models/i3d/transforms/transforms.py``)."""
    return (2.0 * frames_u8.astype(jnp.float32) / 255.0 - 1.0).astype(dtype)


def i3d_preprocess_flow(flow: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """Raw flow (B, T, H, W, 2) → clamp ±20 → uint8 quantize → [-1, 1].

    Reference sandwich (``extract_i3d.py:59-72`` + ``transforms.py:43-51``):
    ``Clamp(-20, 20)`` → ``ToUInt8`` = round(128 + 255/40·f), round-half-to-even and
    deliberately *not* clipped (a +20 flow maps to 255.5 → 256) → ``ScaleTo1_1``.
    The quantization is part of how the pretrained flow stream was trained, so it is
    reproduced exactly, quirk included.
    """
    f = jnp.clip(flow.astype(jnp.float32), -20.0, 20.0)
    q = jnp.round(128.0 + 255.0 / 40.0 * f)
    return (2.0 * q / 255.0 - 1.0).astype(dtype)

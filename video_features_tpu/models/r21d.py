"""R(2+1)D-18 video network in Flax (NDHWC), torchvision ``r2plus1d_18`` numerics.

Behavioral spec — the reference consumes torchvision's pretrained model with the fc
head swapped for identity (``/root/reference/models/r21d/extract_r21d.py:57-62``):
- stem: (1,7,7)/s(1,2,2) conv → BN → ReLU → (3,1,1) conv → BN → ReLU (45 midplanes);
- 4 stages of 2 BasicBlocks; every 3D conv is factored spatial (1,3,3) + BN + ReLU +
  temporal (3,1,1); midplanes ``⌊in·out·27 / (in·9 + 3·out)⌋`` is computed ONCE per
  block from (block_in, cout) and shared by both convs (so conv2 of downsampling
  blocks gets 230/460/921, not a per-conv recomputation); stages 2–4 open with
  stride 2 on both the spatial and temporal factors and a (1,1,1)/2 downsample;
- global average pool → 512-d features (fc applied only for ``--show_pred``).

Module names mirror the torchvision state_dict (``stem.0``, ``layer1.0.conv1.0.0``,
...) so conversion is a pure name/layout map. Channel-last NDHWC: both factored convs
land on the MXU with native tiling.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from .layers import TorchBatchNorm


def _conv3d(features, kernel, stride, padding, dtype, name):
    """Direct nn.Conv with explicit torch pads for ALL dtypes.

    R(2+1)D's kernels are already factored, (1,k,k) and (k,1,1): an earlier
    installation read, same-run on v5e, plain conv3d bf16 91.4 clips/s vs fp32
    70.5, while routing them through I3D's temporal-tap lowering
    (``layers.TapConv3D``) DROPPED bf16 to 72.8 (the strided temporal slicing
    relayout costs more than it saves when kt·kh·kw is already factored). I3D's
    kernels (7×7×7, 3×3×3, 1×1×1) all take the taps since PR 37, where the
    benchmark's I3D cell read 11 % faster for it; no cell runs R(2+1)D, so
    it keeps the direct conv and nothing here calls ``TapConv3D``.
    """
    return nn.Conv(features, tuple(kernel), strides=tuple(stride),
                    padding=tuple(tuple(p) for p in padding), use_bias=False,
                    dtype=dtype, name=name)

STAGE_CHANNELS = (64, 128, 256, 512)
NUM_FEATURES = 512


def midplanes(cin: int, cout: int) -> int:
    return (cin * cout * 3 * 3 * 3) // (cin * 3 * 3 + 3 * cout)


class Conv2Plus1D(nn.Module):
    """Factored 3D conv: spatial (1,3,3) → BN → ReLU → temporal (3,1,1)."""

    cout: int
    mid: int
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        s = self.stride
        x = _conv3d(self.mid, (1, 3, 3), (1, s, s),
                    ((0, 0), (1, 1), (1, 1)), self.dtype, "0")(x)
        x = TorchBatchNorm(dtype=self.dtype, name="1")(x)
        x = nn.relu(x)
        return _conv3d(self.cout, (3, 1, 1), (s, 1, 1),
                       ((1, 1), (0, 0), (0, 0)), self.dtype, "3")(x)


class BasicBlock(nn.Module):
    cin: int
    cout: int
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # torchvision computes midplanes ONCE per block from (inplanes, planes)
        # and passes the same value to both conv_builder calls
        # (torchvision/models/video/resnet.py BasicBlock.__init__); conv2 does
        # NOT recompute from (planes, planes).
        mid = midplanes(self.cin, self.cout)
        y = Conv2Plus1D(self.cout, mid, self.stride, self.dtype, name="conv1.0")(x)
        y = TorchBatchNorm(dtype=self.dtype, name="conv1.1")(y)
        y = nn.relu(y)
        y = Conv2Plus1D(self.cout, mid, 1, self.dtype, name="conv2.0")(y)
        y = TorchBatchNorm(dtype=self.dtype, name="conv2.1")(y)
        if self.stride != 1 or self.cin != self.cout:
            x = _conv3d(self.cout, (1, 1, 1), (self.stride,) * 3,
                        ((0, 0), (0, 0), (0, 0)), self.dtype, "downsample.0")(x)
            x = TorchBatchNorm(dtype=self.dtype, name="downsample.1")(x)
        return nn.relu(x + y)


class R2Plus1D18(nn.Module):
    """Input NDHWC (B, T, H, W, 3) float, Kinetics-normalized."""

    num_classes: int = 400
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, features: bool = True) -> jnp.ndarray:
        x = x.astype(self.dtype)
        x = _conv3d(45, (1, 7, 7), (1, 2, 2),
                    ((0, 0), (3, 3), (3, 3)), self.dtype, "stem.0")(x)
        x = TorchBatchNorm(dtype=self.dtype, name="stem.1")(x)
        x = nn.relu(x)
        x = _conv3d(64, (3, 1, 1), (1, 1, 1),
                    ((1, 1), (0, 0), (0, 0)), self.dtype, "stem.3")(x)
        x = TorchBatchNorm(dtype=self.dtype, name="stem.4")(x)
        x = nn.relu(x)

        cin = 64
        for stage, cout in enumerate(STAGE_CHANNELS, start=1):
            for blk in range(2):
                stride = 2 if (stage > 1 and blk == 0) else 1
                x = BasicBlock(cin, cout, stride, self.dtype, name=f"layer{stage}.{blk}")(x)
                cin = cout

        x = jnp.mean(x, axis=(1, 2, 3))  # adaptive avg pool (1,1,1) → (B, 512)
        if features:
            return x
        return nn.Dense(self.num_classes, dtype=self.dtype, name="fc")(x)


KINETICS_MEAN = (0.43216, 0.394666, 0.37645)
KINETICS_STD = (0.22803, 0.22145, 0.216989)
PRE_CROP_SIZE = (128, 171)
CROP_SIZE = 112


def r21d_preprocess(frames_u8: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """uint8 (T, H, W, 3) native-resolution frames → (T, 112, 112, 3) normalized.

    Reference transform stack in order (``extract_r21d.py:32-38``):
    ``ToFloatTensorInZeroOne`` (/255) → ``Resize((128,171))`` (bilinear,
    align_corners=False) → Kinetics ``Normalize`` → ``CenterCrop(112)``
    (round-half offsets, ``rgb_transforms.py:14-20``). Runs on device so XLA fuses
    it into the stem convs.
    """
    from ..ops.warp import resize_bilinear_torch

    x = frames_u8.astype(jnp.float32) / 255.0
    x = resize_bilinear_torch(x, *PRE_CROP_SIZE)
    x = ((x - jnp.asarray(KINETICS_MEAN, jnp.float32))
         / jnp.asarray(KINETICS_STD, jnp.float32))
    h, w = x.shape[-3], x.shape[-2]
    i = int(round((h - CROP_SIZE) / 2.0))
    j = int(round((w - CROP_SIZE) / 2.0))
    return x[..., i : i + CROP_SIZE, j : j + CROP_SIZE, :].astype(dtype)


def r21d_conv_shapes() -> Dict[str, Tuple]:
    """torch-layout shapes keyed by state_dict prefix: conv (O,I,kt,kh,kw),
    'bn' → (C,), fc → (O, I). Shared by the random init and the torch mirror."""
    shapes: Dict[str, Tuple] = {
        "stem.0": (45, 3, 1, 7, 7), "stem.1": ("bn", 45),
        "stem.3": (64, 45, 3, 1, 1), "stem.4": ("bn", 64),
    }
    cin = 64
    for stage, cout in enumerate(STAGE_CHANNELS, start=1):
        for blk in range(2):
            p = f"layer{stage}.{blk}"
            block_in = cin if blk == 0 else cout
            # one midplanes per block, shared by conv1 and conv2 (torchvision)
            mid = midplanes(block_in, cout)
            shapes[f"{p}.conv1.0.0"] = (mid, block_in, 1, 3, 3)
            shapes[f"{p}.conv1.0.1"] = ("bn", mid)
            shapes[f"{p}.conv1.0.3"] = (cout, mid, 3, 1, 1)
            shapes[f"{p}.conv1.1"] = ("bn", cout)
            shapes[f"{p}.conv2.0.0"] = (mid, cout, 1, 3, 3)
            shapes[f"{p}.conv2.0.1"] = ("bn", mid)
            shapes[f"{p}.conv2.0.3"] = (cout, mid, 3, 1, 1)
            shapes[f"{p}.conv2.1"] = ("bn", cout)
            if blk == 0 and stage > 1:
                shapes[f"{p}.downsample.0"] = (cout, block_in, 1, 1, 1)
                shapes[f"{p}.downsample.1"] = ("bn", cout)
        cin = cout
    shapes["fc"] = (400, 512)
    return shapes

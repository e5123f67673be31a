"""Shared layers reproducing torch/TF numerics on channel-last layouts.

These are the few primitives whose *exact* semantics decide feature parity with the
reference: inference-mode BatchNorm, the reference's size-independent "TF-SAME"
padding rule, and zero-padded ceil-mode max pooling
(``/root/reference/models/i3d/i3d_src/i3d_net.py:8-34,108-120``).
"""

from __future__ import annotations

import math
from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5  # torch BatchNorm default


class TorchBatchNorm(nn.Module):
    """Inference BatchNorm: y = (x - mean) / sqrt(var + eps) * scale + bias.

    Running statistics live in ``params`` (converted weights, never updated), so the
    whole model stays one frozen pytree. Affine math runs in fp32 then casts,
    matching torch eval-mode numerics for bf16 compute.
    """

    eps: float = BN_EPS
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        mean = self.param("mean", nn.initializers.zeros, (c,), jnp.float32)
        var = self.param("var", nn.initializers.ones, (c,), jnp.float32)
        inv = jnp.asarray(scale, jnp.float32) / jnp.sqrt(jnp.asarray(var, jnp.float32) + self.eps)
        y = (x.astype(jnp.float32) - mean) * inv + bias
        return y.astype(self.dtype)


def tf_same_pads(kernel: Sequence[int], stride: Sequence[int]) -> Tuple[Tuple[int, int], ...]:
    """Per-axis (lo, hi) pads of the reference's TF-SAME rule: ``max(k - s, 0)``
    split floor/ceil (``i3d_net.py:8-25``). Size-independent — equals true TF SAME
    whenever the input is divisible by the stride, which holds for every I3D layer
    at the 224/64 input geometry."""
    pads = []
    for k, s in zip(kernel, stride):
        p = max(k - s, 0)
        pads.append((p // 2, p - p // 2))
    return tuple(pads)


class TapConv3D(nn.Module):
    """conv3d as a sum of per-temporal-tap conv2ds, with the reference's
    TF-SAME pads: every bias-free I3D convolution, in both dtypes.

    Semantics: identical to ``nn.Conv(kernel, stride, tf_same_pads(kernel,
    stride), use_bias=False)`` — the input is zero-padded in time, each
    temporal kernel tap becomes a strided conv2d over the (N·T_out) frame
    batch with the spatial pads, and the taps are summed in ``dtype`` (a 1×1×1
    kernel is one reshape and one conv2d). The taps reorder the temporal
    sum: ~1e-6 relative in float32 against the direct convolution. The
    products take the caller's ``jax.default_matmul_precision``; no
    ``precision=`` is passed here. Param tree matches ``nn.Conv`` (``kernel``
    of shape (kt, kh, kw, in, out)) so converted checkpoints load unchanged.

    Why, on the v5e (PERF.md §6, PR 37: the benchmark's I3D cell, float32 at
    ``highest``, pages of 4 stacks × 64 × 224², videos/s cold and warm):

    =====================================================  ===============
    direct ``nn.Conv`` everywhere                          0.8576, 0.8594
    taps for the two 7×7×7/2 stems only                    0.9335, 0.9360
    taps for the 3×3×3 only                                0.8515, 0.8517
    taps for both                                          0.9291, 0.9298
    taps for the stems and the 1×1×1, 3×3×3 direct         0.9165, 0.9182
    **taps for every kernel, the 1×1×1 too (this class)**  0.9528, 0.9542
    =====================================================  ===============

    The direct 7×7×7 over 3 and 2 input channels ran at 9 % of the six-pass
    peak (74 + 46 ms a page; 21 + 16 ms as taps). The 3×3×3 and the 1×1×1
    each LOSE when only one of them is lowered to 2-D, and win together: a
    tower whose every convolution is a conv2d over (N·T, H, W, C) pays for no
    relayout between 5-D and 4-D operands. So the lowering is one per tower,
    not one per kernel shape, and it is the same on every backend. In
    bfloat16 an earlier installation read the direct conv3d of the stem
    *slower* than float32 (21.7 against 13.5 ms, the taps 5.5 ms), which is why
    bfloat16 took this path first. R(2+1)D's factored (1,k,k)/(k,1,1)
    kernels read slower under taps there and keep ``nn.Conv``
    (``models/r21d.py``).
    """

    features: int
    kernel: Sequence[int]
    stride: Sequence[int]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c = x.shape[-1]
        kt, kh, kw = self.kernel
        st, sh, sw = self.stride
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (kt, kh, kw, c, self.features), jnp.float32,
        ).astype(self.dtype)
        x = x.astype(self.dtype)
        (pt0, pt1), sp_h, sp_w = tf_same_pads(self.kernel, self.stride)
        if pt0 or pt1:
            x = jnp.pad(x, ((0, 0), (pt0, pt1), (0, 0), (0, 0), (0, 0)))
        n, tp, h, w, _ = x.shape
        t_out = (tp - kt) // st + 1
        acc = None
        for dt in range(kt):
            xt = x[:, dt : dt + (t_out - 1) * st + 1 : st]
            xt = xt.reshape((n * t_out, h, w, c))
            y = lax.conv_general_dilated(
                xt, kernel[dt], window_strides=(sh, sw), padding=(sp_h, sp_w),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            acc = y if acc is None else acc + y
        return acc.reshape((n, t_out) + acc.shape[1:])


def max_pool_tf_same(
    x: jnp.ndarray, kernel: Sequence[int], stride: Sequence[int]
) -> jnp.ndarray:
    """Zero-padded TF-SAME max pool with torch ceil_mode semantics on NDHWC/NHWC.

    The reference zero-pads (not -inf: activations are post-ReLU, so zero is a
    neutral element) then pools with ``ceil_mode=True`` (``i3d_net.py:108-120``).
    Ceil-mode windows that run past the padded input ignore the overhang — expressed
    here as extra -inf padding on the high side of each axis.
    """
    spatial = x.shape[1:-1]
    zero_pads = tf_same_pads(kernel, stride)
    cfg_pad = [(0, 0)]
    cfg_win = [1]
    cfg_str = [1]
    for size, k, s, (lo, hi) in zip(spatial, kernel, stride, zero_pads):
        padded = size + lo + hi
        n_out = max(math.ceil((padded - k) / s), 0) + 1
        extra = (n_out - 1) * s + k - padded
        cfg_pad.append((0, max(extra, 0)))
        cfg_win.append(k)
        cfg_str.append(s)
    cfg_pad.append((0, 0))
    cfg_win.append(1)
    cfg_str.append(1)

    x = jnp.pad(
        x,
        [(0, 0)] + [(lo, hi) for lo, hi in zero_pads] + [(0, 0)],
        constant_values=0,
    )
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        tuple(cfg_win),
        tuple(cfg_str),
        cfg_pad,
    )


def avg_pool_valid(x: jnp.ndarray, kernel: Sequence[int], stride: Sequence[int]) -> jnp.ndarray:
    """VALID average pool on channel-last input (torch ``AvgPool3d`` semantics)."""
    window = (1, *kernel, 1)
    strides = (1, *stride, 1)
    summed = lax.reduce_window(x, 0.0, lax.add, window, strides, "VALID")
    return summed / math.prod(kernel)

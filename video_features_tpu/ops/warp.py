"""Bilinear gather ops replacing torch ``grid_sample`` on TPU.

Both flow nets sample feature maps at fractional pixel coordinates: RAFT's
correlation lookup (``/root/reference/models/raft/raft_src/utils/utils.py:57-71``,
``align_corners=True`` + zero padding) and PWC's backward warp
(``/root/reference/models/pwc/pwc_src/pwc_net.py:23-41``; under the pinned
torch 1.2 ``grid_sample`` also behaves as align_corners=True). Working in *pixel*
coordinates directly — the normalize/denormalize round-trip of grid_sample with
align_corners=True is the identity — keeps the math exact and avoids the (W−1)/2
rescaling noise.

All shapes static. What still gathers is what samples at data: the warp's
corner taps (:func:`bilinear_sample`; on a TPU they run on the scalar unit)
and RAFT's lookup (models/raft.py).
:func:`resize_bilinear_torch` samples at compile-time constants and does not.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax


def bilinear_sample(img: jnp.ndarray, coords_xy: jnp.ndarray) -> jnp.ndarray:
    """Sample ``img`` (N, H, W, C) at pixel coords (N, P, Q, 2) (x, y) order.

    Zero padding: out-of-bounds corner taps contribute 0 — per-corner masking,
    matching ``grid_sample(..., padding_mode='zeros', align_corners=True)``.
    Returns (N, P, Q, C) float32.
    """
    n, h, w, c = img.shape
    x = coords_xy[..., 0].astype(jnp.float32)
    y = coords_xy[..., 1].astype(jnp.float32)

    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    wx = x - x0
    wy = y - y0

    out = None
    flat = img.reshape(n, h * w, c).astype(jnp.float32)
    for dy, dx, wgt in (
        (0, 0, (1 - wy) * (1 - wx)),
        (0, 1, (1 - wy) * wx),
        (1, 0, wy * (1 - wx)),
        (1, 1, wy * wx),
    ):
        xi = x0 + dx
        yi = y0 + dy
        inb = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
        xg = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yg = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        idx = (yg * w + xg).reshape(n, -1)
        vals = jnp.take_along_axis(flat, idx[..., None], axis=1).reshape(*x.shape, c)
        contrib = vals * (wgt * inb.astype(jnp.float32))[..., None]
        out = contrib if out is None else out + contrib
    return out


def equalize_chunks(n: int, cap: int) -> tuple[int, int, int]:
    """Split ``n`` items into equal chunks of at most ``cap``.

    Returns ``(n_chunks, chunk, pad)`` with ``chunk ≤ cap`` and
    ``n_chunks · chunk = n + pad``. Equalized (vs bare ceil-capping) so an
    unlucky ``n``/``cap`` ratio cannot nearly double the padded tail's work
    (e.g. n=4096, cap=3787 → two 3787-chunks would be 45 % padding; this
    yields two 2048-chunks). RAFT's on-demand matmul lookup chunks its
    queries by it."""
    cap = max(1, min(n, cap))
    n_chunks = -(-n // cap)
    chunk = -(-n // n_chunks)
    return n_chunks, chunk, n_chunks * chunk - n


def warp_backward(img: jnp.ndarray, flow: jnp.ndarray) -> jnp.ndarray:
    """PWC backward warp: sample ``img`` at ``base + flow``, zeroing partial taps.

    Reference semantics (``pwc_net.py:23-41``): a ones channel rides along; where its
    sampled value is ≤ 0.999 (any out-of-bounds leakage) the whole output pixel is
    zeroed, otherwise scaled by exactly 1.0.

    ``img`` (N, H, W, C); ``flow`` (N, H, W, 2) in pixels (u, v). Returns (N, H, W, C).
    """
    n, h, w, _ = flow.shape
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    base = jnp.stack([xs, ys], axis=-1)[None]
    coords = base + flow
    ones = jnp.ones(img.shape[:-1] + (1,), jnp.float32)
    sampled = bilinear_sample(
        jnp.concatenate([img.astype(jnp.float32), ones], -1), coords)
    out, mask = sampled[..., :-1], sampled[..., -1:]
    keep = (mask > 0.999).astype(jnp.float32)
    return out * keep


def coords_grid(n: int, h: int, w: int) -> jnp.ndarray:
    """(N, H, W, 2) grid of (x, y) pixel coordinates (RAFT ``coords_grid``)."""
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    return jnp.broadcast_to(jnp.stack([xs, ys], axis=-1), (n, h, w, 2))


def _lerp_matrix(size: int, out_size: int) -> np.ndarray:
    """(out_size, size) float32 taps of torch's bilinear resize along one axis
    (align_corners=False): ``1 − f`` and ``f`` around the source coordinate
    (i + 0.5)·scale − 0.5, clamped to [0, size − 1]. The scale is float32 and
    the coordinate rounded once (float64 holds it exactly), as the fused
    multiply-add of torch's kernels gives it: the weights are torch's own."""
    scale = np.float64(np.float32(size / out_size))
    src = (scale * (np.arange(out_size) + 0.5) - 0.5).astype(np.float32)
    src = np.clip(src, 0, size - 1)
    i0 = np.floor(src).astype(np.int64)
    f = src - i0.astype(np.float32)
    m = np.zeros((out_size, size), np.float32)
    m[np.arange(out_size), i0] = 1 - f
    m[np.arange(out_size), np.minimum(i0 + 1, size - 1)] += f
    return m


def resize_bilinear_torch(img: jnp.ndarray, out_h: int, out_w: int) -> jnp.ndarray:
    """Bilinear resize with align_corners=False (torch default), NHWC → float32.

    Both geometries are static: each axis that changes is one contraction with
    its constant :func:`_lerp_matrix` (H, then W), no gather. The products are
    float32 whatever the ambient matmul precision says: pinned here."""
    _, h, w, _ = img.shape
    out = img.astype(jnp.float32)
    for spec, size, out_size in (("oh,nhwc->nowc", h, out_h),
                                 ("ow,nhwc->nhoc", w, out_w)):
        if out_size != size:
            out = jnp.einsum(spec, _lerp_matrix(size, out_size), out,
                             precision=lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    return out

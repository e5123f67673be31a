"""What the plain references share: decoding a file the way a user's player
would (OpenCV, frame by frame), the reference's PIL resize, and float32
convolution at ``Precision.HIGHEST``. Nothing here imports the program."""

from __future__ import annotations

from typing import List, Tuple

import cv2
import numpy as np
from PIL import Image

import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST

def decode_rgb(path: str) -> Tuple[List[np.ndarray], List[float], float]:
    """Every frame of ``path`` as RGB uint8, its position in ms, and the fps."""
    cap = cv2.VideoCapture(path)
    if not cap.isOpened():
        raise IOError(f"cannot open {path}")
    fps = cap.get(cv2.CAP_PROP_FPS)
    frames, stamps = [], []
    while True:
        ok, bgr = cap.read()
        if not ok:
            break
        frames.append(cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB))
        stamps.append(cap.get(cv2.CAP_PROP_POS_MSEC))
    cap.release()
    return frames, stamps, fps


def resize_smaller_edge(rgb: np.ndarray, size: int) -> np.ndarray:
    """PIL bilinear so the smaller edge is ``size``; the other edge is
    ``int(size * other / smaller)`` as the published transform truncates it."""
    h, w = rgb.shape[:2]
    if (w <= h and w == size) or (h <= w and h == size):
        return rgb
    if w < h:
        ow, oh = size, int(size * h / w)
    else:
        ow, oh = int(size * w / h), size
    return np.asarray(Image.fromarray(rgb).resize((ow, oh), Image.BILINEAR))


def conv(x, kernel, stride, padding, dilation=None, lhs_dilation=None):
    """N-d convolution, channels last, float32 products and sums."""
    nd = x.ndim - 2
    spatial = "DHW"[-nd:]
    dn = ("N" + spatial + "C", spatial + "IO", "N" + spatial + "C")
    if isinstance(stride, int):
        stride = (stride,) * nd
    return lax.conv_general_dilated(
        x.astype(jnp.float32), jnp.asarray(kernel, jnp.float32),
        window_strides=tuple(stride), padding=padding,
        rhs_dilation=dilation, lhs_dilation=lhs_dilation,
        dimension_numbers=dn, precision=HIGHEST,
        preferred_element_type=jnp.float32)


def batch_norm(x, p, eps=1e-5):
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["scale"] + p["bias"]

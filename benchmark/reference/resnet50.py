"""Plain reference for ``resnet50_fp32``: torchvision's ResNet-50 v1.5 with
the classifier removed, as He et al. 2015 and torchvision describe it, in
straightforward ``jax.numpy`` float32 at ``Precision.HIGHEST``.

A video's answer is one 2048-d row per decoded frame: decode, PIL bilinear
resize of the smaller edge to 256, centre crop 224 (torchvision's rounded
offsets), /255, ImageNet mean and deviation, the network, global average pool.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .common import batch_norm, conv, decode_rgb, resize_smaller_edge

STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
BLOCK_ROWS = 128  # frames per reference call, so that it fits beside nothing


def _bn(spec, name, c):
    for leaf in ("scale", "bias", "mean", "var"):
        spec[f"{name}/{leaf}"] = (c,)


def weight_specs() -> Dict[str, Dict[str, Tuple[int, ...]]]:
    spec: Dict[str, Tuple[int, ...]] = {"conv1/kernel": (7, 7, 3, 64)}
    _bn(spec, "bn1", 64)
    cin = 64
    for stage, (planes, blocks) in enumerate(STAGES, start=1):
        for b in range(blocks):
            pre = f"layer{stage}.{b}"
            spec[f"{pre}/conv1/kernel"] = (1, 1, cin, planes)
            _bn(spec, f"{pre}/bn1", planes)
            spec[f"{pre}/conv2/kernel"] = (3, 3, planes, planes)
            _bn(spec, f"{pre}/bn2", planes)
            spec[f"{pre}/conv3/kernel"] = (1, 1, planes, planes * 4)
            _bn(spec, f"{pre}/bn3", planes * 4)
            if b == 0:
                spec[f"{pre}/downsample.0/kernel"] = (1, 1, cin, planes * 4)
                _bn(spec, f"{pre}/downsample.1", planes * 4)
            cin = planes * 4
    return {"resnet50": spec}


def forward(p: dict, frames_u8: jnp.ndarray) -> jnp.ndarray:
    """(N, 224, 224, 3) uint8 → (N, 2048) float32."""
    x = frames_u8.astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(MEAN, jnp.float32)) / jnp.asarray(STD, jnp.float32)
    x = jax.nn.relu(batch_norm(conv(x, p["conv1"]["kernel"], 2, [(3, 3)] * 2), p["bn1"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for stage, (_planes, blocks) in enumerate(STAGES, start=1):
        for b in range(blocks):
            q = p[f"layer{stage}.{b}"]
            stride = 2 if (stage > 1 and b == 0) else 1
            y = jax.nn.relu(batch_norm(conv(x, q["conv1"]["kernel"], 1, "VALID"), q["bn1"]))
            y = jax.nn.relu(batch_norm(
                conv(y, q["conv2"]["kernel"], stride, [(1, 1)] * 2), q["bn2"]))
            y = batch_norm(conv(y, q["conv3"]["kernel"], 1, "VALID"), q["bn3"])
            if b == 0:
                x = batch_norm(conv(x, q["downsample.0"]["kernel"], stride, "VALID"),
                               q["downsample.1"])
            x = jax.nn.relu(y + x)
    return jnp.mean(x, axis=(1, 2))


def host_rows(path: str):
    frames, stamps, fps = decode_rgb(path)
    rows = []
    for rgb in frames:
        rgb = resize_smaller_edge(rgb, 256)
        h, w = rgb.shape[:2]
        i, j = int(round((h - 224) / 2.0)), int(round((w - 224) / 2.0))
        rows.append(rgb[i:i + 224, j:j + 224])
    return np.stack(rows), np.asarray(stamps), fps


def make_answer_fn(weights: Dict[str, dict]):
    """→ ``answer(path) -> {key: array}``: what the ``.npy`` files of one
    video must hold."""
    params = jax.device_put(weights["resnet50"])
    step = jax.jit(forward)

    def answer(path: str) -> Dict[str, np.ndarray]:
        rows, stamps, fps = host_rows(path)
        out = []
        for a in range(0, len(rows), BLOCK_ROWS):
            block = rows[a:a + BLOCK_ROWS]
            pad = BLOCK_ROWS - len(block)
            if pad:
                block = np.concatenate([block, np.zeros((pad,) + block.shape[1:], np.uint8)])
            out.append(np.asarray(step(params, block))[:BLOCK_ROWS - pad])
        return {"resnet50": np.concatenate(out), "fps": np.asarray(fps),
                "timestamps_ms": stamps}

    return answer


# keys of an answer that are feature rows (compared by their gap) and keys
# that must be equal
FEATURE_KEYS = ("resnet50",)
EXACT_KEYS = ("fps", "timestamps_ms")

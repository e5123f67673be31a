"""Seeded weights for the benchmark, made from a reference's own shape table.

The plain reference of a configuration states the name and shape of every
leaf (``weight_specs()``); nothing is asked of the program. Values follow the
leaf's name: conv and dense kernels are He-scaled normals (fan-in from the
HWIO layout), BatchNorm ``scale``/``var`` lie in [0.8, 1.2], ``mean``/``bias``
are small normals, so deep stacks keep O(1) activations and a BatchNorm that
is skipped or folded wrongly changes the result. The same seed gives the same
bytes; the program reads them through its normal checkpoint path
(``$VFT_CHECKPOINT_DIR/<name>.npz``) and the reference gets the same arrays.
"""

from __future__ import annotations

import os
import zlib
from typing import Dict, Tuple

import numpy as np


def make_leaf(rng: np.random.Generator, key: str, shape: Tuple[int, ...]) -> np.ndarray:
    name = key.rsplit("/", 1)[-1]
    if name in ("scale", "var"):
        return rng.uniform(0.8, 1.2, shape).astype(np.float32)
    if name in ("mean", "bias"):
        return (rng.standard_normal(shape) * 0.05).astype(np.float32)
    fan_in = int(np.prod(shape[:-1])) or 1
    return (rng.standard_normal(shape, dtype=np.float32)
            * np.float32((2.0 / fan_in) ** 0.5))


def make_weights(spec: Dict[str, Tuple[int, ...]], seed: int, name: str) -> Dict[str, np.ndarray]:
    """Flat ``a/b/c`` → array for one weight file; ``name`` salts the stream
    so two files of one seed (the two I3D towers) do not share values."""
    rng = np.random.default_rng([int(seed), zlib.crc32(name.encode())])
    return {key: make_leaf(rng, key, tuple(shape)) for key, shape in spec.items()}


def write_npz(directory: str, name: str, flat: Dict[str, np.ndarray]) -> str:
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, name + ".npz")
    np.savez(path, **flat)
    return path


def unflatten(flat: Dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for key, v in flat.items():
        node = tree
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree

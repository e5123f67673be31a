"""Spatially-sharded convolution: halo exchange over the mesh with ppermute.

The reference never scales BEYOND one GPU per video — a frame too large for one
device's memory is simply unsupported. The TPU-native answer is model-axis
sharding: split the image's H axis across the mesh, keep every conv local, and
exchange only the kernel-halo rows with mesh neighbors over ICI
(``lax.ppermute`` inside ``shard_map``). This module provides the building
block and a reference composition; conv-stack models (ResNet stem, I3D) can be
laid over it when frames outgrow HBM (e.g. 8K video dense flow).

Semantics: an unsharded stride-1 SAME convolution. Boundary devices receive
zeros from ``ppermute`` (devices without a send partner), which is exactly SAME
zero padding at the image border. Tests assert numerical equality (1e-5)
against the unsharded op on the virtual 8-device CPU mesh — not bitwise: the
halo path lowers as a VALID-on-H conv, so XLA may reduce in a different order
(tests/test_spatial.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS


def _halo_pad_rows(x: jnp.ndarray, halo: int, n_dev: int) -> jnp.ndarray:
    """Pad the local H shard with ``halo`` rows from each mesh neighbor.

    ``x``: (N, H_local, W, C) per-device block. Edge devices get zero rows —
    ppermute delivers zeros to devices no one sends to — matching the SAME
    zero-pad of the unsharded op.
    """
    if halo == 0 or n_dev == 1:
        pad = ((0, 0), (halo, halo), (0, 0), (0, 0))
        return jnp.pad(x, pad) if halo else x
    # rows flowing "down" (device i → i+1): my top halo comes from above
    from_above = lax.ppermute(
        x[:, -halo:], DATA_AXIS, [(i, i + 1) for i in range(n_dev - 1)]
    )
    # rows flowing "up" (device i → i-1): my bottom halo comes from below
    from_below = lax.ppermute(
        x[:, :halo], DATA_AXIS, [(i + 1, i) for i in range(n_dev - 1)]
    )
    return jnp.concatenate([from_above, x, from_below], axis=1)


def sharded_same_conv2d(mesh: Mesh, x: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """Stride-1 SAME conv2d with the H axis sharded across the mesh.

    ``x``: (N, H, W, C) NHWC with H divisible by the mesh size and per-device
    H ≥ the halo (kh // 2). ``kernel``: (kh, kw, C, O) HWIO, odd kh/kw.
    Output matches ``lax.conv_general_dilated(..., padding='SAME')`` exactly.
    """
    kh, kw = kernel.shape[0], kernel.shape[1]
    if kh % 2 == 0 or kw % 2 == 0:
        raise ValueError(f"odd kernel sizes required, got {(kh, kw)}")
    n_dev = mesh.devices.size
    halo = kh // 2
    if (x.shape[1] // n_dev) < halo:
        raise ValueError(
            f"per-device H {x.shape[1] // n_dev} smaller than halo {halo}; "
            f"use fewer devices or larger inputs"
        )

    def local(xb, k):
        xb = _halo_pad_rows(xb, halo, n_dev)
        # halo rows replace SAME padding on H (VALID there); SAME on W
        return lax.conv_general_dilated(
            xb, k, (1, 1),
            padding=((0, 0), (kw // 2, kw // 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P()),
        out_specs=P(None, DATA_AXIS),
    )
    return fn(x, kernel)


def shard_spatial(mesh: Mesh) -> NamedSharding:
    """NamedSharding splitting axis 1 (H of NHWC) across the mesh."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def sharded_conv_stack(mesh: Mesh, x: jnp.ndarray, kernels) -> jnp.ndarray:
    """ReLU conv chain, H-sharded end to end — activations never gather.

    Demonstrates the composition property: each layer halo-exchanges only its
    own kernel radius; intermediate activations stay sharded on device.
    """
    y = jax.device_put(x, shard_spatial(mesh))
    for k in kernels:
        y = sharded_same_conv2d(mesh, y, k)
        y = jnp.maximum(y, 0.0)
    return y

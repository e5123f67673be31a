"""Frame-axis halo exchange for encode-once sharded flow.

A (B+1)-frame flow window holds B consecutive pairs; sharding the B source
frames across a mesh leaves each shard needing ONE feature map it does not
own — its last pair's target frame, which is the NEXT shard's first frame
(or, on the final shard, the window's extra last frame). Re-encoding that
frame per shard would re-introduce a slice of the double-encode the
shared-frame formulation exists to kill; instead the boundary FEATURE map is
exchanged over ICI with ``lax.ppermute`` (one (1, h', w', c) message per
shard per step — bytes that are ~1/64 of one frame's encoder FLOPs' worth of
HBM traffic).

The same pattern as the spatial halo in :mod:`..parallel.spatial`, but along
the batch/frame axis and carrying model features rather than input rows.
Used by :func:`video_features_tpu.models.raft.raft_forward_frames_sharded`
and :func:`video_features_tpu.models.pwc.pwc_forward_frames_sharded` inside
``shard_map``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def frame_axis_mesh(mesh, n_pairs: int):
    """Shared scaffolding for a frame-axis sharded forward:
    ``(axis_name, n_dev)`` for ``mesh``, after validating that the pair count
    divides the mesh. Both sharded flow forwards (and any future
    frame-sharded model) go through here so the divisibility contract has
    one home.
    """
    n_dev = int(mesh.devices.size)
    if n_pairs % n_dev:
        raise ValueError(
            f"pair count {n_pairs} must be divisible by the mesh size {n_dev}")
    return mesh.axis_names[0], n_dev


def recv_from_next(x: jnp.ndarray, axis_name: str, n_dev: int) -> jnp.ndarray:
    """Each shard receives the NEXT shard's ``x``; the last shard gets zeros
    (``ppermute`` delivers zeros to devices without a send partner)."""
    if n_dev == 1:
        return jnp.zeros_like(x)
    return lax.ppermute(x, axis_name, [(i + 1, i) for i in range(n_dev - 1)])


def boundary_from_next(first_block: jnp.ndarray, last_block: jnp.ndarray,
                       axis_name: str, n_dev: int) -> jnp.ndarray:
    """Per-shard boundary block for pair formation along a sharded frame axis.

    Shard ``i < n_dev-1`` takes shard ``i+1``'s ``first_block`` (one ppermute
    hop); the final shard takes ``last_block`` — the replicated extra frame's
    features, the only frame of the window encoded outside the sharded batch.
    Shapes: both blocks ``(1, ...)`` per shard, returned unchanged.
    """
    if n_dev == 1:
        return last_block
    recv = recv_from_next(first_block, axis_name, n_dev)
    is_last = lax.axis_index(axis_name) == n_dev - 1
    return jnp.where(is_last, last_block, recv)

"""A routed expert layer for a chip that holds some of the experts.

The router scores every token over ALL of the model's experts and keeps the
published top-k; this chip is told which experts it holds (``slot_of``: expert
id → its row in the stacked weights, -1 where the expert lives elsewhere) and
computes its own experts' part of the result. What the absent experts would add
is left out, as expert parallelism leaves it to the other chips; nothing here
stands in for them or for their exchange.

Held assignments are sorted by expert and run as grouped matrix products (one
ragged product per projection over all held experts: no loop over experts and
no capacity, an assignment is never dropped). The buffers are sized for the
worst case, every assignment held (``tokens * top_k`` rows); the grouped
product visits only the rows its groups cover, and the rows past them are
never read back.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

GMM_TILING = (512, 1024, 1024)  # rows, contraction, columns of one grouped-product tile


class Dispatch(NamedTuple):
    token_of_row: jnp.ndarray  # (tokens * top_k,) sorted row → token
    row_of_slot: jnp.ndarray   # (tokens, top_k) assignment → sorted row
    held: jnp.ndarray          # (tokens, top_k) bool: a real token's assignment to an expert held here
    group_sizes: jnp.ndarray   # (held experts,) int32 rows per held expert, in weight order


def route(h, w_router, top_k: int, scale: float, scoring: str = "softmax", bias=None):
    """Scores over all experts in float32 by the model's ``scoring`` —
    ``"softmax"`` of the logits, or ``"sigmoid"`` of each (the auxiliary-loss-
    free scheme) — then the ``top_k`` largest, renormalised to sum 1, times
    the routed scaling factor → (weights float32, expert ids), both (tokens,
    top_k). ``bias`` (all experts, float32) is added to the scores for the
    CHOICE only: the weights come from the unbiased scores."""
    logits = jnp.dot(h, w_router, preferred_element_type=jnp.float32)
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"route: scoring {scoring!r} is neither softmax nor sigmoid")
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top, experts = lax.top_k(scores, top_k)
    else:
        _, experts = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    return top / top.sum(-1, keepdims=True) * scale, experts.astype(jnp.int32)


def dispatch(experts, valid, slot_of, num_held: int) -> Dispatch:
    """Sort the held assignments of real tokens by expert (stable: token order
    inside an expert). ``slot_of``: (all experts,) int32, the chip's share."""
    tokens, top_k = experts.shape
    slot = slot_of[experts]
    held = (slot >= 0) & valid[:, None]
    keys = jnp.where(held, slot, num_held).reshape(-1)  # not held: past every group
    rows = jnp.arange(tokens * top_k, dtype=jnp.int32)
    sorted_keys, order = lax.sort((keys, rows), num_keys=1, is_stable=True)
    bounds = jnp.searchsorted(sorted_keys, jnp.arange(num_held + 1, dtype=jnp.int32))
    row_of_slot = jnp.zeros_like(rows).at[order].set(rows, unique_indices=True)
    return Dispatch(order // top_k, row_of_slot.reshape(tokens, top_k), held,
                    jnp.diff(bounds).astype(jnp.int32))


def grouped_matmul(lhs, rhs, group_sizes, interpret: bool = False):
    """``lhs[rows of group g] @ rhs[g]`` for every group, float32 sums, result
    in ``lhs``'s type; rows past the groups are undefined. One path: the
    Pallas grouped product (it walks only the tiles the groups cover;
    ``lax.ragged_dot`` took 9.7 ms where it takes 5.4, PERF.md section 5).
    ``interpret=True`` runs it in the Pallas interpreter (off the TPU)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiling = tuple(min(t, n) for t, n in
                   zip(GMM_TILING, (lhs.shape[0], lhs.shape[1], rhs.shape[2])))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype, tiling=tiling,
               interpret=interpret)


def combine(expert_out, weights, d: Dispatch):
    """Each token's held experts' outputs times their router weights, summed
    in float32 → (tokens, width). An assignment not held here adds nothing."""
    # the gather stands alone: fused into the sum it ran at a twentieth of
    # the memory's speed (PERF.md section 6, PR 34)
    picked = lax.optimization_barrier(expert_out[d.row_of_slot])
    w = jnp.where(d.held, weights, 0.0)[..., None]
    picked = jnp.where(d.held[..., None], picked.astype(jnp.float32), 0.0)  # rows past the groups hold anything
    return jnp.sum(picked * w, axis=1)

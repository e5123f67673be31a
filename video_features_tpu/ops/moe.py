"""A routed expert layer for a chip that holds some of the experts.

The router scores every token over ALL of the model's experts and keeps the
published top-k; this chip is told which experts it holds (``slot_of``: expert
id → its row in the stacked weights, -1 where the expert lives elsewhere) and
computes its own experts' part of the result. What the absent experts would add
is left out, as expert parallelism leaves it to the other chips; nothing here
stands in for them or for their exchange.

Held assignments are sorted by expert, first in the sorted order, and run as
grouped matrix products (one ragged product per projection over all held
experts: no loop over experts and no capacity, an assignment is never
dropped). The buffers are sized by what the chip's share of the experts is
expected to hold, not by ``tokens * top_k``: the held prefix of the sorted
rows is covered in chunks of :func:`chunk_rows` rows, one chunk where the
router is near even and as many more as a page needs (:func:`chunks`; the
count is decided on the device). A chunk gathers its rows, runs both products
and adds each row times its router weight into its token's float32 sum
(:func:`combine`); an expert cut by a chunk's edge is computed in two parts,
which is exact, since the rows of a product do not meet. The grouped products
visit only the rows their groups cover; the rows past them hold anything and
meet no sum.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

GMM_TILING = (512, 1024, 1024)  # rows, contraction, columns of one grouped-product tile
CHUNK_ROOM = 1.5  # a chunk's rows over what an even router sends to the held experts
TOKEN_TILE = 128  # tokens whose rows one group of the combine's product sums


class Dispatch(NamedTuple):
    """A page's assignments sorted by expert, the held ones first."""
    token_of_row: jnp.ndarray   # (tokens * top_k,) sorted row → token
    weight_of_row: jnp.ndarray  # (tokens * top_k,) float32 router weight of the sorted row; None: none given
    held: jnp.ndarray           # (tokens, top_k) bool: a real token's assignment to an expert held here
    group_sizes: jnp.ndarray    # (held experts,) int32 rows per held expert, in weight order


class Chunk(NamedTuple):
    """``rows`` consecutive sorted rows of a page (:func:`chunks`): all that
    :func:`combine` is told of the page, its kernels' mode included."""
    token_of_row: jnp.ndarray   # (rows,) sorted row → token
    weight_of_row: jnp.ndarray  # (rows,) float32
    group_sizes: jnp.ndarray    # (held experts,) int32: each expert's rows inside the chunk
    interpret: bool             # the page's kernels run in the Pallas interpreter (off the TPU)


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


def dispatch(experts, valid, slot_of, num_held: int, weights=None) -> Dispatch:
    """Sort the held assignments of real tokens by expert (stable: token order
    inside an expert). ``slot_of``: (all experts,) int32, the chip's share;
    ``weights``: the router's (tokens, top_k) float32, carried through the
    sort."""
    tokens, top_k = experts.shape
    # the table read by comparison: as a gather of tokens * top_k ints it cost the sort 1.3 ms (PERF.md section 6, PR 39)
    slot = jnp.max(jnp.where(experts[..., None] == jnp.arange(slot_of.shape[0], dtype=jnp.int32),
                             slot_of, -1), axis=-1)
    held = (slot >= 0) & valid[:, None]
    keys = jnp.where(held, slot, num_held).reshape(-1)  # not held: past every group
    rows = jnp.arange(tokens * top_k, dtype=jnp.int32)
    operands = (keys, rows) if weights is None else (keys, rows, weights.astype(jnp.float32).reshape(-1))
    sorted_keys, order, *weight = lax.sort(operands, num_keys=1, is_stable=True)
    bounds = jnp.searchsorted(sorted_keys, jnp.arange(num_held + 1, dtype=jnp.int32))
    return Dispatch(order // top_k, weight[0] if weight else None, held,
                    jnp.diff(bounds).astype(jnp.int32))


def chunk_rows(assignments: int, num_held: int, num_experts: int) -> int:
    """Rows of one chunk: ``CHUNK_ROOM`` times what an even router sends to
    ``num_held`` of ``num_experts`` experts, in whole row tiles of the grouped
    product; all ``assignments`` where that is less (every expert held)."""
    tile = GMM_TILING[0]
    even = assignments * num_held / num_experts
    return min(assignments, -(-math.ceil(CHUNK_ROOM * even) // tile) * tile)


def chunks(d: Dispatch, rows: int, interpret: bool = False):
    """→ (trips, part). ``trips`` (int32, on the device) chunks of ``rows``
    sorted rows cover the held prefix of ``d`` (dispatched with its weights);
    ``part(c)`` is the :class:`Chunk` ``c``: its slice of the sorted rows and
    each expert's rows inside it. Rows of the last chunk past the held prefix
    belong to no group."""
    pad = -d.token_of_row.shape[0] % rows  # the last chunk's slice may pass the end
    token, weight = (jnp.pad(a, (0, pad)) for a in (d.token_of_row, d.weight_of_row))
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(d.group_sizes)])

    def part(c):
        lo = c * rows
        return Chunk(lax.dynamic_slice(token, (lo,), (rows,)), lax.dynamic_slice(weight, (lo,), (rows,)),
                     jnp.diff(jnp.clip(bounds, lo, lo + rows)), interpret)

    return -(-bounds[-1] // rows), part


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


def combine(expert_out, into, chunk: Chunk):
    """``into`` (tokens, width) float32 plus a chunk's part of the routed sum:
    each of ``expert_out``'s rows (the chunk's, in sorted order) times its
    router weight, added to its token's row in float32. Nothing is gathered
    back to ``tokens * top_k`` rows and nothing is scattered: the chunk's rows
    are ordered by token, so a tile of ``TOKEN_TILE`` tokens owns a run of
    them, and one transposed grouped product (a group a tile) multiplies each
    run by its weighted one-hot matrix (tile's tokens × rows of the run). The
    float32 weight goes in as the parts the products' type holds exactly
    (three for bfloat16), stacked on the product's rows: the products are
    exact and their sums float32. A scatter-add of the same rows took 12.8 ms
    where this takes 8.1, a gather to every assignment 15.1 (PERF.md section
    6, PR 39)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm  # the package exports gmm alone

    (tokens, width), rows, dtype = into.shape, expert_out.shape[0], expert_out.dtype
    tile = math.gcd(tokens, TOKEN_TILE)
    # rows past the groups hold anything, NaN included: sorted past every
    # token they are in no group, and the product reads its groups' rows alone
    covered = jnp.arange(rows, dtype=jnp.int32) < jnp.sum(chunk.group_sizes)
    token, weight, order = lax.sort((jnp.where(covered, chunk.token_of_row, tokens), chunk.weight_of_row,
                                     jnp.arange(rows, dtype=jnp.int32)), num_keys=1)
    sizes = jnp.diff(jnp.searchsorted(token, jnp.arange(0, tokens + 1, tile, dtype=jnp.int32)))
    lane = jnp.arange(tile, dtype=jnp.int32)[:, None] == (token % tile)[None, :]
    kind, parts, rest = jnp.finfo(dtype), [], weight
    for _ in range(-(-24 // (kind.nmant + 1))):
        # not ``astype`` there and back: the compiler may keep the excess precision, and the rest would be 0
        part = lax.reduce_precision(rest, kind.nexp, kind.nmant)
        parts.append(jnp.where(lane, part[None, :], 0.0).astype(dtype))
        rest = rest - part
    sums = tgmm(jnp.concatenate(parts), expert_out[order], sizes,
                preferred_element_type=jnp.float32, interpret=chunk.interpret,
                tiling=(min(GMM_TILING[0], rows), len(parts) * tile, min(GMM_TILING[2], width)))
    return into + sums.reshape(tokens // tile, len(parts), tile, width).sum(axis=1).reshape(tokens, width)

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
COMBINE_VMEM = 80 << 20  # bytes of VMEM the combine's tiles of the carry may take
COMBINE_DEPTH = 8  # slabs of rows the combine has in flight
COMBINE_VREGS = 16  # float32 vector registers of rows the combine loads before it stores


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


def route(h, w_router, top_k: int, scale: float, scoring: str = "softmax", bias=None,
          eps: float = 0.0):
    """Scores over all experts in float32 by the model's ``scoring`` —
    ``"softmax"`` of the logits, or ``"sigmoid"`` of each (the auxiliary-loss-
    free scheme) — then the ``top_k`` largest, renormalised to sum 1 (over
    their sum plus ``eps``), times the routed scaling factor → (weights
    float32, expert ids), both (tokens, top_k). ``bias`` (all experts,
    float32) is added to the scores for the CHOICE only: the weights come from
    the unbiased scores."""
    logits = jnp.dot(h, w_router, preferred_element_type=jnp.float32)
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"route: scoring {scoring!r} is neither softmax nor sigmoid")
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top, experts = lax.top_k(scores, top_k)
    else:
        _, experts = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    total = top.sum(-1, keepdims=True)
    if eps:  # added only where a model states one: without it the program is the same
        total = total + eps
    return top / total * scale, experts.astype(jnp.int32)


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
    """→ (``into`` (tokens, width) float32 plus a chunk's part of the routed
    sum, the runs read). Each of ``expert_out``'s rows (the chunk's, in sorted
    order) times its float32 router weight, added in float32 to its token's row:
    one Pallas kernel, ``moe_combine``, over tiles of tokens, adding into the
    carry in place. Nothing is sorted again and nothing is gathered: the
    dispatch sort is stable, so inside an expert's group the rows are in token
    order and a tile of tokens owns ONE contiguous run of each group (its bounds:
    one ``searchsorted`` of ``slot · tokens + token`` over the chunk). The
    kernel copies each run from HBM in slabs of rows; a row outside its run
    (past the groups it may hold anything, NaN included) meets no sum. The runs
    read, the non-empty (tile, expert) pairs, are the second result. The sizes
    follow the shapes: the tile from what VMEM holds of the carry's width, the
    slab from what an even router sends a tile from one expert, the rows summed
    between stores from what the vector registers hold of a row."""
    (tokens, width), rows = into.shape, expert_out.shape[0]
    held = chunk.group_sizes.shape[0]
    # a token of a tile is held five times: the carry in and out (two buffers each) and its sums
    tile = 1 << int(math.log2(COMBINE_VMEM // (20 * width)))
    tile = tokens if tokens <= tile else tile
    # rows loaded before they are stored: a float32 row fills ceil(width / 1024) registers
    group = 1 << int(math.log2(max(1, COMBINE_VREGS // -(-width // 1024))))
    # a slab holds what an even router sends a tile from one expert, in whole
    # sublane tiles (the copies stay aligned) and whole groups
    align = 8 * 4 // expert_out.dtype.itemsize
    step = max(align, group)
    slab = min(rows, -(-max(1, math.ceil(tile * rows / (tokens * held))) // step) * step)
    return _combine(expert_out, into, chunk, tile, slab, align, math.gcd(group, slab))


def _runs(chunk: Chunk, tokens: int, rows: int, tile: int):
    """(tiles, held) int32 first and past-last chunk row of each (token tile,
    held expert) run."""
    held = chunk.group_sizes.shape[0]
    ends = jnp.cumsum(chunk.group_sizes)
    row = jnp.arange(rows, dtype=jnp.int32)
    # every search by comparison: XLA's binary search is a loop of gathers, 3.9 ms
    # for a chunk of Qwen3-Next (PERF.md section 6, PR 43)
    slot = jnp.searchsorted(ends, row, side="right", method="compare_all").astype(jnp.int32)
    key = jnp.where(row < ends[-1], slot * tokens + chunk.token_of_row, held * tokens)
    edges = jnp.minimum(jnp.arange(0, tokens + tile, tile, dtype=jnp.int32), tokens)
    bounds = jnp.searchsorted(key, (jnp.arange(held, dtype=jnp.int32)[:, None] * tokens
                                    + edges[None, :]).reshape(-1), method="compare_all").astype(jnp.int32)
    bounds = bounds.reshape(held, -1)
    return bounds[:, :-1].T, bounds[:, 1:].T


def _combine(expert_out, into, chunk: Chunk, tile: int, slab: int, align: int, group: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (tokens, width), rows = into.shape, expert_out.shape[0]
    held = chunk.group_sizes.shape[0]
    tiles = -(-tokens // tile)
    first, last = (a.reshape(-1) for a in _runs(chunk, tokens, rows, tile))
    # the slabs ("jobs"), flattened tile-major: a run from its row rounded
    # down to a sublane tile, ``slab`` rows at a time, the last slab of the
    # chunk moved back inside it; each job's rows of its run, as a span
    start = (first // align) * align
    count = jnp.where(last > first, -(-(last - start) // slab), 0)
    upto = jnp.cumsum(count)
    job = jnp.arange(rows // slab + 2 * tiles * held + 1, dtype=jnp.int32)
    run = jnp.minimum(jnp.searchsorted(upto, job, side="right", method="compare_all"), tiles * held - 1)
    want = start[run] + (job - (upto - count)[run]) * slab
    at = jnp.minimum(want, rows - slab)
    lo = jnp.maximum(first[run], want) - at
    hi = jnp.minimum(last[run], want + slab) - at
    span = (lo | (hi << 16)).astype(jnp.int32)
    job_of_tile = jnp.concatenate([jnp.zeros((1,), jnp.int32), upto.reshape(tiles, held)[:, -1]])
    depth = COMBINE_DEPTH

    def kernel(job_of_tile, at, span, token, weight, into_ref, rows_ref, out_ref, buf, wide, sums, sem):
        i = pl.program_id(0)
        j0, j1 = job_of_tile[i], job_of_tile[i + 1]
        # the tile's sums, and past them a row that takes what lies outside the runs
        sums[:tile] = into_ref[...]

        def copy(j):
            return pltpu.make_async_copy(rows_ref.at[pl.ds(pl.multiple_of(at[j], align), slab)],
                                         buf.at[j % depth], sem.at[j % depth])

        for p in range(depth):
            @pl.when(j0 + p < j1)
            def _():
                copy(j0 + p).start()

        def one_slab(j, carry):
            copy(j).wait()
            # float32 rows load a row whole from any sublane: bfloat16's
            # packed pairs would be shuffled row by row
            wide[...] = buf[j % depth].astype(jnp.float32)
            base, lo, hi = at[j], span[j] & 0xFFFF, span[j] >> 16

            def one_group(g, carry):
                # a run's rows go to distinct tokens: all loads, then all stores
                r = [g * group + k for k in range(group)]
                t = [jnp.where((lo <= r[k]) & (r[k] < hi), token[base + r[k]] - i * tile, tile)
                     for k in range(group)]
                new = [sums[pl.ds(t[k], 1), :] + weight[base + r[k]] * wide[pl.ds(r[k], 1), :]
                       for k in range(group)]
                for k in range(group):
                    sums[pl.ds(t[k], 1), :] = new[k]
                return carry

            lax.fori_loop(lo // group, (hi + group - 1) // group, one_group, 0)

            @pl.when(j + depth < j1)
            def _():
                copy(j + depth).start()
            return carry

        lax.fori_loop(j0, j1, one_slab, 0)
        out_ref[...] = sums[:tile]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(tiles,),
            in_specs=[pl.BlockSpec((tile, width), lambda i, *_: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, width), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((depth, slab, width), expert_out.dtype),
                            pltpu.VMEM((slab, width), jnp.float32),
                            pltpu.VMEM((tile + 8, width), jnp.float32),
                            pltpu.SemaphoreType.DMA((depth,))]),
        out_shape=jax.ShapeDtypeStruct(into.shape, jnp.float32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=COMBINE_VMEM + (16 << 20)),
        name="moe_combine",
        interpret=chunk.interpret,
    )(job_of_tile, at, span, chunk.token_of_row, chunk.weight_of_row, into, expert_out)
    return out, jnp.sum(last > first).astype(jnp.int32)

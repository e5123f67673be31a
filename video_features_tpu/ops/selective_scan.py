"""Mamba-1's selective scan over a page of packed documents: one Pallas kernel.

Per channel ``c`` and state ``n`` a state ``H`` that is zero at every
document's first token::

    H_t[c, n] = exp(Δ_t[c] A[c, n]) · H_{t−1}[c, n] + Δ_t[c] B_t[n] u_t[c]
    y_t[c]    = Σ_n C_t[n] H_t[c, n] + D[c] u_t[c]

and the output is ``y ⊙ silu(z)``. The decay differs for every (channel,
state) pair and every token, so no chunk of tokens turns the recurrence into
matrix products (the gated delta rule's state is one matrix a head and does,
``ops/gated_delta.py``): it is a sequential, element-wise walk on the vector
units. Written as the published slow path writes it, a page of 16,384 tokens
and 5,120 channels would materialise 16,384 × 5,120 × 16 float32 decays (5.4
GB); here the state never leaves VMEM.

Every operation of the token loop is on whole ``(8, 128)`` float32 vector
registers. A token's channels of a block are ``rows`` rows of 128 lanes (the
published 5,120 are 40 rows, five registers), and the state is one such
``(rows, 128)`` plane a state, ``(state, rows, 128)`` in VMEM scratch, carried
from chunk to chunk; ``A`` arrives in the same planes. ``B_t[n]`` and
``C_t[n]`` are scalars read from SMEM, so a token's step is, for each state,
``H ← 2^(A' Δ_t) · H + B_t[n] · Δ_t u_t`` and ``y_t += C_t[n] · H`` (``A'`` is
``A · log2 e``, folded in once by the wrapper: ``exp`` on the chip is a
multiply by ``log2 e`` and a power of two, and the multiply goes), with no
broadcast of a column over lanes and no sum over sublanes. Moving a scalar to
the vector unit costs a slot, so a grid step takes the whole width and each
moved scalar serves all its registers; the vector ALU's four slots a bundle
bound the loop (the exponential unit's power of two, one a register, is 0.9 ms
a page and layer at the published shape, memory's least time 1.03 ms).

Rows arrive from HBM as tiles of tokens × 128 channels, and the exchange
between that and a token's ``(rows, 128)`` plane stays in VMEM: the grid is
channel blocks (parallel) × token chunks (in order); inside a chunk the tokens
go in groups of ``GROUP``, whose tiles of ``Δ`` and ``Δ u`` are stacked slab
by slab in scratch, ``EXCHANGE`` tokens a slab, so that a token's plane is
every ``EXCHANGE``-th row from its own: one strided load. The token's ``y``
goes back by a strided store, and ``D u`` and the gate ``silu(z)`` are vector
work over the group's tiles. Nothing of ``u``, ``Δ`` or ``z`` is transposed or
reshaped outside the kernel.

A page holds whole documents back to back (``parallel/pages.py``): where a
token's ``pos`` (a scalar from SMEM too) is 0 the decay's ``Δ`` is replaced
by a large number, so that ``exp(Δ A)`` (``A < 0``) is 0 and the state is
multiplied by zero before the token adds its own term: a document that starts
mid-chunk starts from zero and no token sees another document. Pads (``pos``
0 too) restart at every token: finite, read by nobody. ``Δ``, ``B``, ``C``,
``A``, the state and ``y`` are float32; ``u`` and ``z`` are read in their own
type and the output is written in ``u``'s. ``interpret=True`` runs the same
kernel in the Pallas interpreter; the caller says so (off the TPU:
``extractors/token_pages.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

GROUP = 16  # tokens a loop step: a whole (16, 128) tile of bfloat16 u, z and output
EXCHANGE = 8  # tokens whose rows one strided load takes apart: a (8, 128) float32 tile
TOKENS_PER_STEP = 256
CHANNELS_PER_STEP = 5120  # the published width: B and C move to vector registers once a token
LANES = 128
LOG2E = 1.4426950408889634  # exp(x) = 2^(x · log2 e): the factor goes into A once
RESTART = 1e30  # a decay's Δ at a document's first token: exp(RESTART · A) is 0 for A < 0
_VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(a_ref, d_ref, bc_ref, pos_ref, u_ref, dt_ref, z_ref, o_ref, h_ref, dt_rows, du_rows,
            y_rows, *, chunk: int, state: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    rows, lanes = a_ref.shape[1:]  # a token's channels of the block: ``rows`` rows of ``lanes``

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    def at(j, k):  # the row of token j's channels k·lanes … in the group's exchange
        return (j // EXCHANGE) * rows * EXCHANGE + k * EXCHANGE + j % EXCHANGE

    def one_group(g, carry):
        first = pl.multiple_of(g * GROUP, GROUP)
        tokens = pl.ds(first, GROUP)
        # in: the group's tiles, rows of ``EXCHANGE`` tokens × ``lanes``
        # channels, stacked channel slab by slab; a token's channels are then
        # every ``EXCHANGE``-th row from its first
        for k in range(rows):
            cols = slice(k * lanes, (k + 1) * lanes)
            dt = dt_ref[tokens, cols]
            du = dt * u_ref[tokens, cols].astype(f32)
            for s in range(0, GROUP, EXCHANGE):
                dt_rows[pl.ds(at(s, k), EXCHANGE), :] = dt[s:s + EXCHANGE]
                du_rows[pl.ds(at(s, k), EXCHANGE), :] = du[s:s + EXCHANGE]
        for j in range(GROUP):
            t = first + j
            mine = pl.ds(at(j, 0), rows, stride=EXCHANGE)
            dt, du = dt_rows[mine, :], du_rows[mine, :]
            decay = jnp.where(pos_ref[0, t] == 0, RESTART, dt)
            y = None
            for n in range(state):
                b, c = bc_ref[0, t * 2 * state + n], bc_ref[0, t * 2 * state + state + n]
                h = jnp.exp2(a_ref[n] * decay) * h_ref[n] + b * du
                h_ref[n] = h
                y = c * h if y is None else y + c * h
            y_rows[mine, :] = y
        # out: the rows back to the group's tiles, with D·u and the gate
        for k in range(rows):
            cols = slice(k * lanes, (k + 1) * lanes)
            y = jnp.concatenate([y_rows[pl.ds(at(s, k), EXCHANGE), :]
                                 for s in range(0, GROUP, EXCHANGE)])
            u = u_ref[tokens, cols].astype(f32)
            z = z_ref[tokens, cols].astype(f32)
            o_ref[tokens, cols] = ((y + d_ref[:, cols].astype(f32) * u)
                                   * (z * jax.nn.sigmoid(z))).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, chunk // GROUP, one_group, 0)


@functools.partial(jax.jit, static_argnames=("gate_column", "chunk", "channels", "interpret"))
def selective_scan(u, dt, b, c, a, d, gate, pos, *, gate_column: int = 0,
                   chunk: int = TOKENS_PER_STEP, channels: int = CHANNELS_PER_STEP,
                   interpret: bool = False):
    """The scan above over a page, gated: ``y ⊙ silu(z)``. ``u`` ``(tokens,
    width)`` (after the convolution); ``dt`` the same shape, float32 (after the
    softplus); ``b``, ``c`` ``(tokens, state)`` float32 (after their norms);
    ``a`` ``(state, width)`` float32, ``A`` transposed (negative); ``d``
    ``(width,)``; ``z`` the ``width`` columns of ``gate`` from ``gate_column``
    on (the in-projection's product as it stands: nothing is sliced out
    first); ``pos`` the page's position of each token in its document. →
    ``(tokens, width)`` in ``u``'s type. ``tokens`` must be a whole number of
    chunks, a chunk of groups of ``GROUP``, ``width`` of channel blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, width = u.shape
    state = a.shape[0]
    chunk, block = min(chunk, tokens), min(channels, width)
    lanes = min(LANES, block)
    if (tokens % chunk or chunk % GROUP or width % block or block % lanes or gate_column % block
            or dt.shape != u.shape or b.shape != (tokens, state) or c.shape != (tokens, state)
            or a.shape != (state, width) or d.shape != (width,) or pos.shape != (tokens,)
            or gate.shape[0] != tokens or gate.shape[1] < gate_column + width):
        raise ValueError(f"selective_scan: u {u.shape} in chunks of {chunk} tokens and blocks of "
                         f"{block} channels, a {a.shape}, b {b.shape}, gate {gate.shape} from "
                         f"column {gate_column}")
    f32 = jnp.float32
    rows, steps = block // lanes, tokens // chunk
    # a chunk's B_t, C_t and pos_t as scalars, one SMEM row a chunk; A as planes
    bc = jnp.concatenate([b, c], axis=1).astype(f32).reshape(steps, 1, chunk * 2 * state)
    planes = (a.astype(f32) * LOG2E).reshape(state, width // lanes, lanes)
    first = gate_column // block
    kernel = functools.partial(_kernel, chunk=chunk, state=state)
    smem = functools.partial(pl.BlockSpec, index_map=lambda i, t: (t, 0, 0),
                             memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(width // block, steps),
        in_specs=[
            pl.BlockSpec((state, rows, lanes), lambda i, t: (0, i, 0)),
            pl.BlockSpec((1, block), lambda i, t: (0, i)),
            smem((None, 1, chunk * 2 * state)),
            smem((None, 1, chunk)),
            pl.BlockSpec((chunk, block), lambda i, t: (t, i)),
            pl.BlockSpec((chunk, block), lambda i, t: (t, i)),
            pl.BlockSpec((chunk, block), lambda i, t: (t, first + i)),
        ],
        out_specs=pl.BlockSpec((chunk, block), lambda i, t: (t, i)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((state, rows, lanes), f32)]
        + [pltpu.VMEM((rows * GROUP, lanes), f32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        name="selective_scan",
        interpret=interpret,
    )(planes, d[None, :], bc, pos.astype(jnp.int32).reshape(steps, 1, chunk), u, dt.astype(f32),
      gate)

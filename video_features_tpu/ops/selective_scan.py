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

The grid is channel blocks (parallel) × token chunks (in order); a grid step
holds the ``(state, channel block)`` float32 state in VMEM scratch, carried
from chunk to chunk. Inside a chunk the tokens go in groups of ``GROUP``: the
group's rows of ``u``, ``Δ`` and ``z`` are read as tiles (``Δ u``, ``D u`` and
the gate are vector work over the whole group), then its tokens one after
another. ``B`` and ``C`` arrive as one array laid out by group, ``(tokens /
GROUP, 2 · state, GROUP)``: a token's ``B_t`` and ``C_t`` are one column of
its group's tile, broadcast along the lanes, so no lane of the state is ever
sliced at a position known only at run time.

A page holds whole documents back to back (``parallel/pages.py``): where a
token's ``pos`` is 0 the decay's ``Δ`` is replaced by a large number, so that
``exp(Δ A)`` (``A < 0``) is 0 and the state is multiplied by zero before the
token adds its own term: a document that starts mid-chunk starts from zero and
no token sees another document. Pads (``pos`` 0 too) restart at every token:
finite, read by nobody. ``Δ``, ``B``, ``C``, ``A``, the state and ``y`` are
float32; ``u`` and ``z`` are read in their own type and the output is written
in ``u``'s. ``interpret=True`` runs the same kernel in the Pallas interpreter;
the caller says so (off the TPU: ``extractors/token_pages.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

GROUP = 16  # tokens whose rows are read as one tile: a whole (16, 128) tile of bfloat16
TOKENS_PER_STEP = 512
CHANNELS_PER_STEP = 512
RESTART = 1e30  # a decay's Δ at a document's first token: exp(RESTART · A) is 0 for A < 0
_VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(a_ref, d_ref, bc_ref, pos_ref, u_ref, dt_ref, z_ref, o_ref, h_ref, y_ref, *,
            chunk: int, state: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]
    d = d_ref[...].astype(f32)

    def one_group(g, h):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        dt = dt_ref[rows, :]
        u = u_ref[rows, :].astype(f32)
        decay_dt = jnp.where(pos_ref[rows, :] == 0, RESTART, dt)
        du = dt * u
        bc = bc_ref[g]  # (2 · state, GROUP): column j is token j's B, then its C
        for j in range(GROUP):
            h = (jnp.exp(a * decay_dt[j:j + 1, :]) * h
                 + bc[:state, j:j + 1] * du[j:j + 1, :])
            y_ref[j:j + 1, :] = jnp.sum(bc[state:, j:j + 1] * h, axis=0, keepdims=True)
        z = z_ref[rows, :].astype(f32)
        o_ref[rows, :] = ((y_ref[...] + d * u) * (z * jax.nn.sigmoid(z))).astype(o_ref.dtype)
        return h

    h_ref[...] = lax.fori_loop(0, chunk // GROUP, one_group, h_ref[...])


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
    if (tokens % chunk or chunk % GROUP or width % block or gate_column % block
            or dt.shape != u.shape or b.shape != (tokens, state) or c.shape != (tokens, state)
            or a.shape != (state, width) or d.shape != (width,) or pos.shape != (tokens,)
            or gate.shape[0] != tokens or gate.shape[1] < gate_column + width):
        raise ValueError(f"selective_scan: u {u.shape} in chunks of {chunk} tokens and blocks of "
                         f"{block} channels, a {a.shape}, b {b.shape}, gate {gate.shape} from "
                         f"column {gate_column}")
    f32 = jnp.float32
    bc = jnp.concatenate([b, c], axis=1).astype(f32).reshape(
        tokens // GROUP, GROUP, 2 * state).transpose(0, 2, 1)
    first = gate_column // block
    kernel = functools.partial(_kernel, chunk=chunk, state=state)
    return pl.pallas_call(
        kernel,
        grid=(width // block, tokens // chunk),
        in_specs=[
            pl.BlockSpec((state, block), lambda i, t: (0, i)),
            pl.BlockSpec((1, block), lambda i, t: (0, i)),
            pl.BlockSpec((chunk // GROUP, 2 * state, GROUP), lambda i, t: (t, 0, 0)),
            pl.BlockSpec((chunk, 1), lambda i, t: (t, 0)),
            pl.BlockSpec((chunk, block), lambda i, t: (t, i)),
            pl.BlockSpec((chunk, block), lambda i, t: (t, i)),
            pl.BlockSpec((chunk, block), lambda i, t: (t, first + i)),
        ],
        out_specs=pl.BlockSpec((chunk, block), lambda i, t: (t, i)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((state, block), f32), pltpu.VMEM((GROUP, block), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        name="selective_scan",
        interpret=interpret,
    )(a.astype(f32), d[None, :], bc, pos[:, None].astype(jnp.int32), u, dt.astype(f32), gate)

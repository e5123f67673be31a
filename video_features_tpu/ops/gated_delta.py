"""The gated delta rule over a page of packed documents: one chunked Pallas
kernel (and the gated norm that follows it in a layer, :func:`gated_norm`).

Per value head a state ``S`` (key width × value width) that restarts at every
document's first token::

    S ← α_t S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ;  o_t = Sᵀ q_t

with ``α_t = exp(g_t)``, ``g_t ≤ 0``, ``q`` and ``k`` unit rows. The kernel
walks a head's chunks of ``CHUNK`` tokens in order with ``S`` in VMEM scratch
(as ``segment_attention`` walks key blocks), in the chunked form of Yang et al.
2024 (arXiv:2412.06464): inside a chunk the corrections ``δ`` solve a unit
lower-triangular system, ``(I + L) δ = β (v − Γ₀ k S)`` with ``L_ij = β_i
(k_i·k_j) Γ_ij`` for ``j < i`` and ``Γ_ij = exp(G_i − G_j)`` the decay between
two tokens of the chunk (``G`` the chunk's cumulative ``g``); then three
products against the carried state and one state update a chunk.

The system is inverted by halves on the MXU: a 2 × 2 diagonal block of ``I +
L`` inverts to ``I − L``, and a block of twice the size ``[[A, 0], [B, D]]`` to
``[[A⁻¹, 0], [−D⁻¹ B A⁻¹, D⁻¹]]``: two products a doubling over the whole chunk
(the blocks are masks), ten for 64 tokens. NOT the shorter-looking
product ``(I − L)(I + L²)(I + L⁴)…`` (exact for a nilpotent ``L``): its powers
grow to 1e17 where keys repeat and decay is weak (a page's pads: one token id,
so one key, many times), float32 cancels to garbage of 1e9, the pads' state
overflows within a page and the segment mean's product spreads the NaN to
every row (PERF.md section 6, PR 40: read on the chip).

A page holds whole documents back to back at arbitrary offsets
(``parallel/pages.py``) and they are NOT aligned to chunks. Two tokens of one
chunk meet only if they share a document: that is a mask on ``Γ`` (never a
``-inf`` in ``G``), which makes the system block-diagonal by document. The
carried state is the document's that the previous chunk ended in: a token
reads it only if it continues that document, and the update keeps it only if
the chunk's last token does. Differences ``G_i − G_j`` are taken for ``j ≤ i``
alone, so no ``exp`` sees a positive number. A page's trailing pads share
document -1 and run as one more document: finite, read by nobody.

Layout is the projections' own: the convolution's output ``(tokens, (2 *
key_heads + value_heads) * width)`` goes in as it stands and the result is
``(tokens, value_heads * width)``; a grid step holds ``KEY_HEADS_PER_STEP`` key
heads and the ``value_heads / key_heads`` value heads each serves. ``g``, its
cumulative sums, the decays, the unit rows' norms, the triangular system
(its products: each float32 operand as two parts of ``qkv``'s type, three MXU
passes) and the carried state are float32; the other products take operands
of ``qkv``'s type and sum in float32. ``interpret=True`` runs the same kernels in the Pallas interpreter;
the caller says so (off the TPU: ``extractors/token_pages.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
# key heads a grid step holds: 1 read 10.2 ms a page and layer, 2 6.1, 4 4.9, 8 4.8
# (PERF.md section 6, PR 40)
KEY_HEADS_PER_STEP = 4
NORM_ROWS, NORM_HEADS_PER_STEP = 512, 8  # a block of the gated norm: 512 tokens by 8 heads
_VMEM_LIMIT = 64 * 1024 * 1024


def chunk_edges(doc: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """(tokens,) document index per token → int32 (2, chunks): the document of
    the token before each chunk (the carried state's; -2 before the page:
    nobody's) and of each chunk's last token."""
    last = doc[chunk - 1::chunk]
    return jnp.stack([jnp.concatenate([jnp.full((1,), -2, doc.dtype), last[:-1]]), last])


def _kernel(edge_ref, end_ref, q_ref, k_ref, v_ref, col_ref, row_ref, o_ref, state, *,
            chunk: int, width: int, per_key: int, key_step: int):
    from jax.experimental import pallas as pl

    h, c = pl.program_id(0), pl.program_id(1)
    f32, dtype = jnp.float32, q_ref.dtype
    carried_doc, last_doc = edge_ref[0, c], edge_ref[1, c]

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def dot(a, b, dims=(((1,), (0,)), ((), ())), **kw):
        return lax.dot_general(a, b, dims, preferred_element_type=f32, **kw)

    def system_dot(a, b):
        """A product of the triangular system, float32 in and out. On a
        bfloat16 page each operand goes in as two bfloat16 parts and three
        products are summed (a relative 2^-16, XLA's ``HIGH``): the MXU's own
        float32 product is six passes, and read 7.8 ms a layer where this reads
        4.9, with the same digits against the token recurrence (PERF.md section
        6, PR 40). On a float32 page (the tests) it is a float32 product."""
        if dtype == f32:
            return dot(a, b, precision=lax.Precision.HIGHEST)
        (a_hi, a_lo), (b_hi, b_lo) = (
            (x.astype(dtype), (x - x.astype(dtype).astype(f32)).astype(dtype)) for x in (a, b))
        return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))

    contract_last = (((1,), (1,)), ((), ()))
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    doc_col = col_ref[0, :, 2 * per_key:2 * per_key + 1]
    together = (doc_col == row_ref[0, per_key:per_key + 1, :]) & (cols <= rows)
    continues = (doc_col == carried_doc.astype(f32)).astype(f32)  # (chunk, 1): reads the carried state
    ends_with = (doc_col == last_doc.astype(f32)).astype(f32)     # shares the last token's document
    keeps = jnp.where(carried_doc == last_doc, 1.0, 0.0)
    eye = (rows == cols).astype(f32)
    apart = rows ^ cols  # under s: both in one aligned block of s tokens (s a power of two)

    def unit_rows(ref, n, scale):
        x = ref[:, n * width:(n + 1) * width].astype(f32)
        return x * (lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + 1e-6) * scale)

    # Every stage runs over ALL the step's heads before the next stage starts:
    # a head's ten triangular products each wait for the one before, and only
    # another head's work can fill that wait (head by head the same kernel
    # read 20.4 ms a page and layer where this order reads 12.0: PERF.md section 6, PR 40)
    keys = range(key_step)
    heads = [(n, a) for n in keys for a in range(per_key)]  # head n * per_key + a of the step
    q = [unit_rows(q_ref, n, width ** -0.5) for n in keys]
    k = [unit_rows(k_ref, n, 1.0) for n in keys]
    k_low = [x.astype(dtype) for x in k]
    kk = [dot(k_low[n], k_low[n], contract_last) for n in keys]
    qk = [dot(q[n].astype(dtype), k_low[n], contract_last) for n in keys]
    g_col = [col_ref[n, :, a:a + 1] for n, a in heads]
    beta = [col_ref[n, :, per_key + a:per_key + a + 1] for n, a in heads]
    decay = [jnp.where(together, jnp.exp(jnp.minimum(g_col[i] - row_ref[n, a:a + 1, :], 0.0)), 0.0)
             for i, (n, a) in enumerate(heads)]
    # (I + L)⁻¹ by halves: a 2 × 2 diagonal block of I + L inverts to I − L, and a
    # block of twice the size [[A, 0], [B, D]] to [[A⁻¹, 0], [−D⁻¹ B A⁻¹, D⁻¹]]:
    # two products a doubling, on the whole chunk at once (blocks by mask)
    lower = [jnp.where(cols < rows, beta[i] * kk[n] * decay[i], 0.0)
             for i, (n, _a) in enumerate(heads)]
    inverse = [eye - jnp.where(apart < 2, low, 0.0) for low in lower]
    size = 4
    while size <= chunk:
        corner = (apart >= size // 2) & (apart < size)
        below = [system_dot(jnp.where(corner, low, 0.0), inv) for low, inv in zip(lower, inverse)]
        inverse = [inv - system_dot(inv, b) for inv, b in zip(inverse, below)]
        size *= 2
    # decay from the carried state to each token, and what the state gives the
    # deltas and the outputs: one product a head
    from_start = [jnp.exp(g) * continues for g in g_col]
    solved = [dot(inverse[i].astype(dtype), jnp.concatenate(
        [beta[i] * v_ref[:, i * width:(i + 1) * width].astype(f32),
         (beta[i] * from_start[i]) * k[n]], axis=1).astype(dtype))
        for i, (n, _a) in enumerate(heads)]
    carried = [dot(jnp.concatenate([solved[i][:, width:], q[n] * from_start[i]],
                                   axis=0).astype(dtype), state[i].astype(dtype))
               for i, (n, _a) in enumerate(heads)]
    delta = [(solved[i][:, :width] - carried[i][:chunk]).astype(dtype) for i in range(len(heads))]
    for i, (n, a) in enumerate(heads):
        o = carried[i][chunk:] + dot((qk[n] * decay[i]).astype(dtype), delta[i])
        o_ref[:, i * width:(i + 1) * width] = o.astype(o_ref.dtype)
    for i, (n, a) in enumerate(heads):
        g_last = end_ref[(h * key_step + n) * per_key + a, c]  # a scalar: the chunk's whole decay
        to_end = jnp.exp(g_last - g_col[i]) * ends_with  # decay from each token to the chunk's end
        state[i] = (state[i] * (jnp.exp(jnp.full((1, width), g_last, f32)) * keeps)
                    + dot((k[n] * to_end).astype(dtype), delta[i], (((0,), (0,)), ((), ()))))


@functools.partial(jax.jit, static_argnames=("key_heads", "chunk", "interpret"))
def gated_delta(qkv, g, beta, doc, *, key_heads: int, chunk: int = CHUNK,
                interpret: bool = False):
    """The rule above for every value head over a page. ``qkv`` ``(tokens,
    (2 * key_heads + value_heads) * width)`` is the convolution's output as it
    stands, every key head's query, every key head's key, every value head's
    value; the kernel makes each head's query and key a unit row (``x /
    sqrt(Σ x² + 1e-6)``, float32, queries over ``sqrt(width)`` besides). ``g``
    (≤ 0) and ``beta`` are float32 ``(tokens, value_heads)``; ``doc`` is the
    page's document index per token. → ``(tokens, value_heads * width)`` in
    ``qkv``'s type. ``tokens`` must be a multiple of ``chunk``, a power of
    two."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, value_heads = g.shape
    per_key = max(value_heads // key_heads, 1)
    width = qkv.shape[1] // (2 * key_heads + value_heads)
    # a step's key heads: as many as KEY_HEADS_PER_STEP that cut the key heads
    # evenly and leave a step's values on a whole block of the values' columns
    fits = [s for s in range(1, min(KEY_HEADS_PER_STEP, key_heads) + 1)
            if key_heads % s == 0 and (2 * key_heads) % (s * per_key) == 0]
    if (tokens % chunk or chunk & (chunk - 1) or value_heads != per_key * key_heads or not fits
            or qkv.shape != (tokens, (2 * key_heads + value_heads) * width)):
        raise ValueError(f"gated_delta: {tokens} tokens in chunks of {chunk}, qkv {qkv.shape} "
                         f"over {key_heads} key heads and {value_heads} value heads")
    key_step = fits[-1]
    chunks, steps = tokens // chunk, key_heads // key_step
    f32 = jnp.float32
    total = jnp.cumsum(g.astype(f32).reshape(chunks, chunk, key_heads, per_key), axis=1)
    docs = jnp.broadcast_to(doc.astype(f32).reshape(chunks, chunk, 1, 1),
                            (chunks, chunk, key_heads, 1))
    # what a step reads of its tokens besides q, k, v, in float32 (a document
    # index is exact there), once down its rows and once along its lanes:
    # cumulative g and beta of the key head's value heads, the document
    col = jnp.concatenate([total, beta.astype(f32).reshape(chunks, chunk, key_heads, per_key),
                           docs], axis=-1).transpose(2, 0, 1, 3).reshape(key_heads, tokens, -1)
    row = jnp.concatenate([total, docs], axis=-1).transpose(2, 0, 3, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps, chunks),
        in_specs=[
            # one array three times: a step's queries, its keys (past every query),
            # its values (past every key, `per_key` times as wide)
            pl.BlockSpec((chunk, key_step * width), lambda h, c, *_: (c, h)),
            pl.BlockSpec((chunk, key_step * width), lambda h, c, *_: (c, steps + h)),
            pl.BlockSpec((chunk, key_step * per_key * width),
                         lambda h, c, *_: (c, 2 * steps // per_key + h)),
            pl.BlockSpec((key_step, chunk, 2 * per_key + 1), lambda h, c, *_: (h, c, 0)),
            pl.BlockSpec((key_step, None, per_key + 1, chunk), lambda h, c, *_: (h, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, key_step * per_key * width), lambda h, c, *_: (c, h)),
        scratch_shapes=[pltpu.VMEM((key_step * per_key, width, width), f32)],
    )
    kernel = functools.partial(_kernel, chunk=chunk, width=width, per_key=per_key,
                               key_step=key_step)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, value_heads * width), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        name="gated_delta_chunk",
        interpret=interpret,
    )(chunk_edges(doc, chunk), total[:, -1].reshape(chunks, value_heads).T, qkv, qkv, qkv, col, row)


def _norm_kernel(o_ref, z_ref, w_ref, out_ref, *, heads: int, width: int, eps: float):
    w = w_ref[...].astype(jnp.float32)
    for a in range(heads):
        cols = slice(a * width, (a + 1) * width)
        o, z = o_ref[:, cols].astype(jnp.float32), z_ref[:, cols].astype(jnp.float32)
        y = o * lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps) * w
        out_ref[:, cols] = (y * z * jax.nn.sigmoid(z)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "gate_column", "eps", "interpret"))
def gated_norm(o, gate, weight, *, heads: int, gate_column: int = 0, eps: float = 1e-6,
               interpret: bool = False):
    """``RMSNorm(o) · weight · silu(z)`` head by head (float32 inside): ``o``
    ``(tokens, heads * width)`` is the delta rule's output, ``weight``
    ``(width,)`` one scale for every head, and ``z`` the ``heads * width``
    columns of ``gate`` from ``gate_column`` on (the projections' product as it
    stands: nothing is sliced out first). One pass over ``o`` and ``z`` in
    their own layout; as plain ``jax.numpy`` the per-head mean is a relayout of
    both (PERF.md section 6, PR 40)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, total = o.shape
    width = total // heads
    step = min(NORM_HEADS_PER_STEP, heads)
    rows = min(NORM_ROWS, tokens)
    if tokens % rows or heads % step or gate_column % (step * width) or weight.shape != (width,):
        raise ValueError(f"gated_norm: o {o.shape} in blocks of {rows} rows and {step} heads of "
                         f"{width}, gate {gate.shape} from column {gate_column}")
    first = gate_column // (step * width)
    kernel = functools.partial(_norm_kernel, heads=step, width=width, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(tokens // rows, heads // step),
        in_specs=[pl.BlockSpec((rows, step * width), lambda i, j: (i, j)),
                  pl.BlockSpec((rows, step * width), lambda i, j: (i, first + j)),
                  pl.BlockSpec((1, width), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((rows, step * width), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="gated_norm",
        interpret=interpret,
    )(o, gate, weight[None, :])

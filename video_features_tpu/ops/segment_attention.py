"""Causal attention over a page of packed documents: one blocked Pallas kernel.

A page holds whole documents back to back (``parallel/pages.py``), so the keys
a query may see are one contiguous run of the page: from its own document's
first token (or ``window - 1`` tokens back, in a sliding layer) up to itself.
Per block of queries that is a contiguous range of key blocks, known only at
run time. The kernel takes the first key block of every query block as a
prefetched scalar and walks from there to the diagonal; key blocks outside the
range are neither fetched nor computed (the grid step is there, its body and
its copy are not), and inside the range the document, causal and window
structure is a mask. Scores never leave VMEM: online softmax in float32,
products on the MXU with float32 accumulation.

Layout is the projections' own: ``q`` is ``(tokens, heads * head_dim)``,
``k``/``v`` ``(tokens, kv_heads * head_dim)``, and a grid step holds the
``heads / kv_heads`` query heads that share one key/value head, so nothing is
transposed on the way in or out. ``interpret=True`` runs the same kernel in the
Pallas interpreter; the caller says so (off the TPU: ``extractors/laguna.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

MASKED = -1e30  # finite: a row masked in a whole block is wiped by the next block's rescale
_VMEM_LIMIT = 64 * 1024 * 1024


def first_key_block(doc: jnp.ndarray, block: int, window: Optional[int]) -> jnp.ndarray:
    """(tokens,) document index per token (pads share -1) → for each block of
    ``block`` queries the first block of keys any of them may see."""
    idx = jnp.arange(doc.shape[0], dtype=jnp.int32)
    begins = jnp.concatenate([jnp.ones((1,), bool), doc[1:] != doc[:-1]])
    start = lax.cummax(jnp.where(begins, idx, 0))  # page index of each token's document
    first = start[::block]  # tokens are in page order: a block's first query reaches back farthest
    if window is not None:
        first = jnp.maximum(first, idx[::block] - (window - 1))
    return first // block


def _kernel(lo_ref, q_ref, k_ref, v_ref, dq_ref, dk_ref, o_ref, m_scr, l_scr, acc_scr, *,
            group: int, head_dim: int, block: int, window: Optional[int]):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)
    kv = lo_ref[i] + j

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kv <= i)
    def _():
        rows = i * block + lax.broadcasted_iota(jnp.int32, (block, block), 0)
        cols = kv * block + lax.broadcasted_iota(jnp.int32, (block, block), 1)
        mask = (dq_ref[...] == dk_ref[...]) & (cols <= rows)
        if window is not None:
            mask &= rows - cols < window
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            cols_g = slice(g * head_dim, (g + 1) * head_dim)
            s = lax.dot_general(q_ref[:, cols_g], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, MASKED)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[g] = alpha * l_scr[g] + p.sum(axis=1, keepdims=True)
            acc_scr[:, cols_g] = alpha * acc_scr[:, cols_g] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[g] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for g in range(group):
            cols_g = slice(g * head_dim, (g + 1) * head_dim)
            o_ref[:, cols_g] = (acc_scr[:, cols_g] / l_scr[g]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_heads", "head_dim", "window", "block", "interpret"))
def segment_attention(q, k, v, doc, *, kv_heads: int, head_dim: int,
                      window: Optional[int] = None, block: int = 512,
                      interpret: bool = False):
    """softmax(q·kᵀ) v per head, each query over the keys at or before it in
    its own document (and fewer than ``window`` back). ``q`` comes scaled and
    rotated; ``doc`` is the page's document index per token. ``tokens`` must be
    a multiple of ``block``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, width = q.shape
    group = width // (kv_heads * head_dim)
    if tokens % block or width != group * kv_heads * head_dim:
        raise ValueError(f"segment_attention: {tokens} tokens in blocks of {block}, "
                         f"q width {width} over {kv_heads} key/value heads of {head_dim}")
    nq = tokens // block
    # the farthest a block of queries reaches back, in key blocks, diagonal included
    steps = nq if window is None else min(nq, -(-(window - 1) // block) + 1)
    lo = first_key_block(doc, block, window)

    def kv_block(h, i, j, lo_ref):
        return jnp.minimum(lo_ref[i] + j, i), h  # past the diagonal: stay, nothing is copied

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kv_heads, nq, steps),
        in_specs=[
            pl.BlockSpec((block, group * head_dim), lambda h, i, j, lo_ref: (i, h)),
            pl.BlockSpec((block, head_dim), kv_block),
            pl.BlockSpec((block, head_dim), kv_block),
            pl.BlockSpec((block, 1), lambda h, i, j, lo_ref: (i, 0)),
            pl.BlockSpec((1, block), lambda h, i, j, lo_ref: (0, kv_block(h, i, j, lo_ref)[0])),
        ],
        out_specs=pl.BlockSpec((block, group * head_dim), lambda h, i, j, lo_ref: (i, h)),
        scratch_shapes=[
            pltpu.VMEM((group, block, 1), jnp.float32),
            pltpu.VMEM((group, block, 1), jnp.float32),
            pltpu.VMEM((block, group * head_dim), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, group=group, head_dim=head_dim, block=block,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="segment_attention_window" if window is not None else "segment_attention_full",
        interpret=interpret,
    )(lo, q, k, v, doc[:, None], doc[None, :])

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
transposed on the way in or out. Heads narrower than a lane row go as many to
a step as fill one: at ``head_dim`` 64 a grid step holds two key/value heads
(128 lanes of ``k`` and ``v``) and the query heads of both, since Mosaic
refuses a 64-lane block of a wider array. ``interpret=True`` runs the same kernel in the
Pallas interpreter; the caller says so (off the TPU: ``extractors/token_pages.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

MASKED = -1e30  # finite: a row masked in a whole block is wiped by the next block's rescale
_VMEM_LIMIT = 64 * 1024 * 1024
# heads of their own keys a grid step holds beside a shared key: 8 read 257.7 ms
# for a pass of the benchmark's corpus at 64 heads, 4 269.1, 16 270.4, 2 342.0
# (PERF.md section 6, PR 38)
LATENT_HEADS_PER_STEP = 8
LANES = 128  # a vector register's lanes: the narrowest block of k and v Mosaic takes


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


def _kernel(lo_ref, q_ref, k_ref, v_ref, *rest, group: int, kv_step: int, head_dim: int,
            block: int, window: Optional[int], shared: int):
    from jax.experimental import pallas as pl

    if shared:
        qs_ref, ks_ref, dq_ref, dk_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        dq_ref, dk_ref, o_ref, m_scr, l_scr, acc_scr = rest
    i, j = pl.program_id(1), pl.program_id(2)
    kv = lo_ref[i] + j
    contract_last = (((1,), (1,)), ((), ()))

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
        for n in range(kv_step):
            cols_n = slice(n * head_dim, (n + 1) * head_dim)
            k, v = k_ref[:, cols_n], v_ref[:, cols_n]
            for g in range(group):
                a = n * group + g
                cols_a = slice(a * head_dim, (a + 1) * head_dim)
                s = lax.dot_general(q_ref[:, cols_a], k, contract_last,
                                    preferred_element_type=jnp.float32)
                if shared:
                    # the head's own columns of its lane row of shared-part
                    # queries meet the shared key laid into the same columns
                    # of a zero row (`_lane_rows`): whole lane rows on both
                    # sides, and the zeros add nothing
                    per_row = 128 // shared
                    row = slice(a // per_row * 128, (a // per_row + 1) * 128)
                    at = slice(a % per_row * 128, (a % per_row + 1) * 128)
                    s += lax.dot_general(qs_ref[:, row], ks_ref[:, at], contract_last,
                                         preferred_element_type=jnp.float32)
                s = jnp.where(mask, s, MASKED)
                m_prev = m_scr[a]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_scr[a] = alpha * l_scr[a] + p.sum(axis=1, keepdims=True)
                acc_scr[:, cols_a] = alpha * acc_scr[:, cols_a] + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                m_scr[a] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for a in range(kv_step * group):
            cols_a = slice(a * head_dim, (a + 1) * head_dim)
            o_ref[:, cols_a] = (acc_scr[:, cols_a] / l_scr[a]).astype(o_ref.dtype)


def _lane_rows(ks):
    """(tokens, r) shared key, ``r`` a whole fraction of a lane row → (tokens,
    128 * 128/r): for each place a head's ``r`` columns can take in a lane row
    of queries, a lane row that is zero but for the key at that place."""
    r = ks.shape[1]
    per_row = 128 // r
    eye = jnp.eye(per_row, dtype=ks.dtype)
    return (eye[None, :, :, None] * ks[:, None, None, :]).reshape(ks.shape[0], per_row * 128)


@functools.partial(jax.jit, static_argnames=("kv_heads", "head_dim", "window", "block", "interpret"))
def segment_attention(q, k, v, doc, *, kv_heads: int, head_dim: int,
                      window: Optional[int] = None, block: int = 512,
                      interpret: bool = False, q_shared=None, k_shared=None):
    """softmax(q·kᵀ) v per head, each query over the keys at or before it in
    its own document (and fewer than ``window`` back). ``q`` comes scaled and
    rotated; ``doc`` is the page's document index per token. ``tokens`` must be
    a multiple of ``block``.

    With ``q_shared`` ``(tokens, heads * r)`` and ``k_shared`` ``(tokens, r)``
    the score is a sum of two products, ``q·kᵀ + q_shared·k_sharedᵀ``: every
    head's second part meets the ONE shared key (latent attention's decoupled
    rope key, never copied per head). Every head has its own key and value
    there (``kv_heads == heads``), a grid step holds ``LATENT_HEADS_PER_STEP``
    of them, and the kernel is named ``segment_attention_latent``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, width = q.shape
    group = width // (kv_heads * head_dim)
    if tokens % block or width != group * kv_heads * head_dim:
        raise ValueError(f"segment_attention: {tokens} tokens in blocks of {block}, "
                         f"q width {width} over {kv_heads} key/value heads of {head_dim}")
    # key/value heads a grid step holds: as many as fill a lane row (`head_dim` 64: two)
    shared, kv_step = 0, math.gcd(kv_heads, max(1, LANES // head_dim))
    if q_shared is not None:
        shared, kv_step = k_shared.shape[1], min(LATENT_HEADS_PER_STEP, kv_heads)
        if (group != 1 or window is not None or 128 % shared or kv_heads % kv_step
                or (kv_step * shared) % 128 or q_shared.shape != (tokens, kv_heads * shared)):
            raise ValueError(f"segment_attention: a shared key of {shared} columns for "
                             f"{q_shared.shape} shared-part queries, {kv_heads} heads of their "
                             f"own keys in steps of {kv_step}")
    nq = tokens // block
    # the farthest a block of queries reaches back, in key blocks, diagonal included
    steps = nq if window is None else min(nq, -(-(window - 1) // block) + 1)
    lo = first_key_block(doc, block, window)

    def kv_block(h, i, j, lo_ref):
        return jnp.minimum(lo_ref[i] + j, i), h  # past the diagonal: stay, nothing is copied

    def q_block(h, i, j, lo_ref):
        return i, h

    heads_step = kv_step * group
    in_specs = [pl.BlockSpec((block, heads_step * head_dim), q_block),
                pl.BlockSpec((block, kv_step * head_dim), kv_block),
                pl.BlockSpec((block, kv_step * head_dim), kv_block)]
    operands = [q, k, v]
    if shared:
        ks = _lane_rows(k_shared)
        in_specs += [pl.BlockSpec((block, kv_step * shared), q_block),
                     pl.BlockSpec((block, ks.shape[1]),
                                  lambda h, i, j, lo_ref: (kv_block(h, i, j, lo_ref)[0], 0))]
        operands += [q_shared, ks]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kv_heads // kv_step, nq, steps),
        in_specs=in_specs + [
            pl.BlockSpec((block, 1), lambda h, i, j, lo_ref: (i, 0)),
            pl.BlockSpec((1, block), lambda h, i, j, lo_ref: (0, kv_block(h, i, j, lo_ref)[0])),
        ],
        out_specs=pl.BlockSpec((block, heads_step * head_dim), q_block),
        scratch_shapes=[
            pltpu.VMEM((heads_step, block, 1), jnp.float32),
            pltpu.VMEM((heads_step, block, 1), jnp.float32),
            pltpu.VMEM((block, heads_step * head_dim), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, group=group, kv_step=kv_step, head_dim=head_dim,
                               block=block, window=window, shared=shared)
    name = "latent" if shared else "window" if window is not None else "full"
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="segment_attention_" + name,
        interpret=interpret,
    )(lo, *operands, doc[:, None], doc[None, :])

"""Operations one ``lfm2_24b_a2b_bf16`` row (one real token) needs, counted by
hand from the published shapes for the six layers the configuration holds.
Embedding look-ups, norms, rope, the sigmoid and top-k, softmax and the
segment mean's sums are not counted.

Per token: a conv layer's in-projection ``2048 x 6144`` and out-projection
``2048 x 2048``, and its short-convolution core, 7 operations a channel
(``B ⊙ u``, three taps of a multiply and an add less one add, ``C ⊙ w``); an
attention layer's ``q`` and ``o`` ``2048 x 2048`` each, ``k`` and ``v`` ``2048 x
512`` each, and attention by each document's own extent: query ``i`` meets ``i +
1`` keys, ``4 x 64`` operations per query, key and head (scores and the weighted
sum) over 32 heads; a dense layer (0, 1) three ``2048 x 11776``; a routed layer
(2–5) the router ``2048 x 64`` and 4 experts of three ``2048 x 1536``, every
expert held here. ``flops_per_row()`` is the mean over the traffic's fixed
multiset of document lengths, which is exact for every window that holds whole
passes; ``step_mfu``'s reader passes no traffic, so the lengths are read from
the one traffic file named here (``benchmark/tests/test_lfm2_cpu.py`` holds
every traffic of a configuration that counts with this module to that
multiset).

The short-convolution core (``core_flops``, ``core_bytes``) is counted apart,
for its roofline alone: the least traffic is ``B``, ``C`` and ``u`` read and ``y``
written in bfloat16, 16,384 bytes a token and conv layer, 0.33 ms a full page
at 819 GB/s, which bounds it (its operations take 0.001 ms at the bf16
peak). The layers are the reference's statement of the model, not restated.
"""

import json
import os

from reference.lfm2 import LAYERS, PUBLISHED, is_attention, is_dense

HIDDEN, INTERMEDIATE = 2048, 11776
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 64
EXPERTS, TOP_K, EXPERT_WIDTH = 64, 4, 1536
CORE_OPS = 7  # a token and channel: B·u, three taps (3 multiplies, 2 adds), C·w
CORE_BYTES = 4 * 2 * HIDDEN  # B, C, u in and y out, bfloat16
TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "traffic", "corpus_transcripts_64k.json")


def is_conv(layer: int) -> bool:
    return not is_attention(PUBLISHED, layer)


CONV_LAYERS = tuple(l for l in LAYERS if is_conv(l))


def projection_flops(layer: int) -> int:
    if is_conv(layer):
        return 2 * (HIDDEN * 3 * HIDDEN + HIDDEN * HIDDEN)
    return 2 * (2 * HIDDEN * HEADS * HEAD_DIM + 2 * HIDDEN * KV_HEADS * HEAD_DIM)


def mlp_flops(layer: int) -> int:
    if is_dense(PUBLISHED, layer):
        return 3 * 2 * HIDDEN * INTERMEDIATE
    return 2 * HIDDEN * EXPERTS + TOP_K * 3 * 2 * HIDDEN * EXPERT_WIDTH


def core_flops(tokens: int) -> int:
    """The short-convolution core over ``tokens`` real tokens of one conv layer."""
    return CORE_OPS * HIDDEN * tokens


def core_bytes(tokens: int) -> int:
    """``B``, ``C``, ``u`` in and ``y`` out in bfloat16, each crossing the memory
    once: 16,384 a token and conv layer."""
    return CORE_BYTES * tokens


def attention_pairs(tokens: int) -> int:
    """(query, key) pairs of one document of ``tokens`` in an attention layer."""
    return tokens * (tokens + 1) // 2


def attention_core_flops(documents, layer: int) -> int:
    """Scores and weighted sums (64 wide each) of one layer over a page's
    documents; a conv layer has none."""
    if is_conv(layer):
        return 0
    return 4 * HEAD_DIM * HEADS * sum(attention_pairs(n) for n in documents)


def document_lengths(traffic: str = TRAFFIC) -> list:
    with open(traffic) as f:
        t = json.load(f)
    k, lo, hi = int(t["documents"]), t["min_tokens"], t["max_tokens"]
    return [int(round(lo * (hi / lo) ** (i / (k - 1)))) for i in range(k)]


def product_flops_per_token() -> float:
    return sum(projection_flops(l) + mlp_flops(l) + (core_flops(1) if is_conv(l) else 0)
               for l in LAYERS)


def attention_flops_per_token() -> float:
    docs = document_lengths()
    return sum(attention_core_flops(docs, l) for l in LAYERS) / sum(docs)


def flops_per_row() -> float:
    return product_flops_per_token() + attention_flops_per_token()

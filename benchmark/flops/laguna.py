"""Operations one ``laguna_s21_bf16`` row (one real token) needs, counted by
hand from the published shapes for the layers and experts the configuration's
chip holds (layers 0-4, 64 of 256 experts). Products only: embedding look-ups,
norms, rope, softmax, the gate's multiply and the segment mean are not counted.

Per token and layer: the projections (query ``3072 x H*128``, key and value
``3072 x 1024`` each, gate ``3072 x H``, output ``H*128 x 3072``; ``H`` 48 in a
full layer, 72 in a sliding one), then either the dense unit (three matrices
``3072 x 12288``) or the router (``3072 x 256``), the shared expert (three
``3072 x 1024``) and the routed experts HELD: 10 choices x 64/256 = 2.5 of them
a token on average (``routed_held`` in the program's counters says how many
there were). Attention by each document's own extent: query ``i`` of a document
meets ``i + 1`` keys in a full layer and ``min(i + 1, 512)`` in a sliding one,
``4 * 128`` operations per query, key and head (scores and the weighted sum).
``flops_per_row()`` is the mean over the traffic's fixed multiset of document
lengths, which is exact for every window that holds whole passes. ``step_mfu``'s
reader passes no traffic, so the lengths are read from the one traffic file
named here: ``benchmark/tests/test_laguna_cpu.py`` holds every traffic of a
configuration that counts with this module to that multiset. The matrices are
written out by hand; which layers and how many experts the chip holds is the
reference's statement of the cut, not restated.
"""

import json
import os

from reference.laguna import EXPERTS as _HELD, LAYERS

HIDDEN, HEAD_DIM, KV_HEADS = 3072, 128, 8
HEADS_FULL, HEADS_SLIDING, WINDOW = 48, 72, 512
DENSE_WIDTH, EXPERT_WIDTH, EXPERTS, TOP_K = 12288, 1024, 256, 10
EXPERTS_HELD = len(_HELD)
TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "traffic", "corpus_transcripts.json")


def is_full(layer: int) -> bool:
    return layer % 4 == 0


def heads(layer: int) -> int:
    return HEADS_FULL if is_full(layer) else HEADS_SLIDING


def gated_unit_flops(width: int) -> int:
    return 3 * 2 * HIDDEN * width


def projection_flops(layer: int) -> int:
    h = heads(layer)
    return 2 * HIDDEN * (2 * h * HEAD_DIM + 2 * KV_HEADS * HEAD_DIM + h)


def expert_flops(rows: int) -> int:
    """The routed experts' two grouped products for ``rows`` held assignments."""
    return rows * gated_unit_flops(EXPERT_WIDTH)


def mlp_flops(layer: int) -> float:
    if layer == 0:
        return gated_unit_flops(DENSE_WIDTH)
    return (2 * HIDDEN * EXPERTS + gated_unit_flops(EXPERT_WIDTH)
            + expert_flops(1) * TOP_K * EXPERTS_HELD / EXPERTS)


def attention_pairs(tokens: int, layer: int) -> int:
    """(query, key) pairs of one document of ``tokens`` in ``layer``."""
    if is_full(layer) or tokens <= WINDOW:
        return tokens * (tokens + 1) // 2
    return WINDOW * (WINDOW + 1) // 2 + (tokens - WINDOW) * WINDOW


def attention_core_flops(documents, layer: int) -> int:
    """Scores and weighted sums of one layer over a page's documents."""
    return 4 * HEAD_DIM * heads(layer) * sum(attention_pairs(n, layer) for n in documents)


def document_lengths(traffic: str = TRAFFIC) -> list:
    with open(traffic) as f:
        t = json.load(f)
    k, lo, hi = int(t["documents"]), t["min_tokens"], t["max_tokens"]
    return [int(round(lo * (hi / lo) ** (i / (k - 1)))) for i in range(k)]


def product_flops_per_token() -> float:
    return sum(projection_flops(l) + mlp_flops(l) for l in LAYERS)


def attention_flops_per_token() -> float:
    docs = document_lengths()
    return sum(attention_core_flops(docs, l) for l in LAYERS) / sum(docs)


def flops_per_row() -> float:
    return product_flops_per_token() + attention_flops_per_token()

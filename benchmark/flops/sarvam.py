"""Operations one ``sarvam_105b_bf16`` row (one real token) needs, counted by
hand from the published shapes for the layers and experts the configuration's
chip holds (layers 0-4, 16 of 128 experts). Products only: embedding look-ups,
norms, rope, softmax, the router's sigmoid and the segment mean are not counted.

Per token and layer, latent attention's four projections (query ``4096 x
64*192``, down to the latent and the shared rope key ``4096 x 576``, up to
keys and values ``512 x 64*256``, output ``64*128 x 4096``), then either the
dense unit (three matrices ``4096 x 16384``) or the router (``4096 x 128``),
the shared expert (three ``4096 x 2048``) and the routed experts HELD: 8
choices x 16/128 = 1 of them a token on average (``routed_held`` in the
program's counters says how many there were). Attention by each document's
own extent: query ``i`` of a document meets ``i + 1`` keys, and a (query, key)
pair costs a head ``2 * 192`` operations for its score (128 of the head's own
key, 64 of the shared rope key) and ``2 * 128`` for the weighted sum.
``flops_per_row()`` is the mean over the traffic's fixed multiset of document
lengths, which is exact for every window that holds whole passes.
``step_mfu``'s reader passes no traffic, so the lengths are read from the one
traffic file named here (``benchmark/tests/test_sarvam_cpu.py`` holds every
traffic of a configuration that counts with this module to that multiset). The
matrices are written out by hand; which layers and how many experts the chip
holds is the reference's statement of the cut, not restated.
"""

import json
import os

from reference.sarvam import EXPERTS as _HELD, LAYERS

HIDDEN, HEADS = 4096, 64
NOPE_DIM, ROPE_DIM, VALUE_DIM, LATENT = 128, 64, 128, 512
DENSE_WIDTH, EXPERT_WIDTH, EXPERTS, TOP_K = 16384, 2048, 128, 8
EXPERTS_HELD = len(_HELD)
TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "traffic", "corpus_transcripts.json")


def gated_unit_flops(width: int) -> int:
    return 3 * 2 * HIDDEN * width


def projection_flops() -> int:
    return 2 * (HIDDEN * HEADS * (NOPE_DIM + ROPE_DIM) + HIDDEN * (LATENT + ROPE_DIM)
                + LATENT * HEADS * (NOPE_DIM + VALUE_DIM) + HEADS * VALUE_DIM * HIDDEN)


def expert_flops(rows: int) -> int:
    """The routed experts' two grouped products for ``rows`` held assignments."""
    return rows * gated_unit_flops(EXPERT_WIDTH)


def mlp_flops(layer: int) -> float:
    if layer == 0:
        return gated_unit_flops(DENSE_WIDTH)
    return (2 * HIDDEN * EXPERTS + gated_unit_flops(EXPERT_WIDTH)
            + expert_flops(1) * TOP_K * EXPERTS_HELD / EXPERTS)


def attention_pairs(tokens: int) -> int:
    """(query, key) pairs of one document of ``tokens``: every layer is causal
    over the whole document."""
    return tokens * (tokens + 1) // 2


def attention_core_flops(documents, layer: int) -> int:
    """Scores (192 wide) and weighted sums (128 wide) of one layer over a
    page's documents; every layer has the same 64 heads."""
    del layer
    return (2 * (NOPE_DIM + ROPE_DIM) + 2 * VALUE_DIM) * HEADS * sum(
        attention_pairs(n) for n in documents)


def document_lengths(traffic: str = TRAFFIC) -> list:
    with open(traffic) as f:
        t = json.load(f)
    k, lo, hi = int(t["documents"]), t["min_tokens"], t["max_tokens"]
    return [int(round(lo * (hi / lo) ** (i / (k - 1)))) for i in range(k)]


def product_flops_per_token() -> float:
    return sum(projection_flops() + mlp_flops(l) for l in LAYERS)


def attention_flops_per_token() -> float:
    docs = document_lengths()
    return sum(attention_core_flops(docs, l) for l in LAYERS) / sum(docs)


def flops_per_row() -> float:
    return product_flops_per_token() + attention_flops_per_token()

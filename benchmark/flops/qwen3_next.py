"""Operations one ``qwen3_next_80b_bf16`` row (one real token) needs, counted by
hand from the published shapes for the layers and experts the configuration's
chip holds (layers 0-3, 128 of 512 experts). Products only: embedding
look-ups, norms, the convolution's four taps, rope, softmax, sigmoids and the
segment mean are not counted.

Per token and linear (Gated DeltaNet) layer: the projections (``q, k`` ``2048 x
2048`` each, ``v, z`` ``2048 x 4096`` each, ``b, a`` ``2048 x 32`` each, output
``4096 x 2048``) and the delta rule, counted as step 5's three ``128 x 128``
products a token and value head (``Sᵀk``, ``k δᵀ``, ``Sᵀq``) whatever chunking
computes it. Per token and full layer: query and gate ``2048 x 8192``, key and
value ``2048 x 512`` each, output ``4096 x 2048``, and attention by each
document's own extent: query ``i`` of a document meets ``i + 1`` keys, ``4 x
256`` operations per query, key and head (scores and the weighted sum). Every
layer: the router (``2048 x 512``), the shared expert (three ``2048 x 512``)
with its gate (``2048 x 1``), and the routed experts HELD: 10 choices x 128/512
= 2.5 of them a token on average (``routed_held`` in the program's counters
says how many there were). ``flops_per_row()`` is the mean over the traffic's
fixed multiset of document lengths, which is exact for every window that holds
whole passes. ``step_mfu``'s reader passes no traffic, so the lengths are read
from the one traffic file named here (``benchmark/tests/test_qwen3_next_cpu.py``
holds every traffic of a configuration that counts with this module to that
multiset). The matrices are written out by hand; which layers and how many
experts the chip holds is the reference's statement of the cut, not restated.
"""

import json
import os

from reference.qwen3_next import EXPERTS as _HELD, LAYERS

HIDDEN = 2048
KEY_HEADS, VALUE_HEADS, LINEAR_DIM = 16, 32, 128
HEADS, KV_HEADS, HEAD_DIM = 16, 2, 256
EXPERT_WIDTH, SHARED_WIDTH, EXPERTS, TOP_K = 512, 512, 512, 10
EXPERTS_HELD = len(_HELD)
TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "traffic", "corpus_transcripts.json")


def is_full(layer: int) -> bool:
    return (layer + 1) % 4 == 0


def gated_unit_flops(width: int) -> int:
    return 3 * 2 * HIDDEN * width


def projection_flops(layer: int) -> int:
    if is_full(layer):
        return 2 * (HIDDEN * 2 * HEADS * HEAD_DIM + 2 * HIDDEN * KV_HEADS * HEAD_DIM
                    + HEADS * HEAD_DIM * HIDDEN)
    keys, values = KEY_HEADS * LINEAR_DIM, VALUE_HEADS * LINEAR_DIM
    return 2 * (HIDDEN * (2 * keys + 2 * values + 2 * VALUE_HEADS) + values * HIDDEN)


def delta_rule_flops(tokens: int) -> int:
    """Step 5 over ``tokens`` tokens of one linear layer: three 128 x 128
    products a token and value head."""
    return 3 * 2 * LINEAR_DIM * LINEAR_DIM * VALUE_HEADS * tokens


def delta_rule_bytes(tokens: int) -> int:
    """``q, k`` (16 key heads) in, ``v`` in and ``o`` out (32 value heads) in
    bfloat16, ``g`` and ``β`` in float32, each crossing the memory once:
    24,832 a token and linear layer."""
    return tokens * (2 * (2 * KEY_HEADS + 2 * VALUE_HEADS) * LINEAR_DIM + 2 * 4 * VALUE_HEADS)


def expert_flops(rows: int) -> int:
    """The routed experts' two grouped products for ``rows`` held assignments."""
    return rows * gated_unit_flops(EXPERT_WIDTH)


def mlp_flops() -> float:
    return (2 * HIDDEN * EXPERTS + gated_unit_flops(SHARED_WIDTH) + 2 * HIDDEN
            + expert_flops(1) * TOP_K * EXPERTS_HELD / EXPERTS)


def attention_pairs(tokens: int) -> int:
    """(query, key) pairs of one document of ``tokens`` in a full layer."""
    return tokens * (tokens + 1) // 2


def attention_core_flops(documents, layer: int) -> int:
    """Scores and weighted sums (256 wide each) of one layer over a page's
    documents; a linear layer has none."""
    if not is_full(layer):
        return 0
    return 4 * HEAD_DIM * HEADS * sum(attention_pairs(n) for n in documents)


def document_lengths(traffic: str = TRAFFIC) -> list:
    with open(traffic) as f:
        t = json.load(f)
    k, lo, hi = int(t["documents"]), t["min_tokens"], t["max_tokens"]
    return [int(round(lo * (hi / lo) ** (i / (k - 1)))) for i in range(k)]


def product_flops_per_token() -> float:
    return sum(projection_flops(l) + mlp_flops() + (0 if is_full(l) else delta_rule_flops(1))
               for l in LAYERS)


def attention_flops_per_token() -> float:
    docs = document_lengths()
    return sum(attention_core_flops(docs, l) for l in LAYERS) / sum(docs)


def flops_per_row() -> float:
    return product_flops_per_token() + attention_flops_per_token()

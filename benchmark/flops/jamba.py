"""Operations one ``jamba2_3b_bf16`` row (one real token) needs, counted by hand
from the published shapes for all 28 layers. Products only: embedding look-ups,
norms, the convolution's four taps, the softplus, the selective scan's
element-wise walk, softmax, the gate and the segment mean are not counted.

Per token and Mamba layer: the in-projection ``2560 x 10240``, ``x_proj`` ``5120
x 192``, ``dt_proj`` ``160 x 5120`` and the out-projection ``5120 x 2560``. Per
token and attention layer (7 and 21): ``q`` and ``o`` ``2560 x 2560`` each, ``k``
and ``v`` ``2560 x 128`` each, and attention by each document's own extent: query
``i`` of a document meets ``i + 1`` keys, ``4 x 128`` operations per query, key
and head (scores and the weighted sum) over 20 heads. Every layer: the dense
unit, three ``2560 x 8192``. ``flops_per_row()`` is the mean over the traffic's
fixed multiset of document lengths, which is exact for every window that holds
whole passes; ``step_mfu``'s reader passes no traffic, so the lengths are read
from the one traffic file named here (``benchmark/tests/test_jamba_cpu.py``
holds every traffic of a configuration that counts with this module to that
multiset).

The selective scan (``scan_flops``, ``scan_bytes``) is counted apart, for its
roofline alone: per real token, Mamba layer, channel and state seven
operations (``Δ·A``, its ``exp``, the decay's product with the state, ``Δu·B``,
the sum, ``C·H`` and its share of the reduction over states); the least traffic
is ``u`` and ``z`` in bfloat16, ``Δ`` in float32 and ``B``, ``C`` in float32, and ``y``
out in bfloat16: 51,328 bytes a token, 1.03 ms a full page at 819 GB/s, which
bounds it (its operations are 0.01 ms at the bf16 peak). The layers are the
reference's statement of the model, not restated.
"""

import json
import os

from reference.jamba import LAYERS, PUBLISHED, is_attention

HIDDEN, INTERMEDIATE = 2560, 8192
HEADS, KV_HEADS, HEAD_DIM = 20, 1, 128
INNER, STATE, RANK = 5120, 16, 160
SCAN_OPS = 7  # a token, channel and state
TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "traffic", "corpus_transcripts_64k.json")


def is_mamba(layer: int) -> bool:
    return not is_attention(PUBLISHED, layer)


MAMBA_LAYERS = tuple(l for l in LAYERS if is_mamba(l))


def projection_flops(layer: int) -> int:
    if not is_mamba(layer):
        return 2 * (2 * HIDDEN * HEADS * HEAD_DIM + 2 * HIDDEN * KV_HEADS * HEAD_DIM)
    return 2 * (HIDDEN * 2 * INNER + INNER * (RANK + 2 * STATE) + RANK * INNER + INNER * HIDDEN)


def mlp_flops() -> int:
    return 3 * 2 * HIDDEN * INTERMEDIATE


def scan_flops(tokens: int) -> int:
    """The selective scan over ``tokens`` real tokens of one Mamba layer."""
    return SCAN_OPS * INNER * STATE * tokens


def scan_bytes(tokens: int) -> int:
    """``u``, ``z`` in and ``y`` out in bfloat16, ``Δ`` in float32, ``B`` and
    ``C`` in float32, each crossing the memory once: 51,328 a token and Mamba
    layer."""
    return tokens * (3 * 2 * INNER + 4 * INNER + 2 * 4 * STATE)


def attention_pairs(tokens: int) -> int:
    """(query, key) pairs of one document of ``tokens`` in an attention layer."""
    return tokens * (tokens + 1) // 2


def attention_core_flops(documents, layer: int) -> int:
    """Scores and weighted sums (128 wide each) of one layer over a page's
    documents; a Mamba layer has none."""
    if is_mamba(layer):
        return 0
    return 4 * HEAD_DIM * HEADS * sum(attention_pairs(n) for n in documents)


def document_lengths(traffic: str = TRAFFIC) -> list:
    with open(traffic) as f:
        t = json.load(f)
    k, lo, hi = int(t["documents"]), t["min_tokens"], t["max_tokens"]
    return [int(round(lo * (hi / lo) ** (i / (k - 1)))) for i in range(k)]


def product_flops_per_token() -> float:
    return sum(projection_flops(l) + mlp_flops() for l in LAYERS)


def attention_flops_per_token() -> float:
    docs = document_lengths()
    return sum(attention_core_flops(docs, l) for l in LAYERS) / sum(docs)


def flops_per_row() -> float:
    return product_flops_per_token() + attention_flops_per_token()

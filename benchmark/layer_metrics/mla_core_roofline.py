"""Latent attention's core's share of its roofline, from the device trace: the
least time the chip could take for the scores (192 wide: a head's own key and
the shared rope key) and weighted sums (128 wide) of the traced slice's whole
pages (``flops/sarvam.attention_core_flops`` over each page's own documents
and every layer held: work a kernel skips is not counted, work it only masks
is time it took for nothing) over the self time of the operations under
``…/attn/core`` in those pages. By scope, so it reads the same work whatever
implements it. Compute bounds it for documents over 870 tokens (every head
has keys and values of its own, so queries, keys, values and output crossing
the memory once are 5 % of the least time of a 16,384-token document and 11 %
of an 8,192-token one)."""

from flops import sarvam

from ._laguna import roofline

SCOPE = "/attn/core"


def work_of_page(documents):
    ops = sum(sarvam.attention_core_flops(documents, l) for l in sarvam.LAYERS)
    tokens = sum(documents)
    a_token = (sarvam.HEADS * (2 * sarvam.NOPE_DIM + sarvam.ROPE_DIM + 2 * sarvam.VALUE_DIM)
               + sarvam.ROPE_DIM)  # q, its rotated part, k, v, out; the one shared key
    return ops, 2 * tokens * a_token * len(sarvam.LAYERS)


def read(trace, stats, facts):
    return roofline(trace, stats, facts, SCOPE, work_of_page)

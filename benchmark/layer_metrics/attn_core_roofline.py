"""The attention core's share of its roofline, from the device trace: the
least time the chip could take for the scores and weighted sums of the traced
slice's whole pages (``flops/laguna.attention_core_flops`` over each page's
own documents and every layer's heads, extent and window: work a kernel skips
is not counted, work it only masks is time it took for nothing) over the self
time of the operations under ``…/attn/core`` in those pages. By scope, so it
reads the same work whatever implements it. Compute bounds it (query, key,
value and output cross the memory once: under 2 % of the least time)."""

from flops import laguna

from ._laguna import roofline

SCOPE = "/attn/core"


def work_of_page(documents):
    ops = sum(laguna.attention_core_flops(documents, l) for l in laguna.LAYERS)
    tokens = sum(documents)
    nbytes = sum(2 * tokens * laguna.HEAD_DIM * (2 * laguna.heads(l) + 2 * laguna.KV_HEADS)
                 for l in laguna.LAYERS)
    return ops, nbytes


def read(trace, stats, facts):
    return roofline(trace, stats, facts, SCOPE, work_of_page)

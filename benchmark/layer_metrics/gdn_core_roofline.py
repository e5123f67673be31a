"""The gated delta rule's share of its roofline, from the device trace: the
least time the chip could take for step 5 of the traced slice's whole pages
(``flops/qwen3_next``: three 128 × 128 products a real token and value head in
every linear layer held; ``q, k, v`` in and ``o`` out once in bfloat16, ``g``
and ``β`` in float32, 24,832 bytes a real token and layer) over the self time of
the operations under ``…/attn/gdn/core`` in those pages. By scope, so it reads
the same work whatever implements it: a chunked form that spends more
operations than the recurrence reads lower, never higher. Memory bounds it
(0.50 ms a full page and layer at 819 GB/s against 0.26 ms of products at the
peak): pads are walked and not counted."""

from flops import qwen3_next as counter

from ._laguna import roofline

SCOPE = "/attn/gdn/core"


def work_of_page(documents):
    tokens = sum(documents)
    linear = sum(1 for l in counter.LAYERS if not counter.is_full(l))
    return linear * counter.delta_rule_flops(tokens), linear * counter.delta_rule_bytes(tokens)


def read(trace, stats, facts):
    return roofline(trace, stats, facts, SCOPE, work_of_page)

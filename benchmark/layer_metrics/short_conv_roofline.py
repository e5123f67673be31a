"""The short-convolution core's share of its roofline, from the device trace:
the least time the chip could take for the core of the traced slice's whole
pages (``flops/lfm2``: seven operations a real token and channel in every conv
layer; ``B``, ``C``, ``u`` in and ``y`` out in bfloat16, 16,384 bytes a real token
and layer) over the self time of the operations under
``…/attn/short_conv/core`` in those pages. By scope, so it reads the same work
whatever implements it. Memory bounds it (0.33 ms a full page and layer at 819
GB/s): pads are walked and not counted."""

from flops import lfm2 as counter

from ._laguna import roofline

SCOPE = "/attn/short_conv/core"


def work_of_page(documents):
    tokens = sum(documents)
    layers = len(counter.CONV_LAYERS)
    return layers * counter.core_flops(tokens), layers * counter.core_bytes(tokens)


def read(trace, stats, facts):
    return roofline(trace, stats, facts, SCOPE, work_of_page)

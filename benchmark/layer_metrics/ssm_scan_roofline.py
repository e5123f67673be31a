"""The selective scan's share of its roofline, from the device trace: the least
time the chip could take for the scan of the traced slice's whole pages
(``flops/jamba``: seven operations a real token, channel and state in every
Mamba layer; ``u``, ``z`` in and ``y`` out in bfloat16, ``Δ``, ``B``, ``C`` in
float32, 51,328 bytes a real token and layer) over the self time of the
operations under ``…/attn/mamba/scan`` in those pages. By scope, so it reads
the same work whatever implements it. Memory bounds it (1.03 ms a full page
and layer at 819 GB/s): pads are walked and not counted."""

from flops import jamba as counter

from ._laguna import roofline

SCOPE = "/attn/mamba/scan"


def work_of_page(documents):
    tokens = sum(documents)
    layers = len(counter.MAMBA_LAYERS)
    return layers * counter.scan_flops(tokens), layers * counter.scan_bytes(tokens)


def read(trace, stats, facts):
    return roofline(trace, stats, facts, SCOPE, work_of_page)

"""What the linear-attention (Gated DeltaNet) layers take of the device's busy
time: everything under ``…/attn/gdn/`` (projections, the causal convolution,
the gates and unit rows, the delta rule's core, the gated norm, the output
product). ``attention_pct`` beside it is both kinds of token mixer together."""

from ._laguna import busy_share

SCOPES = ("/attn/gdn/",)


def read(trace, stats, facts):
    return busy_share(trace, SCOPES)

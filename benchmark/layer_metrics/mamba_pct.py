"""What the Mamba layers take of the device's busy time: everything under
``…/attn/mamba/`` (the in-projection, the causal convolution, the scan's
parameters, the selective scan, the out-projection). ``attention_pct`` beside it
is both kinds of token mixer together."""

from ._laguna import busy_share

SCOPES = ("/attn/mamba/",)


def read(trace, stats, facts):
    return busy_share(trace, SCOPES)

"""What the gated short-convolution mixers take of the device's busy time:
everything under ``…/attn/short_conv/`` (the in-projection, the core — ``B ⊙
u``, the three taps, ``C ⊙ w`` — and the out-projection). ``attention_pct``
beside it is both kinds of token mixer together."""

from ._laguna import busy_share

SCOPES = ("/attn/short_conv/",)


def read(trace, stats, facts):
    return busy_share(trace, SCOPES)

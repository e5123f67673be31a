"""Attention's share of the device's busy time: everything under
``…/attn`` (projections, rope, the core, the gate, the output product)."""

from ._laguna import busy_share

SCOPES = ("/attn/",)


def read(trace, stats, facts):
    return busy_share(trace, SCOPES)

"""What routing costs around the experts' products: the share of the device's
busy time under ``…/moe/route`` (router, softmax, top-k), ``…/moe/dispatch``
(the sort by expert and the gather of rows) and ``…/moe/combine`` (rows back
to tokens, weighted sum)."""

from ._laguna import busy_share

SCOPES = ("/moe/route", "/moe/dispatch", "/moe/combine")


def read(trace, stats, facts):
    return busy_share(trace, SCOPES)

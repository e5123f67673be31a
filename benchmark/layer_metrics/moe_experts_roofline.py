"""The routed experts' grouped products' share of their roofline, from the
device trace: the least time for the held assignments of the traced slice's
whole pages (``flops/laguna.expert_flops``; a page's assignments are its real
tokens times the window's own held assignments a token, ``routed_held`` over
``real_slots``, all sparse layers together) over the self time of the
operations under ``…/moe/experts`` in those pages. Bytes: every held expert's
three matrices once a layer and page, rows in and out; compute bounds it at
640 rows an expert (0.77 TFLOP against 1.5 GB a layer)."""

from flops import laguna

from ._laguna import roofline

SCOPE = "/moe/experts"


def read(trace, stats, facts):
    if not stats.get("routed_held") or not stats.get("real_slots"):
        return None
    held_a_token = stats["routed_held"] / stats["real_slots"]
    sparse = sum(1 for l in laguna.LAYERS if l != 0)

    def work_of_page(documents):
        rows = sum(documents) * held_a_token
        weights = sparse * laguna.EXPERTS_HELD * 3 * laguna.HIDDEN * laguna.EXPERT_WIDTH * 2
        moved = rows * 2 * (2 * laguna.HIDDEN + 3 * laguna.EXPERT_WIDTH)
        return laguna.expert_flops(rows), weights + moved

    return roofline(trace, stats, facts, SCOPE, work_of_page)

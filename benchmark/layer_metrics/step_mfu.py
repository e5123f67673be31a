"""The whole step's share of the chip's peak: operations the rows completed
in the window need (counted from the configuration's shapes by
``flops/<name>.py``, never from the program) over window wall time x chips x
the bf16 peak of ``peaks.json``. fp32 steps run as bf16 MXU passes by default,
so the bf16 peak is the ceiling either way."""

import importlib

from peaks import peaks_for


def read(trace, stats, facts):
    if not facts["rows"]:
        return None
    counter = importlib.import_module("flops." + facts["conf"]["flops"])
    peak = peaks_for(facts["device_kind"], facts["peaks"])["bf16_flops_per_s"]
    ops = counter.flops_per_row() * facts["rows"]
    return 100.0 * ops / (facts["wall_s"] * facts["chips"] * peak)

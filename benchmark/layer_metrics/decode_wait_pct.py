"""Share of the window's wall time the consumer thread spent blocked on the
program's 'decode' stage (StageClock)."""

from ._stage import stage_share


def read(trace, stats, facts):
    return stage_share(stats, facts, "decode")

"""Seconds of set-up that are the program's: from its first ``construct``
record's start to the window's ``run`` start (construction, checkpoint
load, warm-up). ``setup_s`` less this is the harness's part: imports, the
chip's start-up, drawing and writing the weights and the corpus."""

from ._setup import setup_of


def read(trace, stats, facts):
    setup = setup_of(stats)
    if setup is None:
        return None
    return (setup[2] - setup[1]) / 1e9

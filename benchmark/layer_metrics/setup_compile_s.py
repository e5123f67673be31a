"""Seconds the program's set-up spent turning Python into loaded
executables: the ``compile``, ``trace`` and ``lower`` records (JAX's own
events), wherever they lie in it — the construction's eager casts and
stacks, the warm-up's first calls — merged where they overlap, up to the
window's start."""

from ._setup import compiling, setup_of


def read(trace, stats, facts):
    setup = setup_of(stats)
    if setup is None:
        return None
    return sum(e - s for s, e in compiling(setup[0])) / 1e9

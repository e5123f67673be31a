"""Seconds the program's set-up spent loading checkpoints: its
``load_weights`` records summed (the read, the host's casts and stacks, the
``place_wait`` for the device copies), less the compiles, traces and
lowerings inside them (the eager casts and stacks), which
``setup_compile_s`` counts: the two add, they do not overlap."""

from ._setup import compiling, overlap, setup_of


def read(trace, stats, facts):
    setup = setup_of(stats)
    if setup is None:
        return None
    records = setup[0]
    pieces = compiling(records)
    return sum(r["end"] - r["start"] - overlap(pieces, r["start"], r["end"])
               for r in records if r["name"] == "load_weights") / 1e9

"""Programs the set-up compiled, not loaded: ``compile`` records with
``cache=miss`` before the window (a hit is a persistent-cache load). A slow
set-up with many is a cold cache; the records name the programs."""

from ._setup import setup_of


def read(trace, stats, facts):
    setup = setup_of(stats)
    if setup is None:
        return None
    return sum(1 for r in setup[0] if r["name"] == "compile" and r["ids"].get("cache") == "miss")

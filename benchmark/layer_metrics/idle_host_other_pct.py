"""Share of the traced span in which no device operation ran AND the
consumer thread was in anything else: its own Python (the self time of
``extract`` and ``run``), ``launch``, ``device``, ``finalize``,
``write_reap``, or no span at all. ``device_idle_pct`` less the other two
shares, so the three add up to it (``_spans``)."""

from ._spans import idle_shares


def read(trace, stats, facts):
    shares = idle_shares(trace, stats, facts)
    return None if shares is None else shares["other"]

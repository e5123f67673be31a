"""Share of the traced span in which no device operation ran AND the
consumer thread's innermost open span was ``stage`` or ``put`` — copying rows
into a staging buffer, waiting for one, or in ``device_put``: the device idle
for want of a page on it (``_spans``)."""

from ._spans import idle_shares


def read(trace, stats, facts):
    shares = idle_shares(trace, stats, facts)
    return None if shares is None else shares["transfer"]

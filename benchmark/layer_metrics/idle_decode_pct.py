"""Share of the traced span in which no device operation ran AND the
consumer thread's innermost open span was ``pull`` — blocked in ``next()`` on
the frame stream: the device idle for want of decoded frames. The program's
span records against the trace's gaps, on one clock (``_spans``). With
``idle_transfer_pct`` and ``idle_host_other_pct`` it adds up to
``device_idle_pct``."""

from ._spans import idle_shares


def read(trace, stats, facts):
    shares = idle_shares(trace, stats, facts)
    return None if shares is None else shares["decode"]

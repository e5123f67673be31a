"""Share of the traced slice in which no operation ran on the device:
1 - union of the device's operation intervals over the slice's length. Both
come from the trace's own clock: the slice is the span from the first traced
operation's start to the last one's end (``trace_reduce``), so the union
cannot overrun it and nothing is clamped. Idle time before the first and
after the last traced operation is outside the span: at most one gap."""


def read(trace, stats, facts):
    if not trace.get("span_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["span_s"])

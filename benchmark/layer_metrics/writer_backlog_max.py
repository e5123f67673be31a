"""Deepest backlog of the output writer in the window: its queue plus the
job in hand, sampled by the program at every submit (a counter of
``AsyncOutputWriter``, handed out in ``_pack_stats``)."""


def read(trace, stats, facts):
    return (stats or {}).get("writer_backlog_max")

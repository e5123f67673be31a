"""Seconds the writer spent on a video: the ``write`` spans of the whole
window (on the ``output-writer`` thread, or inline) summed, over the
``videos_written`` counter. Read from the program's span records alone."""

from ._spans import records_of


def read(trace, stats, facts):
    records = records_of(stats)
    written = (stats or {}).get("videos_written")
    if records is None or not written:
        return None
    total_ns = sum(r["end"] - r["start"] for r in records
                   if r["name"] == "write" and r.get("end") is not None)
    return total_ns / 1e9 / written

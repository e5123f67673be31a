"""Real rows over dispatched rows of every page the window sent to the
device: an exact count of the program's packer."""


def read(trace, stats, facts):
    if not stats.get("dispatched_slots"):
        return None
    return 100.0 * stats["real_slots"] / stats["dispatched_slots"]

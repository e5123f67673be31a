"""Shared by the three StageClock readers: host seconds the consumer thread
spent blocked on one stage (``_pack_stats["stage_seconds"]``, filled in the
traced run only) as a share of the window's wall time."""


def stage_share(stats, facts, stage):
    seconds = (stats.get("stage_seconds") or {}).get(stage)
    if seconds is None:
        return None
    return 100.0 * seconds / facts["wall_s"]

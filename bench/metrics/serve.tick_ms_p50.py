"""Median host wall time of one decode tick (the engine's ``on_tick``
report) over the ticks of the window."""
from bench.harness import nearest_rank


def read(record):
    sv = record.get("serve")
    if sv is None:
        return None
    ticks = [w for now, w in sv["ticks"] if now <= sv["window_s"]]
    if not ticks:
        return None
    return 1000.0 * nearest_rank(ticks, 0.5)

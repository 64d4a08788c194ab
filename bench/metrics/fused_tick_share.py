"""Fused epoch: share of the window's simulated ticks that the fused
core ran, against the per-tick steps it fell back to (``EpochStats``
deltas over the window: ``fused_ticks`` and ``unfused``)."""


def read(ctx):
    fused = ctx.counters.get("fused_ticks")
    if fused is None:
        return None
    total = fused + ctx.counters.get("unfused", 0)
    return 100.0 * fused / total if total else None

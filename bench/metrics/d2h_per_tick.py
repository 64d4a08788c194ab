"""Host control: device-to-host reads per simulated network tick."""


def read(ctx):
    ticks = ctx.counters.get("ticks", 0)
    if not ticks:
        return None
    return ctx.counters["d2h"] / ticks

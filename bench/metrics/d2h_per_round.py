"""Host control: device-to-host reads per served incast round (the
benchmark's transfer counter over the window's rounds)."""


def read(ctx):
    rounds = ctx.counters.get("rounds")
    if not rounds or "d2h" not in ctx.counters:
        return None
    return ctx.counters["d2h"] / rounds

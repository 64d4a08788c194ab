"""jit dispatch: programs lowered inside the measured window (a new
shape or a retrace; 0 when set-up warmed every shape)."""


def read(ctx):
    return ctx.counters.get("lowerings")

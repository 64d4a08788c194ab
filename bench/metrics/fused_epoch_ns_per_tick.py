"""Fused epoch: device time per simulated tick over the first ``TICKS``
ticks of the window's first epoch program (``jit_fused_epoch``).

The TPU profiler stops recording a session after about 6.3 million op
events, and one tick of this cell's epoch runs about 107,000 ops, so a
traced window holds the first 58 or so ticks of its first epoch and
nothing after them.  A fixed count of ticks keeps both sides of a
comparison on the same ticks (the window's first round is the same
round, with the same payloads, on every commit), wherever the cap
falls.  Ticks are found in the trace itself: each tick of the star
fabric begins by sorting the wire (``fused.WIRE_SORTS_PER_TICK`` sorts,
which nothing else in the program adds), so tick ``k`` (from 0) begins
at sort ``k * WIRE_SORTS_PER_TICK``.  The reading is the time from the
epoch's start to the first sort of tick ``TICKS``, over ``TICKS``;
None where the trace holds fewer ticks or the program lacks the count.
"""

MODULE = "jit_fused_epoch"
SORT = "%sort"
TICKS = 40


def first_ticks(trace, sorts_per_tick, ticks=TICKS):
    """Device seconds of the first ``ticks`` ticks of the first epoch
    program in the trace, or None where it holds fewer."""
    mods = sorted((s, e) for n, s, e in trace.modules if MODULE in n)
    if not mods:
        return None
    ms, me = mods[0]
    starts = sorted(s for n, s, e in trace.ops
                    if n.startswith(SORT) and ms <= s and e <= me)
    k = ticks * sorts_per_tick
    if len(starts) <= k:
        return None
    return (starts[k] - ms) * 1e-9


def read(ctx):
    from repro.core import fused
    per_tick = getattr(fused, "WIRE_SORTS_PER_TICK", None)
    if ctx.trace is None or not per_tick:
        return None
    seconds = first_ticks(ctx.trace, per_tick)
    return None if seconds is None else seconds * 1e9 / TICKS

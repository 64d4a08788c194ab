"""Service chain: device time of the chain's own ops around its kernels
(the layout passes, converts and reductions between and after them) per
row handed to the chain.

The ops counted are those that run inside an interval of the chain's
program (``jit_service_chain``) and are not a Pallas kernel (an HLO
``custom-call``, or an instruction the kernel's ``name`` names
``*_pallas``); only the ops' names and intervals are read.  Rows are
the rows of the chain calls the cell records (``dpi_mlp``'s packet
count, padding included), else the packets handed to the RX engine
(``rx_pkts``: the line-rate cells hand every batch to the chain
whole).  As with ``rx_engine_ns_per_pkt``, a served cell's traced
window also holds the calls of its drain, which are not counted as
rows.
"""
import bisect
import re

MODULE = "jit_service_chain"
KERNEL_NAME = re.compile(r"_pallas(\.\d+)?$")


def is_kernel(op: str) -> bool:
    """``op``: an op event's name, its HLO text (``%name.N = shape
    opcode(operands), ...``)."""
    head, _, rest = op.partition(" = ")
    return bool(KERNEL_NAME.search(head)) or "custom-call(" in rest


def chain_rows(ctx):
    dpi = ctx.calls.get("dpi_mlp")
    if dpi:
        return sum(rows for rows, _ in dpi)
    return ctx.counters.get("rx_pkts", 0)


def glue_seconds(trace):
    """Device seconds of non-kernel ops inside the chain's programs, or
    None where the trace holds no such program."""
    mods = sorted((s, e) for n, s, e in trace.modules if MODULE in n)
    if not mods:
        return None
    starts = [s for s, _ in mods]
    total = 0
    for name, s, e in trace.ops:
        if is_kernel(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= mods[i][1]:
            total += e - s
    return total * 1e-9


def read(ctx):
    if ctx.trace is None:
        return None
    rows = chain_rows(ctx)
    seconds = glue_seconds(ctx.trace)
    if not rows or seconds is None:
        return None
    return seconds * 1e9 / rows

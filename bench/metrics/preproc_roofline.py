"""DLRM preprocessing kernel: share of its roofline (bytes bound)."""
from bench.harness import roofline_share
from bench.metrics import kernel_counts

# the Pallas call as the TPU trace names it: the jitted wrapper's name
PATTERNS = ("%preproc_pallas",)


def read(ctx):
    calls = ctx.calls.get("preproc")
    if ctx.trace is None or not calls:
        return None
    seconds, n = ctx.trace.op_seconds(PATTERNS)
    if not n:
        return None
    flops, nbytes = kernel_counts.need("preproc", calls)
    return roofline_share(seconds, flops, nbytes, ctx.peak)

"""ML-DPI kernel: share of its roofline (the larger of its FLOPs over
the bf16 peak and its bytes over HBM bandwidth)."""
from bench.harness import roofline_share
from bench.metrics import kernel_counts

# the Pallas call as the TPU trace names it: the jitted wrapper's name
PATTERNS = ("%dpi_scores_pallas",)


def read(ctx):
    calls = ctx.calls.get("dpi_mlp")
    if ctx.trace is None or not calls:
        return None
    seconds, n = ctx.trace.op_seconds(PATTERNS)
    if not n:
        return None
    flops, nbytes = kernel_counts.need("dpi_mlp", calls)
    return roofline_share(seconds, flops, nbytes, ctx.peak)

"""RX engine: device time of the ``rx_pipeline_batched`` program per
packet it was handed."""

PATTERNS = ("rx_pipeline_batched",)


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("rx_pkts"):
        return None
    seconds, n = ctx.trace.module_seconds(PATTERNS)
    if not n:
        return None
    return seconds * 1e9 / ctx.counters["rx_pkts"]

"""Share of the admission prefill's rows that were padding over the
traced window: 1 - prefill_rows / prefill_bucket_rows, from the engine's
own counters (the real prompt rows prefilled, and the rows of the
power-of-two buckets that computed them)."""
from bench.lib import spans


def read(ctx):
    return spans.pad_share(*ctx.stats)

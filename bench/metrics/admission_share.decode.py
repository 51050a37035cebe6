"""Share of the engine's ticks spent admitting: the summed
``serve.admit`` spans (prefix match, batch-1 prefill, the host's wait on
it, the insert into the pool) over the summed ``serve.tick`` spans
wholly inside the traced window (``lib/spans.py``)."""
from bench.lib import spans


def read(ctx):
    return spans.admission_share(
        spans.ticks(spans.of_run(), ctx.trace.window))

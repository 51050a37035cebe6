"""Host milliseconds an engine tick: over the ``serve.tick`` spans
wholly inside the traced window, the mean of each tick's length less its
``serve.decode_wait`` and ``serve.prefill_wait`` children, the time the
host spent blocked on the device (``lib/spans.py``)."""
from bench.lib import spans


def read(ctx):
    return spans.host_ms_per_tick(
        spans.ticks(spans.of_run(), ctx.trace.window))

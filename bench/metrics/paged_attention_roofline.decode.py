"""Share of its roofline that ``nq_paged_attention`` reaches in the
decode step: per traced decode step, the larger of its FLOPs over peak
and its bytes over HBM bandwidth, where the bytes are the K and V rows of
each decoded slot's real context (tracked by the harness), over the
kernel's device time in ``decode_fn`` programs of the trace."""
from bench.lib import spec

step = spec.load_module("work", "decode_step")


def read(ctx):
    if not ctx.peaks:
        return None
    t = ctx.trace.kernel_s("nq_paged_attention", "decode_fn")
    steps = [s for s in ctx.traced_steps() if s.decode_tokens]
    if t <= 0 or not steps:
        return None
    bound = 0.0
    for s in steps:
        f, b = step.paged_work(ctx.mc, s.kv_rows, s.decode_tokens)
        bound += max(f / ctx.peaks["bf16_flops"],
                     b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t

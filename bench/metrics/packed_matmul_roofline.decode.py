"""Share of its roofline that ``nq_packed_matmul`` reaches in the decode
step: the least time a chip needs for the traced decode steps'
packed-matmul calls (per call, the larger of FLOPs over peak bf16
FLOP/s and bytes over HBM bandwidth, from ``work/``: the two calls of
each linear whose rank the fused kernel cannot hold, at the share one
chip computes) over the kernel's device time a chip in ``decode_fn``
programs of the trace."""
from bench.lib import spec

step = spec.load_module("work", "decode_step")


def read(ctx):
    if not ctx.peaks:
        return None
    t = ctx.trace.kernel_s("nq_packed_matmul", "decode_fn")
    n = ctx.traced_stat("decode_steps")
    if t <= 0 or n <= 0:
        return None
    calls = step.packed_calls(ctx.mc, ctx.linears(), ctx.cell["max_batch"],
                              ctx.chips)
    if not calls:
        return None
    bound = sum(max(f / ctx.peaks["bf16_flops"],
                    b / ctx.peaks["hbm_bytes_per_s"]) for f, b in calls)
    return 100.0 * n * bound / t

"""Share of its roofline that ``nq_fused_lowrank_matmul`` reaches in the
decode step: the least time a chip needs for the traced decode steps'
fused-matmul calls (the larger of FLOPs over peak bf16 FLOP/s and bytes
over HBM bandwidth, from ``work/``: only the linears the fused kernel
runs, each at the share one chip computes) over the kernel's device
time a chip in ``decode_fn`` programs of the trace."""
from bench.lib import spec

step = spec.load_module("work", "decode_step")


def read(ctx):
    if not ctx.peaks:
        return None
    t = ctx.trace.kernel_s("nq_fused_lowrank_matmul", "decode_fn")
    n = ctx.traced_stat("decode_steps")
    if t <= 0 or n <= 0:
        return None
    f, b = step.fused_work(ctx.mc, ctx.linears(), ctx.cell["max_batch"],
                           ctx.chips)
    bound = max(f / ctx.peaks["bf16_flops"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * n * bound / t

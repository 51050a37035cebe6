"""Model FLOP/s utilization of decoding: the model FLOPs of every token
decoded in the traced window (packed linears, head, attention over each
token's real context; ``work/decode_step.py``) over the traced window's
length and the peak bf16 FLOP/s of the cell's chips. Prefill tokens are
not counted."""
from bench.lib import spec

step = spec.load_module("work", "decode_step")


def read(ctx):
    if not ctx.peaks:
        return None
    lin = ctx.linears()
    lo, hi = ctx.traced
    flops = 0
    for r in ctx.driver.records.values():
        for t, rows in zip(r.times[1:], r.decode_ctx):
            if lo <= t <= hi:
                flops += step.token_flops(ctx.mc, lin, rows)
    if flops <= 0:
        return None
    return 100.0 * flops / (hi - lo) / (ctx.peaks["bf16_flops"] * ctx.chips)

"""Share of decode-slot steps that carried a request over the traced
window: 1 - wasted_slot_steps / (decode_steps x max_batch), from the
engine's own counters."""


def read(ctx):
    steps = ctx.traced_stat("decode_steps")
    if steps <= 0:
        return None
    wasted = ctx.traced_stat("wasted_slot_steps")
    return 100.0 * (1.0 - wasted / (steps * ctx.cell["max_batch"]))

"""Share of the block tables' pages that the paged gather walked in the
traced window's decode steps: 100 x gather_pages_live /
gather_pages_table, from the engine's own counters (each slot's linear
table up to its live bound, inactive slots included, over every slot's
whole table). None where the engine has no such counters."""


def read(ctx):
    try:
        table = ctx.traced_stat("gather_pages_table")
        live = ctx.traced_stat("gather_pages_live")
    except KeyError:
        return None
    if table <= 0:
        return None
    return 100.0 * live / table

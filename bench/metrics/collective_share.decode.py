"""Share of the decode step's device time spent in collectives: on each
chip, the time in which a collective op (by its HLO opcode: all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all; a psum's op
is named ``psum.N``) is running inside ``decode_fn`` programs, over
those programs' device time, summed over the chips. An asynchronous
collective is one interval, from its ``-start`` op to its ``-done`` op,
so the pair counts once; overlapping collectives count once."""
from bench.lib import trace

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
MODULE = "decode_fn"


def kind(opcode: str):
    """(kind, 'start' | 'done' | None) of a collective op, else None."""
    for k in KINDS:
        if opcode == k:
            return k, None
        for part in ("start", "done"):
            if opcode == f"{k}-{part}":
                return k, part
    return None


def intervals(ops) -> list:
    """[start, end) of each collective among one chip's ops: a sync op
    its own, an async pair from its start op to its done op."""
    out, open_ = [], {}
    for o in sorted(ops, key=lambda o: o.start):
        kd = kind(o.opcode)
        if kd is None:
            continue
        k, part = kd
        if part == "start":
            open_.setdefault(k, []).append((o.start, o.start + o.dur))
        elif part == "done" and open_.get(k):
            out.append((open_[k].pop(0)[0], o.start + o.dur))
        else:
            out.append((o.start, o.start + o.dur))
    for starts in open_.values():      # a start whose done fell outside
        out.extend(starts)
    return out


def read(ctx):
    s = ctx.trace
    num = den = 0
    found = False
    for dev in {m[3] for m in s.modules}:
        runs = [(st, st + d) for n, st, d, dv in s.modules
                if dv == dev and MODULE in n]
        iv = intervals([o for o in s.ops
                        if o.device == dev and MODULE in o.module])
        found = found or bool(iv)
        den += sum(e - b for b, e in runs)
        num += sum(trace.union_ns(iv, b, e) for b, e in runs)
    if den <= 0 or not found:
        return None
    return 100.0 * num / den

"""Reduction of a JAX profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData`` and nothing else. Device planes are
``/device:TPU:<n>``; on each, the ``XLA Modules`` line holds one event
per executed program (named after the jitted function) and the
``XLA Ops`` line one event per operation, named by its HLO text, whose
instruction name for a Pallas kernel is the ``name`` it was given
(``%nq_paged_attention.8 = ...``). The host plane holds the harness's own
spans (``bench.*``).

The traced window runs from the first to the last host span of the
harness. Busy time is the union of the operation intervals of a device
inside that window, averaged over the devices.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
TOP_N = 10
# ops whose events enclose the events of the ops they run (a layer scan
# is one `while`); busy time takes the union, the breakdown skips them
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start: int                      # ns
    dur: int                        # ns
    device: int


@dataclasses.dataclass
class Summary:
    ops: List[Op]
    modules: List[Tuple[str, int, int, int]]    # (name, start, dur, device)
    spans: List[Tuple[str, int, int]]           # host (name, start, dur)
    window: Tuple[int, int]                     # ns
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        per_dev: Dict[int, List[Tuple[int, int]]] = {}
        for o in self.ops:
            per_dev.setdefault(o.device, []).append((o.start, o.start + o.dur))
        total = sum(union_ns(iv, *self.window) for iv in per_dev.values())
        return total / max(self.n_devices, 1) / 1e9

    def kernel_s(self, kernel: str, module: str) -> float:
        """Device seconds of ops named `kernel` inside programs whose
        name holds `module`, averaged over the devices."""
        ns = sum(o.dur for o in self.ops
                 if o.name.startswith(kernel) and module in o.module)
        return ns / max(self.n_devices, 1) / 1e9

    def module_runs(self, module: str) -> List[Tuple[int, int]]:
        """(start, dur) of each run of programs named with `module`, on
        the first device."""
        first = min((m[3] for m in self.modules), default=0)
        return [(s, d) for n, s, d, dev in self.modules
                if module in n and dev == first]

    def breakdown(self) -> dict:
        by_op: Dict[str, int] = {}
        for o in self.ops:
            if o.name.split(".")[0] in CONTAINERS:
                continue
            by_op[o.name] = by_op.get(o.name, 0) + o.dur
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP_N]
        return {"device_ops": [[k, v / 1e9 / max(self.n_devices, 1)]
                               for k, v in top],
                "idle_gaps": [[k, v / 1e9] for k, v in self.idle_gaps()]}

    def idle_gaps(self) -> List[Tuple[str, int]]:
        """The longest gaps between device ops (first device), each named
        by the harness span the host was in at the gap's middle."""
        first = min((o.device for o in self.ops), default=0)
        iv = sorted((o.start, o.start + o.dur) for o in self.ops
                    if o.device == first)
        gaps, end = [], self.window[0]
        for s, e in iv:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if self.window[1] > end:
            gaps.append((end, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:TOP_N]:
            mid = (s + e) // 2
            name = "host: no harness span"
            for n, hs, hd in self.spans:
                if hs <= mid <= hs + hd:
                    name = f"host: {n}"
            out.append((name, e - s))
        return out


def op_name(text: str) -> str:
    """An op event's name: the TPU trace names an op by its HLO text,
    ``%nq_paged_attention.8 = bf16[...] custom-call(...)``; the name is
    what stands before `` = ``, without the ``%``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of [s, e) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def options():
    """Profiler options of a traced run: device and host trace events
    (TraceMe, which carries the harness's spans), no Python function
    tracing (it would slow the host path it measures) and no HLO
    protos."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    o.enable_hlo_proto = False
    return o


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split("/")[0] or 0)
            n_dev += 1
            lines = {ln.name: ln for ln in plane.lines}
            mods = []
            if MODULES_LINE in lines:
                for ev in lines[MODULES_LINE].events:
                    mods.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns), dev))
            modules.extend(mods)
            mods_sorted = sorted(mods, key=lambda m: m[1])
            if OPS_LINE in lines:
                j = 0
                for ev in sorted(lines[OPS_LINE].events,
                                 key=lambda e: e.start_ns):
                    s = int(ev.start_ns)
                    while j + 1 < len(mods_sorted) and \
                            mods_sorted[j + 1][1] <= s:
                        j += 1
                    mod = ""
                    if mods_sorted and mods_sorted[j][1] <= s < \
                            mods_sorted[j][1] + mods_sorted[j][2]:
                        mod = mods_sorted[j][0]
                    ops.append(Op(op_name(ev.name), mod, s,
                                  int(ev.duration_ns), dev))
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)))
    if spans:
        lo = min(s for _, s, _ in spans)
        hi = max(s + d for _, s, d in spans)
    else:
        lo = min((o.start for o in ops), default=0)
        hi = max((o.start + o.dur for o in ops), default=0)
    return Summary(ops, modules, spans, (lo, hi), n_dev)


def reduce(trace_dir) -> Summary:
    return load(find_xplane(trace_dir))


def describe(path: str, n: int = 8) -> str:
    """A look at a trace's planes, lines and first events, for bring-up."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for ln in plane.lines:
            evs = list(ln.events)
            out.append(f"  line {ln.name!r}: {len(evs)} events")
            for ev in evs[:n]:
                stats = {}
                try:
                    stats = {k: str(v)[:80] for k, v in ev.stats}
                except Exception as e:     # stats differ by version
                    stats = {"?": repr(e)}
                out.append(f"    {ev.name[:100]!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {stats}")
    return "\n".join(out)

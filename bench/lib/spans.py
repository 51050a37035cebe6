"""The serving engine's own host spans in a profiler trace.

``InferenceEngine.step`` wraps each tick and its parts in
``jax.profiler.TraceAnnotation`` spans named ``serve.*`` (docs/serving.md
§Observability): ``serve.tick`` around the tick, and inside it
``serve.reap``, ``serve.admit`` (``serve.prefill``, ``serve.insert``,
``serve.prefill_wait``), ``serve.reserve_pages`` (``serve.preempt``),
``serve.decode_dispatch``, ``serve.decode_wait``, ``serve.emit`` and
``serve.callbacks``. They land on the trace's host plane, on the clock of
the device planes, with their args (``step``, ``uid``, ``slot``,
``rows``, ``bucket``) as event stats; the nesting on a host thread gives
each span its parent.

This module reads them and reduces them to what the engine's per-layer
metrics read: the host's part of a tick, the share of the ticks spent
admitting, the padding of admission prefill, and the device's idle time
under each span. A trace of a program without these spans holds none,
and each reader then gives None.

    python3 -m bench.lib.spans [trace_dir]

prints that reduction for a trace directory (the harness's by default)
as one JSON object.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

from bench.lib import spec, trace

PREFIX = "serve."
TICK = "serve.tick"
ADMIT = "serve.admit"
WAITS = ("serve.decode_wait", "serve.prefill_wait")
DECODE = ("serve.reserve_pages", "serve.decode_dispatch",
          "serve.decode_wait", "serve.emit", "serve.spec_cycle")
# where bench/lib/harness.py writes the trace of a `--trace 1` run
RUN_TRACE_DIR = spec.BENCH_DIR / ".runs" / "trace"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int                      # ns
    dur: int                        # ns
    thread: str                     # the host line (one per thread)
    args: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Tick:
    span: Span
    children: List[Span]            # every serve.* span inside it


def load(path: str) -> List[Span]:
    """Every ``serve.*`` event of the trace's host planes, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name, int(ev.start_ns),
                                    int(ev.duration_ns), ln.name,
                                    dict(ev.stats)))
    out.sort(key=lambda s: (s.start, -s.dur))
    return out


@functools.lru_cache(maxsize=1)
def _load_cached(path: str, mtime: float) -> Tuple[Span, ...]:
    return tuple(load(path))


def of_run(trace_dir=None) -> List[Span]:
    """The spans of the run's trace (`trace_dir`, else the harness's
    ``RUN_TRACE_DIR`` as it stands when called), read once per trace
    file; none where the run left no trace."""
    try:
        path = trace.find_xplane(trace_dir or RUN_TRACE_DIR)
    except FileNotFoundError:
        return []
    return list(_load_cached(path, os.path.getmtime(path)))


def ticks(spans: List[Span], window: Tuple[int, int]) -> List[Tick]:
    """The ``serve.tick`` spans wholly inside `window` (ns), each with
    the spans nested in it on its thread."""
    lo, hi = window
    out = []
    for t in spans:
        if t.name != TICK or t.start < lo or t.end > hi:
            continue
        kids = [s for s in spans if s is not t and s.thread == t.thread
                and t.start <= s.start and s.end <= t.end]
        out.append(Tick(t, kids))
    return out


def _total(tks: List[Tick], names) -> int:
    return sum(c.dur for t in tks for c in t.children if c.name in names)


def host_ms_per_tick(tks: List[Tick]) -> Optional[float]:
    """Mean milliseconds a tick in which the host was not waiting on the
    device: the tick less its ``serve.decode_wait`` and
    ``serve.prefill_wait`` children."""
    if not tks:
        return None
    host = sum(t.span.dur for t in tks) - _total(tks, WAITS)
    return host / len(tks) / 1e6


def admission_share(tks: List[Tick]) -> Optional[float]:
    """Percent of the ticks' time spent in ``serve.admit``."""
    whole = sum(t.span.dur for t in tks)
    if whole <= 0:
        return None
    return 100.0 * _total(tks, (ADMIT,)) / whole


def pad_share(stats0: dict, stats1: dict) -> Optional[float]:
    """Percent of the admission prefill's bucket rows that were padding,
    from the engine counters ``prefill_rows`` and
    ``prefill_bucket_rows`` read at two times; None where the engine
    has no such counters or prefilled nothing in between."""
    try:
        rows = stats1["prefill_rows"] - stats0["prefill_rows"]
        bucket = stats1["prefill_bucket_rows"] - stats0["prefill_bucket_rows"]
    except KeyError:
        return None
    if bucket <= 0:
        return None
    return 100.0 * (1.0 - rows / bucket)


def innermost(tick: Tick) -> List[Tuple[int, int, str]]:
    """The tick cut into (start, end, name) pieces, each named by the
    innermost span the host was in; ``serve.tick`` is its self time."""
    spans = [tick.span] + tick.children
    cuts = sorted({s.start for s in spans} | {s.end for s in spans})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        inner = max((s for s in spans if s.start <= mid < s.end),
                    key=lambda s: (s.start, -s.dur))
        out.append((a, b, inner.name))
    return out


def idle_by_span(summary: trace.Summary, tks: List[Tick]) -> Dict[str, int]:
    """Nanoseconds inside the ticks in which no operation ran on the
    (first) device, by the innermost span the host was in."""
    first = min((o.device for o in summary.ops), default=0)
    busy = [(o.start, o.start + o.dur) for o in summary.ops
            if o.device == first]
    out: Dict[str, int] = {}
    for t in tks:
        for a, b, name in innermost(t):
            idle = (b - a) - trace.union_ns(busy, a, b)
            if idle > 0:
                out[name] = out.get(name, 0) + idle
    return out


def report(summary: trace.Summary, spans: List[Span]) -> dict:
    """A traced run's ticks, in milliseconds a tick: the tick, its
    admission and decode parts, the host's part, and the device's idle
    time by innermost span, with the share of that idle time a named
    child span holds."""
    tks = ticks(spans, summary.window)
    if not tks:
        return {"ticks": 0}
    n = len(tks)
    idle = idle_by_span(summary, tks)
    idle_all = sum(idle.values())
    per = {k: v / n / 1e6 for k, v in sorted(idle.items(),
                                                key=lambda kv: -kv[1])}
    admits = [c for t in tks for c in t.children if c.name == ADMIT]
    return {
        "ticks": n,
        "admissions": len(admits),
        "tick_ms": sum(t.span.dur for t in tks) / n / 1e6,
        "admit_ms": _total(tks, (ADMIT,)) / n / 1e6,
        "decode_ms": _total(tks, DECODE) / n / 1e6,
        "host_ms": host_ms_per_tick(tks),
        "admission_share": admission_share(tks),
        "idle_ms": idle_all / n / 1e6,
        "idle_ms_by_span": per,
        "idle_named_share": (100.0 * (1 - idle.get(TICK, 0) / idle_all)
                             if idle_all else None),
    }


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    trace_dir = argv[0] if argv else RUN_TRACE_DIR
    path = trace.find_xplane(trace_dir)
    print(json.dumps(report(trace.load(path), load(path))))


if __name__ == "__main__":
    main()

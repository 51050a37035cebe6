"""One run of one cell: set up, measure, check, print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the end of the window and reports the
per-layer metrics. Both check the served tokens against the plain
reference (``check.py``) and print each number compared beside its
limit, last, on standard error and under ``checks`` in the result line.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time

import numpy as np

from bench.lib import check, device, metrics, serve, spec
from bench.lib import trace as btrace
from bench.lib import traffic as btraffic

TRACE_MAX_S = 12.0


def _pct(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def end_to_end(driver, t0, t1, t_start_proc):
    """Every end-to-end metric the harness can read from this run; the
    cell reports those BENCHMARK.json gives it."""
    recs = list(driver.records.values())
    out = {"setup_s": t0 - t_start_proc}
    toks = sum(sum(1 for t in r.times if t0 <= t <= t1) for r in recs)
    out["decode_tok_s"] = toks / (t1 - t0)
    due = [r for r in recs if t0 <= r.due < t1]
    ttft = [(r.times[0] - r.due) * 1e3 for r in due if r.times]
    gaps = [(b - a) * 1e3 for r in due
            for a, b in zip(r.times, r.times[1:]) if b <= t1]
    out["ttft_p95_ms"] = _pct(ttft, 95)
    out["itl_p95_ms"] = _pct(gaps, 95)
    return out


def window_compiles(stats0: dict, stats1: dict) -> int:
    """Programs the engine traced between two readings of its stats."""
    return (stats1["prefill_traces"] - stats0["prefill_traces"]
            + stats1["decode_traces"] - stats0["decode_traces"])


def outcome(driver, loop: str, t0, t1):
    """(attempted, failed): requests sent by the window's close, those the
    ramp started included; a failure is a request that ended failed,
    expired or cancelled, or, in an open loop, one due in the window that
    never got its first token."""
    recs = [r for r in driver.records.values() if r.submitted <= t1]
    if loop == "open":
        recs = [r for r in recs if t0 <= r.due < t1]
    bad = [r for r in recs if r.status in ("failed", "expired", "cancelled")
           or (loop == "open" and not r.times)]
    return len(recs), len(bad)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        bench=None, conf=None, cellp=None, mix=None, chips=None,
        need_accelerator=True, t_start=None, out=None, err=None):
    t_start = t_start if t_start is not None else time.perf_counter()
    wall_start = time.time() - (time.perf_counter() - t_start)
    out = out or sys.stdout
    err = err or sys.stderr
    bench = bench or spec.benchmark()
    w = spec.workload(workload, bench)
    conf = conf or spec.config(w["config"])
    mix = mix or spec.traffic(w["traffic"])
    cellp = cellp or spec.cell(workload)
    chips = w["chips"] if chips is None else chips
    dev = device.check(chips, need_accelerator)
    cache_dir = device.enable_compile_cache()
    mesh = serve.serving_mesh(chips)

    def log(msg):
        print(f"bench: {msg} at {time.perf_counter() - t_start:.2f} s",
              file=err, flush=True)

    log("device found")
    engine = serve.build_engine(conf, cellp, seed, mesh)
    log("engine built")
    serve.warm(engine, serve.warm_lengths(mix, cellp))
    log("warm-up done")
    items = btraffic.schedule(mix, cellp.get("rate"))
    driver = serve.Driver(engine, items, seed, cellp.get("clients"))
    driver.ramp(cellp.get("ramp_steps", 0))
    log("clients started")
    synced = device.flush_writes(
        [cache_dir, os.environ.get("TPU_LOG_DIR", "")], wall_start)
    log(f"{synced} files written in set-up synced")
    stats0 = dict(engine.stats)

    tr = {}
    trace_dir = spec.BENCH_DIR / ".runs" / "trace"
    hooks = []
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

        def start_trace(t):
            import jax
            tr["stats0"] = dict(engine.stats)
            tr["t0"] = t
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=btrace.options())
        hooks.append((max(0.0, seconds - TRACE_MAX_S), start_trace))

    def on_close(t):
        if trace:
            import jax
            jax.profiler.stop_trace()
            tr["t1"] = t
            tr["stats1"] = dict(engine.stats)

    t0, t1 = driver.run(seconds, hooks=hooks, on_close=on_close)
    log(f"window closed, {len(driver.steps)} ticks")
    compiles = window_compiles(stats0, engine.stats)
    e2e = end_to_end(driver, t0, t1, t_start)
    attempted, failed = outcome(driver, mix["loop"], t0, t1)
    dev["memory_peak_bytes"] = device.peak_bytes()

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        summary = btrace.reduce(trace_dir)
        ctx = metrics.Context(conf=conf, cell=cellp, peaks=dev["peaks"],
                              chips=chips,
                              trace=summary, driver=driver,
                              window=(t0, t1), traced=(tr["t0"], tr["t1"]),
                              stats=(tr["stats0"], tr["stats1"]))
        result["metrics"] = metrics.read_all(
            spec.metrics_for(bench, workload, "per_layer"), ctx)
        ctx = None
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    else:
        wanted = spec.metrics_for(bench, workload, "end_to_end")
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in wanted if e2e.get(m["name"]) is not None}
    dev.pop("peaks")
    result["device"] = dev

    # the check: the program's state goes first, then the reference runs
    samples = check.pick(driver, cellp, seed)
    driver = engine = None
    gc.collect()
    log("program state freed")
    checks, result["correct"] = check.judge(
        cellp, check.served_gap(conf, cellp, seed, samples, mesh),
        check.tokens(samples), compiles, failed)
    result["checks"] = checks
    log("check done")
    check.print_checks(checks, err)
    print(json.dumps(result), file=out, flush=True)
    return result

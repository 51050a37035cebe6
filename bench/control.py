"""Readings that set a cell's limit of the output check, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

For each seed, in this one process: the cell is built, warmed and its
clients started as a run does it, its traffic runs for ``--seconds`` at
the cell's load, the finished requests are sampled as a run samples
them, and the program's state is freed. Then two verdicts are taken on
the same sample, each by the run's own ``check.judge`` with the cell's
limits: the program's, from its served tokens' widest gap against the
float32 reference, and the control's, from the gap of the tokens that
the reference computed in float8 e4m3 puts first at the same positions.
The control's must read ``correct: false``. One JSON line per seed, the
numbers compared beside their limits under ``checks``.

The limit goes between the largest program reading and the smallest
control reading (PERF.md gives both). The benchmark's own runs never
run the control.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench", ".runs",
                                                  "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def readings(workload: str, seed: int, seconds: float, *, bench=None,
             conf=None, cellp=None, mix=None, chips=None,
             need_accelerator=True) -> dict:
    """The program's and the control's verdicts on one seed's run."""
    from bench.lib import check, device, harness, serve, spec
    from bench.lib import traffic as btraffic
    w = spec.workload(workload, bench)
    conf = conf or spec.config(w["config"])
    cellp = cellp or spec.cell(workload)
    mix = mix or spec.traffic(w["traffic"])
    chips = w["chips"] if chips is None else chips
    device.check(chips, need_accelerator)
    device.enable_compile_cache()
    mesh = serve.serving_mesh(chips)
    t = time.perf_counter()
    engine = serve.build_engine(conf, cellp, seed, mesh)
    serve.warm(engine, serve.warm_lengths(mix, cellp))
    driver = serve.Driver(engine, btraffic.schedule(mix, cellp.get("rate")),
                          seed, cellp.get("clients"))
    driver.ramp(cellp.get("ramp_steps", 0))
    stats0 = dict(engine.stats)
    t0, t1 = driver.run(seconds)
    compiles = harness.window_compiles(stats0, engine.stats)
    _, failed = harness.outcome(driver, mix["loop"], t0, t1)
    samples = check.pick(driver, cellp, seed)
    driver = engine = None
    gc.collect()
    n = check.tokens(samples)
    out = {"workload": workload, "seed": seed}
    for who, gap in (("program", check.served_gap(conf, cellp, seed,
                                                  samples, mesh)),
                     ("control", check.control_gap(conf, cellp, seed,
                                                   samples, mesh))):
        checks, correct = check.judge(cellp, gap, n, compiles, failed)
        out[who] = {"correct": correct, "checks": checks}
    out["requests"] = len(samples)
    out["seconds"] = time.perf_counter() - t
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    a = ap.parse_args(argv)
    for seed in [int(s) for s in a.seeds.split(",")]:
        print(json.dumps(readings(a.workload, seed, a.seconds)), flush=True)


if __name__ == "__main__":
    main()

"""The on-chip benchmark of NanoQuant serving.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on, in this
one process, and prints one JSON line last on standard output. It exits
non-zero, printing no result, where JAX finds no accelerator or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout, never in /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench", ".runs",
                                                  "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from bench.lib import harness
    harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                t_start=T_START)


if __name__ == "__main__":
    main()

"""What one run of the benchmark is made of, found by name.

``BENCHMARK.json`` lists the cells; each cell names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``),
and has a file of its own (``cells/<workload>.json``) with the engine's
sizes, the load and the limits of the output check. Per-layer metrics
are readers in ``metrics/<metric>.py``; the operations and bytes of a
kernel are in ``work/<kernel>.py``. A new cell, mix, configuration or
metric is a new file and a new entry, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def cell(name: str) -> dict:
    return load_json(BENCH_DIR / "cells" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no peaks in "
                       f"bench/peaks.json")
    return table[device_kind]


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, workload_name: str, kind: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload_name in m["workloads"]]

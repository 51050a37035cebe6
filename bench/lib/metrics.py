"""Per-layer metrics: each is a reader of its own in
``bench/metrics/<name>.py`` with one function ``read(ctx)``, returning a
number or None where the run gave it nothing to read (then the metric
is left out of the line; a share of a roofline or a peak is never
reported as 0)."""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

from bench.lib import spec


@dataclasses.dataclass
class Context:
    conf: dict
    cell: dict
    peaks: dict
    trace: Any                      # trace.Summary
    driver: Any                     # serve.Driver
    window: Tuple[float, float]     # harness clock
    traced: Tuple[float, float]     # harness clock
    stats: Tuple[dict, dict]        # engine stats at trace start / stop
    chips: int = 1                  # chips the cell serves over, each
    #                                 layer split over all of them

    @property
    def mc(self) -> dict:
        return self.conf["model_config"]

    def traced_stat(self, key: str):
        return self.stats[1][key] - self.stats[0][key]

    def traced_steps(self):
        lo, hi = self.traced
        return [s for s in self.driver.steps if s.start >= lo and s.end <= hi]

    def window_requests(self):
        lo, hi = self.window
        return [r for r in self.driver.records.values() if lo <= r.due < hi]

    def linears(self) -> dict:
        from bench.lib import model as bmodel
        return spec.load_module("work", "decode_step").linears(
            bmodel.weight_shapes(self.conf))


def read_all(entries, ctx: Context) -> dict:
    out = {}
    for m in entries:
        value = spec.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out

"""Per-layer metric readers on hand-made contexts: each reads what it
says, and a reader with nothing to read returns None, never 0."""
import types

import _paths  # noqa: F401
import pytest

from bench.lib import metrics, serve, spec, trace
from bench.lib import traffic as btraffic


def ctx(**kw):
    base = dict(conf=spec.config("qwen1.5-0.5b"),
                cell=spec.cell("qwen1.5-0.5b.decode"),
                peaks=spec.peaks("TPU v5 lite"),
                trace=trace.Summary([], [], [], (0, 10**9), 1),
                driver=types.SimpleNamespace(
                    steps=[], records={},
                    engine=types.SimpleNamespace(admission_step={})),
                window=(0.0, 1.0), traced=(0.0, 1.0),
                stats=({"decode_steps": 0, "wasted_slot_steps": 0},
                       {"decode_steps": 0, "wasted_slot_steps": 0}))
    base.update(kw)
    return metrics.Context(**base)


def read(name, c):
    return spec.load_module("metrics", name).read(c)


@pytest.mark.parametrize("name", [m["name"] for m in
                                  spec.benchmark()["per_layer"]])
def test_nothing_to_read_gives_none(name):
    assert read(name, ctx()) is None


def test_batch_occupancy_from_counters():
    c = ctx(stats=({"decode_steps": 10, "wasted_slot_steps": 5},
                   {"decode_steps": 110, "wasted_slot_steps": 485}))
    assert read("batch_occupancy.decode", c) == pytest.approx(
        100 * (1 - 480 / (100 * c.cell["max_batch"])))


def test_idle_share_from_ops():
    ops = [trace.Op("fusion", "jit_decode_fn(1)", 0, 250_000_000, 0),
           trace.Op("fusion", "jit_decode_fn(1)", 500_000_000,
                    250_000_000, 0)]
    c = ctx(trace=trace.Summary(ops, [], [], (0, 10**9), 1))
    assert read("device_idle_share.decode", c) == pytest.approx(50.0)


def test_fused_roofline_share_by_hand():
    op = trace.Op("nq_fused_lowrank_matmul.3", "jit_decode_fn(1)", 0,
                  50_000_000, 0)
    c = ctx(trace=trace.Summary([op], [], [], (0, 10**9), 1),
            stats=({"decode_steps": 0, "wasted_slot_steps": 0},
                   {"decode_steps": 10, "wasted_slot_steps": 0}))
    step = spec.load_module("work", "decode_step")
    f, b = step.fused_work(c.mc, c.linears(), c.cell["max_batch"])
    bound = max(f / 197e12, b / 819e9)
    assert read("fused_matmul_roofline.decode", c) == pytest.approx(
        100 * 10 * bound / 0.05)


def test_warm_lengths_cover_every_bucket():
    from repro.serve.scheduler import bucket_length
    mix = spec.traffic("conv_closed")
    cellp = spec.cell("qwen1.5-0.5b.decode")
    warm = {bucket_length(n, cellp["max_len"])
            for n in serve.warm_lengths(mix, cellp)}
    need = {bucket_length(n, cellp["max_len"])
            for n in range(1, cellp["max_len"])
            if n >= mix["prompt"]["min"]}
    assert warm == need

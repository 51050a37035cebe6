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
def test_nothing_to_read_gives_none(name, tmp_path, monkeypatch):
    # the span readers read the harness's trace directory: an empty one
    # here, whatever trace an earlier run left in the checkout
    from bench.lib import spans
    monkeypatch.setattr(spans, "RUN_TRACE_DIR", tmp_path)
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


def _tp_ctx(ops, modules, n_devices=4, steps=10):
    return ctx(conf=spec.config("qwen1.5-110b"),
               cell=spec.cell("qwen1.5-110b.tp4"), chips=n_devices,
               trace=trace.Summary(ops, modules, [], (0, 10**9), n_devices),
               stats=({"decode_steps": 0, "wasted_slot_steps": 0},
                      {"decode_steps": steps, "wasted_slot_steps": 0}))


def _op(text, module, start, dur, device):
    """An op as the trace reduction makes it from the event's HLO text."""
    return trace.Op(trace.op_name(text), module, start, dur, device,
                    trace.opcode(text))


def test_collective_share_by_opcode():
    """Collectives by opcode, whatever the op's name, inside decode_fn
    programs only, on each chip over that chip's decode_fn time."""
    mods = [("jit_decode_fn(3)", 0, 1000, d) for d in (0, 1)] + \
        [("jit_prefill_fn(4)", 2000, 1000, d) for d in (0, 1)]
    dec = "jit_decode_fn(3)"
    ops = [_op("%psum.5 = bf16[9,1,8192]{2,0,1} all-reduce(%b.1), "
               "channel_id=1", dec, 100, 100, 0),
           _op("%all-gather.2 = f32[4,1,9]{2,1,0} all-gather(%b.2)", dec,
               300, 50, 0),
           _op("%all-reduce-fusion = bf16[8]{0} fusion(%psum.5)", dec, 400,
               500, 0),                                 # not a collective
           _op("%cp.1 = bf16[3,256]{1,0} collective-permute(%x)", dec, 0,
               200, 1),
           _op("%rs.3 = bf16[8]{0} reduce-scatter(%x)", dec, 150, 100,
               1),                                      # overlaps cp.1
           _op("%a2a.1 = bf16[8]{0} all-to-all(%x)", dec, 600, 100, 1),
           _op("%psum.9 = bf16[8]{0} all-reduce(%x)", "jit_prefill_fn(4)",
               2100, 500, 0)]
    share = read("collective_share.decode", _tp_ctx(ops, mods, 2))
    assert share == pytest.approx(100 * (150 + 250 + 100) / 2000)


def test_collective_async_pair_counts_once():
    """A start/done pair is one collective from the start op to the done
    op, whatever ran between; a second pair overlapping it adds only
    what it does not share."""
    mods = [("jit_decode_fn(3)", 0, 1000, 0)]
    dec = "jit_decode_fn(3)"
    ops = [_op("%collective-permute-start.1 = (bf16[3,9,256]{2,1,0:T(8,128)"
               "(2,1)S(1)}, u32[]{:S(2)}) collective-permute-start(%g.1)",
               dec, 100, 10, 0),
           _op("%fusion.2 = bf16[8]{0} fusion(%x)", dec, 110, 200, 0),
           _op("%collective-permute-done.1 = bf16[3,9,256]{2,1,0} "
               "collective-permute-done(%collective-permute-start.1)", dec,
               310, 40, 0),
           _op("%ag-start = (bf16[8]{0}, bf16[32]{0}) all-gather-start(%x)",
               dec, 300, 10, 0),
           _op("%ag-done = bf16[32]{0} all-gather-done(%ag-start)", dec, 400,
               100, 0)]
    share = read("collective_share.decode", _tp_ctx(ops, mods, 1))
    assert share == pytest.approx(100 * (350 - 100 + 500 - 350) / 1000)
    assert read("collective_share.decode",
                _tp_ctx(ops[1:2], mods, 1)) is None


def test_packed_roofline_per_chip_by_hand():
    """The two-call kernel's share: per call the larger of its compute
    and memory bound, on one chip's share, over its time a chip."""
    dec = "jit_decode_fn(3)"
    ops = [trace.Op("nq_packed_matmul.4", dec, 0, 200_000_000, d)
           for d in range(4)]
    c = _tp_ctx(ops, [])
    step = spec.load_module("work", "decode_step")
    calls = step.packed_calls(c.mc, c.linears(), c.cell["max_batch"], 4)
    bound = sum(max(f / 197e12, b / 819e9) for f, b in calls)
    assert read("packed_matmul_roofline.decode", c) == pytest.approx(
        100 * 10 * bound / 0.2)
    assert read("packed_matmul_roofline.decode", ctx()) is None


def test_fused_roofline_and_mfu_over_four_chips():
    """On four chips the fused kernel's work is one chip's share of the
    linears it runs, and the model's FLOPs go over four chips' peak."""
    dec = "jit_decode_fn(3)"
    ops = [trace.Op("nq_fused_lowrank_matmul.3", dec, 0, 50_000_000, d)
           for d in range(4)]
    c = _tp_ctx(ops, [])
    step = spec.load_module("work", "decode_step")
    f, b = step.fused_work(c.mc, c.linears(), c.cell["max_batch"], 4)
    assert read("fused_matmul_roofline.decode", c) == pytest.approx(
        100 * 10 * max(f / 197e12, b / 819e9) / 0.05)
    rec = types.SimpleNamespace(times=[0.1, 0.2, 0.3], decode_ctx=[500, 501])
    lin = c.linears()
    for chips in (1, 4):
        c = _tp_ctx([], [], chips)
        c.driver.records = {0: rec}
        flops = sum(step.token_flops(c.mc, lin, n) for n in (500, 501))
        assert read("decode_mfu", c) == pytest.approx(
            100 * flops / 1.0 / (197e12 * chips))

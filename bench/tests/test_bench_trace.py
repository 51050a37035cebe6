"""The reduction from a profiler trace to busy time, kernel time and
roofline share: on hand-made intervals, and on a trace of three decode
ticks of qwen1.5-0.5b (eight slots) recorded on a TPU v5e chip
(bench/testdata/decode3.xplane.pb, with what the recording run counted
in decode3.json)."""
import json

import _paths  # noqa: F401
import pytest

from bench.lib import spec, trace

DATA = spec.BENCH_DIR / "testdata"


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 50)]
    assert trace.union_ns(iv, 0, 100) == 15 + 10 + 10
    assert trace.union_ns(iv, 8, 45) == 7 + 10 + 5
    assert trace.union_ns([], 0, 10) == 0


def _summary(ops, spans, window):
    return trace.Summary(
        ops=[trace.Op(n, m, s, d, 0) for n, m, s, d in ops],
        modules=[], spans=spans, window=window, n_devices=1)


def test_busy_kernel_and_gaps_by_hand():
    s = _summary([("nq_fused_lowrank_matmul.3", "jit_decode_fn(1)", 0, 40),
                  ("fusion.1", "jit_decode_fn(1)", 30, 20),
                  ("nq_fused_lowrank_matmul.3", "jit_prefill_fn(2)", 100, 50),
                  ("nq_paged_attention", "jit_decode_fn(1)", 160, 10)],
                 [("bench.step", 0, 60), ("bench.submit", 60, 100)],
                 (0, 200))
    assert s.window_s == 200e-9
    assert s.busy_s == pytest.approx((50 + 50 + 10) * 1e-9)
    assert s.kernel_s("nq_fused_lowrank_matmul", "decode_fn") == \
        pytest.approx(40e-9)
    gaps = s.idle_gaps()
    assert gaps[0] == ("host: bench.submit", 50)
    assert sum(g for _, g in gaps) == 200 - 110
    b = s.breakdown()
    assert b["device_ops"][0][0] == "nq_fused_lowrank_matmul.3"


def test_recorded_trace_reduces_as_on_the_chip():
    facts = json.loads((DATA / "decode3.json").read_text())
    s = trace.load(str(DATA / "decode3.xplane.pb"))
    assert s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.window_s == pytest.approx(facts["window_s"])
    assert s.busy_s == pytest.approx(facts["busy_s"])
    assert len(s.module_runs("decode_fn")) == facts["decode_steps"]
    fused = [o for o in s.ops if o.name.startswith("nq_fused_lowrank_matmul")
             and "decode_fn" in o.module]
    paged = [o for o in s.ops if o.name.startswith("nq_paged_attention")
             and "decode_fn" in o.module]
    # per layer: merged q/k/v, o, merged gate/up, down; one paged read
    assert len(fused) == 4 * facts["n_layers"] * facts["decode_steps"]
    assert len(paged) == facts["n_layers"] * facts["decode_steps"]
    assert s.kernel_s("nq_fused_lowrank_matmul", "decode_fn") == \
        pytest.approx(facts["fused_s"])


def test_recorded_roofline_share_is_a_share():
    facts = json.loads((DATA / "decode3.json").read_text())
    s = trace.load(str(DATA / "decode3.xplane.pb"))
    conf = spec.config("qwen1.5-0.5b")
    from bench.lib import model
    step = spec.load_module("work", "decode_step")
    lin = step.linears(model.weight_shapes(conf))
    f, b = step.fused_work(conf["model_config"], lin, facts["max_batch"])
    pk = spec.peaks("TPU v5 lite")
    bound = max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    share = 100 * facts["decode_steps"] * bound / \
        s.kernel_s("nq_fused_lowrank_matmul", "decode_fn")
    assert 0 < share <= 100


def test_recorded_ops_carry_their_opcode():
    """Each op keeps the opcode of its HLO text beside its name: the
    kernels are custom calls, the async copies start and done ops."""
    s = trace.load(str(DATA / "decode3.xplane.pb"))
    by_name = {o.name.split(".")[0]: o.opcode for o in s.ops}
    assert by_name["nq_paged_attention"] == "custom-call"
    assert by_name["nq_fused_lowrank_matmul"] == "custom-call"
    assert by_name["copy-start"] == "copy-start"
    assert by_name["while"] == "while"
    assert all(o.opcode for o in s.ops)

"""The reduction of the engine's ``serve.*`` host spans to the per-layer
metrics that read them: on hand-made spans, and on a trace of a few
engine ticks of qwen1.5-0.5b (eight slots, one admission) recorded on a
TPU v5e chip (bench/testdata/engine_ticks.xplane.pb, with what the
recording run counted in engine_ticks.json)."""
import json

import _paths  # noqa: F401
import pytest

from bench.lib import spans, spec, trace

DATA = spec.BENCH_DIR / "testdata"
MS = 1_000_000


def S(name, start, dur, thread="main", **args):
    return spans.Span(name, start * MS, dur * MS, thread, args)


def _hand_made():
    """Two ticks of 100 and 60 ms; the first admits (30 ms, 20 of them
    waiting on the prefill) and both decode (waits of 50 and 40 ms).
    A tick that ends after the window and one on another thread's
    spans do not count."""
    return [
        S("serve.tick", 0, 100, step=0),
        S("serve.reap", 0, 1),
        S("serve.admit", 2, 30, uid=7, slot=0, rows=5, bucket=8),
        S("serve.prefill", 3, 5, uid=7),
        S("serve.insert", 8, 2, uid=7),
        S("serve.prefill_wait", 10, 20, uid=7),
        S("serve.decode_dispatch", 35, 5),
        S("serve.decode_wait", 40, 50),
        S("serve.emit", 90, 5),
        S("serve.tick", 110, 60, step=1),
        S("serve.decode_dispatch", 112, 3),
        S("serve.decode_wait", 115, 40),
        S("serve.emit", 155, 10),
        S("serve.decode_wait", 120, 10, thread="other"),
        S("serve.tick", 190, 20, step=2),
    ]


def test_ticks_keep_whole_ticks_inside_the_window_and_their_thread():
    tks = spans.ticks(_hand_made(), (0, 200 * MS))
    assert [t.span.args["step"] for t in tks] == [0, 1]
    assert [len(t.children) for t in tks] == [8, 3]
    assert spans.ticks(_hand_made(), (1, 200 * MS))[0].span.args == \
        {"step": 1}
    assert spans.ticks([], (0, 1)) == []


def test_readers_by_hand():
    tks = spans.ticks(_hand_made(), (0, 200 * MS))
    # (100 - 20 - 50) and (60 - 40), mean of the two
    assert spans.host_ms_per_tick(tks) == pytest.approx((30 + 20) / 2)
    assert spans.admission_share(tks) == pytest.approx(100 * 30 / 160)
    assert spans.pad_share({"prefill_rows": 10, "prefill_bucket_rows": 16},
                           {"prefill_rows": 24, "prefill_bucket_rows": 40}) \
        == pytest.approx(100 * (1 - 14 / 24))


def test_readers_with_nothing_to_read_give_none():
    assert spans.host_ms_per_tick([]) is None
    assert spans.admission_share([]) is None
    assert spans.pad_share({"decode_steps": 0}, {"decode_steps": 5}) is None
    z = {"prefill_rows": 3, "prefill_bucket_rows": 8}
    assert spans.pad_share(z, z) is None


def test_idle_time_goes_to_the_innermost_span():
    tks = spans.ticks(_hand_made(), (0, 200 * MS))
    ops = [trace.Op("fusion", "jit_prefill_fn(1)", 5 * MS, 20 * MS, 0),
           trace.Op("fusion", "jit_decode_fn(2)", 38 * MS, 51 * MS, 0),
           trace.Op("fusion", "jit_decode_fn(2)", 114 * MS, 40 * MS, 0)]
    s = trace.Summary(ops, [], [], (0, 200 * MS), 1)
    idle = {k: v / MS for k, v in spans.idle_by_span(s, tks).items()}
    assert idle == pytest.approx({
        "serve.reap": 1, "serve.tick": 1 + 3 + 5 + 2 + 5,
        "serve.admit": 1 + 2, "serve.prefill": 2, "serve.prefill_wait": 5,
        "serve.decode_dispatch": 3 + 2, "serve.decode_wait": 1 + 1,
        "serve.emit": 5 + 10})
    rep = spans.report(s, _hand_made())
    assert rep["ticks"] == 2 and rep["admissions"] == 1
    assert rep["tick_ms"] == pytest.approx(80)
    assert rep["idle_named_share"] == pytest.approx(
        100 * (1 - 16 / 49))


def test_traced_smoke_run_reads_the_engine_spans():
    """A `--trace 1` run of a smoke-size cell on the CPU reports the
    three metrics that read the engine's spans and counters."""
    import io
    import math

    from test_bench_check import BENCH, CELL, MIX, SEED, smoke_conf

    from bench.lib import harness
    names = ("host_ms_per_tick.decode", "admission_share.decode",
             "prefill_pad_share.decode")
    bench = dict(BENCH, per_layer=[
        {k: v for k, v in m.items() if k != "workloads"}
        for m in spec.benchmark()["per_layer"] if m["name"] in names])
    out = io.StringIO()
    harness.run("smoke", SEED, 2.0, True, bench=bench,
                conf=smoke_conf("qwen1.5-0.5b"), cellp=CELL, mix=MIX,
                need_accelerator=False, out=out, err=io.StringIO())
    r = json.loads(out.getvalue().strip().splitlines()[-1])
    assert r["correct"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(got) == set(names)
    assert all(math.isfinite(v) for v in got.values())
    assert got["host_ms_per_tick.decode"] > 0
    assert 0 < got["admission_share.decode"] < 100
    assert 0 <= got["prefill_pad_share.decode"] < 100


def _recorded():
    facts = json.loads((DATA / "engine_ticks.json").read_text())
    path = str(DATA / "engine_ticks.xplane.pb")
    s = trace.load(path)
    return facts, s, spans.ticks(spans.load(path), s.window)


def test_recorded_ticks_hold_the_admission_and_its_counts():
    facts, s, tks = _recorded()
    assert len(tks) == facts["ticks"]
    admits = [c for t in tks for c in t.children if c.name == spans.ADMIT]
    assert len(admits) == facts["admissions"] == 1
    assert admits[0].args["rows"] == facts["prefill_rows"]
    assert admits[0].args["bucket"] == facts["prefill_bucket_rows"]
    uid = admits[0].args["uid"]
    for part in ("serve.prefill", "serve.insert", "serve.prefill_wait"):
        inner = [c for t in tks for c in t.children if c.name == part]
        assert [c.args["uid"] for c in inner] == [uid]
    assert len(s.module_runs("decode_fn")) == facts["decode_steps"]


def test_recorded_decode_runs_lie_between_dispatch_and_wait():
    """The device trace and the host spans share one clock: each decode
    program runs after its tick dispatched it and before the host's
    wait on it ends."""
    facts, s, tks = _recorded()
    runs = s.module_runs("decode_fn")
    assert len(runs) == facts["decode_steps"] > 0
    for start, dur in runs:
        hits = []
        for t in tks:
            kids = {c.name: c for c in t.children}
            if kids["serve.decode_dispatch"].start <= start and \
                    start + dur <= kids["serve.decode_wait"].end:
                hits.append(t)
        assert len(hits) == 1, (start, dur)


def test_recorded_idle_time_falls_under_named_spans():
    """Every stretch of 1 ms or more in which the device idles inside a
    tick lies under a named child span, not the tick's own time."""
    facts, s, tks = _recorded()
    idle = spans.idle_by_span(s, tks)
    named = 1 - idle.get(spans.TICK, 0) / sum(idle.values())
    assert 100 * named >= 90
    assert 100 * named == pytest.approx(facts["report"]["idle_named_share"])
    first = min(o.device for o in s.ops)
    busy = [(o.start, o.start + o.dur) for o in s.ops if o.device == first]
    for t in tks:
        for a, b, name in spans.innermost(t):
            if name == spans.TICK:
                assert (b - a) - trace.union_ns(busy, a, b) < 1_000_000

"""The serving engine's host spans and prefill counters: a tiny paged
engine runs under the JAX profiler on the CPU, and its trace holds every
``serve.*`` span of a tick with its args, nested as the engine runs
them; ``prefill_rows`` and ``prefill_bucket_rows`` count real and
bucketed admission rows."""
import dataclasses

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.models import transformer as T
from repro.serve import InferenceEngine, Request, ServeConfig

SPANS = {                       # name -> args it carries
    "serve.tick": {"step"},
    "serve.reap": set(),
    "serve.admit": {"uid", "slot", "rows", "bucket"},
    "serve.prefill": {"uid"},
    "serve.prefill_wait": {"uid"},
    "serve.insert": {"uid"},
    "serve.reserve_pages": set(),
    "serve.preempt": {"uid"},
    "serve.decode_dispatch": set(),
    "serve.decode_wait": set(),
    "serve.emit": set(),
    "serve.callbacks": set(),
}


@pytest.fixture(scope="module")
def served_model():
    cfg = dataclasses.replace(configs.get_smoke("llama3.2-1b"),
                              dtype="float32")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def _prompts(cfg, lens):
    rng = np.random.default_rng(3)
    return [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lens]


def _serve(cfg, params, scfg, lens, max_new):
    eng = InferenceEngine(params, cfg, scfg, max_batch=2, max_len=32)
    for uid, p in enumerate(_prompts(cfg, lens)):
        eng.submit(Request(uid, p, max_new_tokens=max_new),
                   on_token=lambda uid, tok: None)
    return eng


def _host_spans(trace_dir):
    """(name, start, end, thread, args) of every serve.* host event."""
    path = next(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith("serve."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                ln.name, dict(ev.stats)))
    return out


def _inside(inner, outer):
    return inner[3] == outer[3] and outer[1] <= inner[1] \
        and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(served_model, tmp_path_factory):
    """Prompts of 5 and 9 rows in a pool of 8 usable pages of 4 rows:
    both admit (2 + 3 pages), and their decode outgrows the pool, so one
    slot is preempted and re-admitted."""
    cfg, params = served_model
    eng = _serve(cfg, params, ServeConfig(greedy=True, page_size=4,
                                          kv_pool_pages=9),
                 [5, 9], max_new=12)
    d = tmp_path_factory.mktemp("spans")
    with jax.profiler.trace(str(d)):
        eng.run()
    assert eng.stats["preemptions"] >= 1
    return eng, _host_spans(d)


def test_every_span_appears_with_its_args(traced):
    eng, spans = traced
    seen = {}
    for name, _, _, _, args in spans:
        seen.setdefault(name, args)
    assert set(SPANS) <= set(seen), set(SPANS) - set(seen)
    for name, want in SPANS.items():
        assert want <= set(seen[name]), (name, seen[name])
    ticks = [a["step"] for n, _, _, _, a in spans if n == "serve.tick"]
    assert ticks == list(range(eng.stats["steps"]))
    assert not any(n.startswith("bench.") for n, *_ in spans)


def test_admission_nests_prefill_wait_and_insert_inside_a_tick(traced):
    _, spans = traced
    admits = [s for s in spans if s[0] == "serve.admit"]
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(admits) >= 3                     # two fresh, one resume
    for a in admits:
        tick = [t for t in ticks if _inside(a, t)]
        assert len(tick) == 1
        for part in ("serve.prefill", "serve.prefill_wait", "serve.insert"):
            inner = [s for s in spans if s[0] == part and _inside(s, a)]
            assert len(inner) == 1, (part, a)
            assert _inside(inner[0], tick[0])
            # one request's admit spans carry its uid
            assert inner[0][4]["uid"] == a[4]["uid"]


def test_decode_parts_sit_in_order_inside_their_tick(traced):
    _, spans = traced
    for t in (s for s in spans if s[0] == "serve.tick"):
        kids = [s for s in spans if s is not t and _inside(s, t)]
        order = [s[0] for s in kids if s[0] in (
            "serve.reserve_pages", "serve.decode_dispatch",
            "serve.decode_wait", "serve.emit")]
        if order:
            assert order == ["serve.reserve_pages", "serve.decode_dispatch",
                             "serve.decode_wait", "serve.emit"]
    pre = [s for s in spans if s[0] == "serve.preempt"]
    assert all(any(_inside(p, r) for r in spans
                   if r[0] == "serve.reserve_pages") for p in pre)


def test_admit_args_add_up_to_the_counters(traced):
    eng, spans = traced
    admits = [s[4] for s in spans if s[0] == "serve.admit"]
    assert sum(a["rows"] for a in admits) == eng.stats["prefill_rows"]
    assert sum(a["bucket"] for a in admits) == \
        eng.stats["prefill_bucket_rows"]
    assert {a["uid"] for a in admits} == {0, 1}


def test_prefill_counters_count_real_and_bucket_rows(served_model):
    """5 and 9 rows prefill in buckets of 8 and 16."""
    cfg, params = served_model
    eng = _serve(cfg, params, ServeConfig(greedy=True, page_size=4),
                 [5, 9], max_new=3)
    eng.run()
    assert eng.stats["prefill_rows"] == 14
    assert eng.stats["prefill_bucket_rows"] == 24
    eng.reset_stats()
    assert eng.stats["prefill_rows"] == eng.stats["prefill_bucket_rows"] == 0

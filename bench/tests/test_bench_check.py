"""The output check at a size a test run holds, on the CPU: the program's
served tokens pass it, and the fp8 control and planted faults of the
timed path fail it.

The cells' limits are set from chip runs at their own sizes (PERF.md).
This smoke cell's limit is set the same way, from readings on the CPU at
smoke size, seeds 1, 2**33 + 5 and 77, 64 checked tokens: the program's
widest gap read 0 to 0.125 over both configurations, the control's 0.445
to 3.9.
"""
import io
import json

import _paths  # noqa: F401
import jax.numpy as jnp
import pytest

from bench.lib import check, harness, serve, spec
from bench.lib import traffic as btraffic

SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
             vocab_size=256, head_dim=32)
LIMIT = 0.3
SEED = 2**33 + 5


def smoke_conf(name):
    conf = spec.config(name)
    conf["model_config"].update(SMOKE)
    return conf


CONFIGS = ["qwen3-4b", "qwen1.5-0.5b"]
MIX = {"loop": "closed", "pool": 256,
       "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                  "min": 4, "max": 40},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                  "min": 4, "max": 20}}
CELL = {"max_batch": 4, "max_len": 64, "page_size": 16,
        "kv_pool_pages": None, "clients": 4, "ramp_steps": 4,
        "check": {"min_tokens": 64, "max_requests": 16},
        "limits": {"logit_gap": LIMIT}}
BENCH = {"workloads": [{"name": "smoke", "config": "smoke",
                        "traffic": "smoke", "chips": 1}],
         "end_to_end": [{"name": "decode_tok_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}


OPEN_MIX = dict(MIX, loop="open", arrivals={"dist": "gamma", "cv": 2.0})
OPEN_CELL = dict(CELL, clients=None, rate=20.0)
OPEN_BENCH = dict(BENCH, end_to_end=[
    {"name": "ttft_p95_ms", "unit": "ms"}, {"name": "itl_p95_ms", "unit": "ms"},
    {"name": "setup_s", "unit": "s"}])


def run_smoke(conf, seconds=1.5, bench=BENCH, cell=CELL, mix=MIX):
    out = io.StringIO()
    harness.run("smoke", SEED, seconds, False, bench=bench, conf=conf,
                cellp=cell, mix=mix, need_accelerator=False, out=out,
                err=io.StringIO())
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("name", CONFIGS)
def test_program_passes_and_control_fails(name):
    """bench/control.py's readings: the run's own verdict passes the
    program's served tokens and fails the fp8 control's first choices."""
    from bench import control
    r = control.readings("smoke", SEED, 1.0, bench=BENCH,
                         conf=smoke_conf(name), cellp=CELL, mix=MIX,
                         need_accelerator=False)
    assert r["program"]["correct"], r
    assert not r["control"]["correct"], r
    program = r["program"]["checks"]["logit_gap"]["value"]
    control = r["control"]["checks"]["logit_gap"]["value"]
    assert program <= LIMIT < control, (program, control)
    assert r["control"]["checks"]["checked_tokens"]["value"] \
        >= CELL["check"]["min_tokens"]


def test_ramp_staggers_clients():
    engine = serve.build_engine(smoke_conf("qwen3-4b"), CELL, SEED)
    serve.warm(engine, serve.warm_lengths(MIX, CELL))
    driver = serve.Driver(engine, btraffic.schedule(MIX), SEED, 4)
    driver.ramp(4)
    first = [driver.records[i] for i in range(4)]
    assert len({r.submitted for r in first}) == 4
    assert len({len(r.tokens) for r in first}) > 1
    assert engine.in_flight


@pytest.mark.parametrize("name", CONFIGS)
def test_harness_run_is_correct(name):
    res = run_smoke(smoke_conf(name))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"decode_tok_s", "setup_s"}


def test_open_loop_run_is_correct_and_timed_from_due():
    res = run_smoke(smoke_conf("qwen3-4b"), 2.0, OPEN_BENCH, OPEN_CELL,
                    OPEN_MIX)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 10 and res["failed"] == 0
    m = res["metrics"]
    assert m["ttft_p95_ms"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0


def _alter_tokens(monkeypatch):
    from repro.serve import engine as E
    orig = E.sample_token

    def altered(logits, key, scfg):
        tok = orig(logits, key, scfg)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(E, "sample_token", altered)


def _drop_cache_writes(monkeypatch):
    from repro.models import layers as L
    monkeypatch.setattr(L, "paged_cache_write",
                        lambda pool, new, table, row: pool)


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_cache_writes],
                         ids=["token_altered", "state_unchanged"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_smoke(smoke_conf("qwen3-4b"))
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT

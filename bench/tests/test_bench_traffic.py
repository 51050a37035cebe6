"""The traffic generator: the same seed gives the same requests, every
seed gives the same sizes in the same order with other prompt tokens,
and sizes keep to their clips."""
import _paths  # noqa: F401
import numpy as np
import pytest

from bench.lib import spec, traffic

MIXES = ["conv_closed", "chat_bursty"]
SEEDS = [0, 2**31 + 7, 2**40 + 3]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = spec.traffic(mix)
    a = traffic.schedule(m, rate=3.0)
    b = traffic.schedule(m, rate=3.0)
    assert a == b
    pa = traffic.prompt_tokens(SEEDS[1], a[5], 151936)
    pb = traffic.prompt_tokens(SEEDS[1], b[5], 151936)
    np.testing.assert_array_equal(pa, pb)
    assert pa.size == a[5].prompt_len


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_reorder_one_multiset(mix):
    """Seeds change what the prompts say, never how much work they are."""
    m = spec.traffic(mix)
    items = traffic.schedule(m, rate=3.0)
    toks = [traffic.prompt_tokens(s, items[0], 151936) for s in SEEDS]
    assert all(t.size == items[0].prompt_len for t in toks)
    assert not np.array_equal(toks[0], toks[1])
    assert not np.array_equal(toks[1], toks[2])
    assert len({i.prompt_len for i in items}) > 10
    if m["loop"] == "open":
        gaps = np.diff([0.0] + [i.due_s for i in items])
        assert (gaps >= 0).all() and len(set(gaps)) > 10


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_sizes_within_clips(mix, seed):
    m = spec.traffic(mix)
    items = traffic.schedule(m, rate=3.0)
    p = np.array([i.prompt_len for i in items])
    o = np.array([i.output_len for i in items])
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert abs(np.median(p) - m["prompt"]["median"]) < 0.1 * m["prompt"]["median"]
    assert abs(np.median(o) - m["output"]["median"]) < 0.1 * m["output"]["median"]
    tok = traffic.prompt_tokens(seed, items[0], 1000)
    assert tok.min() >= 0 and tok.max() < 1000


def test_open_loop_rate_and_burstiness():
    m = spec.traffic("chat_bursty")
    items = traffic.schedule(m, rate=4.0)
    gaps = np.diff([0.0] + [i.due_s for i in items])
    assert abs(gaps.mean() - 0.25) < 0.02
    assert abs(gaps.std() / gaps.mean() - m["arrivals"]["cv"]) < 0.2
    with pytest.raises(ValueError):
        traffic.schedule(m)

"""Paged KV cache: allocator invariants, paged-vs-rectangular greedy
token identity (incl. page-boundary edge cases, the hybrid
sliding-window ring and the MLA compressed cache), overcommit admission
(queue, never crash), decode-time preemption, page-leak regression on
uid reuse, the wave shim on a paged engine, and CPU-interpreter parity
of the Pallas gather-attention kernel against the pure-jax oracle."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice

from repro import configs
from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import transformer as T
from repro.serve import (BatchServer, InferenceEngine, PagedKVState,
                         Request, ServeConfig)
from repro.serve.engine import generate
from repro.serve.paging import (cache_page_kinds, init_paged_cache,
                                kv_cache_bytes, page_kind)


@pytest.fixture(scope="module")
def served_model():
    # f32 so greedy argmax is identical across cache layouts
    cfg = dataclasses.replace(configs.get_smoke("llama3.2-1b"),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lens]


def _run(params, cfg, prompts, budgets, scfg, max_batch=2, max_len=32,
         eos=None):
    eng = InferenceEngine(params, cfg, scfg, max_batch=max_batch,
                          max_len=max_len)
    for uid, (p, b) in enumerate(zip(prompts, budgets)):
        eng.submit(Request(uid, p, max_new_tokens=b,
                           eos_id=eos.get(uid) if eos else None))
    done = eng.run()
    return {u: r.output for u, r in done.items()}, eng


def _assert_paged_matches_rect(params, cfg, prompts, budgets, paged_scfg,
                               **kw):
    rect, _ = _run(params, cfg, prompts, budgets,
                   ServeConfig(greedy=True, paged=False), **kw)
    paged, eng = _run(params, cfg, prompts, budgets, paged_scfg, **kw)
    assert eng.paged
    for u in rect:
        np.testing.assert_array_equal(rect[u], paged[u])
    # drained: no slot maps anything; only the prefix index (when
    # enabled) may still hold refcount-zero cached pages
    assert not eng.kv.ref.any(), "drained engine must hold no mappings"
    assert eng.kv.used_pages == eng.kv.cached_page_count, \
        "drained engine holds non-index pages"
    return eng


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_invariants(served_model):
    cfg, _ = served_model
    kv = PagedKVState(cfg, max_batch=2, max_len=32, page_size=8,
                      n_pages=9)
    assert kv.free_pages == 8 and kv.lin_pages == 4
    ids = kv.admit(0, 9)                       # 2 pages
    assert list(ids) == ["linear"] and ids["linear"].shape == (4,)
    assert (ids["linear"][:2] > 0).all() and (ids["linear"][2:] == 0).all()
    assert 0 not in kv._slot_pages[0], "null page must never be handed out"
    assert kv.ensure(0, 15) and kv.used_pages == 2      # row 15: page 1
    assert kv.ensure(0, 16) and kv.used_pages == 3      # crosses into page 2
    ids1 = kv.admit(1, 32)                     # 4 pages
    assert kv.free_pages == 1
    assert not kv.can_admit(9)                 # 2 pages > 1 free
    assert set(ids1["linear"]).isdisjoint(set(kv.tables["linear"][0]) - {0})
    kv.release(0)
    assert (kv.tables["linear"][0] == 0).all()
    assert kv.free_pages == 4 and kv.can_admit(9)
    kv.release(1)
    assert kv.used_pages == 0 and kv.peak_used_pages == 7


def test_pool_must_fit_one_slot(served_model):
    cfg, _ = served_model
    with pytest.raises(ValueError, match="worst case"):
        PagedKVState(cfg, max_batch=2, max_len=32, page_size=8, n_pages=4)


def test_submit_rejects_unadmittable_watermark(served_model):
    """A prompt that can never clear the admission watermark is rejected
    at submit instead of stalling the queue forever."""
    cfg, params = served_model
    eng = InferenceEngine(params, cfg,
                          ServeConfig(greedy=True, page_size=8,
                                      kv_pool_pages=5, page_watermark=2),
                          max_batch=2, max_len=32)
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(Request(0, np.arange(1, 25, dtype=np.int32),
                           max_new_tokens=2))
    h = eng.submit(Request(1, np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=2))
    assert len(h.result()) == 2


def test_watermark_does_not_livelock_resumes(served_model):
    """Regression: a preempted resume's grown prompt may need more
    pages than submit() validated; the admission watermark must not
    gate it (only fresh work), or the engine livelocks with the whole
    pool free and nothing active."""
    cfg, params = served_model
    eng = InferenceEngine(params, cfg,
                          ServeConfig(greedy=True, page_size=8,
                                      kv_pool_pages=7, page_watermark=4),
                          max_batch=2, max_len=48)
    prompts = _prompts(cfg, [8, 8], seed=10)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid, p, max_new_tokens=20))
    done = eng.run()
    assert eng.stats["preemptions"] >= 1
    for uid, p in enumerate(prompts):
        gen, _ = generate(params, cfg, p[None],
                          ServeConfig(max_new_tokens=20, greedy=True))
        np.testing.assert_array_equal(done[uid].output, np.asarray(gen[0]))


def test_page_kind_classification():
    assert page_kind("layers/k") == "linear"
    assert page_kind("self_layers/v") == "linear"
    assert page_kind("layers/c_kv") == "linear"
    assert page_kind("shared_attn/k") == "ring"
    assert page_kind("cross_kv/k") is None
    assert page_kind("layers/ssm") is None
    hyb = configs.get_smoke("zamba2-1.2b")
    assert cache_page_kinds(hyb, 32) == {"ring"}
    assert cache_page_kinds(configs.get_smoke("mamba2-370m"), 32) == set()


def test_pool_shapes_and_bytes(served_model):
    cfg, _ = served_model
    pool = init_paged_cache(cfg, 4, 32, n_pages=9, page_size=8)
    k = pool["layers"]["k"]
    assert k.shape[1:3] == (9, 8)
    rect = T.init_cache(cfg, 4, 32)
    assert kv_cache_bytes(pool) < kv_cache_bytes(rect)


# ---------------------------------------------------------------------------
# engine identity + page-boundary edge cases
# ---------------------------------------------------------------------------


def test_paged_identity_and_boundaries(served_model):
    """Prompt exactly k*page_size (first decode write opens a fresh
    page), decode across page boundaries, and odd lengths — all
    token-identical to the rectangular engine and the solo generate."""
    cfg, params = served_model
    lens = [8, 16, 5, 9, 12]                  # 8, 16: exactly k*page_size
    budgets = [12, 10, 6, 9, 3]               # 12 from row 8: crosses 16
    prompts = _prompts(cfg, lens)
    eng = _assert_paged_matches_rect(
        params, cfg, prompts, budgets,
        ServeConfig(greedy=True, page_size=8))
    for u, (p, b) in enumerate(zip(prompts, budgets)):
        gen, _ = generate(params, cfg, p[None],
                          ServeConfig(max_new_tokens=b, greedy=True))
        np.testing.assert_array_equal(np.asarray(gen[0]),
                                      eng.done[u].output)


def test_paged_identity_default_page_size(served_model):
    """The production default (page_size=64, clamped to max_len) is a
    drop-in: no overcommit, no behavior change."""
    cfg, params = served_model
    prompts = _prompts(cfg, [5, 9, 12, 6], seed=2)
    eng = _assert_paged_matches_rect(params, cfg, prompts, [6, 3, 8, 5],
                                     ServeConfig(greedy=True))
    assert eng.kv.page_size == 32 and eng.kv.lin_pages == 1
    assert eng.stats["preemptions"] == 0 and eng.stats["page_waits"] == 0


def test_paged_eos_and_streaming(served_model):
    cfg, params = served_model
    prompts = _prompts(cfg, [6, 8], seed=3)
    ref_out, _ = _run(params, cfg, prompts, [8, 8],
                      ServeConfig(greedy=True, paged=False))
    eos = int(ref_out[0][2])
    if eos in (int(ref_out[0][0]), int(ref_out[0][1])):
        pytest.skip("greedy output repeats; eos would hit earlier")
    paged_out, _ = _run(params, cfg, prompts, [8, 8],
                        ServeConfig(greedy=True, page_size=8),
                        eos={0: eos})
    np.testing.assert_array_equal(paged_out[0], ref_out[0][:3])
    np.testing.assert_array_equal(paged_out[1], ref_out[1])


def test_hybrid_ring_wrap_in_paged_pool():
    """Sliding-window ring (window < max_len so decode wraps the ring)
    paged: token-identical to the rectangular ring."""
    cfg = dataclasses.replace(configs.get_smoke("zamba2-1.2b"),
                              dtype="float32", sliding_window=16)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg, [5, 7], seed=4)
    # pos reaches 5+26=31 >= virtual ring 16 -> wraps several times
    eng = _assert_paged_matches_rect(
        params, cfg, prompts, [26, 20],
        ServeConfig(greedy=True, page_size=8))
    assert eng.kv.has_ring and not eng.kv.has_linear
    assert eng.kv.ring_pages == 2


def test_mla_paged_identity():
    cfg = dataclasses.replace(configs.get_smoke("deepseek-v2-lite-16b"),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg, [5, 9, 12], seed=5)
    _assert_paged_matches_rect(params, cfg, prompts, [6, 4, 8],
                               ServeConfig(greedy=True, page_size=8))


def test_ssm_family_falls_back_rectangular():
    cfg = dataclasses.replace(configs.get_smoke("mamba2-370m"),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(params, cfg, ServeConfig(greedy=True),
                          max_batch=2, max_len=16)
    assert not eng.paged and eng.kv is None
    eng.submit(Request(0, np.arange(1, 5, dtype=np.int32),
                       max_new_tokens=3))
    assert len(eng.run()[0].output) == 3


# ---------------------------------------------------------------------------
# overcommit: admission queueing, preemption, leak regression
# ---------------------------------------------------------------------------


def test_pool_exhaustion_queues_without_crash(served_model):
    """A pool half the rectangle: admission gates on free pages (FIFO
    head-of-line), everything still completes token-identically."""
    cfg, params = served_model
    lens = [6, 9, 5, 7, 11, 4]
    budgets = [20, 18, 15, 12, 10, 16]
    prompts = _prompts(cfg, lens, seed=6)
    eng = _assert_paged_matches_rect(
        params, cfg, prompts, budgets,
        ServeConfig(greedy=True, page_size=4, kv_pool_pages=12),
        max_batch=3)
    assert eng.stats["page_waits"] > 0, "the pool never gated admission"
    assert eng.kv.peak_used_pages <= eng.kv.n_pages - 1


def test_decode_exhaustion_preempts_youngest(served_model):
    """Two slots admitted cheap, then both grow: the pool runs dry
    mid-decode, a victim is preempted (requeued, re-prefilled) and
    every output still matches the solo generate loop. With equal
    recompute costs (identical prompt lengths and lockstep positions,
    prefix cache off) the cost-aware policy degenerates to
    youngest-first — the tie-break scheduler.pick_preemption_victim
    guarantees."""
    cfg, params = served_model
    prompts = _prompts(cfg, [4, 4], seed=7)
    out, eng = _run(params, cfg, prompts, [24, 24],
                    ServeConfig(greedy=True, page_size=4,
                                kv_pool_pages=9, prefix_cache=False),
                    max_len=32)
    assert eng.stats["preemptions"] >= 1
    # youngest-first: the first-admitted request is never evicted (its
    # admission step never moves), the younger one is re-admitted later
    assert eng.admission_step[0] == 0
    assert eng.admission_step[1] > 0
    for u, p in enumerate(prompts):
        gen, _ = generate(params, cfg, p[None],
                          ServeConfig(max_new_tokens=24, greedy=True))
        np.testing.assert_array_equal(out[u], np.asarray(gen[0]))
    assert eng.kv.used_pages == 0


def test_uid_reuse_cannot_leak_pages_or_read_stale_tables(served_model):
    """Regression (satellite): completion frees the slot's pages and
    zeroes its block-table rows; reusing the uid after clear_finished()
    allocates fresh pages and reproduces the fresh-engine output."""
    cfg, params = served_model
    eng = InferenceEngine(params, cfg,
                          ServeConfig(greedy=True, page_size=4,
                                      prefix_cache=False),
                          max_batch=1, max_len=32)
    p = _prompts(cfg, [9], seed=8)[0]
    first = eng.submit(Request(0, p, max_new_tokens=6)).result()
    assert eng.kv.used_pages == 0, "completion must free pages"
    assert all((t == 0).all() for t in eng.kv.tables.values()), \
        "stale block-table rows survived completion"
    eng.clear_finished()
    assert not eng.done and eng.kv.used_pages == 0
    again = eng.submit(Request(0, p, max_new_tokens=6)).result()
    np.testing.assert_array_equal(first, again)
    # prompt 9 rows + 6 generated = 15 rows -> never more than 4 pages
    assert eng.kv.used_pages == 0 and eng.kv.peak_used_pages == 4


def test_wave_shim_runs_on_paged_engine(served_model):
    """Satellite: the deprecated BatchServer drives whichever cache
    layout the engine was built with — paged (default) and rectangular
    waves produce identical greedy outputs."""
    cfg, params = served_model
    prompts = _prompts(cfg, [4, 11, 7, 9], seed=9)
    budgets = [5, 2, 7, 4]
    outs = {}
    for name, scfg in (("paged", ServeConfig(greedy=True, page_size=8)),
                       ("rect", ServeConfig(greedy=True, paged=False))):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            srv = BatchServer(params, cfg, scfg, max_batch=2, max_len=32)
        for uid, (p, b) in enumerate(zip(prompts, budgets)):
            srv.submit(Request(uid, p, max_new_tokens=b))
        outs[name] = srv.run()
    assert srv.engine.paged is False
    for uid in range(len(prompts)):
        np.testing.assert_array_equal(outs["paged"][uid].output,
                                      outs["rect"][uid].output)


# ---------------------------------------------------------------------------
# Pallas gather kernel: CPU-interpreter parity vs the pure-jax oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,D,NP,PS,pages,window,ring", [
    (3, 4, 2, 16, 9, 8, 4, 0, False),        # GQA, linear
    (2, 8, 8, 16, 17, 4, 6, 0, False),       # MHA, many small pages
    (2, 4, 2, 16, 9, 8, 2, 6, True),         # sliding-window ring wrap
    (1, 4, 4, 32, 5, 16, 3, 10, False),      # windowed linear
])
def test_paged_kernel_matches_ref(B, Hq, Hkv, D, NP, PS, pages, window,
                                  ring):
    rng = np.random.default_rng(B * 100 + pages)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, NP, size=(B, pages)), jnp.int32)
    rows = pages * PS
    q_pos = jnp.asarray(rng.integers(1, rows + 20, size=(B,)), jnp.int32)
    cache_pos = q_pos % rows if ring else jnp.minimum(q_pos, rows - 1)
    want = ref.paged_attention_ref(q, kp, vp, bt, q_pos, cache_pos,
                                   window=window, scale=0.125)
    got = paged_decode_attention(q, kp, vp, bt, q_pos, cache_pos,
                                 window=window, scale=0.125,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_ref_matches_rectangular_sdpa():
    """The gather oracle == attention over the equivalent rectangle."""
    from repro.models import layers as L
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, PS, pages = 2, 4, 2, 8, 4, 3
    rows = pages * PS
    # build a rectangle, then scatter it into pages per a block table
    k_rect = jnp.asarray(rng.standard_normal((B, rows, Hkv, D)), jnp.float32)
    v_rect = jnp.asarray(rng.standard_normal((B, rows, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    NP = B * pages + 1
    bt = np.zeros((B, pages), np.int32)
    kp = np.zeros((NP, PS, Hkv, D), np.float32)
    vp = np.zeros((NP, PS, Hkv, D), np.float32)
    page = 1
    for b in range(B):
        for j in range(pages):
            bt[b, j] = page
            kp[page] = np.asarray(k_rect[b, j * PS:(j + 1) * PS])
            vp[page] = np.asarray(v_rect[b, j * PS:(j + 1) * PS])
            page += 1
    q_pos = jnp.asarray([5, rows - 1], jnp.int32)
    msk = L._decode_mask(q_pos[:, None], q_pos, rows, 0)
    want = L.sdpa(q, k_rect, v_rect, msk, 0.3)
    got = ref.paged_attention_ref(q, jnp.asarray(kp), jnp.asarray(vp),
                                  jnp.asarray(bt), q_pos, q_pos,
                                  window=0, scale=0.3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# S > 1 verify reads (the speculative k+1 forward) on the paged kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,window,ring", [
    (2, 0, False),                           # shortest multi-token span
    (3, 6, True),                            # windowed, span wraps the ring
    (4, 10, True),
    (5, 0, False),                           # k=4 verify (k+1 queries)
])
def test_paged_kernel_multitoken_matches_ref(S, window, ring):
    """S>1 spans through the shipped S>1 dispatch (ops.paged_attention:
    S shifted single-token launches) against the oracle's joint
    reconstruction, including sliding-window ring wrap under S>1."""
    from repro.kernels import ops as kops
    rng = np.random.default_rng(20 + S)
    B, Hq, Hkv, D, PS, pages = 2, 4, 2, 16, 4, 3
    NP = B * pages + 1
    rows = pages * PS
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    # writable pages exclusive per slot (kernels.ref.decode_step_ref)
    bt = jnp.asarray(np.arange(1, NP).reshape(B, pages), jnp.int32)
    if ring:
        q_pos = jnp.asarray(rng.integers(rows, 2 * rows - S, B), jnp.int32)
        cache_pos = q_pos % rows
    else:
        q_pos = jnp.asarray(rng.integers(0, rows - S, B), jnp.int32)
        cache_pos = q_pos
    pol = kops.KernelPolicy(mode="pallas", interpret=True)
    got = kops.paged_attention(q, kp, vp, bt, q_pos, cache_pos,
                               window=window, scale=0.125, policy=pol)
    want = ref.paged_attention_ref(q, kp, vp, bt, q_pos, cache_pos,
                                   window=window, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_multitoken_exact_page_boundary():
    """Exact page-boundary spans for pos+k verify reads: slot 0's
    4-token span is exactly one full page (rows 4..7 of page 1), slot
    1's starts on the last row of page 0 and crosses into page 1."""
    from repro.kernels import ops as kops
    rng = np.random.default_rng(7)
    B, S, Hq, Hkv, D, PS, pages = 2, 4, 4, 2, 16, 4, 3
    NP = B * pages + 1
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    bt = jnp.asarray(np.arange(1, NP).reshape(B, pages), jnp.int32)
    q_pos = jnp.asarray([PS, PS - 1], jnp.int32)
    pol = kops.KernelPolicy(mode="pallas", interpret=True)
    got = kops.paged_attention(q, kp, vp, bt, q_pos, q_pos,
                               scale=0.25, policy=pol)
    want = ref.paged_attention_ref(q, kp, vp, bt, q_pos, q_pos, scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # ... and per-token sequential equivalence at the same positions
    for j in range(S):
        want_j = ref.paged_attention_ref(q[:, j:j + 1], kp, vp, bt,
                                         q_pos + j, q_pos + j, scale=0.25)
        np.testing.assert_allclose(np.asarray(got[:, j:j + 1]),
                                   np.asarray(want_j),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# live bound: page groups past a slot's last valid row are skipped
# ---------------------------------------------------------------------------

LB_PS, LB_PAGES, LB_HKV, LB_G, LB_D = 4, 6, 4, 2, 16
LB_ROWS = LB_PS * LB_PAGES


def _lb_policy(ppb, hb):
    from repro.kernels import ops as kops
    return kops.KernelPolicy(mode="pallas", interpret=True,
                             paged_block_table=((10 ** 6,) * 4 + (ppb, hb),))


def _lb_positions(mode, ppb, S):
    """Ragged per-slot (q_pos, cache_pos) on the live bound's edges."""
    edges = np.array([0, LB_PS - 1, LB_PS, ppb * LB_PS - 1, ppb * LB_PS,
                      LB_ROWS - 1])
    if mode == "linear":                 # the last edge is a full table
        q = np.minimum(edges, LB_ROWS - S)
        return q, q
    if mode == "behind":                 # cache_pos < q_pos: rows wrap
        q = np.array([2, 7, 9, 13, 18, 21])
        return q, np.array([0, 3, 4, 12, 1, 20])
    q = LB_ROWS + np.minimum(edges, LB_ROWS - S)     # sliding-window ring
    return q, q % LB_ROWS


@pytest.mark.parametrize("ppb,hb,S,mode,window", [
    (1, 0, 1, "linear", 0),
    (2, 0, 1, "linear", 0),
    (4, 2, 1, "linear", 0),              # padded table, head tiles
    (3, 2, 1, "linear", 5),              # windowed linear
    (2, 0, 1, "behind", 0),
    (2, 2, 1, "ring", 10),
    (2, 0, 3, "linear", 0),              # speculative verify spans
    (4, 2, 4, "linear", 0),
    (3, 0, 2, "ring", 10),
])
def test_paged_kernel_live_bound_matches_ref(ppb, hb, S, mode, window):
    """The kernel at ragged per-slot lengths on the live bound's edges
    (q_pos 0, PS-1, PS, ppb*PS-1, ppb*PS, a full table) equals the
    oracle; linear slots map the null page past their last written
    row, as the engine does."""
    from repro.kernels import ops as kops
    q_pos, cache_pos = _lb_positions(mode, ppb, S)
    B = len(q_pos)
    rng = np.random.default_rng(100 * ppb + 10 * S + hb)
    NP = B * LB_PAGES + 1
    shape = (NP, LB_PS, LB_HKV, LB_D)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, S, LB_HKV * LB_G, LB_D)),
                    jnp.float32)
    bt = rng.permutation(np.arange(1, NP)).reshape(B, LB_PAGES)
    if mode == "linear":
        last = (q_pos + S - 1) // LB_PS
        bt[np.arange(LB_PAGES)[None, :] > last[:, None]] = 0
    bt, q_pos, cache_pos = (jnp.asarray(a, jnp.int32)
                            for a in (bt, q_pos, cache_pos))
    got = kops.paged_attention(q, kp, vp, bt, q_pos, cache_pos,
                               window=window, scale=0.25,
                               policy=_lb_policy(ppb, hb))
    want = ref.paged_attention_ref(q, kp, vp, bt, q_pos, cache_pos,
                                   window=window, scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ppb", [1, 2, 4])
def test_live_steps_bound_every_valid_row(ppb):
    """Every group past `live_steps` holds only masked rows, and on a
    linear cache the last live group holds a valid one (the bound is
    tight), by brute force over the ring reconstruction."""
    from repro.kernels.paged_attention import live_steps
    r = np.arange(LB_ROWS)
    qs, cs = np.meshgrid(np.arange(-1, 3 * LB_ROWS),
                         np.arange(-1, 2 * LB_ROWS))
    qs, cs = qs.ravel(), cs.ravel()
    live = np.asarray(live_steps(jnp.asarray(qs), jnp.asarray(cs),
                                 pages=LB_PAGES, page_size=LB_PS, ppb=ppb))
    valid = qs[:, None] - (cs[:, None] - r[None, :]) % LB_ROWS >= 0
    group = r // (ppb * LB_PS)
    assert not (valid & (group[None, :] >= live[:, None])).any()
    assert (live >= 1).all()
    lin = (qs == cs) & (qs >= 0) & (qs < LB_ROWS)
    assert (valid & (group[None, :] == live[:, None] - 1))[lin].any(1).all()


@pytest.mark.parametrize("ppb,hb", [(1, 0), (2, 2), (4, 0)])
def test_paged_kernel_skips_dead_groups(ppb, hb):
    """Real pages holding NaN, mapped past each slot's live bound: the
    oracle (and a kernel that read them) is poisoned through `p @ v`.
    The kernel neither copies their groups (the clamped index map) nor
    computes on them (the skipped body), so its output is finite and
    equals the oracle over the same pool with those pages zeroed."""
    q_pos = np.array([0, 5, 9, 13])
    B = len(q_pos)
    rng = np.random.default_rng(ppb + hb)
    NP = B * LB_PAGES + 1
    shape = (NP, LB_PS, LB_HKV, LB_D)
    kp = rng.standard_normal(shape).astype(np.float32)
    vp = rng.standard_normal(shape).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, LB_HKV * LB_G, LB_D)),
                    jnp.float32)
    bt = np.arange(1, NP).reshape(B, LB_PAGES)
    live_pages = np.maximum(1, -(-(q_pos // LB_PS + 1) // ppb)) * ppb
    dead = np.arange(LB_PAGES)[None, :] >= live_pages[:, None]
    assert dead.any(), "every case holds a dead group"
    kz, vz = kp.copy(), vp.copy()
    kp[bt[dead]] = np.nan
    vp[bt[dead]] = np.nan
    kz[bt[dead]] = 0.0
    vz[bt[dead]] = 0.0
    bt, qp = jnp.asarray(bt, jnp.int32), jnp.asarray(q_pos, jnp.int32)
    got = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp), bt,
                                 qp, qp, scale=0.25, pages_per_step=ppb,
                                 head_block=hb, interpret=True)
    poisoned = ref.paged_attention_ref(q, jnp.asarray(kp), jnp.asarray(vp),
                                       bt, qp, qp, scale=0.25)
    want = ref.paged_attention_ref(q, jnp.asarray(kz), jnp.asarray(vz),
                                   bt, qp, qp, scale=0.25)
    assert np.isnan(np.asarray(poisoned)[dead.any(1)]).all()
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_engine_counts_gather_walk(served_model):
    """`gather_pages_live` / `gather_pages_table` per decode step, over
    both slots (a finished slot keeps its stale pos), against positions
    reckoned by hand: page_size 8, max_len 32, so 4 table pages."""
    cfg, params = served_model
    eng = InferenceEngine(params, cfg, ServeConfig(greedy=True, page_size=8),
                          max_batch=2, max_len=32)
    seen = []
    decode = eng._decode

    def spy(params, tokens, cache, pos, *rest):
        seen.append(np.asarray(pos).tolist())
        return decode(params, tokens, cache, pos, *rest)
    eng._decode = spy
    for uid, (p, b) in enumerate(zip(_prompts(cfg, [5, 11, 20]),
                                     [3, 8, 4])):
        eng.submit(Request(uid, p, max_new_tokens=b))
    eng.run()
    # uid 0 (5 rows, 3 tokens) decodes at 5, 6; uid 2 takes its slot and
    # decodes at 20..22, then the slot idles at 23; uid 1 decodes 11..17
    assert seen == [[5, 11], [6, 12], [20, 13], [21, 14], [22, 15],
                    [23, 16], [23, 17]]
    live = sum(min(4, p // 8 + 1) for step in seen for p in step)
    assert live == 33
    assert eng.stats["gather_pages_live"] == live
    assert eng.stats["gather_pages_table"] == len(seen) * 2 * 4
    assert eng.stats["decode_steps"] == len(seen)


# ---------------------------------------------------------------------------
# tensor-parallel paged engine (forced host devices, subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_paged_tp_engine_token_identity():
    """Satellite: paged pool + 2-way tensor parallelism (pool kv-head
    dim sharded per sharding.rules, cache_pspecs(paged=True)) is greedy
    token-identical to the *rectangular unsharded* engine — mirroring
    test_engine.py::test_sharded_engine_token_identity but crossing
    both the layout and the mesh axis at once."""
    out = run_multidevice("""
        import dataclasses, jax, numpy as np
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as T
        from repro.models.config import ModelConfig
        from repro.serve.engine import InferenceEngine, ServeConfig
        from repro.serve.scheduler import Request

        cfg = ModelConfig(name="tiny", family="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                          vocab_size=256, loss_chunk=0, remat=False,
                          dtype="float32")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [np.arange(1, 7, dtype=np.int32),
                   np.arange(3, 12, dtype=np.int32),
                   np.arange(2, 10, dtype=np.int32)]
        budgets = [6, 3, 5]

        def run(scfg, mesh):
            eng = InferenceEngine(params, cfg, scfg, max_batch=2,
                                  max_len=32, mesh=mesh)
            for uid, (p, b) in enumerate(zip(prompts, budgets)):
                eng.submit(Request(uid, p, max_new_tokens=b))
            return {u: r.output for u, r in eng.run().items()}, eng

        ref, _ = run(ServeConfig(greedy=True, paged=False), None)
        got, eng = run(ServeConfig(greedy=True, page_size=8),
                       make_serving_mesh(2))
        assert eng.paged and eng.mesh is not None
        # the page pool really is kv-head-sharded on the model axis
        # (trailing None may be trimmed from the spec)
        spec = tuple(eng.cache["layers"]["k"].sharding.spec)
        assert spec[:4] == (None, None, None, "model"), spec
        for u in ref:
            np.testing.assert_array_equal(ref[u], got[u])
        print("paged TP token-identity OK")
    """, devices=2)
    assert "OK" in out

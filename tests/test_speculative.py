"""Self-speculative decoding (serve.speculative) + rank-truncated views.

Covers the zero-copy rank_truncated_view (buffer identity, static
EffRank marker, jit-cache sharing), the rank-r' == rmask-zeroed-full
property across the plain / merged-QKV / expert-grid / non-divisible-TP
fallback launches, the PagedKVState reserve/trim rollback primitives,
multi-token paged attention vs sequential single-token decode, and the
engine-level guarantees: greedy token identity vs the plain engine
(exact and truncated drafts), rollback page-leak regression with uid
reuse under an overcommitted pool, gating errors, and the dynamic-k
controller.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice

from repro.kernels import ops, ref
from repro.models import transformer as T
from repro.quant.surgery import (EffRank, _stack_group,
                                 abstract_quantized_params,
                                 rank_truncated_view, truncated_rank)
from repro.serve import (InferenceEngine, PagedKVState, Request,
                         ServeConfig)

_POLICIES = [ops.KernelPolicy(mode="ref"),
             ops.KernelPolicy(mode="pallas", interpret=True)]
_IDS = ["ref", "pallas"]


def _mk_lowrank(m, k, n, r, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    kx, ku, kv, k1, k2 = jax.random.split(key, 5)
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    u = jnp.sign(jax.random.normal(ku, (n, r)))
    v = jnp.sign(jax.random.normal(kv, (k, r)))
    qu_t = ref.pack_signs(jnp.where(u == 0, 1.0, u).T)
    qv = ref.pack_signs(jnp.where(v == 0, 1.0, v))
    s1 = jnp.abs(jax.random.normal(k1, (n,))) + 0.1
    s2 = jnp.abs(jax.random.normal(k2, (k,))) + 0.1
    return x, qv, qu_t, s1, s2


# ---------------------------------------------------------------------------
# rank_truncated_view: arithmetic, zero-copy, static marker
# ---------------------------------------------------------------------------


def test_truncated_rank_arithmetic():
    assert truncated_rank(96, 1.0) == 96
    assert truncated_rank(96, 0.5) == 32       # floor to rank_align
    assert truncated_rank(96, 0.75) == 64
    assert truncated_rank(96, 0.01) == 32      # clamped to one tile
    assert truncated_rank(32, 0.5) == 32       # never below align
    assert truncated_rank(128, 0.5) == 64


def test_view_is_zero_copy_and_static():
    _, qv, qu_t, s1, s2 = _mk_lowrank(4, 64, 64, 96)
    params = {"blk": {"wq": {"qv": qv, "qu_t": qu_t, "s1": s1, "s2": s2},
                      "norm": s1}}
    view = rank_truncated_view(params, 0.5)
    vq = view["blk"]["wq"]
    # every array leaf IS the original buffer — no copies, no slices
    for k in ("qv", "qu_t", "s1", "s2"):
        assert vq[k] is params["blk"]["wq"][k]
    assert view["blk"]["norm"] is params["blk"]["norm"]
    assert int(vq["eff_rank"]) == 48 // 32 * 32
    # EffRank is aux_data, not a traced leaf: same leaf count as params
    assert len(jax.tree.leaves(view)) == len(jax.tree.leaves(params))
    # full-rank fraction returns the very same dict objects
    full = rank_truncated_view(params, 1.0)
    assert full is params
    # equal fractions share one treedef => one jit cache entry
    t1 = jax.tree.structure(rank_truncated_view(params, 0.5))
    t2 = jax.tree.structure(rank_truncated_view(params, 0.5))
    assert t1 == t2
    assert t1 != jax.tree.structure(rank_truncated_view(params, 0.75))
    assert EffRank(64) == EffRank(64) and EffRank(64) != EffRank(32)
    with pytest.raises(ValueError):
        rank_truncated_view(params, 0.0)
    with pytest.raises(ValueError):
        rank_truncated_view(params, 1.5)


# ---------------------------------------------------------------------------
# property: rank-r' view == full model with trailing components zeroed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", _POLICIES, ids=_IDS)
def test_eff_rank_matches_rmask_zeroed_plain(policy):
    x, qv, qu_t, s1, s2 = _mk_lowrank(5, 64, 96, 96)
    rp = 32
    got = ops.lowrank_binary_matmul(x, qv, qu_t, s1, s2, policy=policy,
                                    eff_rank=rp)
    want = ref.lowrank_binary_matmul_fused_ref(
        x, qv, qu_t, s1, s2,
        rmask=(jnp.arange(96) < rp).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("policy", _POLICIES, ids=_IDS)
def test_eff_rank_matches_rmask_zeroed_merged(policy):
    # two sibling projections with DIFFERENT true ranks: the view's
    # eff_rank composes with the pad-rank rmask of the merged layout
    x, qv_a, qu_a, s1_a, s2_a = _mk_lowrank(4, 64, 96, 96, seed=1)
    _, qv_b, qu_b, s1_b, s2_b = _mk_lowrank(4, 64, 64, 64, seed=2)
    subs = [{"qv": qv_a, "qu_t": qu_a, "s1": s1_a, "s2": s2_a},
            {"qv": qv_b, "qu_t": qu_b, "s1": s1_b, "s2": s2_b}]
    mp = _stack_group(subs)                     # padded R = 96
    view = rank_truncated_view({"wqkv": mp}, 0.75)["wqkv"]
    rp = int(view["eff_rank"])
    assert rp == 64
    outs = ops.lowrank_binary_matmul_merged(x, mp, (96, 64),
                                            policy=policy, eff_rank=rp)
    cut = (jnp.arange(96) < rp).astype(jnp.float32)
    for i, (sub, n) in enumerate(zip(subs, (96, 64))):
        want = ref.lowrank_binary_matmul_fused_ref(
            x, mp["qv"][i], mp["qu_t"][i], mp["s1"][i], mp["s2"][i],
            rmask=mp["rmask"][i] * cut)[:, :n]
        np.testing.assert_allclose(np.asarray(outs[i]),
                                   np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("policy", _POLICIES, ids=_IDS)
def test_eff_rank_matches_rmask_zeroed_expert(policy):
    E, C, K, N, R = 3, 4, 64, 64, 96
    packs = [_mk_lowrank(C, K, N, R, seed=7 + e) for e in range(E)]
    x = jnp.stack([p[0] for p in packs])
    qv = jnp.stack([p[1] for p in packs])
    qu_t = jnp.stack([p[2] for p in packs])
    s1 = jnp.stack([p[3] for p in packs])
    s2 = jnp.stack([p[4] for p in packs])
    rp = 64
    got = ops.lowrank_binary_matmul_expert(x, qv, qu_t, s1, s2,
                                           policy=policy, eff_rank=rp)
    cut = (jnp.arange(R) < rp).astype(jnp.float32)
    for e in range(E):
        want = ref.lowrank_binary_matmul_fused_ref(
            x[e], qv[e], qu_t[e], s1[e], s2[e], rmask=cut)
        np.testing.assert_allclose(np.asarray(got[e]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_eff_rank_tp_nondivisible_fallback():
    # d_out=76 is not divisible by tp=2: _tp_lowrank declines and the
    # launch falls back to the local kernel — eff_rank must survive the
    # fallback. d_out=96 goes through the sharded launch for contrast.
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.kernels import ops, ref
        mesh = jax.make_mesh((2,), ("model",))
        pol = ops.KernelPolicy(mode="pallas", interpret=True, mesh=mesh)
        key = jax.random.PRNGKey(3)
        for n in (76, 96):
            kx, ku, kv, k1, k2 = jax.random.split(
                jax.random.fold_in(key, n), 5)
            x = jax.random.normal(kx, (4, 64), jnp.float32)
            u = jnp.sign(jax.random.normal(ku, (n, 96)))
            v = jnp.sign(jax.random.normal(kv, (64, 96)))
            qu_t = ref.pack_signs(jnp.where(u == 0, 1.0, u).T)
            qv = ref.pack_signs(jnp.where(v == 0, 1.0, v))
            s1 = jnp.abs(jax.random.normal(k1, (n,))) + 0.1
            s2 = jnp.abs(jax.random.normal(k2, (64,))) + 0.1
            got = ops.lowrank_binary_matmul(x, qv, qu_t, s1, s2,
                                            policy=pol, tp="col",
                                            eff_rank=64)
            want = ref.lowrank_binary_matmul_fused_ref(
                x, qv, qu_t, s1, s2,
                rmask=(jnp.arange(96) < 64).astype(jnp.float32))
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=1e-5, atol=1e-5)
        print("TP_FALLBACK_OK")
    """, devices=2)
    assert "TP_FALLBACK_OK" in out


# ---------------------------------------------------------------------------
# PagedKVState: reserve_rows / trim (the rollback primitives)
# ---------------------------------------------------------------------------


def test_reserve_rows_and_trim(tiny_dense_cfg):
    kv = PagedKVState(tiny_dense_cfg, max_batch=2, max_len=32,
                      page_size=8, n_pages=7)
    kv.admit(0, 5)                                   # 1 page
    assert kv.used_pages == 1
    assert kv.reserve_rows(0, 17)                    # rows 0..16: 3 pages
    assert kv.used_pages == 3
    assert kv.reserve_rows(0, 17) and kv.used_pages == 3    # idempotent
    # trim back to 6 committed rows: keep ceil(6/8)=1 page, free 2
    assert kv.trim(0, 6) == 2
    assert kv.used_pages == 1
    assert (kv.tables["linear"][0, 1:] == 0).all()
    assert kv.trim(0, 6) == 0                        # nothing to drop
    # freed pages are reusable by another slot
    kv.admit(1, 30)                                  # 4 pages
    assert kv.used_pages == 5
    # pool exhaustion: reserve fails but partial mapping sticks, and a
    # retry after pages free up completes the reservation
    assert not kv.reserve_rows(0, 32)
    kv.release(1)
    assert kv.reserve_rows(0, 32) and kv.used_pages == 4
    kv.release(0)
    assert kv.used_pages == 0
    assert (kv.tables["linear"] == 0).all()


def test_rollback_then_redraft_same_page(tiny_dense_cfg):
    """Mid-page reject: trimming draft rows that live on the committed
    page must free nothing and keep the mapping intact, and the next
    draft cycle reserves straight back into the SAME page (no
    alloc/free churn inside a page)."""
    kv = PagedKVState(tiny_dense_cfg, max_batch=1, max_len=32,
                      page_size=8, n_pages=5)
    kv.admit(0, 3)                             # 3 committed rows, page A
    assert kv.used_pages == 1
    assert kv.reserve_rows(0, 3 + 4)           # draft k=4: rows 3..6
    assert kv.used_pages == 1                  # still inside page A
    before = np.asarray(kv.tables["linear"][0]).copy()
    assert kv.trim(0, 4) == 0                  # accept 1, reject 3
    assert (np.asarray(kv.tables["linear"][0]) == before).all()
    assert kv.reserve_rows(0, 4 + 4)           # redraft: rows 4..7
    assert kv.used_pages == 1                  # same page reused
    assert (np.asarray(kv.tables["linear"][0]) == before).all()
    # a draft that crossed into a fresh page: reject past the boundary
    # frees the overflow page, redraft re-allocates one
    assert kv.reserve_rows(0, 8 + 4)           # rows 8..11: page B
    assert kv.used_pages == 2
    assert kv.trim(0, 8) == 1                  # reject all of page B
    assert kv.used_pages == 1
    assert kv.reserve_rows(0, 8 + 4) and kv.used_pages == 2
    kv.release(0)
    assert kv.used_pages == 0


@pytest.mark.parametrize("policy", _POLICIES, ids=_IDS)
@pytest.mark.parametrize("S", [1, 3])
def test_rollback_stale_rows_never_read(policy, S):
    """After a rollback the pool still holds the rejected drafts' KV
    past the live position — the kernel's position reconstruction must
    exclude them. Kernel on the dirty pool == oracle on a pool with
    every stale row zeroed (random stale values would shift the
    softmax if they leaked in)."""
    rng = np.random.default_rng(40 + S)
    B, Hq, Hkv, D, PS, pages = 2, 4, 2, 16, 4, 3
    NP = B * pages + 1
    rows = pages * PS
    kp = rng.standard_normal((NP, PS, Hkv, D)).astype(np.float32)
    vp = rng.standard_normal((NP, PS, Hkv, D)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    bt = np.arange(1, NP).reshape(B, pages).astype(np.int32)
    # live span p .. p+S-1 (linear, no wrap); rows past it are stale
    p = np.asarray([3, PS - 1], np.int32)
    kc, vc = kp.copy(), vp.copy()
    for b in range(B):
        for r in range(int(p[b]) + S, rows):
            pg, off = bt[b, r // PS], r % PS
            kc[pg, off] = 0.0
            vc[pg, off] = 0.0
    q_pos = jnp.asarray(p, jnp.int32)
    got = ops.paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                              jnp.asarray(bt), q_pos, q_pos,
                              scale=0.25, policy=policy)
    want = ref.paged_attention_ref(q, jnp.asarray(kc), jnp.asarray(vc),
                                   jnp.asarray(bt), q_pos, q_pos,
                                   scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# multi-token paged attention == sequential single-token decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", _POLICIES, ids=_IDS)
def test_multitoken_paged_attention_matches_sequential(policy):
    B, S, Hq, Hkv, D, ps, pages = 2, 3, 4, 2, 16, 4, 9
    key = jax.random.PRNGKey(11)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, Hq, D), jnp.float32)
    k_pool = jax.random.normal(kk, (pages, ps, Hkv, D), jnp.float32)
    v_pool = jax.random.normal(kv_, (pages, ps, Hkv, D), jnp.float32)
    table = np.zeros((B, 4), np.int32)
    table[0, :3] = [1, 2, 3]
    table[1, :3] = [4, 5, 6]
    table = jnp.asarray(table)
    # first query positions: slot 0 at 5, slot 1 at 9 (page-boundary
    # straddle: 9..11 spans rows 9,10,11 across pages 2 and 3)
    pos = jnp.asarray([5, 9], jnp.int32)
    got = ops.paged_attention(q, k_pool, v_pool, table, pos, pos,
                              policy=policy)
    for j in range(S):
        want_j = ops.paged_attention(q[:, j:j + 1], k_pool, v_pool,
                                     table, pos + j, pos + j,
                                     policy=policy)
        np.testing.assert_allclose(np.asarray(got[:, j]),
                                   np.asarray(want_j[:, 0]),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine: greedy token identity, rollback, gating, dynamic k
# ---------------------------------------------------------------------------


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=(n,)).astype(np.int32)
            for n in lens]


def _serve(params, cfg, prompts, budgets, scfg, max_batch=2, max_len=48,
           uids=None):
    eng = InferenceEngine(params, cfg, scfg, max_batch=max_batch,
                          max_len=max_len)
    for uid, (p, b) in zip(uids or range(len(prompts)),
                           zip(prompts, budgets)):
        eng.submit(Request(uid, p, max_new_tokens=b))
    done = eng.run()
    return {u: r.output for u, r in done.items()}, eng


def _random_packed(cfg, seed=0, target_bpw=2.0):
    """Random-valued packed params in the real quantized layout (rank
    64 at bpw 2 for the 64x64 tiny linears — big enough to truncate).
    Logits are junk, but the engine serves them deterministically: a
    genuinely-different truncated draft exercises reject + rollback
    while identity must still hold. Scales are UNIT (a dominant random
    s1 row would make the argmax truncation-invariant — acceptance 1.0
    — and the rollback path would never fire)."""
    tpl = abstract_quantized_params(cfg, target_bpw=target_bpw)
    rng = np.random.default_rng(seed)

    def fill(path, s):
        last = getattr(path[-1], "key", str(path[-1]))
        if s.dtype == jnp.uint32:
            return jnp.asarray(rng.integers(
                0, 2**32, size=s.shape, dtype=np.uint64).astype(np.uint32))
        if last in ("s1", "s2"):
            return jnp.ones(s.shape, s.dtype)
        return jnp.asarray(rng.normal(0, 0.05, s.shape).astype(s.dtype))

    return jax.tree_util.tree_map_with_path(fill, tpl)


def test_spec_identity_fp_full_rank(tiny_dense_cfg, tiny_params):
    # FP params carry no packed dicts: the view IS the params, every
    # draft verifies, and acceptance is exactly 1.0
    cfg, params = tiny_dense_cfg, tiny_params
    prompts = _prompts(cfg.vocab_size, [5, 9, 3])
    budgets = [12, 8, 14]
    base = ServeConfig(greedy=True, page_size=8, prefix_cache=False)
    plain, _ = _serve(params, cfg, prompts, budgets, base)
    spec_cfg = dataclasses.replace(base, spec_rank_frac=1.0, spec_k=4)
    spec, eng = _serve(params, cfg, prompts, budgets, spec_cfg)
    for u in plain:
        np.testing.assert_array_equal(plain[u], spec[u])
    assert eng.spec is not None
    assert eng.spec.draft_params is eng.params          # zero-copy
    assert eng.spec.acceptance_rate() == 1.0
    assert eng.stats["spec_rollback_tokens"] == 0
    # k+1 tokens per cycle => far fewer device calls than tokens
    n_tok = sum(len(v) for v in spec.values())
    assert eng.stats["decode_steps"] < n_tok
    assert eng.kv.used_pages == 0


def test_spec_identity_truncated_draft_with_rollback(tiny_dense_cfg):
    cfg = tiny_dense_cfg
    params = _random_packed(cfg)
    prompts = _prompts(cfg.vocab_size, [6, 11, 4], seed=3)
    budgets = [10, 8, 12]
    base = ServeConfig(greedy=True, page_size=8, prefix_cache=False)
    plain, _ = _serve(params, cfg, prompts, budgets, base)
    spec_cfg = dataclasses.replace(base, spec_rank_frac=0.5, spec_k=4)
    spec, eng = _serve(params, cfg, prompts, budgets, spec_cfg)
    for u in plain:
        np.testing.assert_array_equal(plain[u], spec[u])
    # the rank-32 draft of a random rank-64 model disagrees often:
    # rejects (and page rollback accounting) must actually fire
    assert eng.stats["spec_rollback_tokens"] > 0
    assert eng.stats["spec_draft_tokens"] == \
        eng.stats["spec_accepted_tokens"] + \
        eng.stats["spec_rollback_tokens"]
    assert eng.kv.used_pages == 0
    assert (eng.kv.tables["linear"] == 0).all()


def test_spec_rollback_never_leaks_pages_uid_reuse(tiny_dense_cfg):
    # overcommitted pool: reservation preempts mid-flight slots while
    # rollback trims draft pages — after two full drains with REUSED
    # uids, every page must be home and outputs must reproduce
    cfg = tiny_dense_cfg
    params = _random_packed(cfg, seed=5)
    prompts = _prompts(cfg.vocab_size, [8, 8, 8, 8], seed=9)
    budgets = [12, 12, 12, 12]
    scfg = ServeConfig(greedy=True, page_size=8, kv_pool_pages=10,
                       prefix_cache=False, spec_rank_frac=0.5, spec_k=4)
    first, eng1 = _serve(params, cfg, prompts, budgets, scfg,
                         max_batch=3, max_len=32)
    assert eng1.kv.used_pages == 0, "drained engine must hold no pages"
    assert (eng1.kv.tables["linear"] == 0).all()
    second, eng2 = _serve(params, cfg, prompts, budgets, scfg,
                          max_batch=3, max_len=32,
                          uids=[0, 1, 2, 3])
    for u in first:
        np.testing.assert_array_equal(first[u], second[u])
    assert eng2.kv.used_pages == 0
    assert eng2.kv.free_pages == eng1.kv.free_pages


def test_spec_gating_errors(tiny_dense_cfg, tiny_params):
    cfg, params = tiny_dense_cfg, tiny_params

    def build(**kw):
        return InferenceEngine(params, cfg,
                               ServeConfig(**{"greedy": True,
                                              "page_size": 8, **kw}),
                               max_batch=2, max_len=32)

    with pytest.raises(ValueError, match="greedy"):
        build(greedy=False, spec_rank_frac=0.5)
    with pytest.raises(ValueError, match="paged"):
        build(paged=False, spec_rank_frac=0.5)
    with pytest.raises(ValueError, match="spec_rank_frac"):
        build(spec_rank_frac=1.5)
    with pytest.raises(ValueError, match="spec_k"):
        build(spec_rank_frac=0.5, spec_k=2, spec_k_min=3)


def test_spec_dynamic_k_shrinks_on_low_acceptance(tiny_dense_cfg):
    cfg = tiny_dense_cfg
    params = _random_packed(cfg, seed=1)
    prompts = _prompts(cfg.vocab_size, [6, 6], seed=2)
    scfg = ServeConfig(greedy=True, page_size=8, spec_rank_frac=0.5,
                       spec_k=4, spec_k_min=1)
    _, eng = _serve(params, cfg, prompts, [16, 16], scfg)
    # near-zero acceptance on the random model: the EMA controller must
    # have walked k down from its ceiling
    assert eng.spec.acceptance_rate() < 0.5
    assert eng.spec.k < eng.spec.k_max
    assert eng.spec.k >= eng.spec.k_min
    # per-uid accounting covers exactly the submitted requests
    assert set(eng.spec.acceptance) == {0, 1}


# ---------------------------------------------------------------------------
# bf16 vs f32 greedy argmax divergence under TP=2 (docs/serving.md)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bf16_tp2_argmax_divergence_rate():
    # teacher-forced per-position argmax, TP=2 vs single-device: f32
    # must match exactly (reassociation-safe reductions at this scale);
    # bf16 may flip near-ties — the measured rate is recorded in
    # docs/serving.md §Tensor-parallel serving
    out = run_multidevice("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import transformer as T
        from repro.models.config import ModelConfig
        from repro.serve import InferenceEngine, ServeConfig
        from repro.launch.mesh import make_serving_mesh

        B, S = 4, 48
        mesh = make_serving_mesh(2)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, 256, size=(B, S)), jnp.int32)
        for dtype in ("float32", "bfloat16"):
            cfg = ModelConfig(name="tiny", family="dense", n_layers=2,
                              d_model=64, n_heads=4, n_kv_heads=2,
                              d_ff=128, vocab_size=256, loss_chunk=0,
                              remat=False, dtype=dtype)
            params = T.init_params(jax.random.PRNGKey(0), cfg)
            scfg = ServeConfig(greedy=True, paged=False)
            preds = []
            for m in (None, mesh):
                eng = InferenceEngine(params, cfg, scfg, max_batch=B,
                                      max_len=S + 1, mesh=m)

                def fwd(p, t, cache):
                    with eng._policy_scope():
                        h, _ = T._cached_forward(p, cfg, t, cache, 0)
                        return T.logits_fn(p, cfg, h)

                lg = jax.jit(fwd)(eng.params, toks, eng.cache)
                preds.append(np.asarray(
                    jnp.argmax(lg.astype(jnp.float32), axis=-1)))
            rate = float((preds[0] != preds[1]).mean())
            print(f"DIVERGENCE {dtype} {rate:.6f}")
            if dtype == "float32":
                assert rate == 0.0, "f32 TP must be argmax-identical"
    """, devices=2)
    assert "DIVERGENCE float32 0.000000" in out
    assert "DIVERGENCE bfloat16" in out

"""Differential kernel fuzz harness: fused pallas kernels == unfused
chains == pure-jnp oracles over randomized shapes.

Every property draws ONE integer ``seed`` (via the hypothesis shim —
real hypothesis when installed) and derives the whole case from
``np.random.default_rng(seed)``; the seed is embedded in the assertion
message, so any reported failure replays bit-for-bit with
``_case(seed)``. Example counts scale with the ``NQ_FUZZ_EXAMPLES``
env var (the full profile in ``kernel_bench``/CI docs runs >= 200
generated cases across the suite; the tier-1 default stays small so
the interpreter-mode kernels don't dominate the test wall clock).

Covered differentials:

- fused single-pass matmul vs legacy two-call pallas chain vs
  ``ref.lowrank_binary_matmul_fused_ref`` (dtype in {f32, bf16},
  eff_rank truncation, off-block K like 704 that the divisor-fitted
  tiles must launch pad-free);
- merged multi-projection launch (ragged true ranks via rmask) vs the
  per-projection oracle;
- paged gather attention vs ``ref.paged_attention_ref`` across
  page_size, n_pages, ragged last page, sliding-window ring wrap,
  pages_per_step / head_block knobs, and S in {1..k+1} multi-token
  verify reads;
- decode-step megakernel vs the unfused chain (merged QKV -> RoPE ->
  paged cache write -> paged attention -> wo, each stage the shipped
  pallas op) vs ``ref.decode_step_ref`` — including engine-level
  greedy token identity with the megakernel genuinely engaged, and the
  tensor-parallel fallback (non-qualifying launches return None and
  the chain takes over).
"""
from __future__ import annotations

import dataclasses
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from _hypothesis_compat import given, settings, st  # noqa: E402

from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref, tuning  # noqa: E402
from repro.kernels.megakernel import decode_step_megakernel_raw  # noqa: E402
from repro.models.layers import apply_rope, paged_cache_write  # noqa: E402

PALLAS = kops.KernelPolicy(mode="pallas", interpret=True)
REF = kops.KernelPolicy(mode="ref")
BIG = 10 ** 6
SEEDS = st.integers(min_value=0, max_value=2 ** 31 - 1)


def _examples(default: int) -> int:
    return int(os.environ.get("NQ_FUZZ_EXAMPLES", default))


def _tol(dtype) -> float:
    return 1e-5 if dtype == jnp.float32 else 3e-2


def _close(name, a, b, tol, seed, **case):
    """Relative max-abs comparison; the failure message carries the
    replay seed and the drawn case."""
    a = np.asarray(jnp.asarray(a, jnp.float32))
    b = np.asarray(jnp.asarray(b, jnp.float32))
    assert a.shape == b.shape, (name, a.shape, b.shape, seed, case)
    scale = max(1.0, float(np.max(np.abs(a)))) if a.size else 1.0
    err = float(np.max(np.abs(a - b))) / scale if a.size else 0.0
    assert err <= tol, (f"{name}: rel err {err:.3e} > {tol} "
                        f"[replay seed={seed} case={case}]")


def _pack(rng, k, r):
    """Packed random ±1 matrix (k, r) -> (k//32, r) uint32."""
    signs = (rng.standard_normal((k, r)) > 0).astype(np.float32) * 2 - 1
    return ref.pack_signs(jnp.asarray(signs))


def _operands(rng, m, k, n, r, dtype):
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32).astype(dtype)
    qv = _pack(rng, k, r)
    qu_t = _pack(rng, r, n)
    s1 = jnp.asarray(rng.standard_normal(n), jnp.float32)
    s2 = jnp.asarray(rng.standard_normal(k), jnp.float32)
    return x, qv, qu_t, s1, s2


# ===========================================================================
# packed matmul: fused vs two-call vs oracle
# ===========================================================================


@settings(max_examples=_examples(20), deadline=None)
@given(seed=SEEDS)
def test_matmul_fused_vs_twocall_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.choice([1, 3, 8]))
    k = 32 * int(rng.integers(1, 5))
    n = 8 * int(rng.integers(1, 17))
    r = 32 * int(rng.integers(1, 4))
    dtype = jnp.float32 if rng.integers(2) else jnp.bfloat16
    case = dict(m=m, k=k, n=n, r=r, dtype=str(dtype.__name__))
    x, qv, qu_t, s1, s2 = _operands(rng, m, k, n, r, dtype)

    fused = kops.lowrank_binary_matmul(x, qv, qu_t, s1, s2, policy=PALLAS)
    two = kops.lowrank_binary_matmul(
        x, qv, qu_t, s1, s2,
        policy=dataclasses.replace(PALLAS, fused=False))
    oracle = ref.lowrank_binary_matmul_fused_ref(x, qv, qu_t, s1, s2)
    _close("fused-vs-oracle", oracle, fused, _tol(dtype), seed, **case)
    _close("twocall-vs-oracle", oracle, two, _tol(dtype), seed, **case)


@settings(max_examples=_examples(15), deadline=None)
@given(seed=SEEDS)
def test_matmul_eff_rank_truncation(seed):
    """Rank-truncated launches (the speculative draft forward) read only
    the leading eff_rank components — equal to the sliced oracle."""
    rng = np.random.default_rng(seed)
    m = int(rng.choice([1, 8]))
    k = 32 * int(rng.integers(1, 4))
    n = 8 * int(rng.integers(2, 9))
    r = 32 * int(rng.integers(2, 5))
    er = 32 * int(rng.integers(1, r // 32 + 1))
    case = dict(m=m, k=k, n=n, r=r, eff_rank=er)
    x, qv, qu_t, s1, s2 = _operands(rng, m, k, n, r, jnp.float32)

    got = kops.lowrank_binary_matmul(x, qv, qu_t, s1, s2, policy=PALLAS,
                                     eff_rank=er)
    want = ref.lowrank_binary_matmul_fused_ref(x, qv, qu_t, s1, s2,
                                               eff_rank=er)
    _close("effrank", want, got, 1e-5, seed, **case)


@settings(max_examples=_examples(15), deadline=None)
@given(seed=SEEDS)
def test_matmul_offblock_shapes(seed):
    """K values the preferred bk=512 tile does NOT divide (the
    d_ff=2816 / K=704 family): the divisor-fitted tiles must stay
    exact, launching without padding the packed operands."""
    rng = np.random.default_rng(seed)
    k = int(rng.choice([160, 224, 704]))   # 5, 7, 22 packed words
    m = int(rng.choice([1, 8]))
    n = 8 * int(rng.choice([5, 7, 25]))
    r = 32 * int(rng.integers(1, 3))
    case = dict(m=m, k=k, n=n, r=r)
    x, qv, qu_t, s1, s2 = _operands(rng, m, k, n, r, jnp.float32)
    got = kops.lowrank_binary_matmul(x, qv, qu_t, s1, s2, policy=PALLAS)
    want = ref.lowrank_binary_matmul_fused_ref(x, qv, qu_t, s1, s2)
    _close("offblock", want, got, 1e-5, seed, **case)


@settings(max_examples=_examples(15), deadline=None)
@given(seed=SEEDS)
def test_merged_rmask_vs_oracle(seed):
    """Grouped QKV-style launch with ragged true ranks (rmask) equals
    the per-projection fused oracle on every group's true output dim."""
    rng = np.random.default_rng(seed)
    m = int(rng.choice([1, 4]))
    k = 32 * int(rng.integers(1, 4))
    R = 32 * int(rng.integers(1, 3))
    P = int(rng.integers(2, 4))
    dims = [8 * int(rng.integers(1, 9)) for _ in range(P)]
    ranks = [32 * int(rng.integers(1, R // 32 + 1)) for _ in range(P)]
    n_max = max(dims)
    case = dict(m=m, k=k, R=R, dims=dims, ranks=ranks)

    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    qv = jnp.stack([_pack(rng, k, R) for _ in range(P)])
    qu_t = jnp.stack([_pack(rng, R, n_max) for _ in range(P)])
    s1 = jnp.asarray(rng.standard_normal((P, n_max)), jnp.float32)
    s2 = jnp.asarray(rng.standard_normal((P, k)), jnp.float32)
    rmask = jnp.asarray(np.stack(
        [(np.arange(R) < r).astype(np.float32) for r in ranks]))
    mp = {"qv": qv, "qu_t": qu_t, "s1": s1, "s2": s2, "rmask": rmask}

    got = kops.lowrank_binary_matmul_merged(x, mp, dims, policy=PALLAS)
    for i, n in enumerate(dims):
        want = ref.lowrank_binary_matmul_fused_ref(
            x, qv[i], qu_t[i], s1[i], s2[i], rmask[i])[:, :n]
        _close(f"group{i}", want, got[i], 1e-5, seed, **case)


# ===========================================================================
# paged gather attention
# ===========================================================================


def _paged_case(rng, s_max=1):
    hkv = int(rng.choice([1, 2, 3]))
    G = int(rng.choice([1, 2, 4]))
    D = int(rng.choice([8, 16]))
    PS = int(rng.choice([2, 4, 8]))
    pages = int(rng.integers(1, 7))
    B = int(rng.integers(1, 4))
    NP = B * pages + 2
    rows = pages * PS
    # an S-token span must fit in the pool's rows (and keep the ring
    # draw range non-empty for tiny pools: qpos in [rows, 3*rows - S))
    S = int(rng.integers(1, min(s_max, rows) + 1)) if s_max > 1 else 1
    window = int(rng.choice([0, rng.integers(2, rows + 1)]))
    ring = bool(rng.integers(2)) and window > 0
    dtype = jnp.float32 if rng.integers(2) else jnp.bfloat16

    q = jnp.asarray(rng.standard_normal((B, S, hkv * G, D)),
                    jnp.float32).astype(dtype)
    kp = jnp.asarray(rng.standard_normal((NP, PS, hkv, D)),
                     jnp.float32).astype(dtype)
    vp = jnp.asarray(rng.standard_normal((NP, PS, hkv, D)),
                     jnp.float32).astype(dtype)
    # writable pages exclusive per slot (see decode_step_ref contract)
    flat = rng.choice(np.arange(1, NP), B * pages, replace=False)
    bt = jnp.asarray(flat.reshape(B, pages), jnp.int32)
    # q_pos of the FIRST query token; ragged last page almost surely
    # (positions drawn mid-page), S tokens must fit below the rectangle
    hi = max(rows - S, 1)
    if ring:
        qpos = jnp.asarray(rng.integers(rows, 3 * rows - S, B), jnp.int32)
        cpos = qpos % rows
    else:
        qpos = jnp.asarray(rng.integers(0, hi, B), jnp.int32)
        cpos = qpos
    knobs = (int(rng.integers(1, 5)),                       # pages_per_step
             int(rng.choice([0, 1, 2, 3])))                 # head_block
    case = dict(B=B, S=S, hkv=hkv, G=G, D=D, PS=PS, pages=pages,
                window=window, ring=ring, knobs=knobs,
                dtype=str(np.dtype(dtype).name))
    return q, kp, vp, bt, qpos, cpos, window, knobs, dtype, case


def _paged_policy(ppb, hb):
    return dataclasses.replace(
        PALLAS, paged_block_table=((BIG, BIG, BIG, BIG, ppb, hb),))


@settings(max_examples=_examples(15), deadline=None)
@given(seed=SEEDS)
def test_paged_attention_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    q, kp, vp, bt, qpos, cpos, window, (ppb, hb), dtype, case = \
        _paged_case(rng, s_max=1)
    scale = 1.0 / np.sqrt(q.shape[-1])
    got = kops.paged_attention(q, kp, vp, bt, qpos, cpos, window=window,
                               scale=scale, policy=_paged_policy(ppb, hb))
    want = ref.paged_attention_ref(q, kp, vp, bt, qpos, cpos,
                                   window=window, scale=scale)
    _close("paged", want, got, _tol(dtype), seed, **case)


@settings(max_examples=_examples(15), deadline=None)
@given(seed=SEEDS)
def test_paged_attention_multitoken_vs_oracle(seed):
    """S in {1..5} multi-token verify reads (all S rows pre-written,
    per-query causal masking), including page-boundary-straddling
    spans and ring wrap."""
    rng = np.random.default_rng(seed)
    q, kp, vp, bt, qpos, cpos, window, (ppb, hb), dtype, case = \
        _paged_case(rng, s_max=5)
    scale = 1.0 / np.sqrt(q.shape[-1])
    got = kops.paged_attention(q, kp, vp, bt, qpos, cpos, window=window,
                               scale=scale, policy=_paged_policy(ppb, hb))
    want = ref.paged_attention_ref(q, kp, vp, bt, qpos, cpos,
                                   window=window, scale=scale)
    _close("paged-multitoken", want, got, _tol(dtype), seed, **case)


# ===========================================================================
# decode-step megakernel: one pallas pass vs unfused chain vs oracle
# ===========================================================================


def _mega_case(rng):
    D = int(rng.choice([8, 16]))
    hkv = int(rng.choice([2, 3]))
    G = int(rng.choice([1, 2]))
    hq = hkv * G
    nq, nkv = hq * D, hkv * D
    K = 32 * int(rng.choice([2, 3]))
    R = 32 * int(rng.choice([1, 2]))
    n_max = max(nq, nkv)
    ranks = [32 * int(rng.integers(1, R // 32 + 1)) for _ in range(3)]
    eff = 32 * int(rng.integers(1, R // 32 + 1)) if rng.integers(2) else None
    B = int(rng.integers(1, 3))
    pages, PS = int(rng.integers(2, 5)), 4
    NP = B * pages + 2
    rows = pages * PS
    window = int(rng.choice([0, rng.integers(3, rows)]))
    ring = bool(rng.integers(2)) and window > 0
    ppb = int(rng.integers(1, 4))
    dtype = jnp.float32 if rng.integers(2) else jnp.bfloat16

    mqkv = {
        "qv": jnp.stack([_pack(rng, K, R) for _ in range(3)]),
        "qu_t": jnp.stack([_pack(rng, R, n_max) for _ in range(3)]),
        "s1": jnp.asarray(rng.standard_normal((3, n_max)), jnp.float32),
        "s2": jnp.asarray(rng.standard_normal((3, K)), jnp.float32),
        "rmask": jnp.asarray(np.stack(
            [(np.arange(R) < r).astype(np.float32) for r in ranks])),
    }
    Ko = -(-nq // 32) * 32      # wo packed K is pack-aligned past nq
    s2o = rng.standard_normal(Ko).astype(np.float32)
    s2o[nq:] = 0.0
    wo = {
        "qv": _pack(rng, Ko, R),
        "qu_t": _pack(rng, R, K),
        "s1": jnp.asarray(rng.standard_normal(K), jnp.float32),
        "s2": jnp.asarray(s2o),
    }
    eff_o = 32 * int(rng.integers(1, R // 32 + 1)) if rng.integers(2) \
        else None

    x = jnp.asarray(rng.standard_normal((B, K)), jnp.float32).astype(dtype)
    kp = jnp.asarray(rng.standard_normal((NP, PS, hkv, D)),
                     jnp.float32).astype(dtype)
    vp = jnp.asarray(rng.standard_normal((NP, PS, hkv, D)),
                     jnp.float32).astype(dtype)
    flat = rng.choice(np.arange(1, NP), B * pages, replace=False)
    bt = jnp.asarray(flat.reshape(B, pages), jnp.int32)
    if ring:
        qpos = jnp.asarray(rng.integers(rows, 3 * rows, B), jnp.int32)
        cpos = qpos % rows
    else:
        qpos = jnp.asarray(rng.integers(1, rows, B), jnp.int32)
        cpos = qpos
    kw = dict(dims=(nq, nkv), head_dim=D, theta=10000.0,
              scale=1.0 / np.sqrt(D), window=window,
              eff_rank=eff, eff_rank_o=eff_o)
    case = dict(B=B, K=K, D=D, hq=hq, hkv=hkv, R=R, ranks=ranks,
                pages=pages, window=window, ring=ring, ppb=ppb,
                eff=eff, eff_o=eff_o, dtype=str(np.dtype(dtype).name))
    return x, mqkv, wo, kp, vp, bt, qpos, cpos, ppb, kw, dtype, case


def _unfused_chain(x, mqkv, wo, kp, vp, bt, qpos, cpos, ppb, *, dims,
                   head_dim, theta, scale, window, eff_rank, eff_rank_o):
    """The decode step as the engine runs it when the megakernel does
    not qualify: every stage the shipped pallas op (interpret mode)."""
    nq, nkv = dims
    B = x.shape[0]
    pol = _paged_policy(ppb, 0)
    q, k, v = kops.lowrank_binary_matmul_merged(
        x, mqkv, (nq, nkv, nkv), policy=pol, eff_rank=eff_rank)
    q = apply_rope(q.reshape(B, 1, nq // head_dim, head_dim),
                   qpos[:, None], theta)
    k = apply_rope(k.reshape(B, 1, nkv // head_dim, head_dim),
                   qpos[:, None], theta)
    v = v.reshape(B, 1, nkv // head_dim, head_dim)
    kp = paged_cache_write(kp, k.astype(kp.dtype), bt, cpos)
    vp = paged_cache_write(vp, v.astype(vp.dtype), bt, cpos)
    o = kops.paged_attention(q, kp, vp, bt, qpos, cpos, window=window,
                             scale=scale, policy=pol)
    y = kops.lowrank_binary_matmul(
        o.reshape(B, nq).astype(x.dtype), wo["qv"], wo["qu_t"], wo["s1"],
        wo["s2"], policy=pol, eff_rank=eff_rank_o)
    return y, k[:, 0], v[:, 0]


@settings(max_examples=_examples(8), deadline=None)
@given(seed=SEEDS)
def test_megakernel_vs_unfused_chain_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    x, mqkv, wo, kp, vp, bt, qpos, cpos, ppb, kw, dtype, case = \
        _mega_case(rng)
    y_m, k_m, v_m = decode_step_megakernel_raw(
        x, mqkv, wo, kp, vp, bt, qpos, cpos, pages_per_step=ppb,
        bk=32, bn=32, interpret=True, **kw)
    y_r, k_r, v_r = ref.decode_step_ref(x, mqkv, wo, kp, vp, bt, qpos,
                                        cpos, **kw)
    y_c, k_c, v_c = _unfused_chain(x, mqkv, wo, kp, vp, bt, qpos, cpos,
                                   ppb, **kw)
    tol = _tol(dtype)
    for nm, a, b in (("y", y_r, y_m), ("k_new", k_r, k_m),
                     ("v_new", v_r, v_m)):
        _close(f"mega-vs-oracle:{nm}", a, b, tol, seed, **case)
    for nm, a, b in (("y", y_r, y_c),
                     ("k_new", k_r, k_c.astype(k_r.dtype)),
                     ("v_new", v_r, v_c.astype(v_r.dtype))):
        _close(f"chain-vs-oracle:{nm}", a, b, tol, seed, **case)


def test_megakernel_gating_returns_none_for_nonqualifying():
    """Non-qualifying launches must fall back to the unfused chain
    (return None), never mis-launch: ref/unfused/unmerged policies,
    megakernel=False, off-32 eff_rank, oversized ranks."""
    rng = np.random.default_rng(0)
    x, mqkv, wo, kp, vp, bt, qpos, cpos, ppb, kw, _, _ = _mega_case(rng)
    call = lambda pol, **ov: kops.decode_step_megakernel(
        x, mqkv, wo, kp, vp, bt, qpos, cpos, policy=pol, **{**kw, **ov})
    assert call(REF) is None
    assert call(dataclasses.replace(PALLAS, fused=False)) is None
    assert call(dataclasses.replace(PALLAS, merge_projections=False)) is None
    assert call(dataclasses.replace(PALLAS, megakernel=False)) is None
    assert call(PALLAS, eff_rank=33) is None          # not a 32-multiple
    assert call(PALLAS, eff_rank=mqkv["qv"].shape[-1] + 32) is None
    out = call(PALLAS)                                # qualifying launch
    assert out is not None and len(out) == 3


# ===========================================================================
# engine-level identity: megakernel on == off (greedy), genuinely engaged
# ===========================================================================


def _mega_engine_outputs(monkeypatch):
    from repro.quant.surgery import abstract_quantized_params
    from repro.serve import InferenceEngine, Request, ServeConfig
    from repro.models.config import ModelConfig
    from repro.kernels import megakernel as mk

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      loss_chunk=0, remat=False)
    # min_dim=32: the 64->32 kv projections must quantize, else the
    # attention group never merges and the megakernel silently never
    # engages (the launch counter below guards against exactly that)
    tpl = abstract_quantized_params(cfg, target_bpw=2.0, min_dim=32)
    rng = np.random.default_rng(11)

    def fill(path, s):
        last = getattr(path[-1], "key", str(path[-1]))
        if s.dtype == jnp.uint32:
            return jnp.asarray(rng.integers(
                0, 2 ** 32, size=s.shape, dtype=np.uint64).astype(np.uint32))
        if last in ("s1", "s2"):
            return jnp.ones(s.shape, s.dtype)
        return jnp.asarray(rng.normal(0, 0.05, s.shape).astype(s.dtype))

    params = jax.tree_util.tree_map_with_path(fill, tpl)
    prompts = [list((np.arange(n) * 7 + 3) % cfg.vocab_size)
               for n in (6, 11, 4)]
    budgets = [10, 8, 12]

    launches = [0]
    raw = mk.decode_step_megakernel_raw

    def counting_raw(*a, **k):
        launches[0] += 1
        return raw(*a, **k)

    monkeypatch.setattr(mk, "decode_step_megakernel_raw", counting_raw)

    def serve(scfg):
        eng = InferenceEngine(params, cfg, scfg, max_batch=2, max_len=48)
        for uid, (p, b) in enumerate(zip(prompts, budgets)):
            eng.submit(Request(uid, p, max_new_tokens=b))
        return {u: r.output for u, r in eng.run().items()}

    base = ServeConfig(greedy=True, page_size=8)
    out = {}
    with kops.kernel_policy(PALLAS):
        out["off"] = serve(dataclasses.replace(base, megakernel=False))
        traced_off = launches[0]
        out["on"] = serve(dataclasses.replace(base, megakernel=True))
        assert launches[0] > traced_off, \
            "megakernel=True never launched the megakernel"
        out["spec_off"] = serve(dataclasses.replace(
            base, megakernel=False, spec_rank_frac=0.5, spec_k=4))
        out["spec_on"] = serve(dataclasses.replace(
            base, megakernel=True, spec_rank_frac=0.5, spec_k=4))
    return out


@pytest.mark.slow
def test_megakernel_engine_token_identity(monkeypatch):
    """Greedy outputs token-identical with the megakernel on vs off, on
    the paged engine and composed with speculative decoding (k=4)."""
    out = _mega_engine_outputs(monkeypatch)
    for u in out["off"]:
        np.testing.assert_array_equal(out["off"][u], out["on"][u])
        np.testing.assert_array_equal(out["spec_off"][u], out["spec_on"][u])
        np.testing.assert_array_equal(out["off"][u], out["spec_on"][u])


@pytest.mark.slow
def test_megakernel_tp_fallback_identity():
    """Under a (model=2) tensor-parallel mesh the megakernel launch does
    not qualify (merged padded-Nmax layout is not head-aligned): the
    gate must return None and the engine must stay token-identical to
    the unsharded megakernel=True engine via the unfused-chain
    fallback."""
    from conftest import run_multidevice
    out = run_multidevice("""
        import jax, numpy as np
        from repro.core.pipeline import QuantConfig, nanoquant_quantize
        from repro.data import calib_batches
        from repro.kernels import ops as kops
        from repro.kernels import megakernel as mk
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as T
        from repro.models.config import ModelConfig
        from repro.serve.engine import InferenceEngine, ServeConfig
        from repro.serve.scheduler import Request

        # f32 + TP-divisible dims, same recipe as
        # test_engine.test_sharded_engine_token_identity: greedy argmax
        # must not flip on partitioned-reduction reordering noise
        cfg = ModelConfig(name="tiny", family="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                          vocab_size=256, loss_chunk=0, remat=False,
                          dtype="float32")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        calib = calib_batches(cfg, 2, 32, batch=2)
        qcfg = QuantConfig(admm_iters=2, t_pre=0, t_post=0, t_glob=0,
                           rank_align=32, min_dim=32)
        qp, _ = nanoquant_quantize(params, cfg, calib, qcfg, verbose=False)

        prompts = [np.arange(1, 7, dtype=np.int32),
                   np.arange(3, 12, dtype=np.int32),
                   np.arange(2, 10, dtype=np.int32)]
        budgets = [6, 3, 5]

        launches = [0]
        raw = mk.decode_step_megakernel_raw
        def counting_raw(*a, **k):
            launches[0] += 1
            return raw(*a, **k)
        mk.decode_step_megakernel_raw = counting_raw

        def run(mesh):
            scfg = ServeConfig(greedy=True, page_size=8, megakernel=True)
            eng = InferenceEngine(qp, cfg, scfg, max_batch=2,
                                  max_len=32, mesh=mesh)
            for uid, (p, b) in enumerate(zip(prompts, budgets)):
                eng.submit(Request(uid, p, max_new_tokens=b))
            return {u: r.output for u, r in eng.run().items()}

        pol = kops.KernelPolicy(mode="pallas", interpret=True)
        with kops.kernel_policy(pol):
            ref_out = run(None)
        assert launches[0] > 0, "megakernel never engaged unsharded"
        traced = launches[0]
        with kops.kernel_policy(pol):
            tp_out = run(make_serving_mesh(2))
        # the TP engine must have taken the unfused-chain fallback:
        # no new megakernel launches under the mesh
        assert launches[0] == traced, "megakernel launched under TP"
        for u in ref_out:
            np.testing.assert_array_equal(ref_out[u], tp_out[u])
        print("tp-fallback-identity-ok")
    """, devices=2)
    assert "tp-fallback-identity-ok" in out


# ===========================================================================
# tuning-table behavior
# ===========================================================================


@pytest.mark.sweep
def test_no_pad_in_decode_jaxpr_for_swept_shapes():
    """Divisor-fitted tiles must launch the swept decode shapes (and
    the K=704 off-block GEMV family) without tracing a single pad op
    into the jitted step — padding the packed weights per call was the
    original table-miss regression. M is kept sublane-aligned (8) so
    the one *intended* pad (rounding a tiny activation batch up to the
    sublane) can't mask a weight pad; any ``pad[`` left in the jaxpr is
    a weight pad."""
    shapes = [(8, 704, 512, 64), (8, 512, 512, 128)]
    path = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "bench", "kernel_block_table.json")
    if os.path.exists(path):
        shapes += [(max(m, 8), k, n, r) for (m, k, n, r, *_)
                   in tuning.load_block_table(path)]
    for m, k, n, r in shapes:
        rng = np.random.default_rng(1)
        x, qv, qu_t, s1, s2 = _operands(rng, m, k, n, r, jnp.float32)
        jaxpr = str(jax.make_jaxpr(
            lambda xx: kops.lowrank_binary_matmul(
                xx, qv, qu_t, s1, s2, policy=PALLAS))(x))
        assert "pad[" not in jaxpr, \
            f"shape (M={m},K={k},N={n},r={r}) traced a pad"


def test_tuning_miss_warns_once():
    tuning._MISS_WARNED.clear()
    huge = (2 * BIG, 2 * BIG, 2 * BIG, 2 * BIG)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        tuning.lookup_block_table(*huge)
        tuning.lookup_block_table(*huge)
    msgs = [x for x in w if "no block-table row" in str(x.message)]
    assert len(msgs) == 1, "table miss must warn exactly once per class"


def test_fit_paged_block_sizes_units():
    # ppb clamps to the page count; hb snaps down to a divisor of Hkv
    table = ((BIG, BIG, BIG, BIG, 8, 3),)
    assert tuning.fit_paged_block_sizes(1, 4, 8, 2, table) == (2, 2)
    ppb, hb = tuning.fit_paged_block_sizes(1, 4, 8, 64, table)
    assert ppb == 8 and 4 % max(hb, 1) == 0
    # hb >= Hkv or <= 1 disables head tiling
    assert tuning.fit_paged_block_sizes(1, 2, 8, 64, ((BIG,) * 4 + (4, 2),)
                                        )[1] == 0


@pytest.mark.sweep
def test_committed_block_table_roundtrip():
    path = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "bench", "kernel_block_table.json")
    if not os.path.exists(path):
        pytest.skip("no committed kernel_block_table.json")
    mm = tuning.load_block_table(path)
    assert mm and all(len(r) == 7 for r in mm)
    pg = tuning.load_paged_table(path)
    assert pg and all(len(r) == 6 for r in pg)
    # the loaded rows drive the policy fit without error
    pol = kops.KernelPolicy(mode="pallas", interpret=True,
                            block_table=mm, paged_block_table=pg)
    assert len(pol.block_sizes(1, 256, 256, 64)) == 3
    assert len(pol.paged_block_sizes(4, 2, 16, 4)) == 2


# ===========================================================================
# benchmark regression gates (benchmarks/common.py)
# ===========================================================================


def test_check_regression_gate(monkeypatch):
    """The gate passes inside tolerance, fails loudly past it, fails on
    an injected 20% slowdown (the end-to-end negative test hook), and
    skips cleanly with no checked-in baseline."""
    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, root)
    try:
        from benchmarks import common
    finally:
        sys.path.remove(root)
    base = {"decode_ratio": 1.0}
    common.check_regression(base, {"decode_ratio": 1.2})
    common.check_regression(base, {"decode_ratio": 0.95})   # within 10%
    with pytest.raises(RuntimeError, match="decode_ratio"):
        common.check_regression(base, {"decode_ratio": 0.85})
    with pytest.raises(RuntimeError, match="missing"):
        common.check_regression(base, {})
    monkeypatch.setenv("NQ_BENCH_INJECT_SLOWDOWN", "0.2")
    with pytest.raises(RuntimeError):
        common.check_regression(base, {"decode_ratio": 1.0})
    monkeypatch.delenv("NQ_BENCH_INJECT_SLOWDOWN")
    common.check_regression(None, {"decode_ratio": 0.0})    # no baseline

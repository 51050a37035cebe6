"""Pallas binary-matmul kernels vs pure-jnp oracles (interpret mode on
CPU).

Covers the fused single-pass kernel (bit-exactness vs the fused oracle
across a shape sweep: minimum rank, K/N off block boundaries, bf16
activations, M=1 GEMV), merged-QKV equality vs separate calls, the
expert-grid kernel, block-size fitting (divisor tiles -> no pad ops in
the jitted decode trace), pack-time K alignment, and engine decode
token-identity under the fused policy.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, tuning
from repro.kernels.binary_matmul import (
    fused_lowrank_matmul, fused_lowrank_matmul_grouped,
    lowrank_binary_matmul_twocall, packed_matmul)


def _assert_close(got, want, dtype, f32_tol=1e-4):
    """f32: elementwise-exact up to partial-sum reassociation (pass
    f32_tol=1e-3 for fused-vs-unfused comparisons, where the kernel's
    tiled K reduction reassociates against the single-dot oracle and
    isolated cancellation-heavy elements move by a few ulps). bf16:
    normalized-RMS — the kernel keeps f32 internals while the oracle
    input rounding differs elementwise; aggregate fidelity is the
    meaningful bound."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if dtype == jnp.bfloat16:
        rms = float(np.sqrt(np.mean((g - w) ** 2)))
        ref_rms = float(np.sqrt(np.mean(w ** 2))) + 1e-9
        assert rms / ref_rms < 0.02, rms / ref_rms
    else:
        np.testing.assert_allclose(g, w, rtol=f32_tol, atol=f32_tol)


def _mk(m, k, n, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    kx, kw, k1, k2 = jax.random.split(key, 4)
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    w = jnp.sign(jax.random.normal(kw, (k, n)))
    w = jnp.where(w == 0, 1.0, w)
    packed = ref.pack_signs(w)
    s_k = jnp.abs(jax.random.normal(k1, (k,))) + 0.1
    s_n = jnp.abs(jax.random.normal(k2, (n,))) + 0.1
    return x, packed, s_k, s_n


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
# two-call building block (legacy path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 7, 64, 130])
@pytest.mark.parametrize("k,n", [(32, 32), (64, 96), (512, 128), (96, 160)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_matmul_matches_ref(m, k, n, dtype):
    x, packed, s_k, s_n = _mk(m, k, n, dtype)
    got = packed_matmul(x, packed, s_k, s_n, interpret=True,
                        bm=64, bn=64, bk=64)
    want = ref.packed_matmul_ref(x, packed, s_k, s_n)
    _assert_close(got, want, dtype)


# ---------------------------------------------------------------------------
# fused single-pass kernel
# ---------------------------------------------------------------------------

# (m, k, n, r, bm, bn, bk): minimum rank r=32 (rank_align floor), K/N at
# and off the block boundary, rank off the 128-lane boundary, M=1 GEMV
_FUSED_SWEEP = [
    (1, 64, 96, 32, 8, 32, 32),            # GEMV, min rank
    (7, 96, 160, 32, 8, 64, 32),           # ragged M, N % bn != 0
    (64, 512, 128, 64, 32, 64, 128),       # multi-tile K reduction
    (130, 96, 96, 64, 64, 32, 32),         # M off block boundary
    (3, 160, 96, 96, 8, 96, 64),           # bk refit to a K divisor, odd rank
    (1, 128, 64, 32, 8, 64, 128),          # GEMV, single K tile
]


@pytest.mark.parametrize("m,k,n,r,bm,bn,bk", _FUSED_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_matches_fused_ref(m, k, n, r, bm, bn, bk, dtype):
    x, qv, qu_t, s1, s2 = _mk_lowrank(m, k, n, r, dtype)
    got = fused_lowrank_matmul(x, qv, qu_t, s1, s2, interpret=True,
                               bm=bm, bn=bn, bk=bk)
    want = ref.lowrank_binary_matmul_fused_ref(x, qv, qu_t, s1, s2)
    _assert_close(got, want, dtype, f32_tol=1e-3)


@pytest.mark.parametrize("m,k,n,r,bm,bn,bk", _FUSED_SWEEP[:3])
def test_fused_matches_two_stage_ref(m, k, n, r, bm, bn, bk):
    """Against the *two-stage* oracle too (f32: the stage boundary does
    not round, so both agree)."""
    x, qv, qu_t, s1, s2 = _mk_lowrank(m, k, n, r, jnp.float32)
    got = fused_lowrank_matmul(x, qv, qu_t, s1, s2, interpret=True,
                               bm=bm, bn=bn, bk=bk)
    want = ref.lowrank_binary_matmul_ref(x, qv, qu_t, s1, s2)
    _assert_close(got, want, jnp.float32, f32_tol=1e-3)


def test_fused_matches_twocall_kernel():
    x, qv, qu_t, s1, s2 = _mk_lowrank(5, 96, 128, 64)
    got = fused_lowrank_matmul(x, qv, qu_t, s1, s2, interpret=True,
                               bm=8, bn=64, bk=32)
    want = lowrank_binary_matmul_twocall(x, qv, qu_t, s1, s2,
                                         interpret=True, bm=8, bn=64, bk=32)
    _assert_close(got, want, jnp.float32, f32_tol=1e-3)


def test_gemv_decode_shape():
    """decode regime: M=1 row through the fused chain (paper App. E
    GEMV) in the serving dtype."""
    x, qv, qu_t, s1, s2 = _mk_lowrank(1, 128, 64, 32, jnp.bfloat16, seed=9)
    got = fused_lowrank_matmul(x, qv, qu_t, s1, s2, interpret=True)
    want = ref.lowrank_binary_matmul_fused_ref(x, qv, qu_t, s1, s2)
    _assert_close(got, want, jnp.bfloat16)


# ---------------------------------------------------------------------------
# merged projections / expert grid
# ---------------------------------------------------------------------------


def _merged_group(projs):
    from repro.quant.surgery import _stack_group
    return _stack_group([{"qv": qv, "qu_t": qu, "s1": s1, "s2": s2}
                         for (qv, qu, s1, s2) in projs])


def test_merged_qkv_equals_separate_calls():
    """Grouped QKV launch == three separate fused calls (ragged ranks
    and output widths, i.e. GQA-shaped)."""
    k = 96
    x = jax.random.normal(jax.random.PRNGKey(5), (6, k))
    shapes = [(128, 64), (64, 32), (64, 32)]          # (n_i, r_i)
    projs = [_mk_lowrank(6, k, n, r, seed=i)[1:]
             for i, (n, r) in enumerate(shapes)]
    mp = _merged_group(projs)
    assert mp["qv"].shape == (3, k // 32, 64)
    assert mp["qu_t"].shape == (3, 2, 128)
    pol = ops.KernelPolicy(mode="pallas", interpret=True)
    ys = ops.lowrank_binary_matmul_merged(
        x, mp, tuple(n for n, _ in shapes), policy=pol)
    for (n, _), y, (qv, qu, s1, s2) in zip(shapes, ys, projs):
        assert y.shape == (6, n)
        want = ops.lowrank_binary_matmul(x, qv, qu, s1, s2, policy=pol)
        _assert_close(y, want, jnp.float32, f32_tol=1e-3)


def test_merged_ref_fallback_matches():
    k = 64
    x = jax.random.normal(jax.random.PRNGKey(2), (4, k))
    projs = [_mk_lowrank(4, k, 96, 32, seed=i)[1:] for i in range(2)]
    mp = _merged_group(projs)
    ys = ops.lowrank_binary_matmul_merged(
        x, mp, (96, 96), policy=ops.KernelPolicy(mode="ref"))
    for y, (qv, qu, s1, s2) in zip(ys, projs):
        want = ref.lowrank_binary_matmul_fused_ref(x, qv, qu, s1, s2)
        _assert_close(y, want, jnp.float32, f32_tol=1e-3)


def test_expert_grid_matches_vmap_ref():
    """Expert axis as a kernel grid dimension == per-expert oracle."""
    E, C, k, n, r = 3, 8, 64, 96, 32
    xs = jax.random.normal(jax.random.PRNGKey(11), (E, C, k))
    opsl = [_mk_lowrank(C, k, n, r, seed=20 + e)[1:] for e in range(E)]
    qv = jnp.stack([o[0] for o in opsl])
    qu = jnp.stack([o[1] for o in opsl])
    s1 = jnp.stack([o[2] for o in opsl])
    s2 = jnp.stack([o[3] for o in opsl])
    got = ops.lowrank_binary_matmul_expert(
        xs, qv, qu, s1, s2, policy=ops.KernelPolicy(mode="pallas",
                                                    interpret=True))
    want = jax.vmap(ref.lowrank_binary_matmul_ref)(xs, qv, qu, s1, s2)
    _assert_close(got, want, jnp.float32, f32_tol=1e-3)


# ---------------------------------------------------------------------------
# policy / dispatch
# ---------------------------------------------------------------------------


def test_kernel_policy_switch():
    x, qv, qu_t, s1, s2 = _mk_lowrank(4, 64, 96, 32)
    with ops.kernel_policy("ref"):
        y1 = ops.lowrank_binary_matmul(x, qv, qu_t, s1, s2)
    with ops.kernel_policy(ops.KernelPolicy(mode="pallas", interpret=True)):
        y2 = ops.lowrank_binary_matmul(x, qv, qu_t, s1, s2)
    with ops.kernel_policy(ops.KernelPolicy(mode="pallas", interpret=True,
                                            fused=False)):
        y3 = ops.lowrank_binary_matmul(x, qv, qu_t, s1, s2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y3), rtol=1e-4,
                               atol=1e-4)


def test_policy_block_table_override():
    table = ((100_000, 100_000, 100_000, 100_000, 16, 128, 256),)
    pol = ops.KernelPolicy(block_table=table)
    bm, bn, bk = pol.block_sizes(8, 1024, 512, 32)
    assert (bm, bn, bk) == (8, 128, 256)   # bm covers M=8 at sublane 8
    # a dim with no aligned divisor under the preferred tile takes the
    # whole dim: a full-extent block is the only other legal tile
    assert pol.block_sizes(8, 704, 192, 32) == (8, 192, 704)
    # default policy: decode shape gets a sublane-sized M tile
    bm, bn, bk = ops.KernelPolicy().block_sizes(8, 2048, 2048, 512)
    assert bm == 8
    assert 2048 % bk == 0 and 2048 % bn == 0


def test_block_size_fitting_divisors():
    # K=2816 (llama-style d_ff) misaligns to the old fixed bk=512; the
    # fitter must pick a divisor tile so no weight padding is traced
    bm, bn, bk = tuning.fit_block_sizes(8, 2816, 1024, 256)
    assert 2816 % bk == 0 and bk % 256 == 0
    assert 1024 % bn == 0 and bn % 128 == 0
    # ... and the same width as N: a 128-multiple divisor, never 352
    _, bn, _ = tuning.fit_block_sizes(8, 1024, 2816, 256)
    assert 2816 % bn == 0 and bn % 128 == 0
    # bf16 activations need 16 sublanes
    bm16, _, _ = tuning.fit_block_sizes(4, 256, 256, 32, jnp.bfloat16)
    assert bm16 == 16


def test_no_pad_ops_in_decode_trace():
    """The jitted decode-step kernel call must trace zero pad ops for
    pack-aligned operands (the old path re-padded packed_w/s_k/s_n on
    every call for K % bk != 0)."""
    x, qv, qu_t, s1, s2 = _mk_lowrank(8, 704, 128, 32)   # K=704=32*22
    with ops.kernel_policy(ops.KernelPolicy(mode="pallas", interpret=True)):
        jaxpr = jax.make_jaxpr(
            lambda *a: ops.lowrank_binary_matmul(*a))(x, qv, qu_t, s1, s2)
    assert "pad[" not in str(jaxpr)


def test_prealigned_pack_matches_unaligned():
    """pack_quantized(k_align=...) stores tile-aligned operands; results
    are identical on both the ref and the fused pallas path (the ops
    layer zero-extends x to the stored K)."""
    from repro.core.packing import pack_quantized
    key = jax.random.PRNGKey(4)
    ku, kv, k1, k2, kx = jax.random.split(key, 5)
    d_in, d_out, r = 96, 64, 32
    lu = jax.random.normal(ku, (d_out, r))
    lv = jax.random.normal(kv, (d_in, r))
    s1 = jnp.abs(jax.random.normal(k1, (d_out,))) + 0.1
    s2 = jnp.abs(jax.random.normal(k2, (d_in,))) + 0.1
    x = jax.random.normal(kx, (5, d_in))
    q0 = pack_quantized(lu, lv, s1, s2)                  # k_align=32
    qa = pack_quantized(lu, lv, s1, s2, k_align=128)
    assert qa["qv"].shape == (4, r) and qa["s2"].shape == (128,)
    for pol in (ops.KernelPolicy(mode="ref"),
                ops.KernelPolicy(mode="pallas", interpret=True)):
        y0 = ops.lowrank_binary_matmul(x, q0["qv"], q0["qu_t"], q0["s1"],
                                       q0["s2"], policy=pol)
        ya = ops.lowrank_binary_matmul(x, qa["qv"], qa["qu_t"], qa["s1"],
                                       qa["s2"], policy=pol)
        _assert_close(ya, y0, jnp.float32, f32_tol=1e-3)


# ---------------------------------------------------------------------------
# engine decode token-identity under the fused policy
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_token_identity_fused_policy():
    """Greedy engine outputs are token-identical between the ref policy
    and the fused+merged pallas policy (interpret mode on CPU)."""
    from repro import configs
    from repro.core.pipeline import QuantConfig, nanoquant_quantize
    from repro.data import calib_batches
    from repro.models import transformer as T
    from repro.serve import InferenceEngine, Request, ServeConfig

    cfg = dataclasses.replace(configs.get_smoke("qwen1.5-0.5b"),
                              dtype="float32")      # qkv_bias covered
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    calib = calib_batches(cfg, 2, 32, batch=2)
    qcfg = QuantConfig(admm_iters=2, t_pre=0, t_post=0, t_glob=0,
                       min_dim=32)
    qp, _ = nanoquant_quantize(params, cfg, calib, qcfg, verbose=False)

    prompts = [np.arange(5, dtype=np.int32) % cfg.vocab_size,
               np.arange(7, dtype=np.int32) % cfg.vocab_size]

    def run(policy):
        with ops.kernel_policy(policy):
            eng = InferenceEngine(qp, cfg, ServeConfig(max_new_tokens=4,
                                                       greedy=True),
                                  max_batch=2, max_len=32)
            for uid, pr in enumerate(prompts):
                eng.submit(Request(uid, pr, max_new_tokens=4))
            done = eng.run()
        return [done[uid].output for uid in range(len(prompts))]

    outs_ref = run(ops.KernelPolicy(mode="ref"))
    outs_fused = run(ops.KernelPolicy(mode="pallas", interpret=True,
                                      fused=True, merge_projections=True))
    for a, b in zip(outs_ref, outs_fused):
        np.testing.assert_array_equal(a, b)

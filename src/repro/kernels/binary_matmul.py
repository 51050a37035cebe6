"""Pallas TPU kernels: packed-binary matmul with fused channel scales.

TPU-native adaptation of the paper's binary CUDA GEMV/GEMM (App. E): the
±1 factor matrices stay bit-packed (uint32) in HBM; each grid step
streams a packed tile into VMEM, expands it to ±1 with a vectorized
shift/mask (VPU), and feeds the MXU matmul.

Two execution strategies for the low-rank chain
``y = s1 ⊙ ((x ⊙ s2) @ V±1) @ U±1ᵀ``:

- :func:`fused_lowrank_matmul` (default) — ONE ``pallas_call``. The
  grid is (group, M-tiles, K-tiles ++ N-tiles): the inner grid dim first
  sweeps K accumulating the stage-1 reduction ``(x ⊙ s2) @ V`` into a
  ``(bm, r)`` f32 VMEM scratch (rank r ≪ d_in, so the whole rank
  dimension fits in one block), then sweeps N consuming that scratch
  for stage 2 ``t @ Uᵀ ⊙ s1`` — the rank-r intermediate never touches
  HBM and every packed tile is unpacked exactly once per M-tile.
  The leading *group* grid dimension serves double duty: merged
  multi-projection calls (QKV / gate-up share x, one kernel instead of
  three/two dispatches) and stacked-expert calls (the expert axis is a
  grid dimension instead of a host-level vmap of the kernel).
- :func:`lowrank_binary_matmul_twocall` — the legacy two-``pallas_call``
  form (stage 1 writes t to HBM, stage 2 re-reads it per output tile);
  kept as the baseline `benchmarks/kernel_wallclock.py` races against
  and as a fallback for ranks too large for a single VMEM block.

GEMV (decode) is the same fused kernel with sublane-sized M tiles:
unlike the paper's CUDA GEMV (which deliberately avoids tensor cores),
TPU has no scalar-core bypass — the MXU is always the right unit, so
one kernel serves both regimes; block sizes come from
:mod:`repro.kernels.tuning`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ranks above this don't fit a single VMEM block comfortably alongside
# the unpacked stage-1 tile; fall back to the two-call kernel.
MAX_FUSED_RANK = 4096


def _unpack_tile(packed, rows):
    """(rows//32, cols) uint32 -> (rows, cols) ±1 f32 (VPU shift/mask).

    The 0/1 bit goes through int32 on its way to f32: Mosaic has no
    uint32 -> float32 conversion."""
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    bits = (packed[:, None, :] >> shifts) & jnp.uint32(1)
    bits = bits.astype(jnp.int32)
    return (bits.astype(jnp.float32) * 2.0 - 1.0).reshape(rows, -1)


# ===========================================================================
# two-call building block (legacy path + wallclock baseline)
# ===========================================================================


def _kernel(x_ref, w_ref, sk_ref, sn_ref, o_ref, acc_ref, *, n_k: int, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_tile(w_ref[...], bk)                     # (bk, bn)
    x = x_ref[...].astype(jnp.float32) * sk_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * sn_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def packed_matmul(x, packed_w, s_k=None, s_n=None, *,
                  bm: int = 128, bn: int = 128, bk: int = 512,
                  interpret: bool = False):
    """y = (x ⊙ s_k) @ unpack(packed_w) ⊙ s_n.

    x: (M, K) float; packed_w: (K//32, N) uint32; s_k: (K,); s_n: (N,).
    bm/bn/bk are preferred tiles: K and N tiles are re-fitted to
    divisors of the operand dims (:func:`repro.kernels.tuning.fit_tile`),
    so the packed operand is never padded at call time.
    """
    from repro.kernels import tuning
    M, K = x.shape
    N = packed_w.shape[1]
    assert packed_w.shape[0] * 32 == K

    if s_k is None:
        s_k = jnp.ones((K,), jnp.float32)
    if s_n is None:
        s_n = jnp.ones((N,), jnp.float32)

    bm = min(bm, max(8, M))
    bk = tuning.fit_tile(K, bk, tuning.K_TILE_ALIGN)
    bn = tuning.fit_tile(N, bn, tuning.N_TILE_ALIGN)
    Mp = -(-M // bm) * bm
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))

    n_m, n_n, n_k = Mp // bm, N // bn, K // bk
    sk2 = s_k.reshape(1, K)
    sn2 = s_n.reshape(1, N)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, bk=bk),
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="nq_packed_matmul",
        interpret=interpret,
    )(x, packed_w, sk2, sn2)
    return out[:M]


def lowrank_binary_matmul_twocall(x, qv, qu_t, s1, s2, *, interpret=False,
                                  bm=128, bn=128, bk=512):
    """Two-stage NanoQuant linear, both stages as packed-matmul kernels
    with the rank-r intermediate round-tripping HBM (pre-fusion path)."""
    shape = x.shape
    d_in = shape[-1]
    x2 = x.reshape(-1, d_in)
    t = packed_matmul(x2, qv, s_k=s2, bm=bm, bn=bn, bk=bk, interpret=interpret)
    y = packed_matmul(t, qu_t, s_n=s1, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return y.reshape(*shape[:-1], y.shape[-1])


# deprecated alias (pre-fusion public name)
lowrank_binary_matmul_pallas = lowrank_binary_matmul_twocall


# ===========================================================================
# fused single-pass kernel (grouped: merged projections / stacked experts)
# ===========================================================================


def _fused_kernel(x_ref, qv_ref, qu_ref, s2_ref, s1_ref, rm_ref, o_ref,
                  acc_ref, *, n_k: int, bk: int, r: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < n_k)
    def _stage1():
        v = _unpack_tile(qv_ref[0], bk)                  # (bk, r)
        x = x_ref[0].astype(jnp.float32) * s2_ref[0].astype(jnp.float32)
        acc_ref[...] += jnp.dot(x, v, preferred_element_type=jnp.float32)

    @pl.when(s >= n_k)
    def _stage2():
        u = _unpack_tile(qu_ref[0], r)                   # (r, bn)
        t = acc_ref[...] * rm_ref[0].astype(jnp.float32)
        o_ref[0] = (jnp.dot(t, u, preferred_element_type=jnp.float32)
                    * s1_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def fused_lowrank_matmul_grouped(xg, qv_g, qu_g, s1_g, s2_g, rmask_g=None, *,
                                 x_shared: bool = False, bm: int = 128,
                                 bn: int = 128, bk: int = 512,
                                 eff_rank: int | None = None,
                                 interpret: bool = False):
    """One fused pass over G grouped low-rank binary linears.

    xg:      (Gx, M, K)  — Gx == 1 with ``x_shared`` (merged projections
             reading the same activations) else Gx == G (stacked experts).
    qv_g:    (G, K//32, R) packed V; qu_g: (G, R//32, N) packed Uᵀ.
    s1_g:    (G, N); s2_g: (G, K); rmask_g: (G, R) f32 zeroing rank
             columns past a group's true rank (merged groups pad every
             projection to the widest rank; None => all ranks real).
    eff_rank: optional effective rank R' <= R (multiple of 32). The
             launch then reads only the leading R' rank columns of the
             FULL packed operands via BlockSpec sub-extents — the HBM
             arrays are untouched (zero-copy rank truncation for the
             speculative draft pass, see serve.speculative). Components
             past R' are simply never streamed into VMEM, so the result
             equals the full launch with a ``arange(R) < R'`` rmask.

    Returns (G, M, N) in xg.dtype. Stage-1 accumulates into a (bm, R)
    VMEM scratch; stage 2 consumes it in place — no HBM traffic for the
    intermediate, one unpack per packed tile per M-tile.
    """
    Gx, M, K = xg.shape
    G, _, R = qv_g.shape
    N = qu_g.shape[2]
    assert qv_g.shape[1] * 32 == K, (qv_g.shape, K)
    assert qu_g.shape[1] * 32 == R, (qu_g.shape, R)
    assert Gx == (1 if x_shared else G)
    if eff_rank is not None:
        if not (0 < eff_rank <= R and eff_rank % 32 == 0):
            raise ValueError(
                f"eff_rank must be a multiple of 32 in (0, {R}], "
                f"got {eff_rank}")
        R_eff = int(eff_rank)
    else:
        R_eff = R
    if rmask_g is None:
        rmask_g = jnp.ones((G, R), jnp.float32)

    from repro.kernels import tuning
    bm = min(bm, max(8, M))
    bk = tuning.fit_tile(K, bk, tuning.K_TILE_ALIGN)
    bn = tuning.fit_tile(N, bn, tuning.N_TILE_ALIGN)
    Mp = -(-M // bm) * bm
    if Mp != M:
        xg = jnp.pad(xg, ((0, 0), (0, Mp - M), (0, 0)))

    n_m, n_n, n_k = Mp // bm, N // bn, K // bk
    s2_3 = s2_g.reshape(G, 1, K)
    s1_3 = s1_g.reshape(G, 1, N)
    rm_3 = rmask_g.reshape(G, 1, R)

    def _k(g, i, s):
        return jnp.minimum(s, n_k - 1)

    def _j(g, i, s):
        return jnp.maximum(s - n_k, 0)

    # With eff_rank, the qv / qu_t / rmask blocks are SUB-EXTENTS of the
    # full HBM operands: block index 0 on the rank axis selects the
    # leading R_eff (or R_eff // 32 packed) entries; the trailing
    # R - R_eff components never leave HBM.
    out = pl.pallas_call(
        functools.partial(_fused_kernel, n_k=n_k, bk=bk, r=R_eff),
        grid=(G, n_m, n_k + n_n),
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         (lambda g, i, s: (0, i, _k(g, i, s))) if x_shared
                         else (lambda g, i, s: (g, i, _k(g, i, s)))),
            pl.BlockSpec((1, bk // 32, R_eff),
                         lambda g, i, s: (g, _k(g, i, s), 0)),
            pl.BlockSpec((1, R_eff // 32, bn),
                         lambda g, i, s: (g, 0, _j(g, i, s))),
            pl.BlockSpec((1, 1, bk), lambda g, i, s: (g, 0, _k(g, i, s))),
            pl.BlockSpec((1, 1, bn), lambda g, i, s: (g, 0, _j(g, i, s))),
            pl.BlockSpec((1, 1, R_eff), lambda g, i, s: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, s: (g, i, _j(g, i, s))),
        out_shape=jax.ShapeDtypeStruct((G, Mp, N), xg.dtype),
        scratch_shapes=[pltpu.VMEM((bm, R_eff), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="nq_fused_lowrank_matmul",
        interpret=interpret,
    )(xg, qv_g, qu_g, s2_3, s1_3, rm_3)
    return out[:, :M]


def fused_lowrank_matmul(x, qv, qu_t, s1, s2, *, interpret=False,
                         bm=128, bn=128, bk=512, eff_rank=None):
    """Single-linear fused NanoQuant matmul: one pallas_call, the rank-r
    intermediate lives only in VMEM. x: (..., d_in) -> (..., d_out).
    ``eff_rank`` truncates the launch to the leading R' rank columns
    without touching the packed operands (see the grouped form)."""
    shape = x.shape
    x2 = x.reshape(1, -1, shape[-1])
    y = fused_lowrank_matmul_grouped(
        x2, qv[None], qu_t[None], s1[None], s2[None], x_shared=True,
        bm=bm, bn=bn, bk=bk, eff_rank=eff_rank, interpret=interpret)[0]
    return y.reshape(*shape[:-1], y.shape[-1])

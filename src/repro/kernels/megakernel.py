"""Fused decode-step megakernel: QKV projection → paged attention → wo.

ONE ``pallas_call`` runs a whole attention decode step for a batch of
slots: the merged-QKV packed low-rank matmul (the PR-3 fused kernel's
math), in-register RoPE, the block-table page walk with an online
softmax, the current token's fresh-KV softmax entry, and the packed
output projection. Neither the rank-r intermediate, nor q/k/v, nor the
attention output ever round-trips HBM — the only HBM traffic is the
packed weights (streamed once), the mapped KV pages, and the three
outputs (y, plus the fresh k/v row for the caller's paged cache write).

Grid layout: ``(B, 1 + n_steps + 1)`` — the inner axis is a *phase*
axis, mirroring the K-then-N phase split of the fused matmul kernel:

- phase 0: merged QKV. For each of the three projection groups the
  packed V/Uᵀ tiles stream through VMEM (unpacked once each, K tiles
  then N tiles), the rank-r intermediate lives in registers, and the
  rmask zeros padded rank columns. RoPE is applied to q and the fresh
  k from the scalar-prefetched position; q/k/v land in VMEM scratch
  and k/v are also written to the fresh-row outputs.
- phases 1..n_steps: the widened page walk of
  :mod:`repro.kernels.paged_attention` (``pages_per_step`` pages per
  phase, coalesced block-table DMA, online-softmax carry). The pool
  row this token will overwrite (virtual row == cache_pos) is
  EXCLUDED — the pool has not been written yet at read time — and the
  fresh k/v scratch supplies that entry instead.
- final phase: fold the fresh-KV entry into the online softmax,
  normalize, and run the packed wo projection on the attention output
  while it is still in VMEM.

Weight/scale operands use constant index maps, so each is DMA'd into
VMEM exactly once per launch regardless of batch; ``eff_rank`` /
``eff_rank_o`` truncate the QKV and wo launches to the leading rank
components via BlockSpec sub-extents (zero-copy, exactly like the
fused matmul kernel — the speculative draft pass composes for free).

Intermediate roundings match the unfused chain: projection outputs
round to the activation dtype, fresh k/v round to the pool dtype
before scoring (what writing them to the pool and reading them back
does), scores and accumulators are f32. The oracle is
:func:`repro.kernels.ref.decode_step_ref`; qualifying-shape gating and
the clean fallback to the unfused chain live in
:func:`repro.kernels.ops.decode_step_megakernel` (see docs/kernels.md
§Decode megakernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.binary_matmul import _unpack_tile


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _split_heads(row, head_dim):
    """(1, H*D) lane-flat projection row -> (H, D) head rows.

    Mosaic cannot reshape a lane dim into (H, D) when D < 128, so the
    relayout is a 0/1 selection matmul: spread the row over H sublanes,
    keep each head's own lanes, and gather lane j to column j mod D.
    At HIGHEST precision the selection is exact."""
    n = row.shape[-1]
    h = n // head_dim
    own = _iota((h, n), 1) // head_dim == _iota((h, n), 0)
    spread = jnp.where(own, jnp.broadcast_to(row, (h, n)), 0.0)
    sel = (_iota((n, head_dim), 0) % head_dim
           == _iota((n, head_dim), 1)).astype(jnp.float32)
    return jnp.dot(spread, sel, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _merge_heads(heads, width):
    """(H, D) head rows -> (1, width) lane-flat row (zeros past H*D):
    the inverse selection matmul of :func:`_split_heads`."""
    h, d = heads.shape
    sel_t = (_iota((d, width), 1) % d == _iota((d, width), 0)
             ).astype(jnp.float32)
    # wide[h, j] = heads[h, j % d]
    wide = jnp.dot(heads, sel_t, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    own = _iota((h, width), 1) // d == _iota((h, width), 0)
    return jnp.where(own, wide, 0.0).sum(axis=0, keepdims=True)


def _rope_rows(h, pos, theta):
    """Rotate-half RoPE on (H, D) f32 rows at a single traced position.
    rotate_half is a signed permutation matmul (exact at HIGHEST
    precision): Mosaic has no lane concatenation of D/2-wide halves."""
    d = h.shape[-1]
    half = d // 2
    j = _iota((1, d), 1)
    freq = jnp.where(j < half, j, j - half).astype(jnp.float32)
    inv = 1.0 / (theta ** (2.0 * freq / d))              # (1, D)
    ang = pos.astype(jnp.float32) * inv
    # rot[:, c] = -h[:, c + D/2] for c < D/2, h[:, c - D/2] otherwise
    src, dst = _iota((d, d), 0), _iota((d, d), 1)
    perm = (jnp.where((dst < half) & (src == dst + half), -1.0, 0.0)
            + jnp.where((dst >= half) & (src == dst - half), 1.0, 0.0))
    rot = jnp.dot(h, perm, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    return h * jnp.cos(ang) + rot * jnp.sin(ang)


def _stage1(x_row, qv_ref, sel, r_eff, bk):
    """(1, K) ⊙ s2 @ V±1 with K-tiled unpack -> (1, r_eff) f32."""
    n_k = x_row.shape[1] // bk
    acc = jnp.zeros((1, r_eff), jnp.float32)
    for kt in range(n_k):
        v = _unpack_tile(qv_ref[sel + (pl.ds(kt * (bk // 32), bk // 32),
                                       slice(None))], bk)
        acc += jnp.dot(x_row[:, kt * bk:(kt + 1) * bk], v,
                       preferred_element_type=jnp.float32)
    return acc


def _stage2(t_acc, qu_ref, s1_ref, sel, n, r_eff, bn):
    """(1, r_eff) @ Uᵀ±1 ⊙ s1 with N-tiled unpack -> (1, n) f32."""
    ys = []
    for nt in range(n // bn):
        u = _unpack_tile(qu_ref[sel + (slice(None),
                                       pl.ds(nt * bn, bn))], r_eff)
        ys.append(jnp.dot(t_acc, u, preferred_element_type=jnp.float32)
                  * s1_ref[pl.ds(sel[0], 1), pl.ds(nt * bn, bn)
                           ].astype(jnp.float32))
    return jnp.concatenate(ys, axis=1)


def _kernel(bt_ref, qpos_ref, cpos_ref, x_ref, qv3_ref, qu3_ref, s23_ref,
            s13_ref, rm3_ref, qvo_ref, quo_ref, s2o_ref, s1o_ref, *rest,
            dims, head_dim, pages, page_size, window, scale, theta,
            ppb, n_steps, r_eff, ro_eff, bk, bn, bko, bno):
    kv_refs = rest[:2 * ppb]
    (y_ref, kn_ref, vn_ref, q_s, k_s, v_s, m_ref, l_ref,
     acc_ref) = rest[2 * ppb:]
    b = pl.program_id(0)
    t = pl.program_id(1)
    nq, nkv = dims
    hq, hkv = nq // head_dim, nkv // head_dim
    g_rep = hq // hkv
    x_dtype = x_ref.dtype

    @pl.when(t == 0)
    def _qkv():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        pos = qpos_ref[b]
        outs = []
        for g, n in enumerate((nq, nkv, nkv)):
            xg = (x_ref[0].astype(jnp.float32)
                  * s23_ref[pl.ds(g, 1), :].astype(jnp.float32))  # (1, K)
            t_acc = _stage1(xg, qv3_ref, (g,), r_eff, bk)
            t_acc = t_acc * rm3_ref[pl.ds(g, 1), :].astype(jnp.float32)
            y_g = _stage2(t_acc, qu3_ref, s13_ref, (g,),
                          s13_ref.shape[-1], r_eff, bn)
            # round to the activation dtype — the unfused chain's
            # projection output dtype — before RoPE/scoring.
            outs.append(_split_heads(
                y_g[:, :n].astype(x_dtype).astype(jnp.float32), head_dim))
        q = _rope_rows(outs[0], pos, theta)
        q_s[...] = q.astype(x_dtype).astype(jnp.float32)
        k = _rope_rows(outs[1], pos, theta)
        kn_ref[0] = k.astype(x_dtype).astype(kn_ref.dtype)
        k_s[...] = kn_ref[0].astype(jnp.float32)
        vn_ref[0] = outs[2].astype(vn_ref.dtype)
        v_s[...] = vn_ref[0].astype(jnp.float32)

    @pl.when(jnp.logical_and(t >= 1, t <= n_steps))
    def _walk():
        qg = q_s[...].reshape(hkv, g_rep, head_dim)
        rows = pages * page_size
        for i in range(ppb):
            k = kv_refs[2 * i][0].astype(jnp.float32)    # (PS, Hkv, D)
            v = kv_refs[2 * i + 1][0].astype(jnp.float32)
            s = jax.lax.dot_general(                     # (Hkv, G, PS)
                qg, k, (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32) * scale
            p_idx = (t - 1) * ppb + i
            r = p_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, page_size), 2)
            abs_pos = qpos_ref[b] - (cpos_ref[b] - r) % rows
            # r == cache_pos is the row THIS token overwrites — stale
            # at read time; the fresh-KV scratch supplies that entry in
            # the final phase instead.
            msk = (abs_pos >= 0) & (p_idx < pages) & (r != cpos_ref[b])
            if window:
                msk = jnp.logical_and(msk, abs_pos > qpos_ref[b] - window)
            s = jnp.where(msk, s, -1e30)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
            l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=-1)
            acc_ref[...] = (acc_ref[...] * alpha[..., None]
                            + jax.lax.dot_general(
                                pexp, v, (((2,), (0,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32))
            m_ref[...] = m_new

    @pl.when(t == n_steps + 1)
    def _finish():
        # fresh-KV softmax entry at abs_pos == q_pos (always in-window)
        qg = q_s[...].reshape(hkv, g_rep, head_dim)
        s_new = (qg * k_s[...][:, None, :]).sum(-1) * scale  # (Hkv, G)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s_new)
        alpha = jnp.exp(m_prev - m_new)
        p_new = jnp.exp(s_new - m_new)
        l = l_ref[...] * alpha + p_new
        acc = (acc_ref[...] * alpha[..., None]
               + p_new[..., None] * v_s[...][:, None, :])
        o = acc / jnp.maximum(l, 1e-30)[..., None]       # (Hkv, G, D)
        # wo while the attention output is still in VMEM
        # (stored K may be pack-aligned past Hq*D; padded s2 cols are 0)
        xo = _merge_heads(o.reshape(hq, head_dim).astype(x_dtype)
                          .astype(jnp.float32), s2o_ref.shape[-1])
        xo = xo * s2o_ref[...].astype(jnp.float32)
        t_o = _stage1(xo, qvo_ref, (0,), ro_eff, bko)
        y = _stage2(t_o, quo_ref, s1o_ref, (0,), s1o_ref.shape[-1],
                    ro_eff, bno)
        y_ref[0] = y.astype(y_ref.dtype)


def decode_step_megakernel_raw(x, mqkv, wo, k_pool, v_pool, block_table,
                               q_pos, cache_pos, *, dims, head_dim,
                               theta, scale, window=0, eff_rank=None,
                               eff_rank_o=None, pages_per_step=1,
                               bk=512, bn=512, interpret=False):
    """Launch the decode-step megakernel (no qualification gating — use
    :func:`repro.kernels.ops.decode_step_megakernel` from model code).

    x: (B, K) one decode token per slot, K matched to the packed QKV
    operand; mqkv / wo: packed param dicts (merged layout / single
    projection); dims: (Hq*D, Hkv*D). Returns (y (B, d_model),
    k_new (B, Hkv, D), v_new (B, Hkv, D)) — fresh k/v are post-RoPE in
    the pool dtype for the caller's paged cache write.
    """
    from repro.kernels import tuning
    B, K = x.shape
    nq, nkv = dims
    hq, hkv = nq // head_dim, nkv // head_dim
    NP, PS, Hkv_p, D_p = k_pool.shape
    assert (Hkv_p, D_p) == (hkv, head_dim), (k_pool.shape, dims)
    pages = block_table.shape[1]
    R = mqkv["qv"].shape[-1]
    Nmax = mqkv["qu_t"].shape[-1]
    Ro = wo["qv"].shape[-1]
    No = wo["qu_t"].shape[-1]
    Ko = wo["qv"].shape[0] * 32
    assert mqkv["qv"].shape[1] * 32 == K, (mqkv["qv"].shape, K)

    r_eff = int(eff_rank) if eff_rank else R
    ro_eff = int(eff_rank_o) if eff_rank_o else Ro
    assert 0 < r_eff <= R and r_eff % 32 == 0, (r_eff, R)
    assert 0 < ro_eff <= Ro and ro_eff % 32 == 0, (ro_eff, Ro)
    rmask = mqkv.get("rmask")
    if rmask is None:
        rmask = jnp.ones((3, R), jnp.float32)

    bko = tuning.fit_tile(Ko, bk, tuning.K_TILE_ALIGN)
    bk = tuning.fit_tile(K, bk, tuning.K_TILE_ALIGN)
    bn_q = tuning.fit_tile(Nmax, bn, tuning.N_TILE_ALIGN)
    bno = tuning.fit_tile(No, bn, tuning.N_TILE_ALIGN)

    ppb = max(1, min(int(pages_per_step), pages))
    npad = -(-pages // ppb) * ppb
    bt = block_table.astype(jnp.int32)
    if npad != pages:
        bt = jnp.pad(bt, ((0, 0), (0, npad - pages)))
    n_steps = npad // ppb
    T = n_steps + 2

    def _kv_map(i):
        def f(b, t, bt_, qp, cp):
            in_walk = jnp.logical_and(t >= 1, t <= n_steps)
            p = jnp.clip((t - 1) * ppb + i, 0, npad - 1)
            return (jnp.where(in_walk, bt_[b, p], 0), 0, 0, 0)
        return f

    kv_specs = []
    for i in range(ppb):
        kv_specs.append(pl.BlockSpec((1, PS, hkv, head_dim), _kv_map(i)))
        kv_specs.append(pl.BlockSpec((1, PS, hkv, head_dim), _kv_map(i)))

    const = lambda *ix: (lambda b, t, bt_, qp, cp: ix)   # noqa: E731

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, 1, K), lambda b, t, bt_, qp, cp: (b, 0, 0)),
            # rank sub-extents: eff_rank truncation without repacking
            pl.BlockSpec((3, K // 32, r_eff), const(0, 0, 0)),
            pl.BlockSpec((3, r_eff // 32, Nmax), const(0, 0, 0)),
            pl.BlockSpec((3, K), const(0, 0)),
            pl.BlockSpec((3, Nmax), const(0, 0)),
            pl.BlockSpec((3, r_eff), const(0, 0)),
            pl.BlockSpec((1, Ko // 32, ro_eff), const(0, 0, 0)),
            pl.BlockSpec((1, ro_eff // 32, No), const(0, 0, 0)),
            pl.BlockSpec((1, Ko), const(0, 0)),
            pl.BlockSpec((1, No), const(0, 0)),
            *kv_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, No), lambda b, t, bt_, qp, cp: (b, 0, 0)),
            pl.BlockSpec((1, hkv, head_dim),
                         lambda b, t, bt_, qp, cp: (b, 0, 0)),
            pl.BlockSpec((1, hkv, head_dim),
                         lambda b, t, bt_, qp, cp: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hq, head_dim), jnp.float32),     # roped q
            pltpu.VMEM((hkv, head_dim), jnp.float32),    # fresh k
            pltpu.VMEM((hkv, head_dim), jnp.float32),    # fresh v
            pltpu.VMEM((hkv, hq // hkv), jnp.float32),   # running max
            pltpu.VMEM((hkv, hq // hkv), jnp.float32),   # running sum
            pltpu.VMEM((hkv, hq // hkv, head_dim), jnp.float32),
        ],
    )
    y, k_new, v_new = pl.pallas_call(
        functools.partial(
            _kernel, dims=(nq, nkv), head_dim=head_dim, pages=pages,
            page_size=PS, window=int(window), scale=float(scale),
            theta=float(theta), ppb=ppb, n_steps=n_steps, r_eff=r_eff,
            ro_eff=ro_eff, bk=bk, bn=bn_q, bko=bko, bno=bno),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, No), x.dtype),
            jax.ShapeDtypeStruct((B, hkv, head_dim), k_pool.dtype),
            jax.ShapeDtypeStruct((B, hkv, head_dim), v_pool.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="nq_decode_megakernel",
        interpret=interpret,
    )(bt, q_pos.astype(jnp.int32), cache_pos.astype(jnp.int32),
      x[:, None], mqkv["qv"], mqkv["qu_t"], mqkv["s2"], mqkv["s1"],
      rmask.astype(jnp.float32), wo["qv"][None], wo["qu_t"][None],
      wo["s2"].reshape(1, Ko), wo["s1"].reshape(1, No),
      *([k_pool, v_pool] * ppb))
    return y[:, 0], k_new, v_new

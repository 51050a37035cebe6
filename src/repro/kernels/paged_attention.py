"""Pallas TPU gather-attention decode kernel over a paged KV pool.

One grid step = one (slot, kv-head tile, page *group*): the block specs
walk the slot's block table — prefetched into SMEM via
``PrefetchScalarGridSpec``, so the index maps can compute each page's
pool address before the body runs — and DMA exactly the pages the slot
has mapped, instead of slicing a ``max_batch x max_len`` rectangle.
Scores accumulate across page groups with an online softmax held in
VMEM scratch (flash-attention style), so the slot's virtual rectangle
is never materialized in HBM or VMEM.

Two tuning knobs (``kernels.tuning.fit_paged_block_sizes``):

- ``pages_per_step`` — pages walked per grid step. Each page of a group
  is a separate BlockSpec over the same pool operand, so the group's
  page DMAs are issued together off one scalar-prefetched block-table
  read (coalesced) and the per-step grid overhead amortizes across the
  group. The block table is padded with null-page entries up to a
  multiple; padded entries mask out (and sit past every live bound).
- ``head_block`` — kv-head tile (0 = all heads in one block). A divisor
  of Hkv adds a head grid dimension with per-tile online-softmax
  scratch, for models whose (Hkv, G, D) state would crowd VMEM.

Masking is the rectangular decode-mask math on virtual row indices:
row ``r = page*page_size + offset`` last held absolute position
``q_pos - ((cache_pos - r) mod rows)`` (negative = never written;
``window`` masks past the sliding window) — which makes the same
kernel serve linear caches (``cache_pos == q_pos``) and the hybrid
family's sliding-window ring (``cache_pos == q_pos mod rows``).
Unmapped block-table entries point at the null page 0 and mask out
because their virtual rows sit past every valid position; padded
table entries sit past the virtual rectangle entirely and are masked
explicitly.

Groups past a slot's live bound are skipped, not just masked. The
wrapper derives each slot's live group count from ``q_pos`` and
``cache_pos`` (:func:`live_steps`, a fourth scalar-prefetch operand):
every group once the slot has written the whole table or its live rows
wrap the ring, else up to the group of row ``cache_pos``. The page
index maps clamp the step to the slot's last live group, so the block
index repeats and the pipeline issues no copy for the dead steps, and
the body runs under ``pl.when(j < live)``. A dead group holds only
masked rows, so it would have left the online-softmax state exactly as
it was: the skip changes no bit of the output.

Numerics are validated against :func:`repro.kernels.ref.
paged_attention_ref` on the CPU interpreter (tests/test_paging.py and
the differential fuzz suite, tests/test_kernel_diff.py). The MLA decode
path gathers pages in plain XLA instead (its absorbed-latent scoring
is a dense matmul chain, not a GQA read — see docs/kernels.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _online_update(s, msk, v, m_ref, l_ref, acc_ref):
    """One online-softmax step: fold scores ``s`` (Hb, G, R) with mask
    ``msk`` and values ``v`` (R, Hb, D) into the running (m, l, acc)
    scratch."""
    s = jnp.where(msk, s, -1e30)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
    l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
        pexp, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(bt_ref, qpos_ref, cpos_ref, live_ref, q_ref, *rest,
            pages: int, page_size: int, window: int, scale: float,
            ppb: int, n_steps: int):
    kv_refs = rest[:2 * ppb]
    o_ref, m_ref, l_ref, acc_ref = rest[2 * ppb:]
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hqb, d = q_ref.shape[2], q_ref.shape[3]
    hb = kv_refs[0].shape[2]
    rows = pages * page_size

    # page groups past the slot's live bound hold only masked rows: they
    # would leave (m, l, acc) exactly as they are, so they are skipped
    # (their index maps repeat the last live group, so no DMA either).
    @pl.when(j < live_ref[b])
    def _walk():
        q = q_ref[0, 0].astype(jnp.float32)              # (Hb*G, D)
        qg = q.reshape(hb, hqb // hb, d)                 # (Hb, G, D)

        # the group's pages arrive as ppb separate VMEM blocks whose
        # DMAs were all issued from this step's block-table prefetch;
        # the online softmax carries across the widened page axis
        # within the step.
        for i in range(ppb):
            k = kv_refs[2 * i][0].astype(jnp.float32)    # (PS, Hb, D)
            v = kv_refs[2 * i + 1][0].astype(jnp.float32)
            s = jax.lax.dot_general(                     # (Hb, G, PS)
                qg, k, (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32) * scale

            # virtual-row validity (see module docstring)
            p_idx = j * ppb + i
            r = p_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, page_size), 2)
            abs_pos = qpos_ref[b] - (cpos_ref[b] - r) % rows
            msk = jnp.logical_and(abs_pos >= 0, p_idx < pages)
            if window:
                msk = jnp.logical_and(msk, abs_pos > qpos_ref[b] - window)
            _online_update(s, msk, v, m_ref, l_ref, acc_ref)

    @pl.when(j == n_steps - 1)
    def _flush():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0, 0] = o.reshape(hqb, d).astype(o_ref.dtype)


def live_steps(q_pos, cache_pos, *, pages: int, page_size: int,
               ppb: int):
    """Page groups of each slot's table that hold a valid row, (B,)
    int32, at least 1. Row ``r`` is valid iff ``(cache_pos - r) mod
    rows <= q_pos``: every row once ``q_pos >= rows - 1`` or once the
    live rows wrap (``cache_pos < q_pos``, the sliding-window ring);
    otherwise rows ``cache_pos - q_pos .. cache_pos``, so the last live
    page is ``cache_pos // page_size``."""
    rows = pages * page_size
    q_pos = q_pos.astype(jnp.int32)
    cache_pos = cache_pos.astype(jnp.int32)
    live_pages = jnp.where(
        (q_pos >= rows - 1) | (cache_pos < q_pos), pages,
        jnp.minimum(pages, cache_pos // page_size + 1))
    return jnp.maximum(1, -(-live_pages // ppb)).astype(jnp.int32)


def paged_decode_attention(q, k_pool, v_pool, block_table, q_pos,
                           cache_pos, *, window: int = 0,
                           scale: float = 1.0, pages_per_step: int = 1,
                           head_block: int = 0, interpret: bool = False):
    """Block-table decode attention (one pallas_call).

    q: (B, 1, Hq, D); k_pool / v_pool: (n_pages, page_size, Hkv, D);
    block_table: (B, pages) int32; q_pos / cache_pos: (B,) int32 (see
    :func:`repro.kernels.ref.paged_attention_ref` for the contract).
    pages_per_step / head_block: tuning knobs (see module docstring;
    ``kernels.tuning.fit_paged_block_sizes`` picks them from the paged
    heuristic table). Returns (B, 1, Hq, D) in q.dtype.
    """
    B, S, Hq, D = q.shape
    assert S == 1, "paged attention is a single-token decode read"
    NP, PS, Hkv, Dk = k_pool.shape
    assert Dk == D and Hq % Hkv == 0, (q.shape, k_pool.shape)
    pages = block_table.shape[1]
    G = Hq // Hkv

    ppb = max(1, min(int(pages_per_step), pages))
    hb = int(head_block) or Hkv
    if Hkv % hb:
        hb = Hkv
    n_h = Hkv // hb

    # pad the block table with null-page entries up to a step multiple;
    # padded entries sit past the virtual rectangle and mask out.
    npad = -(-pages // ppb) * ppb
    bt = block_table.astype(jnp.int32)
    if npad != pages:
        bt = jnp.pad(bt, ((0, 0), (0, npad - pages)))
    n_steps = npad // ppb

    live = live_steps(q_pos, cache_pos, pages=pages, page_size=PS,
                      ppb=ppb)

    def _kv_map(i):
        # past the slot's live bound the step index is clamped to its
        # last live group: the block index repeats, so the pipeline
        # issues no new copy for the dead steps.
        def f(b, h, j, bt_, qp, cp, lv):
            jj = jnp.minimum(j, lv[b] - 1)
            return (bt_[b, jj * ppb + i], 0, h, 0)
        return f

    def _q_map(b, h, j, bt_, qp, cp, lv):
        return (b, 0, h, 0)

    kv_specs = []
    for i in range(ppb):
        kv_specs.append(pl.BlockSpec((1, PS, hb, D), _kv_map(i)))
        kv_specs.append(pl.BlockSpec((1, PS, hb, D), _kv_map(i)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_h, n_steps),
        in_specs=[
            # q heads are kv-head-major (GQA group g of kv head h is
            # head h*G+g), so a kv-head tile's queries are contiguous.
            pl.BlockSpec((1, 1, hb * G, D), _q_map),
            *kv_specs,
        ],
        out_specs=pl.BlockSpec((1, 1, hb * G, D), _q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, G), jnp.float32),            # running max
            pltpu.VMEM((hb, G), jnp.float32),            # running sum
            pltpu.VMEM((hb, G, D), jnp.float32),         # output acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, pages=pages, page_size=PS,
                          window=int(window), scale=float(scale),
                          ppb=ppb, n_steps=n_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="nq_paged_attention",
        interpret=interpret,
    )(bt, q_pos.astype(jnp.int32), cache_pos.astype(jnp.int32), live,
      q, *([k_pool, v_pool] * ppb))

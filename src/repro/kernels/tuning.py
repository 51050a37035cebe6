"""Block-size selection for the packed binary matmul + paged kernels.

Two layers per kernel family:

- :data:`DEFAULT_BLOCK_TABLE` — a shape-class heuristic table keyed on
  (M, K, N, r) upper bounds, seeded from an offline sweep
  (``python -m benchmarks.kernel_bench --sweep``) and overridable
  per :class:`~repro.kernels.ops.KernelPolicy` (``block_table=...``).
- :func:`fit_block_sizes` — fits the table's *preferred* tile sizes to a
  concrete shape so that the K and N tiles **divide** the operand dims
  and keep Mosaic's (8, 128) block tiling. This is what lets
  ``packed_matmul`` / the fused kernel skip call-time padding of the
  packed weights: a divisor tile means zero pad ops traced into the
  jitted decode step (padding K up to a fixed bk=512 multiple would
  copy the whole packed tensor once per token for shapes like
  d_ff=2816).

A shape no table row covers is never silently given the generic prefill
tile: :func:`lookup_block_table` falls back to shape-derived preferred
tiles (which :func:`fit_block_sizes` then divisor-fits as usual) and
warns ONCE per shape class so untuned decode shapes surface in logs
instead of shipping a padded GEMV.

The paged gather-attention kernel has its own knob table
(:data:`DEFAULT_PAGED_TABLE` / :func:`fit_paged_block_sizes`): how many
block-table pages one grid step walks (``pages_per_step`` — wider steps
amortize grid overhead and coalesce the block-table DMA) and the
kv-head tile (``head_block`` — 0 keeps all heads in one block; a
divisor of Hkv splits the online-softmax state across a head grid
dimension for large-head models).

Table rows are plain tuples so a :class:`KernelPolicy` carrying one
stays an immutable value type: ``(m_hi, k_hi, n_hi, r_hi, bm, bn, bk)``
(matmul) / ``(b_hi, hkv_hi, d_hi, pages_hi, pages_per_step,
head_block)`` (paged); first row whose bounds cover the shape wins.
"""
from __future__ import annotations

import json
import warnings
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

# sign bits are packed 32-per-word along K, so a K tile of bk rows is a
# (bk // 32)-row block of the packed operand. Mosaic tiles the last two
# dims of every block by (8 sublanes, 128 lanes) unless the block spans
# the whole dim: a packed K tile needs whole 8-row sublane groups (bk a
# multiple of 8 * 32 = 256) and an N tile whole lanes (a multiple of 128).
PACK_ALIGN = 32
K_TILE_ALIGN = 8 * PACK_ALIGN
N_TILE_ALIGN = 128

# (m_hi, k_hi, n_hi, r_hi, bm, bn, bk) — seeded by the offline sweep in
# benchmarks/kernel_wallclock.py (--sweep); ordered decode -> prefill.
# Decode rows keep bm at the dtype sublane so a (B,) slot batch becomes
# a single MXU row tile instead of being padded to 128; wide-N rows
# stream more output columns per packed-tile unpack.
DEFAULT_BLOCK_TABLE: Tuple[Tuple[int, ...], ...] = (
    # decode / GEMV: tiny M, stream weights in wide tiles
    (16, 4096, 100_000, 1024, 8, 512, 512),
    (16, 100_000, 100_000, 100_000, 8, 256, 512),
    # small-batch decode (continuous-batching slot pools)
    (64, 100_000, 100_000, 100_000, 64, 256, 512),
    # prefill / training: square MXU tiles
    (100_000, 100_000, 100_000, 100_000, 128, 128, 512),
)

# (b_hi, hkv_hi, d_hi, pages_hi, pages_per_step, head_block) — knobs for
# the paged gather-attention decode kernel. pages_per_step > 1 walks
# several block-table pages per grid step (the block table is scalar-
# prefetched once, so the per-page index maps coalesce into one DMA
# burst per step); head_block 0 = all kv heads in one block (small-Hkv
# serving shapes), a divisor of Hkv adds a kv-head grid dimension.
DEFAULT_PAGED_TABLE: Tuple[Tuple[int, ...], ...] = (
    # shallow tables (tiny pools / smoke shapes): pair up pages
    (100_000, 100_000, 100_000, 4, 2, 0),
    # serving-depth tables: walk four pages per step
    (100_000, 8, 100_000, 100_000, 4, 0),
    # many kv heads: tile the online-softmax state across heads too
    (100_000, 100_000, 100_000, 100_000, 4, 8),
)


def _sublane(dtype) -> int:
    """Minimum sublane count for the activation dtype (f32: 8, bf16: 16)."""
    try:
        if jnp.dtype(dtype).itemsize <= 2:
            return 16
    except TypeError:
        pass
    return 8


def fit_tile(dim: int, pref: int, align: int) -> int:
    """Largest multiple of `align` that divides `dim` and is <= `pref`;
    the whole `dim` when there is none (a full-extent block is always
    legal). Either way the tile divides `dim`, so operands are never
    padded at call time."""
    best = 0
    d = align
    while d <= min(pref, dim):
        if dim % d == 0:
            best = d
        d += align
    return best or dim


# shape classes that already warned a table miss (once per process per
# class — the decode loop calls block_sizes per trace, not per token,
# but even per-trace repeats would drown logs).
_MISS_WARNED: set = set()


def _miss_tiles(M: int, K: int, N: int, r: int) -> Tuple[int, int, int]:
    """Shape-derived preferred tiles for a shape no table row covers:
    decode-sized M keeps the sublane M tile (GEMV row, never padded to
    128), wide weights stream in wide N tiles. fit_block_sizes then
    divisor-fits K/N exactly like a table hit."""
    if M <= 16:
        return 8, 512 if N >= 512 else 256, 512
    if M <= 64:
        return 64, 256, 512
    return 128, 128, 512


def lookup_block_table(M: int, K: int, N: int, r: int,
                       table: Optional[Sequence[Tuple[int, ...]]] = None
                       ) -> Tuple[int, int, int]:
    """Preferred (bm, bn, bk) for a shape class, before shape fitting.
    A custom (swept) table that covers none of the shape's bounds falls
    through to the built-in heuristic table — a sweep run on small
    shapes must not degrade untuned production shapes. A shape NO table
    covers gets shape-derived tiles plus a one-time warning (it should
    be added to the sweep, see ``kernel_bench --sweep``)."""
    tables = [table, DEFAULT_BLOCK_TABLE] if table else [DEFAULT_BLOCK_TABLE]
    for t in tables:
        for m_hi, k_hi, n_hi, r_hi, bm, bn, bk in t:
            if M <= m_hi and K <= k_hi and N <= n_hi and r <= r_hi:
                return bm, bn, bk
    cls = ("matmul", M <= 16, M <= 64, K, N)
    if cls not in _MISS_WARNED:
        _MISS_WARNED.add(cls)
        warnings.warn(
            f"kernels.tuning: no block-table row covers shape "
            f"(M={M}, K={K}, N={N}, r={r}); using divisor-fitted "
            f"fallback tiles. Re-run `python -m benchmarks.kernel_bench "
            f"--sweep --commit-table` to tune this shape.",
            stacklevel=3)
    return _miss_tiles(M, K, N, r)


def fit_block_sizes(M: int, K: int, N: int, r: int, dtype=jnp.float32,
                    table: Optional[Sequence[Tuple[int, ...]]] = None
                    ) -> Tuple[int, int, int]:
    """Concrete (bm, bn, bk) for one kernel call.

    K/N tiles divide the operand dims (:func:`fit_tile`: bk a multiple
    of 256, bn a multiple of 128, else the whole dim), so the packed
    weights are never padded at call time; the M tile covers the
    (small) activation batch rounded to the dtype sublane.
    """
    bm_p, bn_p, bk_p = lookup_block_table(M, K, N, r, table)
    sub = _sublane(dtype)
    bm = min(max(bm_p, sub), -(-M // sub) * sub)
    bk = fit_tile(K, bk_p, K_TILE_ALIGN)
    bn = fit_tile(N, bn_p, N_TILE_ALIGN)
    return bm, bn, bk


def fit_paged_block_sizes(B: int, Hkv: int, D: int, pages: int,
                          table: Optional[Sequence[Tuple[int, ...]]] = None
                          ) -> Tuple[int, int]:
    """Concrete (pages_per_step, head_block) for one paged-attention
    launch. pages_per_step is clamped to the table depth (the launch
    pads the block table with null-page entries up to a multiple, so
    any value is *correct* — the clamp just avoids walking pure
    padding); head_block is snapped down to a divisor of Hkv (0 = no
    head tiling)."""
    tables = [table, DEFAULT_PAGED_TABLE] if table else [DEFAULT_PAGED_TABLE]
    ppb, hb = 1, 0
    for t in tables:
        hit = False
        for b_hi, h_hi, d_hi, p_hi, p_ppb, p_hb in t:
            if B <= b_hi and Hkv <= h_hi and D <= d_hi and pages <= p_hi:
                ppb, hb, hit = p_ppb, p_hb, True
                break
        if hit:
            break
    else:
        cls = ("paged", Hkv, D, pages <= 4)
        if cls not in _MISS_WARNED:
            _MISS_WARNED.add(cls)
            warnings.warn(
                f"kernels.tuning: no paged-table row covers shape "
                f"(B={B}, Hkv={Hkv}, D={D}, pages={pages}); using "
                f"defaults (pages_per_step=2). Re-run `python -m "
                f"benchmarks.kernel_bench --sweep --commit-table`.",
                stacklevel=3)
        ppb, hb = 2, 0
    ppb = max(1, min(int(ppb), pages))
    hb = int(hb)
    if hb:
        while hb > 1 and Hkv % hb:
            hb -= 1
        if hb <= 1 or hb >= Hkv:
            hb = 0
    return ppb, hb


def _matmul_rows(rows) -> Tuple[Tuple[int, ...], ...]:
    return tuple((int(r["m_hi"]), int(r["k_hi"]), int(r["n_hi"]),
                  int(r["r_hi"]), int(r["bm"]), int(r["bn"]), int(r["bk"]))
                 for r in rows)


def load_block_table(path: str) -> Tuple[Tuple[int, ...], ...]:
    """Parse a swept block table (``python -m benchmarks.kernel_bench
    --sweep``) into the tuple-of-rows form
    `KernelPolicy(block_table=...)` takes. Accepts both the legacy bare
    row list and the committed ``{"meta":..., "matmul":..., "paged":...}``
    envelope (``--commit-table``); this returns the matmul rows — use
    :func:`load_paged_table` for the paged-kernel rows."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        rows = doc.get("matmul", doc.get("rows", []))
    else:
        rows = doc
    return _matmul_rows(rows)


def load_paged_table(path: str) -> Optional[Tuple[Tuple[int, ...], ...]]:
    """Paged-kernel rows of a committed swept table
    (``kernel_bench --sweep --commit-table``), or None for legacy
    matmul-only artifacts."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "paged" not in doc:
        return None
    return tuple((int(r["b_hi"]), int(r["hkv_hi"]), int(r["d_hi"]),
                  int(r["pages_hi"]), int(r["pages_per_step"]),
                  int(r["head_block"])) for r in doc["paged"])

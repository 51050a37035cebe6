"""Jit'd public wrappers for the binary kernels.

The model stack calls :func:`lowrank_binary_matmul` (plus the merged
multi-projection and stacked-expert entry points below); execution is
governed by an explicit, immutable :class:`KernelPolicy`:

- ``mode="ref"``    — pure-jnp oracle. Lowerable on every backend and
  under any pjit sharding, so it is the right choice for CPU runs and
  the multi-pod dry-run (XLA SPMD partitions it like any matmul chain).
- ``mode="pallas"`` — the Pallas TPU kernels (interpret mode off-TPU),
  for real deployments and kernel validation.
- ``mode="auto"``   — pallas on TPU backends, ref elsewhere.

On the pallas path, ``fused=True`` (default) runs the whole low-rank
chain as ONE kernel with the rank-r intermediate held in VMEM
(:func:`repro.kernels.binary_matmul.fused_lowrank_matmul`);
``fused=False`` keeps the legacy two-``pallas_call`` form.
``merge_projections=True`` additionally lets the model layer batch
projections that share an input (QKV, gate/up) into a single grouped
kernel launch. Block sizes come from a heuristic table keyed on
(M, K, N, r) — see :mod:`repro.kernels.tuning` — overridable per policy
via ``block_table=`` (rows from ``tuning.load_block_table``).

Tensor-parallel launch: when the policy carries a ``mesh`` (an axis
named ``tp_axis``, normally ``"model"``), every entry point wraps its
kernel in ``shard_map`` so each device runs the *local* kernel on its
weight shard, mirroring the Megatron pairing of
``repro.sharding.rules``: column-parallel projections (QKV / gate-up /
mamba z/x) compute their d_out shard with no collective, row-parallel
projections (wo / w_down / out_proj) consume a d_in-sharded input and
finish with ONE psum over the small (..., d_out) partial, and merged /
stacked-expert group launches stay shard-local on the group axis.
Shapes that do not divide the axis fall back to the replicated
single-device launch — exactly the rules' divisibility fallback.

A policy can be threaded explicitly (``lowrank_binary_matmul(...,
policy=p)``), installed for a scope (``with kernel_policy(p): ...``), or
set process-wide (:func:`set_kernel_policy`). The scoped form restores
the previous policy on exit and is contextvar-based, so concurrent
threads / asyncio tasks do not trample each other.

``set_kernel_mode`` / ``kernel_mode`` are deprecated shims over the old
mutable process-global mode list; each warns exactly once per process.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import warnings
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import binary_matmul, ref, tuning

_MODES = ("auto", "ref", "pallas")


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Execution policy for the packed binary matmul.

    interpret: run the Pallas kernel in interpreter mode. ``None``
    resolves at call time to "interpret unless on a real TPU backend".
    fused: single-pass kernel (VMEM-resident rank intermediate) vs the
    legacy two-call chain. merge_projections: allow grouped QKV /
    gate-up launches. block_table: optional tuple of
    ``(m_hi, k_hi, n_hi, r_hi, bm, bn, bk)`` rows (first match wins)
    replacing the built-in heuristic table — typically produced by the
    offline sweep (``python -m benchmarks.kernel_bench --sweep``)
    and loaded with :func:`repro.kernels.tuning.load_block_table`.
    """
    mode: str = "auto"
    interpret: Optional[bool] = None
    fused: bool = True
    merge_projections: bool = True
    # decode-step megakernel (QKV → paged attention → wo in one pass);
    # requires the fused merged-projection path and only engages for
    # qualifying launches — see decode_step_megakernel.
    megakernel: bool = True
    block_table: Optional[Tuple[Tuple[int, ...], ...]] = None
    # paged-kernel knob table: (b_hi, hkv_hi, d_hi, pages_hi,
    # pages_per_step, head_block) rows, from tuning.load_paged_table.
    paged_block_table: Optional[Tuple[Tuple[int, ...], ...]] = None
    # tensor-parallel launch: a jax Mesh with a `tp_axis` axis turns
    # every entry point into a shard_map over that axis (col/row per
    # repro.sharding.rules); None = single-device launch (default).
    # NB: sharding.rules places weights on "model" only — a different
    # tp_axis is for custom placements and forgoes the placement/launch
    # agreement (the InferenceEngine always pins "model").
    mesh: Optional[Any] = None
    tp_axis: str = "model"

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(
                f"unknown kernel mode {self.mode!r}; choose from {_MODES}")
        if self.block_table is not None:
            object.__setattr__(self, "block_table",
                               tuple(tuple(r) for r in self.block_table))
        if self.paged_block_table is not None:
            object.__setattr__(self, "paged_block_table",
                               tuple(tuple(r)
                                     for r in self.paged_block_table))

    def use_pallas(self) -> bool:
        if self.mode == "auto":
            return jax.default_backend() == "tpu"
        return self.mode == "pallas"

    def resolve_interpret(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() != "tpu"
        return self.interpret

    def use_merged_projections(self) -> bool:
        """Whether the model layer should issue grouped QKV / gate-up
        kernel calls (requires the fused pallas path)."""
        return self.use_pallas() and self.fused and self.merge_projections

    def use_megakernel(self) -> bool:
        """Whether the model layer should try the fused decode-step
        megakernel (per-launch shape gating still applies — see
        :func:`decode_step_megakernel`)."""
        return self.use_merged_projections() and self.megakernel

    def tp_size(self) -> int:
        """Devices along the tensor-parallel axis (1 = no TP)."""
        if self.mesh is None or self.tp_axis not in self.mesh.axis_names:
            return 1
        return int(self.mesh.shape[self.tp_axis])

    def block_sizes(self, M: int, K: int, N: int, r: int,
                    dtype=jnp.float32) -> Tuple[int, int, int]:
        """(bm, bn, bk) for one call, from the heuristic table fitted to
        the concrete shape (divisor tiles — no weight padding)."""
        return tuning.fit_block_sizes(M, K, N, r, dtype, self.block_table)

    def paged_block_sizes(self, B: int, Hkv: int, D: int,
                          pages: int) -> Tuple[int, int]:
        """(pages_per_step, head_block) for one paged-attention launch,
        from the paged knob table fitted to the concrete shape."""
        return tuning.fit_paged_block_sizes(B, Hkv, D, pages,
                                            self.paged_block_table)


# Scoped overrides live in a ContextVar (thread/async-local); the
# process-wide default lives in a plain module global so that
# set_kernel_policy is visible from every thread (new threads start with
# a fresh contextvars.Context and would miss a ContextVar-only set).
_DEFAULT_POLICY = [KernelPolicy()]
_POLICY: contextvars.ContextVar[Optional[KernelPolicy]] = \
    contextvars.ContextVar("nanoquant_kernel_policy", default=None)


def current_kernel_policy() -> KernelPolicy:
    scoped = _POLICY.get()
    return scoped if scoped is not None else _DEFAULT_POLICY[0]


def set_kernel_policy(policy: KernelPolicy) -> KernelPolicy:
    """Install `policy` process-wide (all threads); returns the previous
    default. Scoped `kernel_policy(...)` overrides still win."""
    prev = _DEFAULT_POLICY[0]
    _DEFAULT_POLICY[0] = _coerce(policy)
    return prev


def _coerce(policy: Union[KernelPolicy, str]) -> KernelPolicy:
    if isinstance(policy, str):
        return KernelPolicy(mode=policy)
    return policy


@contextlib.contextmanager
def kernel_policy(policy: Union[KernelPolicy, str]):
    """Scoped policy override (this thread/task only); restores the
    prior policy on exit."""
    token = _POLICY.set(_coerce(policy))
    try:
        yield current_kernel_policy()
    finally:
        _POLICY.reset(token)


def _match_packed_k(x, qv):
    """Zero-pad x's feature dim up to the packed operand's K. Stored
    operands may be K-aligned past the activation width (surgery packs
    them tile-aligned); the padded s2 columns are zero so the extra
    columns contribute nothing."""
    Kw = qv.shape[-2] * 32
    d = x.shape[-1]
    if Kw == d:
        return x
    assert Kw > d, (qv.shape, x.shape)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, Kw - d)]
    return jnp.pad(x, pad)


def _slice_rank(qv, qu_t, eff_rank: int):
    """In-trace rank truncation for the non-fused paths: keep the
    leading ``eff_rank`` rank columns of packed V (last axis) and the
    leading ``eff_rank // 32`` packed rows of Uᵀ. Pure slices — XLA
    reads sub-extents of the stored operands, no repack (the fused
    pallas launch does the same thing via BlockSpec sub-extents)."""
    r = qv.shape[-1]
    if not (0 < eff_rank <= r and eff_rank % 32 == 0):
        raise ValueError(f"eff_rank must be a multiple of 32 in (0, {r}], "
                         f"got {eff_rank}")
    return qv[..., :eff_rank], qu_t[..., :eff_rank // 32, :]


def _local_lowrank(x, qv, qu_t, s1, s2, p: KernelPolicy, eff_rank=None):
    """Single-device dispatch (x already matched to the packed K)."""
    if p.use_pallas():
        r = qv.shape[-1]
        M = x.size // x.shape[-1]
        bm, bn, bk = p.block_sizes(M, x.shape[-1], qu_t.shape[-1],
                                   eff_rank or r, x.dtype)
        interp = p.resolve_interpret()
        if p.fused and r <= binary_matmul.MAX_FUSED_RANK:
            return binary_matmul.fused_lowrank_matmul(
                x, qv, qu_t, s1, s2, bm=bm, bn=bn, bk=bk,
                eff_rank=eff_rank, interpret=interp)
        if eff_rank is not None:
            qv, qu_t = _slice_rank(qv, qu_t, eff_rank)
        return binary_matmul.lowrank_binary_matmul_twocall(
            x, qv, qu_t, s1, s2, bm=bm, bn=bn, bk=bk, interpret=interp)
    if eff_rank is not None:
        qv, qu_t = _slice_rank(qv, qu_t, eff_rank)
    return ref.lowrank_binary_matmul_ref(x, qv, qu_t, s1, s2)


def _shard_launch(p: KernelPolicy, local, in_specs, out_specs, *operands,
                  reduce_axis=None):
    """shard_map-wrap a ``_local_*`` dispatcher over the policy mesh:
    each device runs `local(*operands_shard, local_policy)` on its
    shard (the local policy is the same policy with the mesh stripped),
    optionally finishing with one psum over `reduce_axis`."""
    lp = dataclasses.replace(p, mesh=None)

    def body(*ops_):
        y = local(*ops_, lp)
        return jax.lax.psum(y, reduce_axis) if reduce_axis else y

    return jax.shard_map(body, mesh=p.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*operands)


def _tp_lowrank(x, qv, qu_t, s1, s2, p: KernelPolicy, role: str,
                eff_rank=None):
    """shard_map launch over the policy mesh (Megatron pairing):

    - col: U/s1 arrive d_out-sharded, each device runs the whole fused
      kernel on its output shard — no collective, output stays sharded.
    - row: V/s2 arrive d_in-sharded with a d_in-sharded input, each
      device computes a full-width partial and ONE psum finishes it.

    Returns None when the shape does not divide the axis (caller falls
    back to the replicated single-device launch, mirroring the
    divisibility fallback of ``sharding.rules``)."""
    ax, n = p.tp_axis, p.tp_size()
    lead = (None,) * (x.ndim - 1)
    # rank axes (qv last, qu_t leading packed) are never the sharded
    # dims, so eff_rank truncation composes with either TP role.
    local = functools.partial(_local_lowrank, eff_rank=eff_rank) \
        if eff_rank is not None else _local_lowrank
    if role == "col" and qu_t.shape[-1] % n == 0:
        return _shard_launch(
            p, local,
            (P(*lead, None), P(None, None), P(None, ax), P(ax), P(None)),
            P(*lead, ax), x, qv, qu_t, s1, s2)
    if role == "row" and qv.shape[-2] % n == 0:
        return _shard_launch(
            p, local,
            (P(*lead, ax), P(ax, None), P(None, None), P(None), P(ax)),
            P(*lead, None), x, qv, qu_t, s1, s2, reduce_axis=ax)
    return None


def lowrank_binary_matmul(x, qv, qu_t, s1, s2,
                          policy: Optional[KernelPolicy] = None,
                          tp: Optional[str] = None,
                          eff_rank: Optional[int] = None):
    """y = s1 ⊙ ((x ⊙ s2) @ V±1) @ U±1ᵀ  — packed operands (paper Eq. 1).

    Dispatches per `policy` (explicit argument wins, else the active
    contextvar policy). `tp`: this linear's Megatron role ('col' |
    'row' | None, see ``sharding.rules.tp_role``) — only consulted when
    the policy carries a mesh, in which case the kernel is launched
    through ``shard_map`` on the policy's tensor-parallel axis.
    `eff_rank`: optional effective rank R' <= r (multiple of 32) — the
    launch reads only the leading R' singular components of the packed
    factors (BlockSpec sub-extents on the fused pallas path, in-trace
    slices elsewhere; the stored operands are never repacked). Equals
    zeroing the trailing r - R' components: the rank-truncated draft
    forward of ``serve.speculative``."""
    p = policy if policy is not None else current_kernel_policy()
    x = _match_packed_k(x, qv)
    if p.tp_size() > 1 and tp in ("col", "row") and qv.ndim == 2:
        y = _tp_lowrank(x, qv, qu_t, s1, s2, p, tp, eff_rank=eff_rank)
        if y is not None:
            return y
    return _local_lowrank(x, qv, qu_t, s1, s2, p, eff_rank=eff_rank)


def lowrank_binary_matmul_merged(x, mp, dims: Sequence[int],
                                 policy: Optional[KernelPolicy] = None,
                                 eff_rank: Optional[int] = None):
    """Grouped projections sharing one input (QKV / gate-up): ONE kernel
    launch instead of len(dims).

    mp: merged param dict from ``quant.surgery.merge_projection_groups``
    — ``qv`` (P, K//32, R), ``qu_t`` (P, R//32, Nmax), ``s1`` (P, Nmax),
    ``s2`` (P, K), ``rmask`` (P, R) (every projection padded to the
    widest rank R / output Nmax; padded s1 columns are 0 and rmask zeros
    the padded rank columns). dims: static true d_out per projection.
    Returns a list of per-projection outputs (..., dims[i]).

    There is no two-call form of the merged launch (merging exists to
    eliminate launches): when the policy disables the fused pallas path
    the fallback is the grouped jnp oracle. The model layer only routes
    here when ``policy.use_merged_projections()`` is true, so a
    ``fused=False`` pallas policy runs per-projection two-call kernels
    via :func:`lowrank_binary_matmul` instead.
    """
    p = policy if policy is not None else current_kernel_policy()
    x = _match_packed_k(x, mp["qv"])
    shape = x.shape
    x2 = x.reshape(1, -1, shape[-1])
    R = mp["qv"].shape[-1]
    rmask = mp.get("rmask")
    if rmask is None:
        rmask = jnp.ones((mp["qv"].shape[0], R), jnp.float32)
    yg = None
    local = functools.partial(_local_merged, eff_rank=eff_rank) \
        if eff_rank is not None else _local_merged
    if p.tp_size() > 1 and mp["qv"].ndim == 3 \
            and mp["qu_t"].shape[-1] % p.tp_size() == 0:
        # merged groups are all column-parallel (QKV / gate-up): the
        # group stacking stays shard-local and each device computes its
        # padded-Nmax output shard; the per-projection :n slices below
        # read the global (sharded) result.
        ax = p.tp_axis
        yg = _shard_launch(
            p, local,
            (P(None, None, None), P(None, None, None), P(None, None, ax),
             P(None, ax), P(None, None), P(None, None)),
            P(None, None, ax),
            x2, mp["qv"], mp["qu_t"], mp["s1"], mp["s2"], rmask)
    if yg is None:
        yg = local(x2, mp["qv"], mp["qu_t"], mp["s1"], mp["s2"], rmask, p)
    return [yg[i, :, :n].reshape(*shape[:-1], n)
            for i, n in enumerate(dims)]


def _local_merged(x2, qv, qu_t, s1, s2, rmask, p: KernelPolicy,
                  eff_rank=None):
    """Single-device grouped launch shared by the plain and shard_map
    paths (x2: (1, M, K) shared input; operands carry the group axis).
    eff_rank truncates every group to its leading min(eff_rank, true
    rank) components — the rmask already zeros past each group's true
    rank, so truncation just caps the shared padded rank R."""
    R = qv.shape[-1]
    if p.use_pallas() and p.fused and R <= binary_matmul.MAX_FUSED_RANK:
        M = x2.shape[1]
        bm, bn, bk = p.block_sizes(M, x2.shape[-1], qu_t.shape[-1],
                                   eff_rank or R, x2.dtype)
        return binary_matmul.fused_lowrank_matmul_grouped(
            x2, qv, qu_t, s1, s2, rmask, x_shared=True,
            bm=bm, bn=bn, bk=bk, eff_rank=eff_rank,
            interpret=p.resolve_interpret())
    return jax.vmap(
        lambda v, u, a, b, rm: ref.lowrank_binary_matmul_fused_ref(
            x2[0], v, u, a, b, rm, eff_rank=eff_rank),
    )(qv, qu_t, s1, s2, rmask)


def lowrank_binary_matmul_expert(x, qv, qu_t, s1, s2,
                                 policy: Optional[KernelPolicy] = None,
                                 eff_rank: Optional[int] = None):
    """Stacked-expert NanoQuant linear: x (E, C, d_in) with per-expert
    packed operands (E, ...). On the fused pallas path the expert axis
    becomes a kernel grid dimension (one launch for all experts) instead
    of a host-level vmap of the kernel. eff_rank truncates every
    expert's factors to the leading R' components (all experts share one
    packed rank)."""
    p = policy if policy is not None else current_kernel_policy()
    x = _match_packed_k(x, qv)
    local = functools.partial(_local_expert, eff_rank=eff_rank) \
        if eff_rank is not None else _local_expert
    if p.tp_size() > 1 and qv.ndim == 3 and x.shape[0] % p.tp_size() == 0:
        # expert-parallel: the expert grid dim shards over the TP axis,
        # each device launching the fused grid over its local experts.
        ax = p.tp_axis
        return _shard_launch(
            p, local,
            (P(ax, None, None), P(ax, None, None), P(ax, None, None),
             P(ax, None), P(ax, None)),
            P(ax, None, None), x, qv, qu_t, s1, s2)
    return local(x, qv, qu_t, s1, s2, p)


def _local_expert(x, qv, qu_t, s1, s2, p: KernelPolicy, eff_rank=None):
    r = qv.shape[-1]
    if p.use_pallas():
        interp = p.resolve_interpret()
        bm, bn, bk = p.block_sizes(x.shape[1], x.shape[-1],
                                   qu_t.shape[-1], eff_rank or r, x.dtype)
        if p.fused and r <= binary_matmul.MAX_FUSED_RANK:
            return binary_matmul.fused_lowrank_matmul_grouped(
                x, qv, qu_t, s1, s2, x_shared=False,
                bm=bm, bn=bn, bk=bk, eff_rank=eff_rank, interpret=interp)
        if eff_rank is not None:
            qv, qu_t = _slice_rank(qv, qu_t, eff_rank)
        return jax.vmap(
            lambda xe, v, u, a, b: binary_matmul.lowrank_binary_matmul_twocall(
                xe, v, u, a, b, bm=bm, bn=bn, bk=bk, interpret=interp)
        )(x, qv, qu_t, s1, s2)
    if eff_rank is not None:
        qv, qu_t = _slice_rank(qv, qu_t, eff_rank)
    return jax.vmap(ref.lowrank_binary_matmul_ref)(x, qv, qu_t, s1, s2)


def paged_attention(q, k_pool, v_pool, block_table, q_pos, cache_pos, *,
                    window: int = 0, scale: float = 1.0,
                    policy: Optional[KernelPolicy] = None):
    """Block-table decode attention over a paged KV pool (serve.paging).

    q: (B, S, Hq, D) — S == 1 for normal decode, S > 1 for the
    speculative multi-token verify forward (token j lives at position
    q_pos + j / cache row cache_pos + j, all S rows already written);
    k_pool / v_pool: (n_pages, page_size, Hkv, D);
    block_table: (B, pages) int32; q_pos / cache_pos: (B,) — see
    :func:`repro.kernels.ref.paged_attention_ref` for the full
    contract (linear caches pass cache_pos == q_pos; sliding-window
    ring pools pass q_pos wrapped modulo the virtual ring).

    Dispatch per `policy`: the Pallas gather kernel
    (:mod:`repro.kernels.paged_attention`) on the pallas path, the
    gather + rectangle-mask oracle otherwise. With a tensor-parallel
    mesh the pool arrives kv-head-sharded (``sharding.rules.
    cache_pspecs(paged=True)``) and the launch shard_maps over the
    head dim — each device attends over its local heads with no
    collective (GQA groups stay shard-aligned because Hq and Hkv
    divide the axis together); non-divisible head counts fall back to
    the replicated single-device launch, mirroring the placement
    fallback."""
    p = policy if policy is not None else current_kernel_policy()
    n = p.tp_size()
    if n > 1 and k_pool.shape[-2] % n == 0 and q.shape[-2] % n == 0:
        ax = p.tp_axis
        lp = dataclasses.replace(p, mesh=None)

        def body(q_, kp_, vp_, bt_, qp_, cp_):
            return _local_paged_attention(q_, kp_, vp_, bt_, qp_, cp_,
                                          window, scale, lp)

        return jax.shard_map(
            body, mesh=p.mesh,
            in_specs=(P(None, None, ax, None), P(None, None, ax, None),
                      P(None, None, ax, None), P(None, None), P(None),
                      P(None)),
            out_specs=P(None, None, ax, None), check_vma=False)(
                q, k_pool, v_pool, block_table, q_pos, cache_pos)
    return _local_paged_attention(q, k_pool, v_pool, block_table, q_pos,
                                  cache_pos, window, scale, p)


def _local_paged_attention(q, k_pool, v_pool, bt, q_pos, cache_pos,
                           window, scale, p: KernelPolicy):
    if p.use_pallas():
        from repro.kernels import paged_attention as pa
        S = q.shape[1]
        ppb, hb = p.paged_block_sizes(q.shape[0], k_pool.shape[2],
                                      k_pool.shape[3], bt.shape[1])
        if S == 1:
            return pa.paged_decode_attention(
                q, k_pool, v_pool, bt, q_pos, cache_pos, window=window,
                scale=scale, pages_per_step=ppb, head_block=hb,
                interpret=p.resolve_interpret())
        # multi-token verify: all S rows are in the pool before any
        # query reads, and the per-query position reconstruction masks
        # later-written rows (see ref.paged_attention_ref), so S
        # single-token kernel launches at shifted positions are exact.
        outs = [pa.paged_decode_attention(
            q[:, j:j + 1], k_pool, v_pool, bt, q_pos + j, cache_pos + j,
            window=window, scale=scale, pages_per_step=ppb, head_block=hb,
            interpret=p.resolve_interpret())
            for j in range(S)]
        return jnp.concatenate(outs, axis=1)
    return ref.paged_attention_ref(q, k_pool, v_pool, bt, q_pos, cache_pos,
                                   window=window, scale=scale)


def decode_step_megakernel(x, mqkv, wo, k_pool, v_pool, block_table,
                           q_pos, cache_pos, *, head_dim: int,
                           dims: Sequence[int], theta: float,
                           scale: float, window: int = 0,
                           policy: Optional[KernelPolicy] = None,
                           eff_rank: Optional[int] = None,
                           eff_rank_o: Optional[int] = None):
    """Whole decode step in one pallas_call: merged-QKV packed matmul →
    RoPE → paged attention (fresh-KV entry folded in-kernel) → packed
    output projection (:mod:`repro.kernels.megakernel`).

    Returns ``(y, k_new, v_new)`` — k_new/v_new are the current token's
    post-RoPE KV rows (pool dtype) for the caller's paged cache write —
    or **None** when the launch does not qualify, in which case the
    caller runs the unfused chain (projections → cache write →
    paged_attention → wo); the two paths are online-softmax-equal (see
    tests/test_kernel_diff.py). Non-qualifying launches: ref-mode /
    unfused / unmerged policies, megakernel=False, tensor-parallel
    meshes (the merged padded-Nmax layout is not head-aligned, so a TP
    shard cannot slice its q/k/v heads locally — the unfused chain's
    per-role shard_map launches handle TP), ranks past MAX_FUSED_RANK,
    and non-32-multiple eff_rank truncations.

    x: (B, K) one decode token per slot; mqkv / wo: packed merged-QKV /
    output-projection param dicts; dims: (Hq*D, Hkv*D).
    """
    p = policy if policy is not None else current_kernel_policy()
    if not p.use_megakernel() or p.tp_size() > 1:
        return None
    if mqkv["qv"].ndim != 3 or wo["qv"].ndim != 2:
        return None
    if mqkv["qv"].shape[-1] > binary_matmul.MAX_FUSED_RANK \
            or wo["qv"].shape[-1] > binary_matmul.MAX_FUSED_RANK:
        return None
    for r_eff, qv in ((eff_rank, mqkv["qv"]), (eff_rank_o, wo["qv"])):
        if r_eff is not None and not (
                0 < r_eff <= qv.shape[-1] and r_eff % 32 == 0):
            return None
    from repro.kernels import megakernel as mk
    x = _match_packed_k(x, mqkv["qv"])
    ppb, _ = p.paged_block_sizes(x.shape[0], k_pool.shape[2],
                                 k_pool.shape[3], block_table.shape[1])
    _, _, bk = p.block_sizes(x.shape[0], x.shape[-1],
                             mqkv["qu_t"].shape[-1],
                             eff_rank or mqkv["qv"].shape[-1], x.dtype)
    return mk.decode_step_megakernel_raw(
        x, mqkv, wo, k_pool, v_pool, block_table, q_pos, cache_pos,
        dims=tuple(dims), head_dim=head_dim, theta=theta, scale=scale,
        window=window, eff_rank=eff_rank, eff_rank_o=eff_rank_o,
        pages_per_step=ppb, bk=bk, interpret=p.resolve_interpret())


# ---------------------------------------------------------------------------
# deprecated process-global mode API (pre-KernelPolicy)
# ---------------------------------------------------------------------------

_SHIM_WARNED = set()


def _warn_once(name: str) -> None:
    if name in _SHIM_WARNED:
        return
    _SHIM_WARNED.add(name)
    warnings.warn(f"{name} is deprecated; use "
                  f"{'set_kernel_policy' if 'set' in name else 'kernel_policy'}",
                  DeprecationWarning, stacklevel=3)


def set_kernel_mode(mode: str) -> None:
    """Deprecated: use ``set_kernel_policy(KernelPolicy(mode=...))``."""
    _warn_once("set_kernel_mode")
    set_kernel_policy(KernelPolicy(mode=mode))


@contextlib.contextmanager
def kernel_mode(mode: str):
    """Deprecated: use ``kernel_policy(mode)``."""
    _warn_once("kernel_mode")
    with kernel_policy(mode):
        yield


pack_signs = ref.pack_signs
unpack_signs = ref.unpack_signs

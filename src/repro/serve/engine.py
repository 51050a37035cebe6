"""Serving engine: slot-scheduled continuous batching + the raw
prefill / decode steps and sampling.

``serve_step`` is the unit the decode-shape dry-runs lower: one new token
against a KV (or SSM-state) cache — memory-bound, and exactly where the
paper's packed binary weights pay off (the whole weight stream shrinks
~16x, see §Roofline FP-vs-quantized decode comparison).

:class:`InferenceEngine` is the serving surface built on those steps: a
fixed pool of ``max_batch`` decode slots over one persistent cache,
where each slot carries its own position, token budget and EOS state.
Freed slots are refilled mid-flight by per-slot prefill (prompt lengths
bucketed to powers of two so prefill compiles once per bucket), and
finished slots are masked on device so they are no-ops until refilled.

    engine = InferenceEngine(params, cfg, ServeConfig(), max_batch=8)
    handle = engine.submit(Request(0, prompt), on_token=print)
    for tok in handle:          # streams; pumps engine.step() as needed
        ...
    done = engine.run()         # or drain everything at once
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels import ops as kops
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serve import paging
from repro.serve.faults import InjectedDeviceError as _InjectedDeviceError
from repro.serve.scheduler import (Request, SlotScheduler, bucket_length,
                                   cache_insert_slot, cache_select_active,
                                   pick_preemption_victim)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    temperature: float = 0.8
    top_k: int = 32
    max_new_tokens: int = 64
    greedy: bool = False
    # --- paged KV cache (docs/serving.md §Paged KV cache) ---
    # paged=True (default) backs the engine's persistent cache with a
    # page pool + per-slot block tables (serve.paging); paged=False
    # keeps the rectangular max_batch x max_len pool (the oracle layout,
    # kept for one release). Families with no pageable KV (pure SSM)
    # silently stay rectangular.
    paged: bool = True
    page_size: int = 64                    # KV rows per page (clamped to
    #                                        max_len for tiny servers)
    # total pool pages; None = full capacity (max_batch worst-case slots
    # + the null page — a drop-in for the rectangle). Smaller values
    # OVERCOMMIT: admission gates on free pages, decode reserves lazily,
    # and the engine preempts the youngest slot if the pool runs dry.
    kv_pool_pages: Optional[int] = None
    page_watermark: int = 0                # extra free pages required
    #                                        to admit (beyond the prompt)
    # --- prefix caching (docs/serving.md §Prefix caching) ---
    # prefix_cache=True (default) shares prompt-prefix KV pages across
    # requests through a chained-hash index (serve.prefix): admission
    # maps the longest cached prefix read-only and prefills only the
    # suffix; writes into shared pages copy-on-write; cached pages are
    # evicted LRU at refcount zero under pool pressure. Greedy outputs
    # stay token-identical to the no-sharing engine. Requires the paged
    # linear-only-table cache and a token-determined KV (ring/hybrid,
    # SSM and VLM families silently serve unshared).
    prefix_cache: bool = True
    # --- self-speculative decoding (docs/serving.md §Speculative) ---
    # spec_rank_frac enables the rank-truncated draft: each engine tick
    # drafts up to spec_k tokens through a zero-copy rank-r' view of the
    # packed params (quant.surgery.rank_truncated_view) and verifies
    # them in ONE batched full-rank forward. Greedy outputs stay
    # token-identical to the plain engine. Requires greedy=True and the
    # paged linear-table cache (serve.speculative validates).
    spec_rank_frac: Optional[float] = None  # draft rank fraction (0, 1]
    spec_k: int = 4                         # max draft tokens per cycle
    spec_k_min: int = 1                     # dynamic-k controller floor
    # --- robustness (docs/serving.md §Failure handling) ---
    # debug=True audits the page-pool invariants
    # (paging.check_invariants) and the slot/task alignment at the end
    # of every tick instead of only on faults. Pure host work; meant
    # for tests, chaos runs and bring-up, not the steady-state hot
    # path.
    debug: bool = False
    # --- decode megakernel (docs/kernels.md §Decode megakernel) ---
    # tri-state: None defers to the ambient KernelPolicy (megakernel on
    # by default on the fused merged pallas path); True/False force the
    # policy bit for this engine's traces. Per-launch qualification
    # still applies — non-qualifying shapes (TP mesh, oversized rank)
    # fall back to the unfused chain with identical greedy outputs.
    megakernel: Optional[bool] = None


def sample_token(logits: jnp.ndarray, key, scfg: ServeConfig) -> jnp.ndarray:
    """logits (B, 1, V[, K-codebooks already folded]) -> token ids (B, 1).
    Temperature + top-k sampling (paper App. E benchmark settings:
    temperature 0.8, top-k 32)."""
    lf = logits.astype(jnp.float32)
    if lf.ndim == 4:                       # audio: (B, 1, K, V)
        lf = lf.reshape(lf.shape[0], -1, lf.shape[-1])  # (B, K, V)
    else:
        lf = lf[:, -1]                                   # (B, V)
        lf = lf[:, None]                                 # (B, 1, V)
    if scfg.greedy:
        out = jnp.argmax(lf, axis=-1)
    else:
        lf = lf / max(scfg.temperature, 1e-6)
        if scfg.top_k:
            kth = jax.lax.top_k(lf, scfg.top_k)[0][..., -1:]
            lf = jnp.where(lf < kth, -jnp.inf, lf)
        out = jax.random.categorical(key, lf, axis=-1)
    return out.astype(jnp.int32)           # (B, 1) or (B, K)


def make_serve_step(cfg: ModelConfig):
    """(params, token (B,1[,K]), cache, pos) -> (logits, new_cache)."""
    def serve_step(params, token, cache, pos):
        return T.decode_step(params, cfg, token, cache, pos)
    return serve_step


def make_prefill_step(cfg: ModelConfig, max_len: Optional[int] = None):
    """(params, tokens (B,S)[, image_embeds]) -> (last logits, cache).

    The cache is created inside the step (sized max_len or S), so the
    lowered computation covers allocation + fill — what a serving runtime
    executes on admission."""
    def prefill_step(params, tokens, image_embeds=None):
        B, S = tokens.shape[0], tokens.shape[1]
        cache = T.init_cache(cfg, B, max_len or S)
        return T.prefill(params, cfg, tokens, cache, image_embeds)
    return prefill_step


def make_slot_prefill_step(cfg: ModelConfig, max_len: int):
    """(params, tokens (1, bucket[, K]), last_idx) -> (logits, cache).

    The single-slot admission unit: allocates a batch-1 cache sized
    `max_len` (so it inserts into the pooled cache shape-for-shape),
    prefills a right-padded prompt and reads logits at `last_idx`, the
    final real token. `last_idx` is traced, so one compilation covers
    every prompt length inside a bucket."""
    def prefill_step(params, tokens, last_idx, image_embeds=None):
        cache = T.init_cache(cfg, tokens.shape[0], max_len)
        return T.prefill(params, cfg, tokens, cache, image_embeds,
                         last_idx=last_idx)
    return prefill_step


def generate(params, cfg: ModelConfig, tokens, scfg: ServeConfig,
             key=None, image_embeds=None,
             jit_prefill=None, jit_decode=None) -> Tuple[Any, Any]:
    """Host-driven generation loop (prefill once, then decode steps).
    Returns (generated (B, max_new[,K]), per-step logits list)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    B, S = tokens.shape[0], tokens.shape[1]
    max_len = S + scfg.max_new_tokens
    prefill = jit_prefill or jax.jit(make_prefill_step(cfg, max_len))
    decode = jit_decode or jax.jit(make_serve_step(cfg))

    if cfg.family == "vlm":
        logits, cache = prefill(params, tokens, image_embeds)
    else:
        logits, cache = prefill(params, tokens)
    outs = []
    tok = None
    for i in range(scfg.max_new_tokens):
        key, k = jax.random.split(key)
        tok = sample_token(logits, k, scfg)
        if cfg.family == "audio":
            tok = tok[:, None, :]          # (B, 1, K)
        outs.append(tok)
        logits, cache = decode(params, tok, cache, jnp.asarray(S + i))
    gen = jnp.concatenate(outs, axis=1)
    return gen, logits


# ===========================================================================
# continuous-batching engine
# ===========================================================================


#: Terminal request statuses. "done" is the only successful one;
#: the other three carry a :class:`RequestError` on the handle.
TERMINAL_STATUSES = ("done", "cancelled", "expired", "failed")


class RequestError(RuntimeError):
    """Structured terminal error for one request: the request reached a
    non-successful terminal status (``cancelled`` / ``expired`` /
    ``failed``) while the rest of the engine kept serving. Raised by
    ``RequestHandle.result()`` and at the end of handle iteration;
    also stored on ``handle.error``."""

    def __init__(self, uid: int, status: str, reason: str):
        super().__init__(f"request {uid} {status}: {reason}")
        self.uid = uid
        self.status = status
        self.reason = reason


class RequestHandle:
    """Streaming view of one submitted request.

    `tokens` grows as the engine emits; iterate the handle to stream
    (iteration pumps `engine.step()` when it runs out of buffered
    tokens), or call `result()` to block until completion.

    Lifecycle (docs/serving.md §Failure handling): ``status`` moves
    ``"pending"`` → ``"running"`` (first admission; preemption does not
    move it back) → one of :data:`TERMINAL_STATUSES`. Non-``done``
    terminals carry a :class:`RequestError` on ``error``; ``result()``
    raises it instead of returning a partial array, and iteration
    yields whatever was emitted before the terminal, then raises.
    ``cancel()`` requests cancellation; the engine honours it at the
    next tick boundary (tokens may still arrive in between)."""

    def __init__(self, engine: "InferenceEngine", request: Request,
                 on_token: Optional[Callable] = None):
        self._engine = engine
        self.request = request
        self.uid = request.uid
        self.on_token = on_token
        self.tokens: List[Any] = []
        self.status = "pending"
        self.error: Optional[RequestError] = None
        self.cancel_requested = False
        self.cancel_reason = "cancelled by client"
        self.deadline_at: Optional[float] = None   # engine-clock absolute
        self.submit_t = time.monotonic()
        self.first_token_t: Optional[float] = None
        self.finish_t: Optional[float] = None

    @property
    def finished(self) -> bool:
        """True once the request reached any terminal status."""
        return self.status in TERMINAL_STATUSES

    @property
    def done(self) -> bool:
        """True only for the *successful* terminal status."""
        return self.status == "done"

    def cancel(self, reason: str = "cancelled by client") -> None:
        """Request cancellation. Takes effect at the engine's next tick
        boundary: a queued request is dropped before admission, an
        active slot is torn down with its pages freed exactly (the
        preemption teardown path). No-op once terminal."""
        if not self.finished:
            self.cancel_requested = True
            self.cancel_reason = reason

    def _finalize(self, status: str,
                  error: Optional[RequestError] = None) -> None:
        assert status in TERMINAL_STATUSES, status
        self.status = status
        self.error = error
        self.finish_t = time.monotonic()

    def _append(self, token) -> None:
        if self.first_token_t is None:
            self.first_token_t = time.monotonic()
        self.tokens.append(token)

    def result(self) -> np.ndarray:
        """Block (pumping the engine) until terminal; return the full
        output, or raise this request's :class:`RequestError` if it
        ended cancelled / expired / failed."""
        while not self.finished:
            if not self._engine.in_flight:
                raise RuntimeError(
                    f"request {self.uid} unfinished but engine is idle")
            self._engine.step()
        if self.error is not None:
            raise self.error
        return self.request.output

    def __iter__(self):
        # a fresh iterator per call, starting from token 0 — re-iterating
        # a finished handle replays the buffered tokens instead of
        # silently yielding nothing
        i = 0
        while True:
            if i < len(self.tokens):
                yield self.tokens[i]
                i += 1
            elif self.finished:
                if self.error is not None:
                    raise self.error
                return
            else:
                if not self._engine.in_flight:
                    raise RuntimeError(
                        f"request {self.uid} unfinished but engine is idle")
                self._engine.step()

    @property
    def latency(self) -> Optional[float]:
        if self.finish_t is None:
            return None
        return self.finish_t - self.submit_t

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token: submission -> first emitted token (the
        admission-queue wait plus the prefill). What prefix caching
        shrinks — both directly (suffix-only prefill) and through
        admission headroom (shared pages are nearly free to admit)."""
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t


class _AbortAdmission(Exception):
    """Internal: a cancel/expire landed mid-prefill (noticed between
    the prefill and slot activation); unwind to the given terminal."""

    def __init__(self, status: str, reason: str):
        super().__init__(f"{status}: {reason}")
        self.status = status
        self.reason = reason


@dataclasses.dataclass
class _SlotTask:
    """Host-side record of the request occupying one decode slot."""
    handle: RequestHandle
    budget: int                        # new tokens still allowed
    toks: List[Any] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Resume:
    """A preempted request re-queued for admission (paged engine, pool
    exhausted mid-decode): re-prefills prompt + already-emitted tokens
    and continues with the remaining budget. Greedy decoding makes the
    recompute token-exact; already-emitted tokens are never re-emitted."""
    handle: RequestHandle
    prompt: np.ndarray                 # original prompt + emitted tokens
    budget: int                        # new tokens still allowed
    emitted: List[Any] = dataclasses.field(default_factory=list)

    @property
    def uid(self) -> int:
        return self.handle.uid


class InferenceEngine:
    """Slot-scheduled, continuously-batched serving engine.

    A fixed pool of `max_batch` decode slots over one persistent cache.
    Each slot carries its own position, budget and EOS state; one fused
    decode step advances every active slot (per-slot positions, cache
    writes and causal masks — see `models.transformer.decode_step`),
    while finished slots are masked on device into no-ops. Freed slots
    are refilled mid-flight: admission prefills the new prompt into a
    single-slot cache (right-padded to a power-of-two bucket so the
    prefill compiles once per bucket) and scatters it into the pool.

    `admission="wave"` reproduces the legacy drain-then-refill
    `BatchServer` schedule for comparison; greedy outputs are identical
    per request under either policy.

    The persistent cache is a **paged KV pool** by default
    (`ServeConfig.paged`, serve.paging): fixed-size pages + per-slot
    block tables instead of a `max_batch x max_len` rectangle. Pages
    are reserved at admission for the prompt, lazily per decode step as
    a slot crosses a page boundary, and freed on completion. With
    `kv_pool_pages` below full capacity the pool *overcommits* total
    sequence capacity: admission gates on free pages (FIFO, queueing
    instead of crashing when exhausted) and a dry pool preempts the
    youngest slot (token-exact re-prefill under greedy). Greedy outputs
    are token-identical to the rectangular engine
    (`ServeConfig(paged=False)`, the oracle layout).

    `mesh` (optional) turns the engine tensor-parallel: packed U/s1 and
    V/s2 are placed per `sharding.rules` (Megatron col/row pairing —
    see `quant.surgery.place_on_mesh`), the pooled KV cache shards its
    kv-head (or sequence) dim over the `model` axis, and the jitted
    prefill / decode steps trace under a mesh-carrying `KernelPolicy`
    so every packed linear launches through the shard_map-wrapped fused
    kernel (`kernels.ops`). Greedy outputs are token-identical to the
    unsharded engine in f32 (bf16 near-tie argmaxes can flip under
    partitioned-reduction reorder — see ROADMAP Open items). With
    `mesh=None` (default) nothing changes — single-device dispatch, no
    placement, no collectives.

    Caveat (MoE families): capacity-bounded expert dispatch couples
    batch rows — any slot's tokens (including an inactive slot's masked
    pad row) consume per-expert capacity and can, under tight
    `capacity_factor`, drop an active neighbor's expert assignment.
    This is inherent to batched capacity-bounded MoE decode (the wave
    scheduler routed finished requests' real tokens, which is strictly
    worse); per-request identity with a solo decode holds exactly for
    non-MoE families and for MoE when capacity is not saturated.
    """

    def __init__(self, params, cfg: ModelConfig,
                 scfg: Optional[ServeConfig] = None, max_batch: int = 8,
                 max_len: int = 512, seed: int = 0,
                 admission: str = "continuous", mesh=None,
                 sharding_policy=None, faults=None, clock=None):
        if kops.current_kernel_policy().use_merged_projections():
            # serving-side operand grouping: QKV / gate-up projections
            # additionally carry stacked operands so attention and MLP
            # issue one fused kernel launch instead of three/two. The
            # engine's copy only — saved artifacts keep the flat layout.
            from repro.quant.surgery import merge_projection_groups
            params = merge_projection_groups(params)
        self.mesh = mesh
        self._shard_policy = None
        self._kpolicy = None
        if mesh is not None:
            from repro.quant.surgery import place_on_mesh
            from repro.sharding import rules
            self._shard_policy = (sharding_policy if sharding_policy
                                  is not None else rules.SERVE)
            params = place_on_mesh(params, cfg, mesh, self._shard_policy)
            # tp_axis pinned to "model": sharding.rules only ever
            # places on that axis, and launch must agree with placement
            self._kpolicy = dataclasses.replace(
                kops.current_kernel_policy(), mesh=mesh, tp_axis="model")
        self.params, self.cfg = params, cfg
        self.scfg = scfg or ServeConfig()
        self.max_batch, self.max_len = max_batch, max_len
        self.key = jax.random.PRNGKey(seed)
        self.scheduler = SlotScheduler(max_batch, admission)
        # deadline clock: monotonic seconds. Injectable so tests and the
        # fault harness can expire requests deterministically.
        self.clock: Callable[[], float] = clock or time.monotonic
        # fault-injection plan (serve.faults.FaultPlan) — None in
        # production; when set, its hooks fire at the engine's seams.
        self.faults = faults
        # drain(): True stops admission of fresh requests (preempted
        # _Resume items still re-admit, so in-flight work can finish).
        self.draining = False
        # paged KV pool (serve.paging) unless disabled or the family has
        # no pageable cache (pure SSM state is O(1)/slot either way)
        self.kv: Optional[paging.PagedKVState] = None
        kinds = paging.cache_page_kinds(cfg, max_len) if self.scfg.paged \
            else set()
        if kinds:
            self.kv = paging.PagedKVState(
                cfg, max_batch, max_len, self.scfg.page_size,
                self.scfg.kv_pool_pages, self.scfg.page_watermark,
                kinds=kinds)
        self.paged = self.kv is not None
        if self.paged:
            self.cache = paging.init_paged_cache(
                cfg, max_batch, max_len, self.kv.n_pages, self.kv.page_size)
        else:
            self.cache = T.init_cache(cfg, max_batch, max_len)
        if mesh is not None:
            from repro.quant.surgery import place_cache_on_mesh
            self.cache = place_cache_on_mesh(self.cache, cfg, mesh,
                                             self._shard_policy,
                                             paged=self.paged)
        self.pos = np.zeros((max_batch,), np.int32)
        self.active = np.zeros((max_batch,), bool)
        tok_shape = ((max_batch, 1, cfg.n_codebooks)
                     if cfg.family == "audio" else (max_batch, 1))
        self.tokens = np.zeros(tok_shape, np.int32)
        self._tasks: List[Optional[_SlotTask]] = [None] * max_batch
        self._callbacks: List[Tuple[Callable, int, Any]] = []
        self.handles: Dict[int, RequestHandle] = {}
        self.done: Dict[int, Request] = {}
        # observability: per-uid admission/completion step and slot, plus
        # aggregate counters (trace counters increment at trace time only,
        # so they count *compilations*, not calls).
        self.slot_of: Dict[int, int] = {}
        self.admission_step: Dict[int, int] = {}
        self.completion_step: Dict[int, int] = {}
        self.stats: Dict[str, int] = {}
        self.reset_stats()

        # prefix cache (serve.prefix): share prompt-prefix KV pages
        # across requests. Linear-only table families with token-
        # determined KV; the VLM's cache depends on image embeddings the
        # index cannot key, so it serves unshared.
        self.prefix = None
        if self.paged and self.scfg.prefix_cache \
                and set(self.kv.tables) == {"linear"} \
                and cfg.family != "vlm":
            from repro.serve.prefix import PrefixCache
            self.prefix = PrefixCache(self.kv, self.stats)

        slot_prefill = make_slot_prefill_step(cfg, max_len)

        def prefill_fn(params, tokens, last_idx):
            self.stats["prefill_traces"] += 1
            with self._policy_scope():
                return slot_prefill(params, tokens, last_idx)
        self._prefill = jax.jit(prefill_fn)

        # suffix prefill (prefix-cache hits): run only the uncached
        # tail of a prompt directly against the donated pool, writing
        # rows [start, start+S) through the slot's linear block table —
        # the admission-sized sibling of the speculative S>1 verify.
        def suffix_fn(params, tokens, start, last_idx, cache, table):
            self.stats["prefill_traces"] += 1
            with self._policy_scope():
                return T.prefill(params, cfg, tokens, cache,
                                 last_idx=last_idx, start_pos=start,
                                 block_tables={"linear": table})
        self._suffix_prefill = jax.jit(suffix_fn, donate_argnums=(4,))
        # copy-on-write page duplication (one compile, traced page ids)
        self._copy_page = jax.jit(paging.copy_page, donate_argnums=(0,))
        # donate the pooled cache: insert/decode consume the old pool and
        # return the next one, so XLA can update it in place instead of
        # materializing a second full KV pool per token (the decode loop
        # is memory-bound — this is the dominant non-weight traffic).
        # Same discipline for the paged pool: the page scatters and
        # block-table-walking decode writes update the donated buffers.
        if self.paged:
            self._insert = jax.jit(paging.paged_insert_slot,
                                   donate_argnums=(0,))
        else:
            self._insert = jax.jit(cache_insert_slot, donate_argnums=(0,))
        select_active = (paging.paged_select_active if self.paged
                         else cache_select_active)

        def decode_fn(params, tokens, cache, pos, active, key, tables):
            self.stats["decode_traces"] += 1
            with self._policy_scope():
                logits, new_cache = T.decode_step(params, cfg, tokens,
                                                  cache, pos,
                                                  block_tables=tables)
                new_cache = select_active(new_cache, cache, active)
                tok = sample_token(logits, key, self.scfg)
            if cfg.family == "audio":
                tok = tok[:, None, :]
            keep = active.reshape((-1,) + (1,) * (tok.ndim - 1))
            return jnp.where(keep, tok, 0), new_cache
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))

        self.spec = None
        if self.scfg.spec_rank_frac is not None:
            from repro.serve.speculative import SpecDecodeController
            self.spec = SpecDecodeController(self)

    @contextlib.contextmanager
    def _policy_scope(self):
        """JAX trace-time context for the jitted steps (not a profiler
        span: those are the ``serve.*`` TraceAnnotations of `step`).
        Scopes in this engine's kernel policy (the ambient policy, plus
        the ServeConfig's megakernel override and — with a mesh — the
        mesh for shard_map TP kernel launches) and, with a mesh,
        activation-sharding constraints. Both are contextvar-based, so concurrent traces
        from other engines or training cells are untouched, and dispatch
        is baked into the traced computation (execution needs no ambient
        globals)."""
        pol = self._kpolicy if self._kpolicy is not None \
            else kops.current_kernel_policy()
        if self.scfg.megakernel is not None:
            pol = dataclasses.replace(pol,
                                      megakernel=self.scfg.megakernel)
        if self.mesh is None:
            with kops.kernel_policy(pol):
                yield
            return
        from repro.models import layers as L
        from repro.sharding import rules
        with L.activation_sharding(
                self.mesh, rules.data_axes(self.mesh),
                "model" if "model" in self.mesh.axis_names else None):
            with kops.kernel_policy(pol):
                yield

    # ---- submission -------------------------------------------------------

    def submit(self, req: Request,
               on_token: Optional[Callable] = None) -> RequestHandle:
        """Queue a request; returns a streaming handle. `on_token`
        (optional) is called as `on_token(uid, token)` per emitted
        token. Rejects prompts that leave no room to generate; budgets
        beyond `max_len - prompt_len` are truncated."""
        prompt = np.asarray(req.prompt)
        n = prompt.shape[0]
        if n == 0:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.uid}: max_new_tokens must be "
                             f">= 1, got {req.max_new_tokens}")
        if n >= self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt length {n} >= max_len "
                f"{self.max_len} leaves no room to generate — raise "
                f"max_len or truncate the prompt before submitting")
        if prompt.size and (prompt.min() < 0
                            or prompt.max() >= self.cfg.vocab_size):
            raise ValueError(
                f"request {req.uid}: prompt token ids outside "
                f"[0, {self.cfg.vocab_size}) — refusing to embed "
                f"out-of-vocabulary ids")
        if req.deadline_s is not None and req.deadline_s < 0:
            raise ValueError(f"request {req.uid}: deadline_s must be "
                             f">= 0, got {req.deadline_s}")
        if self.paged:
            need = self.kv.pages_for_prompt(n)
            if need + self.kv.watermark > self.kv.n_pages - 1:
                raise ValueError(
                    f"request {req.uid}: prompt needs {need} pages but "
                    f"the pool holds {self.kv.n_pages - 1} (watermark "
                    f"{self.kv.watermark}) — it could never be admitted")
        old = self.handles.get(req.uid)
        if old is not None:
            if not old.finished:
                raise ValueError(f"duplicate request uid {req.uid} "
                                 f"still pending or decoding")
            self._forget(req.uid)          # uid reuse after completion
        handle = RequestHandle(self, req, on_token)
        if req.deadline_s is not None:
            handle.deadline_at = self.clock() + req.deadline_s
        self.handles[req.uid] = handle
        self.scheduler.submit(handle)
        return handle

    # ---- stepping ---------------------------------------------------------

    @property
    def in_flight(self) -> bool:
        return bool(self.scheduler.pending) or bool(self.active.any())

    def step(self) -> List[Request]:
        """One scheduler tick: admit into free slots, then one fused
        decode step across the pool. Returns requests finished now.

        User `on_token` callbacks fire only after every slot's engine
        state (positions, budgets, cache, completion bookkeeping) has
        been committed for the tick — a raising callback cannot leave
        the engine inconsistent (the exception still propagates).

        The tick and its parts are host spans for the JAX profiler
        (``serve.tick`` and its ``serve.*`` children, docs/serving.md
        §Observability); without a running profiler they cost about a
        microsecond each."""
        with TraceAnnotation("serve.tick", step=self.stats["steps"]):
            finished = []
            self._callbacks = []
            if self.faults is not None:
                self.faults.on_step(self)
            with TraceAnnotation("serve.reap"):
                self._reap()
            for slot, handle in self.scheduler.admit_batch(
                    self._admission_gate()):
                fin = self._admit(slot, handle)
                if fin is not None:
                    finished.append(fin)
            if self.prefix is not None:
                self.prefix.unprotect_all()
            self.stats["peak_active"] = max(self.stats["peak_active"],
                                            int(self.active.sum()))
            if self.active.any():
                t0 = time.monotonic()
                try:
                    if self.spec is not None:
                        with TraceAnnotation("serve.spec_cycle"):
                            self.spec.tick(finished)
                    else:
                        self._decode_tick(finished)
                except _InjectedDeviceError as e:
                    self._on_device_fault(e)
                self.stats["decode_time_s"] += time.monotonic() - t0
            self.stats["steps"] += 1
            if self.scfg.debug:
                self.check_invariants()
            callbacks, self._callbacks = self._callbacks, []
            err = None
            with TraceAnnotation("serve.callbacks"):
                for cb, uid, token in callbacks:
                    try:
                        cb(uid, token)
                    except BaseException as e:  # deliver to every consumer,
                        err = err or e          # then surface the first one
            if err is not None:
                raise err
            return finished

    def _admission_gate(self) -> Optional[Callable[[Any], bool]]:
        """This tick's admission check for `scheduler.admit_batch`: free
        pages above the watermark (paged), no fresh work while draining,
        and the fault plan's gate hook. None admits whatever fits a
        slot."""
        gate = None
        if self.paged:
            promised = [0]     # pages owed to earlier admissions in this
            #                    batch (kv.admit runs after admit_batch)

            def gate(item):
                n = self._item_prompt_len(item)
                need = self.kv.pages_for_prompt(n)
                if self.prefix is not None:
                    # a matched prefix is nearly free admission: shared
                    # pages only bump refcounts. A full-cover match
                    # still pays one page — the tail is copy-on-written
                    # so the re-emitted last row has a private home.
                    p, pages, keys = self.prefix.match(
                        self._item_prompt(item))
                    need += (1 if p == n else 0) - len(pages)
                    # pin the matched chain BEFORE the availability
                    # check: available_pages must not count the pages
                    # this item is about to share as evictable slack,
                    # and a later admission's reclaim in this batch
                    # must not evict them before kv.admit refs them
                    # (_admit re-matches; protection guarantees the
                    # fresh match finds at least this chain)
                    self.prefix.protect(keys)
                # the watermark holds back slack for *fresh* work only:
                # a preempted _Resume was already admitted once and its
                # grown prompt (<= one slot's worst case, which always
                # fits) may legitimately exceed what submit() validated
                # — gating it on the watermark could livelock the queue.
                wm = 0 if isinstance(item, _Resume) else self.kv.watermark
                # available_pages counts evictable cached pages too —
                # reclaim frees them on demand during kv.admit
                ok = self.kv.available_pages - promised[0] - need >= wm
                if ok:
                    promised[0] += need
                else:
                    self.stats["page_waits"] += 1
                return ok
        page_gate = gate
        if self.draining or self.faults is not None:
            def gate(item):               # noqa: F811 — wraps page_gate
                if self.draining and not isinstance(item, _Resume):
                    return False          # drain: no fresh admissions
                if self.faults is not None:
                    # e.g. evict a matched prefix chain between the
                    # match and kv.admit — protection must hold it
                    self.faults.on_gate(self)
                return page_gate(item) if page_gate is not None else True
        return gate

    def run(self) -> Dict[int, Request]:
        """Drain the queue; returns {uid: completed Request}."""
        while self.in_flight:
            self.step()
        return dict(self.done)

    # ---- request lifecycle: cancellation, deadlines, drain ----------------

    def _verdict(self, handle: RequestHandle) -> Optional[Tuple[str, str]]:
        """(terminal_status, reason) if `handle` should be reaped now
        (client cancellation or past deadline), else None."""
        if handle.cancel_requested:
            return "cancelled", handle.cancel_reason
        if handle.deadline_at is not None \
                and self.clock() >= handle.deadline_at:
            return "expired", (f"deadline "
                               f"{handle.request.deadline_s}s exceeded")
        return None

    @staticmethod
    def _item_handle(item) -> RequestHandle:
        return item.handle if isinstance(item, _Resume) else item

    def _reap(self) -> None:
        """Tick-boundary reaping: drop cancelled/expired requests from
        the queue and tear down cancelled/expired active slots, freeing
        pages and prefix refcounts exactly (the preemption teardown
        path minus the requeue)."""
        if not (self.scheduler.pending or self.active.any()):
            return
        for item in self.scheduler.reap(
                lambda it: self._verdict(self._item_handle(it)) is not None):
            handle = self._item_handle(item)
            status, reason = self._verdict(handle)
            toks = item.emitted if isinstance(item, _Resume) else []
            self._finalize_aborted(handle, status, reason, toks)
        for slot in np.nonzero(self.active)[0]:
            task = self._tasks[int(slot)]
            v = self._verdict(task.handle)
            if v is not None:
                self._abort_slot(int(slot), *v)

    def _abort_slot(self, slot: int, status: str, reason: str) -> None:
        """Tear down an active slot to a non-successful terminal: the
        preemption teardown (pages + prefix refcounts freed exactly)
        without the requeue, then finalize the handle."""
        task = self._tasks[slot]
        self.active[slot] = False
        self._tasks[slot] = None
        self.slot_of.pop(task.handle.uid, None)
        if self.paged:
            self.kv.release(slot)
        self.scheduler.release(slot)
        self._finalize_aborted(task.handle, status, reason, task.toks)
        if status == "failed":             # every fault audits the pool
            self.check_invariants()

    def _finalize_aborted(self, handle: RequestHandle, status: str,
                          reason: str, toks: List[Any]) -> None:
        """Move `handle` to a non-successful terminal status. Partial
        output (tokens emitted before the terminal) stays readable on
        ``request.output`` / ``handle.tokens``; ``result()`` raises."""
        req = handle.request
        req.output = (np.asarray(toks, np.int32) if toks
                      else np.zeros((0,), np.int32))
        handle._finalize(status, RequestError(req.uid, status, reason))
        self.completion_step[req.uid] = self.stats["steps"]
        self.stats[status] += 1

    def _on_device_fault(self, err: "_InjectedDeviceError") -> None:
        """Recover from a (simulated) device error in the decode step:
        the error is raised *before* the donated device call, so the
        pool buffer is intact — fail the attributed slot with a
        structured RequestError, preempt every other active slot
        (token-exact resume re-prefills them), and audit the pool.
        Models the recoverable class of device faults; a real
        XlaRuntimeError after donation has no cache to resume from."""
        uid = err.uid if err.uid in self.slot_of else None
        if uid is None and self.active.any():
            slot = int(np.nonzero(self.active)[0][-1])
            uid = self._tasks[slot].handle.uid
        self.stats["device_faults"] += 1
        if uid is not None:
            self._abort_slot(self.slot_of[uid], "failed",
                             f"device error in decode step: {err}")
        if self.paged:
            for slot in np.nonzero(self.active)[0]:
                self._preempt(int(slot))
        # else: the rectangular engine keeps its cache (nothing was
        # donated before the raise) and the neighbours continue in place
        self.check_invariants()

    def drain(self, timeout: Optional[float] = None) -> Dict[int, Request]:
        """Graceful drain: stop admitting fresh requests, keep stepping
        until every active slot finishes (or `timeout` seconds of
        engine-clock pass), then checkpoint whatever is still active as
        requeued ``_Resume`` items — ``serve.recovery.snapshot`` can
        persist the result and rebuild an engine that resumes
        token-identically under greedy. Returns requests completed so
        far. Admission stays closed until :meth:`resume_admission`."""
        self.draining = True
        t0 = self.clock()
        while self.active.any():
            if timeout is not None and self.clock() - t0 >= timeout:
                break
            self.step()
        for slot in np.nonzero(self.active)[0]:
            if self.paged:
                self._preempt(int(slot))
            else:
                self._abort_slot(int(slot), "failed",
                                 "drain timeout: rectangular engine "
                                 "cannot checkpoint a live slot")
        return dict(self.done)

    def resume_admission(self) -> None:
        """Reopen admission after :meth:`drain`."""
        self.draining = False

    def check_invariants(self) -> None:
        """Audit page-pool accounting (paging.check_invariants), the
        prefix index (prefix.check_invariants) and engine/slot
        alignment. Raises paging.PageAccountingError on the first
        violation. Run on every fault and, under
        ``ServeConfig(debug=True)``, at the end of every tick."""
        if self.paged:
            self.kv.check_invariants()
        if self.prefix is not None:
            self.prefix.check_invariants()
        for slot in range(self.max_batch):
            task = self._tasks[slot]
            if bool(self.active[slot]) != (task is not None):
                raise paging.PageAccountingError(
                    f"slot {slot}: active={bool(self.active[slot])} but "
                    f"task={'set' if task is not None else 'none'}")
            if task is not None:
                uid = task.handle.uid
                if self.scheduler.slots[slot] != uid:
                    raise paging.PageAccountingError(
                        f"slot {slot}: scheduler owner "
                        f"{self.scheduler.slots[slot]} != task uid {uid}")
                if self.paged and self.kv.has_linear \
                        and self.kv._mapped[slot] * self.kv.page_size \
                        < self.pos[slot]:
                    raise paging.PageAccountingError(
                        f"slot {slot}: pos {int(self.pos[slot])} beyond "
                        f"mapped rows "
                        f"{self.kv._mapped[slot] * self.kv.page_size}")

    def _decode_tick(self, finished: List[Request]) -> None:
        """One fused single-token decode across the pool: reserve the
        next cache row per active slot (possibly preempting), run the
        jitted decode, commit positions and emit. Shared by the plain
        step and the speculative controller's k<1 fallback."""
        if self.paged:
            with TraceAnnotation("serve.reserve_pages"):
                self._ensure_decode_pages()
        if not self.active.any():          # everything self-preempted
            return
        if self.faults is not None:
            # raises _InjectedDeviceError *before* the donated device
            # call, so the pool buffer is still valid for recovery
            self.faults.before_decode(self)
        with TraceAnnotation("serve.decode_dispatch"):
            tables = self.kv.device_tables() if self.paged else {}
            self.key, k = jax.random.split(self.key)
            tok, self.cache = self._decode(
                self.params, jnp.asarray(self.tokens), self.cache,
                jnp.asarray(self.pos), jnp.asarray(self.active), k, tables)
        with TraceAnnotation("serve.decode_wait"):
            tok = np.array(tok)    # writable copy: slots mutate it
        self.tokens = tok
        self.stats["decode_steps"] += 1
        self.stats["wasted_slot_steps"] += int(
            self.max_batch - self.active.sum())
        if self.paged and self.kv.has_linear:
            # the gather walks each slot's linear table up to its live
            # bound, inactive slots included (their stale pos)
            tp = self.kv.lin_pages
            self.stats["gather_pages_live"] += int(np.minimum(
                tp, self.pos // self.kv.page_size + 1).sum())
            self.stats["gather_pages_table"] += self.max_batch * tp
        with TraceAnnotation("serve.emit"):
            for slot in range(self.max_batch):
                if not self.active[slot]:
                    continue
                self.pos[slot] += 1
                fin = self._emit(slot, tok[slot][0])
                if fin is not None:
                    finished.append(fin)

    def reset_stats(self) -> None:
        for k in ("steps", "decode_steps", "wasted_slot_steps",
                  "tokens_emitted", "admissions", "prefill_traces",
                  "decode_traces", "preemptions", "page_waits",
                  "peak_active", "preempt_recompute_tokens",
                  "spec_cycles", "spec_draft_tokens",
                  "spec_accepted_tokens", "spec_rollback_tokens",
                  "spec_rollback_pages",
                  # prefix cache (docs/serving.md §Prefix caching):
                  # hit/lookup tokens give the hit rate; shared_pages is
                  # the peak pages mapped by >1 slot; cow_copies counts
                  # copy-on-write page duplications; evicted_pages
                  # counts LRU index evictions under pool pressure.
                  "prefix_hit_tokens", "prefix_lookup_tokens",
                  "shared_pages", "cow_copies", "evicted_pages",
                  # failure handling (docs/serving.md §Failure handling):
                  # terminal-status counters + recovered device errors
                  "cancelled", "expired", "failed", "device_faults",
                  # admission prefill: real prompt rows prefilled (fresh
                  # and resumed; a prefix hit counts its suffix) and the
                  # rows their compiled buckets computed — the
                  # difference is padding
                  "prefill_rows", "prefill_bucket_rows",
                  # paged gather: linear-table pages the decode read
                  # walked (each slot up to its live bound) and the
                  # pages of the whole tables
                  "gather_pages_live", "gather_pages_table"):
            self.stats[k] = 0
        # host wall-clock spent in the decode/spec device step + commit,
        # admission excluded (serve_bench's decode_tok_s divides output
        # tokens by it)
        self.stats["decode_time_s"] = 0.0

    def kv_cache_bytes(self) -> int:
        """Bytes held by the persistent attention-cache leaves — the
        paged pool's footprint vs the rectangle's (paging.kv_cache_bytes)."""
        return paging.kv_cache_bytes(self.cache)

    def _forget(self, uid: int) -> None:
        for d in (self.handles, self.done, self.slot_of,
                  self.admission_step, self.completion_step):
            d.pop(uid, None)

    def clear_finished(self) -> None:
        """Drop bookkeeping (handles, outputs, step logs) for completed
        requests — reclaims memory on a long-running server. Callers
        keep their RequestHandles; only the engine's references go."""
        for uid in list(self.done):
            self._forget(uid)

    # ---- internals --------------------------------------------------------

    @staticmethod
    def _item_prompt(item) -> np.ndarray:
        """Tokens an admission unit will prefill (resumes prefill
        prompt + already-emitted tokens — so a resume's own previously
        registered chunks match, which is exactly the preemption
        recompute the prefix index refunds)."""
        if isinstance(item, _Resume):
            return item.prompt
        return np.asarray(item.request.prompt, np.int32)

    @staticmethod
    def _item_prompt_len(item) -> int:
        """Prompt rows an admission unit will prefill (resumes prefill
        prompt + already-emitted tokens)."""
        return InferenceEngine._item_prompt(item).shape[0]

    def _admit(self, slot: int, item) -> Optional[Request]:
        """Failure-isolated admission: a poison request (non-finite
        prefill logits, a malformed prompt that slipped past submit,
        any exception its own prefill raises) fails *that* handle with
        a structured RequestError — its partial slot state is torn down
        page-exactly and the other slots keep decoding. Page-accounting
        violations stay engine-fatal: broken pool bookkeeping cannot be
        attributed to one request."""
        with TraceAnnotation("serve.admit", uid=self._item_handle(item).uid,
                             slot=slot) as span:
            try:
                return self._admit_impl(slot, item, span)
            except paging.PageAccountingError:
                raise
            except _AbortAdmission as e:   # cancel/expire mid-prefill
                self._teardown_admission(slot, item, e.status, e.reason)
            except Exception as e:
                self._teardown_admission(slot, item, "failed",
                                         f"{type(e).__name__}: {e}")
                self.check_invariants()    # every fault audits the pool
            return None

    def _teardown_admission(self, slot: int, item, status: str,
                            reason: str) -> None:
        """Unwind a partially-admitted slot (kv.admit / table writes may
        or may not have happened — release is tolerant of both) and
        finalize the handle."""
        handle = self._item_handle(item)
        self.active[slot] = False
        self._tasks[slot] = None
        self.slot_of.pop(handle.uid, None)
        if self.paged:
            self.kv.release(slot)
        self.scheduler.release(slot)
        toks = item.emitted if isinstance(item, _Resume) else []
        self._finalize_aborted(handle, status, reason, toks)

    def _count_prefill(self, span: TraceAnnotation, rows: int,
                       bucket: int) -> None:
        """Record an admission prefill of `rows` real rows in a compiled
        bucket of `bucket` rows: the counters and the admit span's args."""
        self.stats["prefill_rows"] += int(rows)
        self.stats["prefill_bucket_rows"] += int(bucket)
        span.set_metadata(rows=int(rows), bucket=int(bucket))

    def _admit_impl(self, slot: int, item,
                    span: TraceAnnotation) -> Optional[Request]:
        """Prefill `item`'s prompt into `slot` and emit its next token.
        `item` is a fresh RequestHandle or a preempted _Resume; `span` is
        its ``serve.admit`` span. Returns the request if it finished
        immediately."""
        if isinstance(item, _Resume):
            handle, prompt = item.handle, item.prompt
            budget_cap, prior = item.budget, item.emitted
        else:
            handle, prior = item, []
            prompt = np.asarray(handle.request.prompt, np.int32)
            budget_cap = handle.request.max_new_tokens
        req = handle.request
        n = prompt.shape[0]
        if isinstance(item, _Resume):
            # every row of the resume prefill is recomputed work (the
            # original prefill + decode already produced them once) —
            # same unit as spec_rollback_tokens, so preemption cost and
            # speculative rollback cost are directly comparable.
            self.stats["preempt_recompute_tokens"] += int(n)
        hit = (0, [])
        with TraceAnnotation("serve.prefill", uid=req.uid):
            if self.prefix is not None:
                # match fresh (not the gate's estimate): an earlier
                # _admit in this same batch may have registered chunks
                # this prompt can now share. Gate-matched entries are
                # protected, so the fresh match only ever covers MORE
                # than the gate promised pages for — and kv.admit refs
                # the pages immediately, with no reclaim possible in
                # between (same host thread).
                p, pages, _ = self.prefix.match(prompt)
                hit = (p, pages)
                self.stats["prefix_lookup_tokens"] += int(n)
                self.stats["prefix_hit_tokens"] += int(p)
            if hit[0] > 0:
                logits = self._admit_shared(slot, prompt, n, *hit,
                                            span=span)
            else:
                if self.cfg.is_ssm_layer_stack:
                    # right-padding would leak pad tokens into the
                    # recurrent SSM/conv state, so SSM-stack families
                    # prefill at the exact prompt length (one compile
                    # per distinct length).
                    bucket = n
                else:
                    bucket = bucket_length(n, self.max_len)
                self._count_prefill(span, n, bucket)
                padded = np.zeros((1, bucket) + prompt.shape[1:], np.int32)
                padded[0, :n] = prompt
                logits, single = self._prefill(
                    self.params, jnp.asarray(padded),
                    jnp.asarray(n - 1, jnp.int32))
        if hit[0] == 0:
            with TraceAnnotation("serve.insert", uid=req.uid):
                if self.paged:
                    ids = self.kv.admit(slot, n)   # gated by admit_batch
                    self.cache = self._insert(
                        self.cache, single, jnp.asarray(slot, jnp.int32),
                        {k: jnp.asarray(v) for k, v in ids.items()})
                else:
                    self.cache = self._insert(self.cache, single,
                                              jnp.asarray(slot, jnp.int32))
        if self.faults is not None \
                and self.faults.poison_prefill(self, req.uid):
            logits = jnp.full_like(logits, jnp.nan)
        # the host waits here for the prefill (and the insert behind it)
        with TraceAnnotation("serve.prefill_wait", uid=req.uid):
            finite = bool(jnp.isfinite(logits.astype(jnp.float32)).all())
        if not finite:
            # checked BEFORE prefix.register: NaN logits mean the
            # prefilled KV is suspect too, and a registered chunk would
            # poison every future sharer of those pages
            raise ValueError("non-finite prefill logits (poison request)")
        if self.faults is not None:
            self.faults.on_prefill(self, handle)
        v = self._verdict(handle)
        if v is not None:                  # cancel/expire mid-prefill
            raise _AbortAdmission(*v)
        if self.prefix is not None:
            # adopt this slot's full-chunk pages; chunks already indexed
            # (including everything just mapped shared) are skipped
            self.prefix.register(prompt, n, self.kv.tables["linear"][slot])
            self.stats["shared_pages"] = max(self.stats["shared_pages"],
                                             self.kv.shared_page_count)
        self.key, k = jax.random.split(self.key)
        tok = sample_token(logits, k, self.scfg)       # (1,1) or (1,K)
        if self.cfg.family == "audio":
            tok = tok[:, None, :]                      # (1,1,K)
        tok = np.asarray(tok)
        task = _SlotTask(handle, budget=min(budget_cap, self.max_len - n),
                         toks=list(prior))
        handle.status = "running"          # sticky across preemption
        self._tasks[slot] = task
        self.pos[slot] = n
        self.slot_of[req.uid] = slot
        self.admission_step[req.uid] = self.stats["steps"]
        self.stats["admissions"] += 1
        fin = self._emit(slot, tok[0][0])
        if fin is None:
            self.active[slot] = True
            self.tokens[slot] = tok[0]
        return fin

    def _admit_shared(self, slot: int, prompt: np.ndarray, n: int,
                      p: int, pages: List[int], *,
                      span: TraceAnnotation) -> jnp.ndarray:
        """Prefix-hit admission: map the `p` matched tokens' pages
        (`pages`) read-only into `slot` and prefill only the uncached
        suffix directly into the pool (the start-offset prefill path).
        A full-cover match (p == n) still re-emits from the last prompt
        token, so its row is copy-on-written first and exactly one
        token is re-prefilled. Returns the next-token logits."""
        with TraceAnnotation("serve.insert", uid=self.scheduler.slots[slot]):
            self.kv.admit(slot, n, shared=pages)
            start = n - 1 if p == n else p
            ok = self._cow_rows(slot, start, n)
        assert ok, "admission COW starved: gate promised the page"
        suffix = prompt[start:]
        ps = self.kv.page_size
        # clamp the compile bucket to the slot's row capacity: bucketed
        # pad rows past it would wrap (paged_cache_write writes modulo
        # table_width * page_size) and trash the shared prefix pages
        bucket = min(bucket_length(suffix.shape[0], self.max_len),
                     self.kv.lin_pages * ps - start)
        self._count_prefill(span, suffix.shape[0], bucket)
        padded = np.zeros((1, bucket) + prompt.shape[1:], np.int32)
        padded[0, :suffix.shape[0]] = suffix
        table = jnp.asarray(self.kv.tables["linear"][slot:slot + 1])
        logits, self.cache = self._suffix_prefill(
            self.params, jnp.asarray(padded),
            jnp.asarray([start], jnp.int32),
            jnp.asarray(suffix.shape[0] - 1, jnp.int32),
            self.cache, table)
        return logits

    def _ensure_decode_pages(self) -> None:
        """Lazy page reservation before a decode step: every active slot
        must have the page its next cache write lands in (privately —
        a shared page is copy-on-written first). If the pool runs dry,
        the cheapest-to-recompute active slot is preempted — requeued
        at the queue front as a _Resume (re-prefill prompt + emitted,
        token-exact under greedy) — until the write fits. The victim
        may be the needy slot itself (it then self-preempts rather than
        evicting a costlier neighbour); each preemption shrinks the
        active set, one slot's worst case fits the pool by construction
        (PagedKVState rejects smaller pools), and a preempted slot's
        registered prefix pages stay evictable-on-demand — so a lone
        survivor always progresses."""
        for slot in np.nonzero(self.active)[0]:
            while self.active[slot] and not self._reserve_decode_rows(
                    int(slot), int(self.pos[slot]) + 1):
                self._preempt(self._select_victim())

    def _reserve_decode_rows(self, slot: int, n_rows: int) -> bool:
        """Make rows [pos, n_rows) of `slot` privately writable: map
        their pages, then copy-on-write any the slot shares (with the
        prefix index or another slot). False => pool dry even after
        LRU eviction; the caller preempts and retries (both steps are
        idempotent). Shared by the plain decode tick (n_rows = pos+1)
        and the speculative cycle (pos+k+1)."""
        if not self.kv.reserve_rows(slot, n_rows):
            return False
        return self._cow_rows(slot, int(self.pos[slot]), n_rows)

    def _cow_rows(self, slot: int, row0: int, row1: int) -> bool:
        """Copy-on-write every shared page covering upcoming writes to
        rows [row0, row1) of `slot`. False => pool dry."""
        while True:
            idx = self.kv.next_shared_write_page(slot, row0, row1)
            if idx is None:
                return True
            pair = self.kv.cow(slot, idx)
            if pair is None:
                return False
            self.cache = self._copy_page(self.cache,
                                         jnp.asarray(pair[0], jnp.int32),
                                         jnp.asarray(pair[1], jnp.int32))
            self.stats["cow_copies"] += 1

    def _select_victim(self) -> int:
        """Preemption victim = the active slot with the lowest
        recompute cost: the tokens its resume would re-prefill that the
        prefix index does NOT already cover (scheduler.
        pick_preemption_victim; ties break youngest-first). Without a
        prefix index nothing is covered, so cost is simply the resume
        length."""
        cands = []
        for s in np.nonzero(self.active)[0]:
            s = int(s)
            task = self._tasks[s]
            resume = np.concatenate(
                [np.asarray(task.handle.request.prompt, np.int32),
                 np.asarray(task.toks, np.int32).reshape(
                     (len(task.toks),)
                     + np.asarray(task.handle.request.prompt).shape[1:])],
                axis=0)
            cost = resume.shape[0]
            if self.prefix is not None:
                cost -= self.prefix.match_len(resume)
            cands.append((s, cost,
                          self.admission_step.get(task.handle.uid, -1)))
        return pick_preemption_victim(cands)

    def _preempt(self, slot: int) -> None:
        """Evict `slot` mid-decode: free its pages and requeue the rest
        of its generation as a _Resume. Its handle keeps streaming —
        emitted tokens are never replayed."""
        task = self._tasks[slot]
        with TraceAnnotation("serve.preempt", uid=task.handle.uid):
            emitted = np.asarray(task.toks, np.int32)
            prompt = np.concatenate(
                [np.asarray(task.handle.request.prompt, np.int32), emitted],
                axis=0)
            self.active[slot] = False
            self._tasks[slot] = None
            self.slot_of.pop(task.handle.uid, None)   # queued, not placed
            self.kv.release(slot)
            self.scheduler.release(slot)
            self.scheduler.requeue(_Resume(task.handle, prompt, task.budget,
                                           list(task.toks)))
            self.stats["preemptions"] += 1

    def _emit(self, slot: int, token) -> Optional[Request]:
        """Record one emitted token for `slot`; finish the slot on EOS
        or budget exhaustion. `token`: scalar (text) or (K,) (audio)."""
        task = self._tasks[slot]
        req = task.handle.request
        task.toks.append(np.asarray(token))
        task.budget -= 1
        self.stats["tokens_emitted"] += 1
        task.handle._append(token)
        if task.handle.on_token is not None:   # deferred to end of step()
            self._callbacks.append((task.handle.on_token,
                                    task.handle.uid, token))
        flat = int(token if np.ndim(token) == 0 else token[0])
        if (req.eos_id is not None and flat == req.eos_id) \
                or task.budget <= 0:
            return self._finish(slot)
        return None

    def _finish(self, slot: int) -> Request:
        task = self._tasks[slot]
        req = task.handle.request
        req.output = np.asarray(task.toks, np.int32)
        self.done[req.uid] = req
        self.completion_step[req.uid] = self.stats["steps"]
        task.handle._finalize("done")
        self.active[slot] = False
        self._tasks[slot] = None
        if self.paged:
            # free-on-completion: the slot's pages return to the pool
            # and its block-table rows zero out, so a reused uid (or the
            # next occupant) can neither leak pages nor read a stale
            # mapping (clear_finished() only reclaims host bookkeeping).
            self.kv.release(slot)
        self.scheduler.release(slot)
        return req

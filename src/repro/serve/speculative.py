"""Self-speculative decoding: rank-truncated draft + batched paged
verification (docs/serving.md §Speculative decoding).

NanoQuant's low-rank binary factorization carries a free draft model:
truncating the rank-r factors to r' < r is a strictly cheaper
approximate forward pass at ZERO extra storage — the draft is the same
packed buffers read through a static effective rank
(`quant.surgery.rank_truncated_view`; the kernels read sub-extents, see
`kernels.ops`). The full-rank model is the exact verifier, so greedy
outputs are **token-identical** to the non-speculative engine by
construction.

One engine tick becomes one fused device call (`lax.scan` draft loop +
one multi-token verify forward under a single jit):

1. **Draft** — k single-token decode steps through the truncated view,
   greedy-sampling d_1..d_k. Draft KV lands in the slot's own pages at
   rows ``pos..pos+k-1`` (draft tokens are just extra rows — the paged
   pool and block tables are untouched machinery).
2. **Verify** — ONE full-rank forward over ``[t_0, d_1..d_k]`` (S=k+1
   queries at positions ``pos..pos+k``), REwriting those rows with
   exact full-rank KV and emitting the exact next token e_i after every
   prefix. Multi-token paged causality needs no new masking: a row
   written by a later query of the same call reconstructs to a negative
   absolute position for every earlier query
   (`kernels.ref.paged_attention_ref`).
3. **Commit / rollback** — the acceptance length a = number of leading
   i with d_{i+1} == e_i; tokens e_0..e_a are committed (a+1 per cycle,
   ≥1 always — e_0 is exactly what the plain engine would emit).
   Rows past the new frontier are dead (negative reconstruction ⇒
   never read), so rollback is purely host-side: ``PagedKVState.trim``
   returns pages covering only rejected rows to the pool — the same
   token-exact accounting the preemption resume path relies on.

Committed token i of a cycle only ever attends to KV of rows holding
the committed prefix (acceptance guarantees rows ``pos+1..pos+i`` hold
d_j == e_{j-1}), and every row was rewritten full-rank by the verify —
hence exact identity, whatever the draft proposes.

A dynamic-k controller shrinks the draft length when acceptance drops
(EMA-gated, one jit cache entry per distinct k in
``[spec_k_min, spec_k]``) so a badly-truncated draft degrades toward
plain decode instead of burning k wasted rows per cycle.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.quant.surgery import rank_truncated_view
from repro.serve import paging

# dynamic-k controller: shrink when the EMA of per-cycle acceptance
# fraction (a/k averaged over active slots) falls below SHRINK, grow
# when it exceeds GROW. Hysteresis band keeps k stable in steady state.
_EMA_BETA = 0.2
_SHRINK_BELOW = 0.4
_GROW_ABOVE = 0.8


class SpecDecodeController:
    """Per-engine speculative decode driver (one per InferenceEngine,
    built by the engine when ``ServeConfig.spec_rank_frac`` is set).

    Holds the zero-copy draft view, the per-k jitted draft+verify
    cycle cache, per-slot acceptance tracking (``acceptance`` maps uid
    -> [accepted, drafted]) and the dynamic-k state. ``tick`` replaces
    the engine's single-token decode tick."""

    def __init__(self, engine):
        scfg = engine.scfg
        frac = scfg.spec_rank_frac
        if not (0.0 < frac <= 1.0):
            raise ValueError(
                f"spec_rank_frac must be in (0, 1], got {frac}")
        if scfg.spec_k < 1 or scfg.spec_k_min < 1 \
                or scfg.spec_k_min > scfg.spec_k:
            raise ValueError(
                f"need 1 <= spec_k_min <= spec_k, got "
                f"spec_k_min={scfg.spec_k_min} spec_k={scfg.spec_k}")
        if not scfg.greedy:
            raise ValueError(
                "speculative decoding requires greedy=True: the verify "
                "forward replays the draft deterministically, and "
                "token identity with the plain engine is only defined "
                "for greedy sampling")
        if not engine.paged:
            raise ValueError(
                "speculative decoding requires the paged KV cache "
                "(draft tokens live in the slot's pages; rollback is "
                "page trimming) — this family/config has none")
        if set(engine.kv.tables) != {"linear"}:
            raise ValueError(
                "speculative decoding supports linear page tables only "
                "(sliding-window ring pools wrap draft rows over "
                f"committed KV); got kinds {sorted(engine.kv.tables)}")
        if engine.cfg.is_ssm_layer_stack:
            raise ValueError(
                "speculative decoding is undefined for recurrent-state "
                "families: rejected drafts cannot be rolled out of an "
                "SSM/conv state by page trimming")
        if engine.cfg.family == "audio":
            raise ValueError("speculative decoding does not support "
                             "multi-codebook audio decode")
        self.engine = engine
        self.rank_frac = float(frac)
        self.k_min = int(scfg.spec_k_min)
        self.k_max = int(scfg.spec_k)
        self.k = self.k_max
        # zero-copy: every array leaf of the view IS the corresponding
        # engine.params leaf (rank_truncated_view only adds static
        # EffRank markers), so the draft adds no weight memory and no
        # placement work — sharded params stay sharded.
        self.draft_params = rank_truncated_view(engine.params, frac)
        self._cycles: Dict[int, callable] = {}
        self.acceptance: Dict[int, List[int]] = {}
        self._ema = None

    # ---- reporting --------------------------------------------------------

    def acceptance_rate(self, uid=None) -> float:
        """Accepted / drafted over the engine lifetime (or one uid)."""
        if uid is not None:
            a, d = self.acceptance.get(uid, (0, 0))
        else:
            a = sum(v[0] for v in self.acceptance.values())
            d = sum(v[1] for v in self.acceptance.values())
        return a / d if d else 0.0

    # ---- fused draft + verify cycle ---------------------------------------

    def _cycle(self, k: int):
        if k not in self._cycles:
            self._cycles[k] = self._build_cycle(k)
        return self._cycles[k]

    def _build_cycle(self, k: int):
        eng = self.engine
        cfg = eng.cfg

        def cycle(params, draft, tokens, cache, pos, active, tables):
            eng.stats["decode_traces"] += 1
            with eng._policy_scope():
                def body(carry, _):
                    tok, c, p = carry
                    lg, c = T.decode_step(draft, cfg, tok, c, p,
                                          block_tables=tables)
                    nxt = jnp.argmax(lg[:, -1].astype(jnp.float32),
                                     axis=-1).astype(jnp.int32)
                    return (nxt[:, None], c, p + 1), nxt

                (_, c, _), drafts = jax.lax.scan(
                    body, (tokens, cache, pos), None, length=k)
                drafts = jnp.moveaxis(drafts, 0, 1)          # (B, k)
                xs = jnp.concatenate([tokens, drafts], axis=1)
                lg, c = T.decode_step(params, cfg, xs, c, pos,
                                      block_tables=tables)
                # exact[:, i] = full-rank greedy token after prefix
                # ..t0,d_1..d_i — e_0 is the plain engine's next token
                exact = jnp.argmax(lg.astype(jnp.float32),
                                   axis=-1).astype(jnp.int32)  # (B, k+1)
                match = (drafts == exact[:, :k]).astype(jnp.int32)
                acc = jnp.cumprod(match, axis=1).sum(axis=1)   # (B,)
                c = paging.paged_select_active(c, cache, active)
            return exact, acc, c

        return jax.jit(cycle, donate_argnums=(3,))

    # ---- the tick ---------------------------------------------------------

    def tick(self, finished) -> None:
        """Speculative replacement for the engine's decode tick: one
        fused draft+verify call, then host-side commit + rollback."""
        eng = self.engine
        # cap k so the verify's last write row pos+k stays < max_len
        # for every active slot (the linear table covers max_len rows —
        # the invariant the causality masking rests on)
        k = self.k
        for s in np.nonzero(eng.active)[0]:
            k = min(k, eng.max_len - 1 - int(eng.pos[s]))
        if k < 1:
            # some slot is on its last row: no draft headroom this tick
            eng._decode_tick(finished)
            return
        # reserve pages for rows [0, pos+k+1) per slot — the cycle
        # writes k+1 rows before the next host sync — COWing any the
        # slot shares (prefix cache) so draft writes never touch a
        # sharer's KV. Dry pool preempts the cheapest-to-recompute slot
        # (identical policy to _ensure_decode_pages).
        for s in np.nonzero(eng.active)[0]:
            while eng.active[s] and not eng._reserve_decode_rows(
                    int(s), int(eng.pos[s]) + k + 1):
                eng._preempt(eng._select_victim())
        if not eng.active.any():
            return
        slots = np.nonzero(eng.active)[0]
        if eng.faults is not None:
            # raises before the donated cycle call (recoverable: the
            # engine preempt-resumes the survivors, fails the target)
            eng.faults.before_decode(eng)
        tables = eng.kv.device_tables()
        exact, acc, eng.cache = self._cycle(k)(
            eng.params, self.draft_params, jnp.asarray(eng.tokens),
            eng.cache, jnp.asarray(eng.pos), jnp.asarray(eng.active),
            tables)
        exact, acc = np.array(exact), np.array(acc)
        if eng.faults is not None:
            # cancel-mid-spec-rollback: lands between the batched
            # verify and the commit+trim below; the commit still runs
            # (cancellation is honoured at the next tick boundary), so
            # rollback accounting must stay exact for a doomed slot
            eng.faults.on_spec_cycle(eng)
        eng.stats["decode_steps"] += 1
        eng.stats["spec_cycles"] += 1
        eng.stats["wasted_slot_steps"] += int(eng.max_batch - len(slots))
        accept_fracs = []
        for s in slots:
            s = int(s)
            a = int(acc[s])
            accept_fracs.append(a / k)
            eng.stats["spec_draft_tokens"] += k
            eng.stats["spec_accepted_tokens"] += a
            eng.stats["spec_rollback_tokens"] += k - a
            rec = self.acceptance.setdefault(
                eng._tasks[s].handle.uid, [0, 0])
            rec[0] += a
            rec[1] += k
            committed = 0
            for i in range(a + 1):
                eng.pos[s] += 1
                committed += 1
                fin = eng._emit(s, exact[s][i])
                if fin is not None:       # EOS / budget: slot released
                    finished.append(fin)
                    break
            if eng.active[s]:
                # next tick feeds the last committed token at pos
                eng.tokens[s] = exact[s, committed - 1]
                # rollback: pages covering only rejected rows (past the
                # committed frontier pos) go back to the pool
                eng.stats["spec_rollback_pages"] += eng.kv.trim(
                    s, int(eng.pos[s]))
        # dynamic k: EMA of the batch acceptance fraction
        if accept_fracs:
            f = sum(accept_fracs) / len(accept_fracs)
            self._ema = f if self._ema is None else \
                (1 - _EMA_BETA) * self._ema + _EMA_BETA * f
            if self._ema < _SHRINK_BELOW and self.k > self.k_min:
                self.k -= 1
            elif self._ema > _GROW_ABOVE and self.k < self.k_max:
                self.k += 1

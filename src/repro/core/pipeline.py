"""NanoQuant end-to-end pipeline (paper Alg. 1).

Phase 1  global calibration      -> diagonal K-FAC stats via model taps
Phase 2  block reconstruction    -> per block: TuneFP (error-propagation
         mitigation) -> LB-ADMM init + magnitude balancing -> STE latent
         refinement -> bit packing
Phase 3  model reconstruction    -> KL-distillation of the packed model,
         tuning only the floating-point scales {s1, s2}

Two activation streams are maintained (paper §3.2 Step 1): X_q flows
through the already-compressed prefix, X_fp through the FP teacher; the
per-block target is always Y = B_fp(X_fp), so TuneFP genuinely absorbs
accumulated quantization error instead of fitting a zero residual.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing, precond, quantize, util
from repro.core.admm import ADMMConfig, QuantizationError
from repro.core.layout import EXCLUDE_LINEARS, quantizable_linear
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import transformer as T
from repro.train.optim import AdamW, cosine_schedule


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    target_bpw: float = 1.0
    rank_align: int = 32
    # pack-time tile alignment of the packed d_in dim (stored operands
    # are padded ONCE here instead of per kernel call; 32 = packing
    # word, i.e. no extra padding — see core.packing.pack_quantized)
    pack_k_align: int = 32
    admm_iters: int = 40
    rho_init: float = 0.01
    rho_final: float = 1.0
    lam: float = 1e-4
    gamma: float = 0.2            # shrinkage (paper: 0.2 llama/qwen, 0.6 gemma)
    t_pre: int = 40               # TuneFP steps per block
    t_post: int = 60              # STE refinement steps per block
    t_glob: int = 60              # global KD steps
    lr_pre: float = 1e-4
    lr_post: float = 1e-5
    lr_glob: float = 1e-6
    microbatch: int = 4
    weighted_mse: bool = True
    min_dim: int = 48             # leave smaller linears in FP
    kd_temp: float = 1.0
    seed: int = 0
    # ablation switches (paper Tables 5-6)
    init_method: str = "lb_admm"  # lb_admm | dual_svid | dbf_admm
    skip_tune_fp: bool = False
    skip_ste: bool = False
    skip_kd: bool = False
    # init-method fallback ladder: on a diverged block (non-finite
    # latents / losses / reconstruction error) the block is retried
    # with these ``@register_init_method`` names, in order, after
    # ``init_method`` (comma-separated so the config stays hashable
    # and JSON-manifest round-trippable). "" disables fallbacks.
    fallback_inits: str = "dbf_admm,dual_svid"

    def admm(self) -> ADMMConfig:
        return ADMMConfig(rank=0, iters=self.admm_iters,
                          rho_init=self.rho_init, rho_final=self.rho_final,
                          lam=self.lam)


# ---------------------------------------------------------------------------
# block enumeration
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BlockRef:
    stack: str                      # param-tree key
    idx: Any                        # index into the stack (int / tuple / None)
    tap_idx: Any                    # layer index in tap stats (None=aggregate)
    kind: str                       # attn | mamba | cross

    def get(self, params):
        bp = params[self.stack]
        if self.idx is None:
            return bp
        if isinstance(self.idx, tuple):
            for i in self.idx:
                bp = util.tree_index(bp, i)
            return bp
        return util.tree_index(bp, self.idx)


def blocks_of(cfg) -> List[BlockRef]:
    fam = cfg.family
    if fam in ("dense", "audio", "ssm"):
        kind = "mamba" if fam == "ssm" else "attn"
        return [BlockRef("layers", i, i, kind) for i in range(cfg.n_layers)]
    if fam == "moe":
        out = [BlockRef("dense_layers", i, i, "attn")
               for i in range(cfg.first_k_dense)]
        out += [BlockRef("layers", i, i, "attn")
                for i in range(cfg.n_layers - cfg.first_k_dense)]
        return out
    if fam == "hybrid":
        # shared attention block first (on teacher inputs), then SSM layers
        return ([BlockRef("shared_attn", None, None, "attn")]
                + [BlockRef("layers", i, i, "mamba")
                   for i in range(cfg.n_layers)])
    if fam == "vlm":
        per = cfg.cross_attn_every
        out: List[BlockRef] = []
        for g in range(cfg.n_layers // per):
            for i in range(per - 1):
                out.append(BlockRef("self_layers", (g, i),
                                    g * (per - 1) + i, "attn"))
            out.append(BlockRef("cross_layers", g, g, "cross"))
        return out
    raise ValueError(fam)


def make_apply(cfg, kind):
    if kind == "attn":
        def f(bp, x, ctx):
            return T._apply_attn_block(bp, cfg, x, jnp.arange(x.shape[1]))[0]
    elif kind == "mamba":
        def f(bp, x, ctx):
            return T._apply_mamba_block(bp, cfg, x)[0]
    elif kind == "cross":
        def f(bp, x, ctx):
            kv = L.image_kv(bp["xattn"], cfg, ctx["image_embeds"])
            return T._apply_cross_block(bp, cfg, x, kv)
    else:
        raise ValueError(kind)
    return f


# ---------------------------------------------------------------------------
# linear enumeration within a block
# ---------------------------------------------------------------------------

# selection rule + FP exclusions single-sourced in core.layout (shared
# with quant.surgery's abstract walk)
_EXCLUDE = EXCLUDE_LINEARS


def linear_paths(bp, min_dim: int) -> List[Tuple[str, ...]]:
    paths = []

    def walk(d, path):
        for k in sorted(d.keys()):
            v = d[k]
            if isinstance(v, dict):
                if "w" in v and not isinstance(v["w"], dict):
                    if quantizable_linear(k, v["w"].shape, min_dim):
                        paths.append(path + (k,))
                else:
                    walk(v, path + (k,))

    walk(bp, ())
    return paths


def _get_path(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _set_path(tree, path, val):
    out = dict(tree)
    if len(path) == 1:
        out[path[0]] = val
        return out
    out[path[0]] = _set_path(tree[path[0]], path[1:], val)
    return out


# ---------------------------------------------------------------------------
# tuning loops
# ---------------------------------------------------------------------------


def _mse(out, tgt, weight=None):
    d = (out.astype(jnp.float32) - tgt.astype(jnp.float32))
    if weight is not None:
        d = d * weight
    return jnp.mean(jnp.square(d))


def _channel_weight(Y, enabled):
    if not enabled:
        return None
    rms = jnp.sqrt(jnp.mean(jnp.square(Y.astype(jnp.float32)),
                            axis=tuple(range(Y.ndim - 1))) + 1e-8)
    w = 1.0 / rms
    return w / jnp.mean(w)


def _tune(apply_fn, bp, pred, Xq, Y, ctx, steps, lr, mb, weighted,
          key):
    """Generic block tuning: optimize leaves selected by `pred` so that
    apply_fn(bp, Xq) matches Y."""
    if steps <= 0:
        return bp, []
    trainable, frozen = util.partition(bp, pred)
    if not any(l is not None for l in jax.tree.leaves(
            trainable, is_leaf=lambda x: x is None)):
        return bp, []
    weight = _channel_weight(Y, weighted)
    opt = AdamW(cosine_schedule(lr, steps), clip_norm=1.0)
    state = opt.init(trainable)

    # frozen leaves and the weight are arguments, not closed-over
    # constants: jit embeds a closed-over array in the program text.
    def loss(tr, fr, w, xb, yb, cb):
        out = apply_fn(util.combine(tr, fr), xb, cb)
        return _mse(out, yb, w)

    vg = jax.jit(jax.value_and_grad(loss))
    n = Xq.shape[0]
    losses = []
    for s in range(steps):
        i0 = (s * mb) % max(n - mb + 1, 1)
        xb, yb = Xq[i0:i0 + mb], Y[i0:i0 + mb]
        cb = {k: v[i0:i0 + mb] for k, v in ctx.items()}
        lval, grads = vg(trainable, frozen, weight, xb, yb, cb)
        trainable, state, _ = opt.update(grads, state, trainable)
        losses.append(float(lval))
    return util.combine(trainable, frozen), losses


_LATENT_KEYS = ("lu", "lv", "s1", "s2")


def _is_latent_path(path: str) -> bool:
    leaf = path.rsplit("/", 1)[-1]
    return leaf in _LATENT_KEYS


def _is_scale_path(path: str) -> bool:
    leaf = path.rsplit("/", 1)[-1]
    return leaf in ("s1", "s2")


# ---------------------------------------------------------------------------
# init dispatch (Table 5 ablation) — resolved through the repro.api
# init-method registry; new methods plug in via @register_init_method
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted_init(fn, rank: int, admm: ADMMConfig):
    return jax.jit(lambda w, d_in, d_out, key: fn(
        w, d_in, d_out, rank=rank, admm=admm, key=key))


def _init_latent_2d(w, d_in, d_out, rank, admm, method, key):
    # resolve on every call (cheap dict lookup) so re-registered /
    # unregistered methods take effect; the jit cache keys on the
    # resolved function object
    from repro.api.init_methods import get_init_method
    return _jitted_init(get_init_method(method), rank, admm)(
        w, d_in, d_out, key)


def _init_latent(p, d_in, d_out, qcfg: QuantConfig, key):
    from repro.core.bpw import rank_for_bpw
    w = p["w"]
    admm = qcfg.admm()
    if w.ndim == 3:
        E, din, dout = w.shape
        r = rank_for_bpw(dout, din, qcfg.target_bpw, qcfg.rank_align)
        keys = jax.random.split(key, E)
        lat = jax.vmap(lambda we, di, do, k: _init_latent_2d(
            we, di, do, r, admm, qcfg.init_method, k))(w, d_in, d_out, keys)
    else:
        din, dout = w.shape
        r = rank_for_bpw(dout, din, qcfg.target_bpw, qcfg.rank_align)
        lat = _init_latent_2d(w, d_in, d_out, r, admm, qcfg.init_method, key)
    lat = dict(lat)
    if "b" in p:
        lat["b"] = p["b"]
    return lat, r


def _pack_latent(lat: dict, k_align: int = 32) -> dict:
    def pack2d(lu, lv, s1, s2):
        return packing.pack_quantized(lu, lv, s1, s2, k_align=k_align)
    if lat["lu"].ndim == 3:
        q = jax.vmap(pack2d)(lat["lu"], lat["lv"],
                             lat["s1"].astype(jnp.float32),
                             lat["s2"].astype(jnp.float32))
    else:
        q = pack2d(lat["lu"], lat["lv"], lat["s1"], lat["s2"])
    if "b" in lat:
        q["b"] = lat["b"]
    return q


# ---------------------------------------------------------------------------
# health guards + per-block quantization with the init-method
# fallback ladder (docs/quantization.md)
# ---------------------------------------------------------------------------


def _ladder(qcfg: QuantConfig) -> List[str]:
    out = [qcfg.init_method]
    for m in qcfg.fallback_inits.split(","):
        m = m.strip()
        if m and m not in out:
            out.append(m)
    return out


def _check_finite(tree, block: str, layer, reason: str, iteration=None):
    """Raise a structured :class:`QuantizationError` if any float leaf
    of `tree` is non-finite — the guard that keeps NaNs out of
    ``quant.surgery`` packing and the saved artifact."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        arr = jnp.asarray(leaf)
        if not jnp.issubdtype(arr.dtype, jnp.floating):
            continue
        if not bool(jnp.isfinite(arr.astype(jnp.float32)).all()):
            where = "/".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            raise QuantizationError(
                layer=layer if layer is not None else where, block=block,
                iteration=iteration,
                reason=f"{reason}: non-finite values in {where}")


def _attempt_key(kb, ai: int, li: int):
    """Per-(attempt, linear) RNG key. Attempt 0 reproduces the
    historical keying exactly, so journal/resume bit-identity holds
    across code that never falls back."""
    if ai == 0:
        return jax.random.fold_in(kb, li)
    return jax.random.fold_in(jax.random.fold_in(kb, 7919 + ai), li)


def _quantize_block(apply_fn, bp_fp, Xq_b, Y, ctx_b, stats, bref,
                    label: str, qcfg: QuantConfig, kb, faults, bi: int,
                    log):
    """Steps 1-3 + packing for one block, with divergence detection and
    the init-method fallback ladder. Returns (packed bp, out_q, report
    row, {rank-key: rank})."""
    # Step 1: error-propagation mitigation (method-independent — run
    # once, shared across ladder attempts)
    if not qcfg.skip_tune_fp:
        bp_base, pre_losses = _tune(apply_fn, bp_fp, lambda p: True, Xq_b,
                                    Y, ctx_b, qcfg.t_pre, qcfg.lr_pre,
                                    qcfg.microbatch, qcfg.weighted_mse, kb)
    else:
        bp_base, pre_losses = bp_fp, []
    if pre_losses and not np.isfinite(pre_losses[-1]):
        raise QuantizationError(
            None, label, None, "TuneFP (error-propagation mitigation) "
            "diverged: non-finite loss — no init method can fix a "
            "poisoned block input; check calibration data")
    _check_finite(bp_base, label, None, "TuneFP output")

    lpaths = linear_paths(bp_base, qcfg.min_dim)
    ladder = _ladder(qcfg)
    fallbacks: List[dict] = []
    for ai, method in enumerate(ladder):
        try:
            # Step 2: low-rank binary initialization
            bp, ranks_b = bp_base, {}
            for li, path in enumerate(lpaths):
                pdict = _get_path(bp, path)
                name = ".".join(path)
                w = pdict["w"]
                expert = w.shape[0] if w.ndim == 3 else None
                d_in, d_out = precond.preconditioners_for(
                    stats, bref.stack, name, bref.tap_idx,
                    w.shape[-2], w.shape[-1], qcfg.gamma,
                    expert_shape=expert)
                lat, r = _init_latent(
                    pdict, d_in, d_out,
                    dataclasses.replace(qcfg, init_method=method),
                    _attempt_key(kb, ai, li))
                fault = (faults.poison_init(bi, li)
                         if faults is not None else None)
                if fault is not None:
                    lat = dict(lat, lu=jnp.full_like(lat["lu"], jnp.nan))
                _check_finite(
                    {k: lat[k] for k in _LATENT_KEYS}, label, name,
                    f"init method {method!r} produced non-finite latents",
                    iteration=fault.iteration if fault is not None else None)
                ranks_b[f"{bref.stack}[{bref.idx}].{name}"] = r
                bp = _set_path(bp, path, lat)

            # Step 3: factorized component refinement (STE)
            if not qcfg.skip_ste:
                bp, ste_losses = _tune(apply_fn, bp, _is_latent_path,
                                       Xq_b, Y, ctx_b, qcfg.t_post,
                                       qcfg.lr_post, qcfg.microbatch,
                                       qcfg.weighted_mse, kb)
                if ste_losses and not np.isfinite(ste_losses[-1]):
                    raise QuantizationError(
                        None, label, None,
                        f"STE refinement diverged under init "
                        f"{method!r}: non-finite loss")
            else:
                ste_losses = []

            # pack + final guard
            for path in lpaths:
                bp = _set_path(bp, path,
                               _pack_latent(_get_path(bp, path),
                                            qcfg.pack_k_align))
            _check_finite(bp, label, None,
                          f"packed block under init {method!r}")
            out_q = apply_fn(bp, Xq_b, ctx_b)
            blk_err = float(_mse(out_q, Y))
            if not np.isfinite(blk_err):
                raise QuantizationError(
                    None, label, None, f"block reconstruction error is "
                    f"non-finite under init {method!r}")
            row = {"block": label,
                   "pre_loss": pre_losses[-1] if pre_losses else None,
                   "ste_loss": ste_losses[-1] if ste_losses else None,
                   "block_err": blk_err,
                   "init_method": method,
                   "fallbacks": list(fallbacks)}
            return bp, out_q, row, ranks_b
        except QuantizationError as e:
            fallbacks.append({"method": method, "layer": e.layer,
                              "iteration": e.iteration, "reason": e.reason})
            if ai == len(ladder) - 1:
                raise QuantizationError(
                    e.layer, label, e.iteration,
                    f"init-method fallback ladder exhausted "
                    f"({' -> '.join(ladder)}); last failure: {e.reason}")
            log(f"[nanoquant] {label}: init {method!r} diverged "
                f"({e.reason}) -> falling back to {ladder[ai + 1]!r}")


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------


def nanoquant_quantize(params, cfg, calib_batches, qcfg: QuantConfig,
                       verbose: bool = True, journal_dir: str = None,
                       resume: bool = False, faults=None,
                       heartbeat=None):
    """Quantize `params` (FP teacher) to packed low-rank binary form.

    calib_batches: list of {'tokens','labels'[,'image_embeds']} dicts.
    Returns (quantized_params, report).

    Crash safety (docs/quantization.md): with `journal_dir`, every
    finished block's packed leaves plus a crc32'd journal entry are
    written through ``checkpoint.journal.QuantJournal`` as the run
    progresses; `resume=True` validates the journal against this run's
    fingerprint (model/quant config, params, calibration) and skips
    finished blocks — the final artifact is bit-identical to an
    uninterrupted run (per-block RNG keying, deterministic streams).
    Diverging blocks retry through the ``QuantConfig.fallback_inits``
    init-method ladder; every decision lands in the journal and the
    report. `faults` (a ``quant.faults.QuantFaultPlan``) injects a
    deterministic fault schedule for chaos testing; `heartbeat` is
    called with a short progress string at block/phase boundaries (what
    ``launch/quantize.py --supervise`` hang detection watches).

    The quantizer's programs run the packed linears on the jnp path
    whatever kernel policy is active: scale-only KD differentiates
    through them, and the Pallas kernels have no VJP."""
    with kops.kernel_policy(kops.KernelPolicy(mode="ref")):
        return _quantize(params, cfg, calib_batches, qcfg, verbose,
                         journal_dir, resume, faults, heartbeat)


def _quantize(params, cfg, calib_batches, qcfg, verbose, journal_dir,
              resume, faults, heartbeat):
    t0 = time.time()
    key = jax.random.PRNGKey(qcfg.seed)
    report: Dict[str, Any] = {"blocks": [], "ranks": {}}

    def beat(msg: str) -> None:
        if heartbeat is not None:
            heartbeat(msg)

    def log(msg: str) -> None:
        if verbose:
            print(msg, flush=True)

    # ---- Phase 1: global calibration -------------------------------------
    stats = precond.collect_stats(T.loss_fn, params, cfg, calib_batches)

    # ---- activation streams ----------------------------------------------
    toks = jnp.concatenate([b["tokens"] for b in calib_batches], 0)
    ctx = {}
    if cfg.family == "vlm":
        ctx["image_embeds"] = jnp.concatenate(
            [b["image_embeds"] for b in calib_batches], 0)
    x0 = T.embed_tokens(params, cfg, toks)
    Xq, Xfp = x0, x0

    blocks = blocks_of(cfg)
    applies = {b.kind: make_apply(cfg, b.kind) for b in blocks}
    quantized: Dict[Tuple, Any] = {}
    hybrid_boundary = (lambda i: cfg.family == "hybrid"
                       and (i + 1) % cfg.attn_every == 0)

    # ---- journal / resume --------------------------------------------------
    journal, done = None, {}
    if journal_dir:
        from repro.checkpoint.journal import QuantJournal, run_fingerprint
        journal = QuantJournal(journal_dir)
        fingerprint = run_fingerprint(params, cfg, qcfg, calib_batches,
                                      len(blocks))
        if resume:
            done = journal.entries_for_resume(fingerprint)
            if done is None:                # no journal yet: fresh start
                done = {}
                journal.start(fingerprint)
            elif done:
                log(f"[nanoquant] resuming: {len(done)}/{len(blocks)} "
                    f"blocks journaled in {journal_dir}")
        else:
            journal.start(fingerprint)
    elif resume:
        raise ValueError("resume=True requires journal_dir")

    # For the hybrid shared block: gather its application inputs from the
    # teacher stream up-front (it is quantized first, see DESIGN.md §5).
    shared_inputs = None
    if cfg.family == "hybrid":
        xs, gathered = x0, []
        fp_blocks = [b for b in blocks if b.stack == "layers"]
        for b in fp_blocks:
            xs = applies["mamba"](b.get(params), xs, ctx)
            if hybrid_boundary(b.idx):
                gathered.append(xs)
                xs = applies["attn"](params["shared_attn"], xs, ctx)
        shared_inputs = jnp.concatenate(gathered, 0)

    # ---- Phase 2: block reconstruction ------------------------------------
    for bi, bref in enumerate(blocks):
        label = f"{bref.stack}[{bref.idx}]"
        kb = jax.random.fold_in(key, bi)
        bp_fp = bref.get(params)
        apply_fn = applies[bref.kind]
        if bref.stack == "shared_attn":
            Xq_b = shared_inputs
            Xfp_b = shared_inputs
            ctx_b = {k: jnp.concatenate([v] * (Xq_b.shape[0] // v.shape[0]), 0)
                     for k, v in ctx.items()}
        else:
            Xq_b, Xfp_b, ctx_b = Xq, Xfp, ctx
        Y = apply_fn(bp_fp, Xfp_b, ctx_b)

        if bi in done:
            # resumed: reload the packed block, replay its report row
            # (the recomputation it replaces is deterministic, so the
            # artifact stays bit-identical to an uninterrupted run)
            entry = done[bi]
            bp = journal.load_block(bi)
            report["ranks"].update(entry["ranks"])
            row = dict(entry["row"])
            out_q = apply_fn(bp, Xq_b, ctx_b)
            beat(f"block={bi}/{len(blocks)} {label} resumed")
        else:
            if faults is not None:
                faults.on_block_start(bi)
            beat(f"block={bi}/{len(blocks)} {label} start")
            bp, out_q, row, ranks_b = _quantize_block(
                apply_fn, bp_fp, Xq_b, Y, ctx_b, stats, bref, label,
                qcfg, kb, faults, bi, log)
            report["ranks"].update(ranks_b)
            if journal is not None:
                extra = journal.save_block(bi, label, bp)
                if faults is not None:
                    faults.after_block_save(bi)
                journal.append_block({"bi": bi, "block": label,
                                      "ranks": ranks_b, "row": row,
                                      **extra})
                if faults is not None:
                    faults.on_journal_append(bi, journal)
            beat(f"block={bi}/{len(blocks)} {label} done "
                 f"err={row['block_err']:.5f}")
        quantized[(bref.stack, bref.idx)] = bp

        # advance streams
        if bref.stack != "shared_attn":
            Xq = out_q
            Xfp = Y
            if hybrid_boundary(bref.idx):
                Xq = applies["attn"](quantized[("shared_attn", None)], Xq, ctx)
                Xfp = applies["attn"](params["shared_attn"], Xfp, ctx)
        report["blocks"].append(row)
        log(f"[nanoquant] {label} err={row['block_err']:.5f}"
            + (f" init={row['init_method']}" if row["fallbacks"] else ""))

    qparams = _assemble(params, cfg, quantized)

    # ---- Phase 3: scale-only model reconstruction (KD) --------------------
    if not qcfg.skip_kd and qcfg.t_glob > 0:
        qparams, kd_losses = _tune_scales_kd(params, qparams, cfg,
                                             calib_batches, qcfg,
                                             heartbeat=heartbeat)
        report["kd_losses"] = kd_losses

    report["wall_s"] = time.time() - t0
    return qparams, report


def _assemble(params, cfg, quantized):
    out = dict(params)
    stacks: Dict[str, dict] = {}
    for (stack, idx), bp in quantized.items():
        stacks.setdefault(stack, {})[idx] = bp
    for stack, items in stacks.items():
        if None in items:                       # unstacked (shared_attn)
            out[stack] = items[None]
        elif isinstance(next(iter(items)), tuple):   # (g, i) — vlm self
            gs = sorted({g for g, _ in items})
            per = sorted({i for _, i in items})
            out[stack] = util.tree_stack(
                [util.tree_stack([items[(g, i)] for i in per]) for g in gs])
        else:
            out[stack] = util.tree_stack(
                [items[i] for i in sorted(items)])
    return out


def _kd_loss_chunked(hS, hT, params_s, params_t, cfg, temp):
    wS = T._head_w(params_s, cfg)
    wT = T._head_w(params_t, cfg)
    S = hS.shape[1]
    chunk = min(cfg.loss_chunk or S, S)
    if S % chunk:
        chunk = S
    nc = S // chunk

    def body(carry, inp):
        hs, ht = inp
        zS = (hs @ wS.astype(hs.dtype)).astype(jnp.float32) / temp
        zT = (ht @ wT.astype(ht.dtype)).astype(jnp.float32) / temp
        pT = jax.nn.softmax(zT, -1)
        kl = jnp.sum(pT * (jax.nn.log_softmax(zT, -1)
                           - jax.nn.log_softmax(zS, -1)), -1)
        return carry + kl.sum(), None

    hSc = hS.reshape(hS.shape[0], nc, chunk, -1).swapaxes(0, 1)
    hTc = hT.reshape(hT.shape[0], nc, chunk, -1).swapaxes(0, 1)
    tot, _ = jax.lax.scan(body, jnp.zeros(()), (hSc, hTc))
    return tot / (hS.shape[0] * S)


def _tune_scales_kd(teacher, qparams, cfg, calib_batches, qcfg: QuantConfig,
                    heartbeat=None):
    """Phase 3 (Eq. 11): packed binaries frozen, optimize only {s1,s2}."""
    trainable, frozen = util.partition(qparams, _is_scale_path)
    opt = AdamW(cosine_schedule(qcfg.lr_glob, qcfg.t_glob), clip_norm=1.0)
    state = opt.init(trainable)

    # the teacher and the frozen packed leaves are arguments: a
    # closed-over array would be embedded in the program text (GBs at
    # published widths)
    def loss(tr, fr, tp, batch):
        qp = util.combine(tr, fr)
        hS = T.backbone(qp, cfg, batch["tokens"], batch.get("image_embeds"))
        hT = T.backbone(tp, cfg, batch["tokens"], batch.get("image_embeds"))
        return _kd_loss_chunked(hS, hT, qp, tp, cfg, qcfg.kd_temp)

    vg = jax.jit(jax.value_and_grad(loss))
    losses = []
    for s in range(qcfg.t_glob):
        b = calib_batches[s % len(calib_batches)]
        lval, grads = vg(trainable, frozen, teacher, b)
        trainable, state, _ = opt.update(grads, state, trainable)
        losses.append(float(lval))
        if heartbeat is not None and (s % 10 == 0 or s == qcfg.t_glob - 1):
            heartbeat(f"kd step={s + 1}/{qcfg.t_glob} loss={losses[-1]:.5f}")
    return util.combine(trainable, frozen), losses


# public name (repro.api): run Phase 3 standalone with its own data
# budget (paper Table 9 block-vs-model reconstruction splits)
tune_scales_kd = _tune_scales_kd

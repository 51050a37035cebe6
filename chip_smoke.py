"""Bring-up check on a TPU: NanoQuant's main path, quantize then serve,
for llama3.2-1b at its published widths (16 layers, d_model 2048,
d_ff 8192, 32 heads over 8 KV heads, vocab 128256).

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # tensor-parallel serving on 4 chips

One chip: a random-init teacher (seeded) is quantized through
``repro.api.NanoQuantModel.quantize`` with small budgets, then a few
requests are served through ``model.engine(...)`` on the paged pool
with the Pallas kernels compiled for the chip. The same artifact and
prompts are served again with the pure-jnp reference kernels, and a
teacher-forced replay of the decode step compares the two paths'
logits. The run fails unless every request ends ``done``, the decode
step's compiled HLO holds the Pallas kernels, and the Pallas logits
agree with the reference within ``LOGIT_TOL``.

``--tp N`` runs only the tensor-parallel phase: the quantized artifact
is served over ``make_serving_mesh(N)`` and again on one chip, in f32
so that partitioned reductions cannot flip a near-tied argmax, and the
greedy tokens must be identical. The weights and the KV pool must be
spread over the N devices.

The script refuses to run unless JAX's first device is a TPU, and it
drives the chip from this one process. Seconds and bytes it prints are
a bring-up record, not benchmark numbers. Its last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama3.2-1b"
# Pallas vs reference logits, bf16 activations through 16 layers: the
# max-abs difference over every replayed position, relative to the
# reference's max |logit|. A misplaced head, tile or bit plane moves
# logits by their own scale; bf16 rounding moves them by a few percent
# at most.
LOGIT_TOL = 0.05
PROMPT_LENS = (12, 20, 7, 29)
MAX_NEW = 16
PAGE_SIZE = 16
SEED = 0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def quantize(cfg):
    """Random-init teacher (as ``launch/serve.py`` builds one, no
    Trainer) quantized with small budgets. Returns (model, seconds)."""
    import jax
    from repro import api
    from repro.data import calib_batches
    from repro.models import transformer as T

    params = jax.jit(lambda k: T.init_params(k, cfg))(
        jax.random.PRNGKey(SEED))
    calib = calib_batches(cfg, n_samples=4, seq=32, batch=2, seed=SEED)
    qcfg = api.QuantConfig(admm_iters=3, t_pre=2, t_post=2, t_glob=2,
                           rank_align=32, seed=SEED)
    t0 = time.perf_counter()
    model = api.NanoQuantModel.quantize(
        params, cfg, calib, qcfg, verbose=False,
        heartbeat=lambda m: log(f"quantize {m} at "
                                f"{time.perf_counter() - t0:.1f} s"))
    jax.block_until_ready(model.params)
    return model, time.perf_counter() - t0


def prompts_for(cfg):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def serve(model, policy, prompts, mesh=None):
    """Serve `prompts` greedily under `policy`; fails unless every
    request ends ``done``. Returns (engine, outputs, seconds)."""
    from repro import api
    from repro.kernels import ops as kops

    scfg = api.ServeConfig(greedy=True, max_new_tokens=MAX_NEW,
                           page_size=PAGE_SIZE)
    t0 = time.perf_counter()
    with kops.kernel_policy(policy):
        eng = model.engine(scfg, max_batch=len(prompts),
                           max_len=max(PROMPT_LENS) + MAX_NEW, mesh=mesh)
        handles = [eng.submit(api.Request(uid, p, max_new_tokens=MAX_NEW))
                   for uid, p in enumerate(prompts)]
        eng.run()
    dt = time.perf_counter() - t0
    bad = {h.uid: (h.status, str(h.error)) for h in handles if not h.done}
    if bad:
        raise SystemExit(f"[chip_smoke] requests did not end done: {bad}")
    return eng, [h.result() for h in handles], dt


def replay_logits(model, policy, seqs):
    """Teacher-forced decode over `seqs` (B, L) through
    ``transformer.decode_step`` on a paged pool, the step the engine
    runs. Returns (logits (L, B, V) f32 on the host, compile seconds,
    compiled HLO text)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops
    from repro.models import transformer as T
    from repro.quant.surgery import merge_projection_groups
    from repro.serve import paging

    cfg = model.cfg
    B, L = seqs.shape
    pages = -(-L // PAGE_SIZE)
    tables = {"linear": jnp.asarray(
        1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages))}
    with kops.kernel_policy(policy):
        params = (merge_projection_groups(model.params)
                  if policy.use_merged_projections() else model.params)
        cache = paging.init_paged_cache(cfg, B, pages * PAGE_SIZE,
                                        B * pages + 1, PAGE_SIZE)
        step = jax.jit(lambda p, tok, c, pos: T.decode_step(
            p, cfg, tok, c, pos, block_tables=tables), donate_argnums=(2,))
        tok0 = jnp.asarray(seqs[:, :1])
        pos0 = jnp.zeros((B,), jnp.int32)
        t0 = time.perf_counter()
        compiled = step.lower(params, tok0, cache, pos0).compile()
        compile_s = time.perf_counter() - t0
    out = []
    for t in range(L):
        logits, cache = compiled(params, jnp.asarray(seqs[:, t:t + 1]),
                                 cache, jnp.full((B,), t, jnp.int32))
        out.append(np.asarray(logits[:, 0].astype(jnp.float32)))
    return np.stack(out), compile_s, compiled.as_text()


def one_chip(cfg, pallas) -> None:
    """Quantize, serve with Pallas and with the reference kernels, and
    compare. Raises SystemExit on any failed check."""
    import jax
    import numpy as np
    from repro.kernels import ops as kops
    from repro.roofline.hlo import pallas_kernels

    model, quant_s = quantize(cfg)
    log(f"quantize: {quant_s:.1f} s ({cfg.n_layers} layers, "
        f"{len(model.ranks)} ranked linears)")
    prompts = prompts_for(cfg)
    eng, outs, serve_s = serve(model, pallas, prompts)
    n_tok = sum(len(o) for o in outs)
    log(f"served {len(outs)} requests, {n_tok} tokens in {serve_s:.1f} s "
        f"(compile included), all done")
    _, ref_outs, _ = serve(model, kops.KernelPolicy(mode="ref"), prompts)
    same = sum(int(np.sum(a == b)) for a, b in zip(outs, ref_outs))
    log(f"greedy tokens, pallas engine vs ref engine: {same}/{n_tok} agree")

    lens = [len(p) + len(o) - 1 for p, o in zip(prompts, outs)]
    seqs = np.zeros((len(prompts), max(lens)), np.int32)
    for b, (p, o) in enumerate(zip(prompts, outs)):
        seqs[b, :lens[b]] = np.concatenate([p, o])[:lens[b]]
    lg_p, comp_p, hlo = replay_logits(model, pallas, seqs)
    lg_r, comp_r, _ = replay_logits(model, kops.KernelPolicy(mode="ref"),
                                    seqs)
    log(f"decode-step compile: pallas {comp_p:.1f} s, ref {comp_r:.1f} s")
    counts = pallas_kernels(hlo)
    log(f"decode-step Pallas custom calls by kernel: {counts}")
    if not pallas.interpret and not counts:
        raise SystemExit("[chip_smoke] decode step holds no Pallas kernel")
    valid = np.arange(seqs.shape[1])[:, None] < np.asarray(lens)[None]
    diff = float(np.max(np.abs(lg_p - lg_r)[valid]))
    scale = float(np.max(np.abs(lg_r)[valid]))
    agree = float(np.mean((lg_p.argmax(-1) == lg_r.argmax(-1))[valid]))
    log(f"logits pallas vs ref: max-abs diff {diff:.4g}, ref max |logit| "
        f"{scale:.4g}, ratio {diff / scale:.4g} (tolerance {LOGIT_TOL}); "
        f"teacher-forced argmax agreement {agree:.4f}")
    if not diff <= LOGIT_TOL * scale:
        raise SystemExit("[chip_smoke] Pallas logits outside tolerance")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")


def _bytes_by_device(tree) -> dict:
    import jax
    per = Counter()
    for leaf in jax.tree.leaves(tree):
        for sh in getattr(leaf, "addressable_shards", []):
            per[sh.device.id] += sh.data.nbytes
    return dict(sorted(per.items()))


def tensor_parallel(cfg, pallas, n: int) -> None:
    """Serve the quantized artifact over an n-device mesh and on one
    device, in f32; greedy tokens must be identical, and weights and
    KV pool must be spread over the mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.launch.mesh import make_serving_mesh

    if len(jax.devices()) < n:
        raise SystemExit(f"[chip_smoke] --tp {n} needs {n} devices, "
                         f"found {len(jax.devices())}")
    # set-up for this comparison; the budgets of the one-chip phase, so
    # that a warm compilation cache serves its programs
    model, quant_s = quantize(cfg)
    log(f"quantize (set-up): {quant_s:.1f} s")
    f32 = api.NanoQuantModel(
        jax.tree.map(lambda a: a.astype(jnp.float32)
                     if jnp.issubdtype(a.dtype, jnp.floating) else a,
                     model.params),
        dataclasses.replace(model.cfg, dtype="float32"), model.qcfg,
        model.report)
    prompts = prompts_for(cfg)
    # f32 matmuls in f32: XLA's default TPU precision rounds their
    # inputs to bf16, and that noise could flip a near-tied argmax
    with jax.default_matmul_precision("highest"):
        _, one, one_s = serve(f32, pallas, prompts)
        eng, tp, tp_s = serve(f32, pallas, prompts,
                              mesh=make_serving_mesh(n))
    log(f"one device: {sum(map(len, one))} tokens in {one_s:.1f} s")
    log(f"{n} devices: {sum(map(len, tp))} tokens in {tp_s:.1f} s")
    packed = [a for a in jax.tree.leaves(eng.params)
              if a.dtype == jnp.uint32]
    split = [a for a in packed if not a.sharding.is_fully_replicated]
    kv = jax.tree.leaves(eng.cache)
    log(f"param bytes by device: {_bytes_by_device(eng.params)}")
    log(f"packed-weight bytes by device: {_bytes_by_device(packed)}; "
        f"{len(split)} of {len(packed)} packed leaves sharded")
    log(f"KV-pool bytes by device: {_bytes_by_device(kv)}")
    # Megatron pairing shards one of each linear's two packed factors
    # (U for column-, V for row-parallel) and the pool's KV heads.
    if len(_bytes_by_device(packed)) != n or 2 * len(split) < len(packed):
        raise SystemExit("[chip_smoke] packed weights are not sharded")
    if len(_bytes_by_device(kv)) != n \
            or any(a.sharding.is_fully_replicated for a in kv):
        raise SystemExit("[chip_smoke] KV pool is not sharded")
    for uid, (a, b) in enumerate(zip(one, tp)):
        if not np.array_equal(a, b):
            raise SystemExit(f"[chip_smoke] request {uid}: tokens differ "
                             f"between 1 and {n} devices:\n{a}\n{b}")
    log(f"greedy tokens identical on 1 and {n} devices (f32)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase on this many "
                         "chips")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2

    from repro import api
    from repro.kernels import ops as kops
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}")
    cfg = api.get_config(ARCH)
    pallas = kops.KernelPolicy(mode="pallas", interpret=False)
    if args.tp > 1:
        tensor_parallel(cfg, pallas, args.tp)
    else:
        one_chip(cfg, pallas)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

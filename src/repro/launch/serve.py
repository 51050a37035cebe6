"""Serving driver: batched generation from a (quantized) model artifact.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --quantized-ckpt /tmp/nq --requests 16 --max-new 32

A ``--quantized-ckpt`` directory written by ``launch/quantize.py`` (a
``NanoQuantModel`` artifact) is self-describing: the manifest carries the
model config, so ``--arch`` is only needed for the fresh-quantize demo
path. ``--engine continuous`` (default) serves through the
slot-scheduled ``InferenceEngine``; ``--engine wave`` reproduces the
legacy drain-then-refill schedule for comparison. ``--tp N`` serves
tensor-parallel over a ``(data=1, model=N)`` mesh (see docs/serving.md).

Crash-restart serving (docs/serving.md §Failure handling):
``--supervise`` re-execs this driver under ``launch/supervisor.py``
with hang detection keyed to the per-tick ``[serve] heartbeat`` lines
(emitted from the serving loop itself, so a wedged device call stops
them and gets the process killed + restarted), and ``--snapshot PATH``
persists the host-side resume state every ``--snapshot-every`` ticks —
a restarted process resumes the interrupted requests token-identically
under greedy. ``--crash-at-step N`` force-crashes for testing the loop.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

from repro import api
from repro.data import calib_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve import recovery


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=api.list_archs())
    ap.add_argument("--quantized-ckpt", default="",
                    help="NanoQuantModel artifact from launch/quantize.py; "
                         "if empty, quantizes a fresh random-init teacher")
    ap.add_argument("--fp", action="store_true",
                    help="serve the FP teacher instead (baseline)")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "wave"],
                    help="slot admission policy (wave = legacy "
                         "BatchServer schedule)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--rect", action="store_true",
                    help="serve on the legacy rectangular KV pool "
                         "instead of the paged pool (the identity "
                         "oracle; see docs/serving.md)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="KV rows per page of the paged pool")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="total pool pages; 0 = full capacity, smaller "
                         "overcommits (admission queues on free pages)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: serve over a "
                         "(data=1, model=N) mesh (needs >= N devices; "
                         "on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--spec-rank-frac", type=float, default=0.0,
                    help="enable self-speculative decoding: draft "
                         "through a rank-truncated view at this rank "
                         "fraction, verify full-rank (forces greedy "
                         "sampling; requires the paged pool, so "
                         "incompatible with --rect)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per speculative cycle")
    ap.add_argument("--no-prefix", action="store_true",
                    help="disable the prefix cache (shared prompt-"
                         "prefix KV pages; on by default for paged "
                         "linear-table families)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt "
                         "tokens to every request (demo of prefix-"
                         "cache page sharing)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline in seconds (0 = none); "
                         "requests past it finish 'expired'")
    ap.add_argument("--snapshot", default="",
                    help="resume-state file: loaded on start if it "
                         "exists (crash recovery), refreshed every "
                         "--snapshot-every ticks, removed on a clean "
                         "finish")
    ap.add_argument("--snapshot-every", type=int, default=32,
                    help="ticks between snapshot refreshes")
    ap.add_argument("--heartbeat-every", type=int, default=16,
                    help="ticks between '[serve] heartbeat' lines "
                         "(what --supervise hang detection watches; "
                         "0 disables)")
    ap.add_argument("--crash-at-step", type=int, default=0,
                    help="testing: snapshot then exit(7) at this tick "
                         "(fresh runs only — a snapshot-resumed "
                         "incarnation runs to completion)")
    ap.add_argument("--supervise", action="store_true",
                    help="run under launch/supervisor.py: restart on "
                         "crash, kill+restart on missing heartbeats, "
                         "resume from --snapshot")
    ap.add_argument("--hang-timeout", type=float, default=60.0,
                    help="--supervise: seconds without a heartbeat "
                         "before the child is declared hung")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="--supervise: restart budget")
    args = ap.parse_args()

    if args.supervise:
        from repro.launch import supervisor
        if not args.snapshot:
            ap.error("--supervise needs --snapshot to resume across "
                     "restarts")
        cmd = [sys.executable, "-m", "repro.launch.serve"] \
            + [a for a in sys.argv[1:] if a != "--supervise"]
        raise SystemExit(supervisor.supervise(
            cmd, max_restarts=args.max_restarts,
            hang_timeout=args.hang_timeout,
            heartbeat_pattern=r"\[serve\] heartbeat"))

    enable_compile_cache()
    if args.quantized_ckpt and not args.fp:
        model = api.NanoQuantModel.load(args.quantized_ckpt)
        print(f"[serve] loaded artifact {args.quantized_ckpt} "
              f"(arch={model.cfg.name}, "
              f"bpw={model.qcfg.target_bpw if model.quantized else 16})")
    else:
        cfg = api.get_smoke(args.arch)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        model = api.NanoQuantModel.from_fp(params, cfg)
        if not args.fp:
            calib = calib_batches(cfg, 8, 64)
            qcfg = api.QuantConfig(admm_iters=10, t_pre=5, t_post=5,
                                   t_glob=5, rank_align=32)
            model = api.NanoQuantModel.quantize(params, cfg, calib, qcfg,
                                                verbose=False)
            print("[serve] quantized random-init teacher (demo)")

    cfg = model.cfg
    spec = args.spec_rank_frac or None
    if spec and args.rect:
        ap.error("--spec-rank-frac needs the paged KV pool; drop --rect")
    scfg = api.ServeConfig(max_new_tokens=args.max_new,
                           paged=not args.rect,
                           page_size=args.page_size,
                           kv_pool_pages=args.kv_pool_pages or None,
                           greedy=bool(spec),
                           spec_rank_frac=spec,
                           spec_k=args.spec_k,
                           prefix_cache=not args.no_prefix)
    if spec:
        print(f"[serve] speculative decode: rank_frac={spec} "
              f"k<={args.spec_k} (greedy sampling forced)")
    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(args.tp)
        print(f"[serve] tensor-parallel over {args.tp} devices "
              f"(mesh axes {mesh.axis_names}, shape {dict(mesh.shape)})")
    eng = model.engine(scfg, max_batch=args.max_batch,
                       max_len=(args.shared_prefix + args.prompt_len
                                + args.max_new),
                       admission=args.engine, mesh=mesh)
    rng = np.random.default_rng(0)
    shape = ((args.prompt_len, cfg.n_codebooks)
             if cfg.family == "audio" else (args.prompt_len,))
    sys_prompt = None
    if args.shared_prefix:
        if cfg.family == "audio":
            ap.error("--shared-prefix does not support audio prompts")
        sys_prompt = rng.integers(0, cfg.vocab_size,
                                  size=args.shared_prefix).astype(np.int32)
    t0 = time.time()
    resumed = args.snapshot and os.path.exists(args.snapshot)
    if resumed:
        snap = recovery.load_snapshot(args.snapshot)
        handles = list(recovery.restore(eng, snap).values())
        print(f"[serve] resumed {len(handles)} in-flight requests from "
              f"{args.snapshot}")
    else:
        handles = []
        for uid in range(args.requests):
            prompt = rng.integers(
                0, cfg.vocab_size, size=shape).astype(np.int32)
            if sys_prompt is not None:
                prompt = np.concatenate([sys_prompt, prompt])
            handles.append(eng.submit(api.Request(
                uid, prompt, max_new_tokens=args.max_new,
                deadline_s=args.deadline or None)))
    # manual step loop (not eng.run()): the heartbeat must come from
    # inside the serving loop — a thread would keep beating while a
    # device call is wedged, which is exactly the hang the supervisor
    # exists to catch
    while eng.in_flight:
        eng.step()
        tick = eng.stats["steps"]
        if args.heartbeat_every and tick % args.heartbeat_every == 0:
            print(f"[serve] heartbeat step={tick} "
                  f"active={int(eng.active.sum())} "
                  f"queued={len(eng.scheduler.pending)}", flush=True)
        if args.snapshot and args.snapshot_every \
                and tick % args.snapshot_every == 0 and eng.in_flight:
            recovery.save_snapshot(eng, args.snapshot)
        if args.crash_at_step and not resumed \
                and tick >= args.crash_at_step:
            if args.snapshot:
                recovery.save_snapshot(eng, args.snapshot)
            print(f"[serve] injected crash at step {tick}", flush=True)
            sys.exit(7)
    done = dict(eng.done)
    if args.snapshot and os.path.exists(args.snapshot):
        os.unlink(args.snapshot)           # clean finish: nothing to resume
    dt = time.time() - t0
    n_tok = sum(len(r.output) for r in done.values())
    n_term = {s: eng.stats[s] for s in ("cancelled", "expired", "failed")
              if eng.stats[s]}
    print(f"[serve] engine={args.engine}: {len(done)} requests, "
          f"{n_tok} tokens in {dt:.2f}s ({n_tok/dt:.1f} tok/s incl. "
          f"compile)" + (f", non-done terminals {n_term}" if n_term
                         else ""))
    lats = np.asarray(sorted(h.latency for h in handles if h.done))
    if lats.size:
        print(f"[serve] request latency: mean {lats.mean():.2f}s  "
              f"p50 {np.percentile(lats, 50):.2f}s  "
              f"p95 {np.percentile(lats, 95):.2f}s")
    print(f"[serve] decode steps {eng.stats['decode_steps']}, wasted "
          f"slot-steps {eng.stats['wasted_slot_steps']}, prefill "
          f"compilations {eng.stats['prefill_traces']}")
    _print_pool_stats(eng)
    if eng.spec is not None:
        st = eng.stats
        print(f"[serve] speculative: {st['spec_cycles']} cycles, "
              f"acceptance {eng.spec.acceptance_rate():.2f} "
              f"({st['spec_accepted_tokens']}/{st['spec_draft_tokens']} "
              f"draft tokens), {st['spec_rollback_tokens']} rolled "
              f"back ({st['spec_rollback_pages']} pages trimmed), "
              f"final k={eng.spec.k}")
    if done:
        first = min(done)
        print(f"[serve] sample output for request {first}: "
              f"{done[first].output[:16]}")
    if eng.stats["failed"]:
        # a failed request may be a kernel that did not compile on this
        # device (the engine isolates any prefill exception); never
        # report that run as a success
        sys.exit(f"[serve] {eng.stats['failed']} request(s) failed")


def _print_pool_stats(eng) -> None:
    """KV-pool line for either cache layout. Keys off ``eng.kv`` — the
    engine serves a rectangular layout both under ``--rect`` and for
    families with no pageable cache (pure SSM state), and neither has a
    ``PagedKVState`` to report on."""
    if eng.kv is None:
        print(f"[serve] rectangular layout (paging disabled): "
              f"max_batch x max_len KV rectangle, "
              f"{eng.kv_cache_bytes()/2**20:.2f} MiB")
        return
    print(f"[serve] paged KV pool: {eng.kv.n_pages} pages x "
          f"{eng.kv.page_size} rows ({eng.kv_cache_bytes()/2**20:.2f} "
          f"MiB), peak {eng.kv.peak_used_pages} pages in use, "
          f"{eng.stats['page_waits']} page waits, "
          f"{eng.stats['preemptions']} preemptions")
    if eng.prefix is not None:
        st = eng.stats
        rate = (st["prefix_hit_tokens"] / st["prefix_lookup_tokens"]
                if st["prefix_lookup_tokens"] else 0.0)
        print(f"[serve] prefix cache: hit rate {rate:.2f} "
              f"({st['prefix_hit_tokens']}/{st['prefix_lookup_tokens']} "
              f"prompt tokens served from shared pages), peak "
              f"{st['shared_pages']} shared pages, "
              f"{st['cow_copies']} COW copies, "
              f"{st['evicted_pages']} cached pages evicted")


if __name__ == "__main__":
    main()

"""Quantization driver: train-or-load an FP teacher, run the NanoQuant
pipeline through the ``repro.api`` facade, save the packed artifact, and
report sizes + perplexities.

    PYTHONPATH=src python -m repro.launch.quantize --arch llama3.2-1b \
        --bpw 1.0 --teacher-steps 150 --out /tmp/nq

Fault tolerance (docs/quantization.md): ``--journal-dir`` makes the run
crash-safe (per-block journaling through ``checkpoint.journal``);
``--resume`` picks up a killed run from its journal and produces a
bit-identical artifact. ``--supervise`` re-execs this driver under
``launch/supervisor.py`` with restart-on-crash and hang detection keyed
to the per-block ``[quant] heartbeat`` lines; restarted children get
``--resume`` appended automatically. ``--crash-at-block N`` injects one
deterministic crash (first attempt only) for drilling the loop.

(Smoke-scale by default: this box is CPU-only. On real hardware the same
driver quantizes the full config from a teacher checkpoint.)
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro import api
from repro.data import SyntheticCorpus, calib_batches, train_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.train import TrainConfig, Trainer

HEARTBEAT_RE = r"\[quant\] heartbeat"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=api.list_archs())
    ap.add_argument("--full", action="store_true",
                    help="full published config (needs real hardware)")
    ap.add_argument("--bpw", type=float, default=1.0)
    ap.add_argument("--init-method", default="lb_admm",
                    choices=api.list_init_methods())
    ap.add_argument("--fallback-inits", default="dbf_admm,dual_svid",
                    help="comma-separated init-method ladder tried when "
                         "a block diverges ('' disables fallbacks)")
    ap.add_argument("--teacher-steps", type=int, default=150)
    ap.add_argument("--calib-samples", type=int, default=16)
    ap.add_argument("--calib-seq", type=int, default=128)
    ap.add_argument("--out", default="")
    ap.add_argument("--teacher-ckpt", default="")
    ap.add_argument("--rank-align", type=int, default=32)
    # pipeline budget knobs (CI smoke uses tiny values)
    ap.add_argument("--admm-iters", type=int, default=40)
    ap.add_argument("--t-pre", type=int, default=40)
    ap.add_argument("--t-post", type=int, default=60)
    ap.add_argument("--t-glob", type=int, default=60)
    # fault tolerance (docs/quantization.md)
    ap.add_argument("--journal-dir", default="",
                    help="per-block progress journal dir (enables "
                         "--resume and supervised restarts)")
    ap.add_argument("--resume", action="store_true",
                    help="resume a killed run from --journal-dir "
                         "(bit-identical artifact)")
    ap.add_argument("--no-preflight", action="store_true",
                    help="skip input validation (calib/params/memory)")
    ap.add_argument("--heartbeat", action="store_true",
                    help="print '[quant] heartbeat ...' per block (what "
                         "--supervise hang detection watches)")
    ap.add_argument("--supervise", action="store_true",
                    help="run under launch/supervisor.py: restart on "
                         "crash, kill+restart on missing heartbeats, "
                         "children resume from --journal-dir")
    ap.add_argument("--hang-timeout", type=float, default=600.0,
                    help="--supervise: seconds without a heartbeat "
                         "before the child is declared hung")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="--supervise: restart budget")
    ap.add_argument("--crash-at-block", type=int, default=-1,
                    help="inject one crash when this block starts, "
                         "first attempt only (restart drill)")
    args = ap.parse_args()

    if args.supervise:
        from repro.launch import supervisor
        if not args.journal_dir:
            ap.error("--supervise needs --journal-dir so restarted "
                     "children can resume instead of redoing everything")
        child = [a for a in sys.argv[1:] if a != "--supervise"]
        for extra in ("--resume", "--heartbeat"):
            if extra not in child:
                child.append(extra)
        raise SystemExit(supervisor.supervise(
            [sys.executable, "-m", "repro.launch.quantize"] + child,
            max_restarts=args.max_restarts,
            hang_timeout=args.hang_timeout,
            heartbeat_pattern=HEARTBEAT_RE))

    enable_compile_cache()
    cfg = api.get_config(args.arch) if args.full else api.get_smoke(args.arch)
    tcfg = TrainConfig(lr=1e-3, warmup=20, total_steps=args.teacher_steps)

    # ---- FP teacher --------------------------------------------------------
    if args.teacher_ckpt:
        mgr = api.CheckpointManager(args.teacher_ckpt)
        tr = Trainer(cfg, tcfg, train_iterator(cfg, 8, args.calib_seq), mgr)
        tr.restore_or_init()
        if tr.step < args.teacher_steps:
            tr.run(args.teacher_steps - tr.step)
        params = tr.state[0]
    else:
        tr = Trainer(cfg, tcfg, train_iterator(cfg, 8, args.calib_seq),
                     log_every=25)
        tr.restore_or_init()
        tr.run(args.teacher_steps)
        params = tr.state[0]

    corpus = SyntheticCorpus(cfg.vocab_size)
    calib = calib_batches(cfg, args.calib_samples, args.calib_seq,
                          corpus=corpus)
    evalb = calib_batches(cfg, 8, args.calib_seq, seed=99, corpus=corpus)

    # ---- preflight (fail fast, not at block 17) ----------------------------
    if not args.no_preflight:
        pf = api.preflight(params, cfg, calib)
        print(f"[quantize] preflight ok: {pf['n_batches']} batches, "
              f"{pf['n_calib_tokens']} calib tokens, "
              f"~{pf['est_block_bytes'] / 2**20:.0f} MiB/block", flush=True)

    ppl_fp = api.NanoQuantModel.from_fp(params, cfg).perplexity(evalb)

    # ---- fault injection drill --------------------------------------------
    faults = None
    if args.crash_at_block >= 0:
        # fire only on the first attempt (journal still empty) so a
        # supervised restart makes progress instead of re-crashing
        already = (api.QuantJournal(args.journal_dir).n_completed_blocks()
                   if args.journal_dir else 0)
        if already == 0:
            faults = api.QuantFaultPlan(
                [api.QuantFault(block=args.crash_at_block,
                                kind="crash_block")])

    heartbeat = None
    if args.heartbeat:
        def heartbeat(msg):
            print(f"[quant] heartbeat {msg}", flush=True)

    # ---- NanoQuant ---------------------------------------------------------
    qcfg = api.QuantConfig(target_bpw=args.bpw, rank_align=args.rank_align,
                           init_method=args.init_method,
                           fallback_inits=args.fallback_inits,
                           admm_iters=args.admm_iters, t_pre=args.t_pre,
                           t_post=args.t_post, t_glob=args.t_glob)
    model = api.NanoQuantModel.quantize(
        params, cfg, calib, qcfg,
        journal_dir=args.journal_dir or None, resume=args.resume,
        faults=faults, heartbeat=heartbeat)
    ppl_q = model.perplexity(evalb)

    sizes = model.size_report()
    print(f"\n[quantize] {cfg.name} target_bpw={args.bpw}")
    print(f"  FP teacher ppl   : {ppl_fp:.3f}")
    print(f"  NanoQuant ppl    : {ppl_q:.3f}")
    print(f"  linears bpw      : {sizes['linears_bpw']:.3f}")
    print(f"  wall time        : {model.report['wall_s']:.1f}s")
    if args.out:
        model.save(args.out)
        with open(os.path.join(args.out, "report.json"), "w") as f:
            json.dump({"ppl_fp": ppl_fp, "ppl_q": ppl_q,
                       "sizes": sizes,
                       "ranks": model.ranks,
                       "wall_s": model.report["wall_s"]}, f, indent=1)
        print(f"  saved to {args.out}")


if __name__ == "__main__":
    main()

"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real TPU slice, drop --smoke and pass --mesh single|multi to train
the full config under the production mesh; on this CPU box the smoke
configs train end-to-end (examples/quickstart.py drives this module).
Fault tolerance: run under launch/supervisor.py — any crash restarts the
process and training resumes from the latest atomic checkpoint with
deterministic data skip.
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import train_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.train import TrainConfig, Trainer, make_train_step


def build_trainer(args) -> Trainer:
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    tcfg = TrainConfig(lr=args.lr, warmup=args.warmup,
                       total_steps=args.steps,
                       grad_accum=args.grad_accum,
                       compress_grads=args.compress_grads, seed=args.seed)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    jit_step = None
    if args.mesh != "none":
        from repro.launch.cells import _ns
        from repro.launch.mesh import make_production_mesh
        from repro.sharding import rules
        from repro.configs import shapes as SH
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        params = SH.param_specs(cfg)
        pspecs = rules.param_pspecs(cfg, params, mesh)
        jit_step = jax.jit(
            make_train_step(cfg, tcfg),
            in_shardings=(_ns(mesh, pspecs), None, None, None),
            donate_argnums=(0, 1))

    # resume-aware deterministic iterator: peek the checkpoint step first
    start = 0
    if mgr is not None and mgr.latest_step() is not None:
        start = mgr.latest_step()
    it = train_iterator(cfg, batch=args.batch, seq=args.seq,
                        seed=args.seed, start_step=start)
    return Trainer(cfg, tcfg, it, mgr, ckpt_every=args.ckpt_every,
                   jit_step=jit_step, log_every=args.log_every)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single", "multi"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    tr = build_trainer(args)
    tr.restore_or_init()
    remaining = args.steps - tr.step
    if remaining <= 0:
        print(f"[train] already at step {tr.step} >= {args.steps}")
        return
    metrics = tr.run(remaining)
    print(f"[train] done at step {tr.step}: {metrics}")


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh)
cell and record memory / cost / collective analysis.

A CPU-only tool: it forces 512 placeholder host devices before JAX
starts. On a machine with a TPU, run it under ``JAX_PLATFORMS=cpu`` so
that it neither claims the chip nor competes with the process using it.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch qwen3-4b --shape train_4k --mesh both --out experiments/dryrun

Each cell writes ``<out>/<arch>__<shape>__<mesh>.json`` with the roofline
terms (§Roofline reads these), and the run prints a summary table. A cell
that fails to lower/compile is a bug in the distribution config — the
error is recorded and the run exits nonzero.
"""
import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.configs.shapes import SHAPES
from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import V5E, analyse_compiled, model_flops


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             quantized=None, policy=None, tag: str = "") -> dict:
    from repro.sharding import rules
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, quantized=quantized,
                      policy=policy or rules.DEFAULT)
    lowered = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    rec = analyse_compiled(compiled)
    n_chips = mesh.devices.size
    cellinfo = SHAPES[shape]
    tokens = cellinfo.global_batch * (
        cellinfo.seq_len if cell.mode in ("train", "prefill") else 1)
    mflops = model_flops(cell.cfg, tokens, cell.mode)

    if cell.quantized:
        # the lowered SPMD reference path unpacks packed weights to the
        # compute dtype in HBM; the Pallas TPU kernel streams the packed
        # bits and unpacks in VMEM. Report the kernel-true memory term
        # alongside the as-lowered one (§Roofline).
        from repro.quant.surgery import quantizable_paths
        from repro.configs.shapes import param_specs
        from repro.core.bpw import rank_for_bpw
        overhead = 0.0
        for _, v in quantizable_paths(param_specs(cell.cfg), cell.cfg):
            w = v["w"]
            *lead, d_in, d_out = w.shape
            n_mat = 1
            for s in lead:
                n_mat *= s
            r = rank_for_bpw(d_out, d_in, 1.0, 32)
            overhead += n_mat * (d_in * r + r * d_out) * (2.0 - 0.125)
        # packed weights shard over the model axis only — the unpack
        # overhead per chip divides by tp, not by all chips
        tp = mesh.shape.get("model", 1)
        mem_true = max(rec["hlo_bytes"] - overhead / tp, 0.0)
        rec["unpack_overhead_bytes_per_chip"] = overhead / tp
        rec["memory_s_kernel_true"] = mem_true / V5E.hbm_bw
    rec.update({
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "mode": cell.mode, "chips": int(n_chips),
        "quantized": cell.quantized, "grad_accum": cell.grad_accum,
        "model_flops_total": mflops,
        "model_flops_per_chip": mflops / n_chips,
        "useful_flops_ratio": (mflops / n_chips) / max(rec["hlo_flops"], 1.0),
        "lower_s": t_lower, "compile_s": t_compile,
    })
    mem = rec.get("memory_analysis", {})
    if mem:
        per_dev = (mem.get("argument_size_in_bytes", 0)
                   + mem.get("output_size_in_bytes", 0)
                   - mem.get("alias_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0))
        rec["hbm_used_bytes"] = int(per_dev)
        rec["fits_hbm"] = bool(per_dev <= V5E.hbm_bytes)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}__{shape}__{mesh_name}{tag}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def _fmt(rec: dict) -> str:
    gb = rec.get("hbm_used_bytes", 0) / 1e9
    return (f"{rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:16s} "
            f"flops/chip={rec['hlo_flops']:.3e} "
            f"mem={gb:6.2f}GB fit={str(rec.get('fits_hbm','?')):5s} "
            f"dom={rec['dominant']:10s} "
            f"frac={rec['roofline_fraction']:.3f} "
            f"compile={rec['compile_s']:.1f}s")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--fp-serve", action="store_true",
                    help="lower serving cells with FP16 params instead of "
                         "NanoQuant-packed")
    args = ap.parse_args()

    archs = configs.list_archs() if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        shape_list = (configs.shapes_for(arch) if args.shape == "all"
                      else [args.shape])
        for shape in shape_list:
            if shape not in configs.shapes_for(arch):
                print(f"skip {arch} x {shape} (see DESIGN.md §5)")
                continue
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape, mp, args.out,
                                   quantized=(False if args.fp_serve
                                              else None),
                                   tag="__fp" if args.fp_serve else "")
                    print(_fmt(rec), flush=True)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch, shape, mp, repr(e)))
    if failures:
        print("\nFAILED CELLS:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nall cells lowered + compiled OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

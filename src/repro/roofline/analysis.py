"""Three-term roofline from the compiled dry-run artifact.

    compute    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory     = HLO_bytes / HBM_bw               (per chip)
    collective = Σ per-op wire bytes / link_bw    (per chip)

``compiled.cost_analysis()`` on an SPMD-partitioned module reports the
*per-device* program, so every term here is per-chip directly (the
prompt's global-quantity formulas divided by `chips` — identical since
the partitioner splits work evenly). Collective bytes are not in
cost_analysis: we parse the optimized HLO and sum operand bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, scaled by a ring-model wire factor.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12          # bf16 per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    link_bw: float = 50e9               # bytes/s per ICI link
    hbm_bytes: float = 16e9             # v5e HBM capacity


V5E = HW()

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# ring-model wire factor per element of *operand* data
_WIRE_FACTOR = {
    "all-reduce": 2.0,          # reduce-scatter + all-gather
    "all-gather": 1.0,          # each shard traverses the ring once
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Per-category operand bytes (per-device shard sizes in post-SPMD
    HLO) + wire-model bytes. '-start' fused ops are counted once."""
    out: Dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        rhs = s.split("=", 1)[1]
        for c in _COLLECTIVES:
            # match opcode use, not variable names: "<opcode>(" or
            # "<opcode>-start("
            m = re.search(rf"\b{c}(?:-start)?\(", rhs)
            if not m:
                continue
            operands = rhs[m.end():]
            depth = 1
            for i, ch in enumerate(operands):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        operands = operands[:i]
                        break
            nbytes = sum(_shape_bytes(d, dims)
                         for d, dims in _SHAPE_RE.findall(operands))
            out[c] += nbytes
            counts[c] += 1
            break
    wire = sum(_WIRE_FACTOR[c] * b for c, b in out.items())
    return {"per_op_bytes": out, "counts": counts,
            "total_operand_bytes": sum(out.values()),
            "wire_bytes": wire}


def model_flops(cfg: ModelConfig, tokens: int, mode: str) -> float:
    """Analytic "useful" FLOPs: 6·N_active·D train, 2·N_active·D inference
    (N_active excludes embedding tables; MoE counts routed-active experts
    only)."""
    n_total = cfg.param_count()
    if cfg.family == "audio":
        emb = cfg.n_codebooks * cfg.vocab_size * cfg.d_model
    else:
        emb = cfg.vocab_size * cfg.d_model
    # pure-lookup embedding tables do no matmul FLOPs; a tied table *is*
    # the head matmul, so it stays counted.
    n_active = n_total - (0 if cfg.tie_embeddings else emb)
    if cfg.n_experts and cfg.n_experts_per_tok:
        n_moe_layers = cfg.n_layers - cfg.first_k_dense
        per_expert = 3 * cfg.d_model * cfg.moe_d_ff
        inactive = (cfg.n_experts - cfg.n_experts_per_tok) * per_expert
        n_active -= n_moe_layers * inactive
    factor = 6.0 if mode == "train" else 2.0
    return factor * n_active * tokens


def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float,
                   hw: HW = V5E) -> Dict[str, float]:
    t_c = flops / hw.peak_flops
    t_m = hbm_bytes / hw.hbm_bw
    t_x = wire_bytes / hw.link_bw
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    bound = max(t_c, t_m, t_x)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom[0], "bound_s": bound,
        "roofline_fraction": (t_c / bound) if bound > 0 else 0.0,
    }


def analyse_compiled(compiled, lowered_text: Optional[str] = None,
                     hw: HW = V5E) -> Dict[str, Any]:
    """Full per-chip analysis of one compiled cell.

    Primary source is the loop-aware HLO walk (roofline.hlo) — XLA's own
    cost_analysis counts while bodies once, which undercounts every
    lax.scan model by ~n_layers; the xla_* fields keep the raw numbers
    for comparison."""
    from repro.roofline.hlo import module_cost
    cost = compiled.cost_analysis()
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    text = lowered_text if lowered_text is not None else compiled.as_text()
    mc = module_cost(text)
    flops = mc["flops"]
    hbm = mc["hbm_bytes"]
    coll = mc["collectives"]
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception:
        pass
    terms = roofline_terms(flops, hbm, coll["wire_bytes"], hw)
    return {
        "hlo_flops": flops,
        "hlo_bytes": hbm,
        "xla_flops_bodies_once": xla_flops,
        "xla_bytes_bodies_once": xla_bytes,
        "collectives": coll,
        "memory_analysis": mem,
        **terms,
    }

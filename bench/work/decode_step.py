"""Work of one decode step of a dense NanoQuant model, from its shapes.

``linears`` reads each packed linear's (K, N, r) from the weight shapes
the program serves. Per layer, q/k/v share one input and gate/up
another, so the step reads four distinct inputs a layer.

Which kernel runs a linear, and what share of it each chip holds, is the
program's routing, read from it: q/k/v and gate/up each launch as one
grouped fused call at the group's widest rank (``quant.surgery``'s
merge groups), a launch past the fused kernel's rank limit
(``binary_matmul.MAX_FUSED_RANK``) runs two packed-matmul calls alone or
the XLA oracle as a group, and over `tp` chips a column-parallel linear
keeps 1/tp of its outputs on each chip and a row-parallel one 1/tp of
its inputs (``sharding.rules``), where the size divides.
"""
from repro.kernels.binary_matmul import MAX_FUSED_RANK
from repro.quant.surgery import MERGE_GROUPS
from repro.sharding.rules import tp_role

from bench.lib import spec

_fused = spec.load_module("work", "nq_fused_lowrank_matmul")
_packed = spec.load_module("work", "nq_packed_matmul")
_paged = spec.load_module("work", "nq_paged_attention")

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
SHARES_INPUT = {"wk", "wv", "w_up"}


def launch(lin: dict) -> dict:
    """{name: 'fused' | 'packed' | 'oracle'}: what runs each linear."""
    out = {}
    for n, (_, _, r) in lin.items():
        group = next((g for g, _ in MERGE_GROUPS
                      if n in g and set(g) <= set(lin)), None)
        rank = max(lin[m][2] for m in group) if group else r
        out[n] = "fused" if rank <= MAX_FUSED_RANK else \
            ("oracle" if group else "packed")
    return out


def per_chip(lin: dict, tp: int) -> dict:
    """{name: (K, N, r)} of the share of each linear one chip computes."""
    out = {}
    for n, (K, N, r) in lin.items():
        if tp_role(n) == "col" and N % tp == 0:
            N //= tp
        elif tp_role(n) == "row" and (K // 32) % tp == 0:
            K //= tp
        out[n] = (K, N, r)
    return out


def linears(shapes) -> dict:
    """{name: (K, N, r)} of one layer's packed linears."""
    out = {}
    lay = shapes["layers"]
    for group, names in (("attn", ATTN), ("ffn", MLP)):
        for n in names:
            p = lay[group][n]
            out[n] = (p["qv"].shape[-2] * 32, p["qu_t"].shape[-1],
                      p["qv"].shape[-1])
    return out


def fused_work(mc: dict, lin: dict, M: int, tp: int = 1) -> tuple:
    """(flops, bytes) of the fused-matmul calls of one decode step on
    one of `tp` chips."""
    f = b = 0
    runs = launch(lin)
    for n, (K, N, r) in per_chip(lin, tp).items():
        if runs[n] != "fused":
            continue
        df, db = _fused.work(M, K, N, r, reads_x=n not in SHARES_INPUT)
        f += df
        b += db
    return f * mc["n_layers"], b * mc["n_layers"]


def packed_calls(mc: dict, lin: dict, M: int, tp: int = 1) -> list:
    """(flops, bytes) of each packed-matmul call of one decode step on
    one of `tp` chips, every layer's."""
    runs = launch(lin)
    calls = [c for n, (K, N, r) in per_chip(lin, tp).items()
             if runs[n] == "packed" for c in _packed.linear(M, K, N, r)]
    return calls * mc["n_layers"]


def paged_work(mc: dict, rows: int, slots: int, tp: int = 1) -> tuple:
    """(flops, bytes) of the paged gather of one decode step on one of
    `tp` chips: each holds 1/tp of the kv heads (and their query
    heads) where both head counts divide."""
    f, b = _paged.work(rows, slots, mc["n_heads"], mc["n_kv_heads"],
                       mc["head_dim"])
    share = tp if mc["n_heads"] % tp == 0 and mc["n_kv_heads"] % tp == 0 \
        else 1
    return f * mc["n_layers"] // share, b * mc["n_layers"] // share


def token_flops(mc: dict, lin: dict, ctx_rows: int) -> int:
    """Model FLOPs of one decoded token that read `ctx_rows` cache rows:
    the packed linears, the head and attention over the real context."""
    packed = sum(2 * r * (K + N) for K, N, r in lin.values())
    attn = 4 * mc["n_heads"] * mc["head_dim"] * ctx_rows
    head = 2 * mc["d_model"] * mc["vocab_size"]
    return mc["n_layers"] * (packed + attn) + head

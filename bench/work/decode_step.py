"""Work of one decode step of a dense NanoQuant model, from its shapes.

``linears`` reads each packed linear's (K, N, r) from the weight shapes
the program serves. Per layer, q/k/v share one input and gate/up
another, so the step reads four distinct inputs a layer.
"""
from bench.lib import spec

_fused = spec.load_module("work", "nq_fused_lowrank_matmul")
_paged = spec.load_module("work", "nq_paged_attention")

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
SHARES_INPUT = {"wk", "wv", "w_up"}


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


def fused_work(mc: dict, lin: dict, M: int) -> tuple:
    """(flops, bytes) of every fused-matmul call of one decode step."""
    f = b = 0
    for n, (K, N, r) in lin.items():
        df, db = _fused.work(M, K, N, r, reads_x=n not in SHARES_INPUT)
        f += df
        b += db
    return f * mc["n_layers"], b * mc["n_layers"]


def paged_work(mc: dict, rows: int, slots: int) -> tuple:
    f, b = _paged.work(rows, slots, mc["n_heads"], mc["n_kv_heads"],
                       mc["head_dim"])
    return f * mc["n_layers"], b * mc["n_layers"]


def token_flops(mc: dict, lin: dict, ctx_rows: int) -> int:
    """Model FLOPs of one decoded token that read `ctx_rows` cache rows:
    the packed linears, the head and attention over the real context."""
    packed = sum(2 * r * (K + N) for K, N, r in lin.values())
    attn = 4 * mc["n_heads"] * mc["head_dim"] * ctx_rows
    head = 2 * mc["d_model"] * mc["vocab_size"]
    return mc["n_layers"] * (packed + attn) + head

"""Operations and bytes of single-token decode attention over a paged KV
pool (``nq_paged_attention``), for one layer.

``rows`` is the sum over the decoded slots of the cache rows each one
reads (its real context, the new token included). FLOPs: q.k and p.v,
4 Hq D per row. Bytes: those rows of K and V, and each slot's query and
output.
"""


def work(rows: int, slots: int, n_heads: int, n_kv_heads: int,
         head_dim: int, kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    flops = 4 * n_heads * head_dim * rows
    kv = 2 * rows * n_kv_heads * head_dim * kv_bytes
    qo = 2 * slots * n_heads * head_dim * act_bytes
    return flops, kv + qo

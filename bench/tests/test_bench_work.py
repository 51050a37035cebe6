"""Operations and bytes of the kernels, against counts by hand."""
import _paths  # noqa: F401

from bench.lib import spec

fused = spec.load_module("work", "nq_fused_lowrank_matmul")
paged = spec.load_module("work", "nq_paged_attention")
step = spec.load_module("work", "decode_step")


def test_fused_matmul_by_hand():
    # x (32, 2560) -> rank 1984 -> 9728 outputs (qwen3-4b gate at decode)
    f, b = fused.work(M=32, K=2560, N=9728, r=1984)
    assert f == 2 * 32 * 2560 * 1984 + 2 * 32 * 1984 * 9728
    packed = 2560 * 1984 // 8 + 1984 * 9728 // 8      # one bit a sign
    assert b == packed + (2560 + 9728) * 4 + 32 * 9728 * 2 + 32 * 2560 * 2
    _, b_shared = fused.work(M=32, K=2560, N=9728, r=1984, reads_x=False)
    assert b - b_shared == 32 * 2560 * 2


def test_paged_attention_by_hand():
    # 3 slots with 100, 200 and 300 rows, 32 q heads over 8 kv heads of 128
    f, b = paged.work(rows=600, slots=3, n_heads=32, n_kv_heads=8,
                      head_dim=128)
    assert f == 600 * 32 * (128 * 2 + 128 * 2)
    assert b == 600 * 8 * 128 * 2 * 2 + 3 * 32 * 128 * 2 * 2


def test_decode_step_sums_layers():
    conf = spec.config("qwen3-4b")
    from bench.lib import model
    lin = step.linears(model.weight_shapes(conf))
    assert lin["w_gate"] == (2560, 9728, 1984)
    assert lin["wk"] == (2560, 1024, 704)
    mc = conf["model_config"]
    f, _ = step.fused_work(mc, lin, M=1)
    assert f == 36 * sum(2 * r * (k + n) for k, n, r in lin.values())
    tok = step.token_flops(mc, lin, ctx_rows=10)
    assert tok == f + 36 * 4 * 32 * 128 * 10 + 2 * 2560 * 151936

"""Operations and bytes of the kernels, against counts by hand."""
import _paths  # noqa: F401

from bench.lib import spec

fused = spec.load_module("work", "nq_fused_lowrank_matmul")
paged = spec.load_module("work", "nq_paged_attention")
packed = spec.load_module("work", "nq_packed_matmul")
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


def test_one_chip_work_unchanged():
    """The per-chip routing leaves both one-chip cells' work as it was
    before the benchmark knew of chips: every linear fused, whole."""
    from bench.lib import model
    want = {"qwen3-4b": (22, (156232581120, 530767872), 8469217280),
            "qwen1.5-0.5b": (34, (10641997824, 48611328), 722468864)}
    for name, (M, fused_fb, tok) in want.items():
        conf = spec.config(name)
        mc = conf["model_config"]
        lin = step.linears(model.weight_shapes(conf))
        assert set(step.launch(lin).values()) == {"fused"}
        assert step.fused_work(mc, lin, M) == fused_fb
        assert step.fused_work(mc, lin, M, tp=1) == fused_fb
        assert step.packed_calls(mc, lin, M) == []
        assert step.token_flops(mc, lin, 1000) == tok
    mc = spec.config("qwen3-4b")["model_config"]
    assert step.paged_work(mc, 12345, 7) == (7281377280, 1824473088)


def test_per_chip_work_of_110b_by_hand():
    from bench.lib import model
    conf = spec.config("qwen1.5-110b")
    mc = conf["model_config"]
    lin = step.linears(model.weight_shapes(conf))
    assert lin["wq"] == (8192, 8192, 4064) and lin["wk"] == (8192, 1024, 864)
    assert lin["w_down"] == (49152, 8192, 6976)
    assert step.launch(lin) == {
        "wq": "fused", "wk": "fused", "wv": "fused", "wo": "fused",
        "w_gate": "oracle", "w_up": "oracle", "w_down": "packed"}
    local = step.per_chip(lin, 4)
    assert local["wq"] == (8192, 2048, 4064)         # column-parallel
    assert local["wk"] == (8192, 256, 864)
    assert local["wo"] == (2048, 8192, 4064)         # row-parallel
    assert local["w_down"] == (12288, 8192, 6976)
    M = 9
    f, b = step.fused_work(mc, lin, M, tp=4)
    want_f = want_b = 0
    for n in ("wq", "wk", "wv", "wo"):
        df, db = fused.work(M, *local[n], reads_x=n not in ("wk", "wv"))
        want_f, want_b = want_f + df, want_b + db
    assert (f, b) == (80 * want_f, 80 * want_b)
    calls = step.packed_calls(mc, lin, M, tp=4)
    assert calls == packed.linear(M, 12288, 8192, 6976) * 80
    f1, b1 = step.paged_work(mc, 1000, 3)
    f4, b4 = step.paged_work(mc, 1000, 3, tp=4)
    assert (f4 * 4, b4 * 4) == (f1, b1)


def test_packed_matmul_by_hand():
    # w_down of qwen1.5-110b on one of 4 chips: x (9, 12288) -> rank 6976
    # -> 8192 outputs, the rank intermediate in bf16 through HBM
    (f1, b1), (f2, b2) = packed.linear(M=9, K=12288, N=8192, r=6976)
    assert f1 == 2 * 9 * 12288 * 6976 and f2 == 2 * 9 * 6976 * 8192
    assert b1 == 12288 * 6976 // 8 + 12288 * 4 + 9 * (12288 + 6976) * 2
    assert b2 == 6976 * 8192 // 8 + 8192 * 4 + 9 * (6976 + 8192) * 2

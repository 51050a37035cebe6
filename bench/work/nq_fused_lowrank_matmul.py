"""Operations and bytes of one NanoQuant linear in the fused kernel
(``nq_fused_lowrank_matmul``): y = s1 * (((x * s2) @ V) @ U^T), V (K, r)
and U (N, r) matrices of +-1 read as packed sign bits.

FLOPs count the two matmuls the algorithm needs, 2 M r (K + N); the
unpacking of sign bits is not an operation of the roofline. Bytes count
what the call must move at least once: both packed factors, the two f32
scale vectors, the bf16 output, and the bf16 input unless another
linear of the same launch already reads it (``reads_x``).
"""


def work(M: int, K: int, N: int, r: int, reads_x: bool = True,
         act_bytes: int = 2) -> tuple:
    flops = 2 * M * r * (K + N)
    packed = (K // 32) * r * 4 + (r // 32) * N * 4
    scales = (K + N) * 4
    io = M * N * act_bytes + (M * K * act_bytes if reads_x else 0)
    return flops, packed + scales + io

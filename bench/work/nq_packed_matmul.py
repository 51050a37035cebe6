"""Operations and bytes of one call of the packed matmul kernel
(``nq_packed_matmul``): y = (x * s_k) @ W * s_n, x (M, K) and W a (K, N)
matrix of +-1 read as packed sign bits. A NanoQuant linear whose rank the
fused kernel cannot hold runs two such calls, the rank-r intermediate
going through HBM: (x * s2) @ V, then that @ U^T * s1.

FLOPs count the matmul, 2 M K N. Bytes count what the call must move at
least once: the packed matrix, the real scale vector of its stage (s2
for the first call, s1 for the second), the input and the output, in
the activation dtype.
"""


def work(M: int, K: int, N: int, act_bytes: int = 2) -> tuple:
    flops = 2 * M * K * N
    return flops, (K // 32) * N * 4 + M * (K + N) * act_bytes


def linear(M: int, K: int, N: int, r: int, act_bytes: int = 2) -> list:
    """(flops, bytes) of each of the two calls of one linear."""
    f1, b1 = work(M, K, r, act_bytes)
    f2, b2 = work(M, r, N, act_bytes)
    return [(f1, b1 + K * 4), (f2, b2 + N * 4)]

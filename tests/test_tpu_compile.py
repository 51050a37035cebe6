"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jaxlib and
compiles for a topology that is described, not attached. These cases
catch what interpret mode cannot (block shapes off the (8, 128) tiling,
casts and relayouts Mosaic does not support, kernels over the VMEM
budget) at the real widths of llama3.2-1b (d_model 2048, d_ff 8192,
32 query / 8 KV heads of 64, ranks 992 / 1600 at 1 bit per weight)
plus qwen1.5-0.5b's d_ff 2816, whose tiles have no power-of-two fit.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.roofline.hlo import pallas_kernels

PALLAS = ops.KernelPolicy(mode="pallas", interpret=False)
TWO_CALL = ops.KernelPolicy(mode="pallas", interpret=False, fused=False)
U32, F32, BF16, I32 = jnp.uint32, jnp.float32, jnp.bfloat16, jnp.int32
DECODE_B, PAGE, PAGES = 4, 64, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep these programs out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


def _linear(S, K, N, r, groups=None):
    g = () if groups is None else (groups,)
    out = {"qv": S(g + (K // 32, r), U32), "qu_t": S(g + (r // 32, N), U32),
           "s1": S(g + (N,), F32), "s2": S(g + (K,), F32)}
    if groups is not None:
        out["rmask"] = S((groups, r), F32)
    return out


def _single(policy, K, N, r):
    def case(S, M):
        w = _linear(S, K, N, r)
        return (lambda x, w: ops.lowrank_binary_matmul(
            x, w["qv"], w["qu_t"], w["s1"], w["s2"], policy=policy),
            (S((M, K), BF16), w))
    return case


def _merged(K, r, dims):
    def case(S, M):
        w = _linear(S, K, max(dims), r, groups=len(dims))
        return (lambda x, w: ops.lowrank_binary_matmul_merged(
            x, w, dims, policy=PALLAS), (S((M, K), BF16), w))
    return case


def _paged(S, _):
    n_pages = DECODE_B * PAGES + 1
    return (lambda q, k, v, bt, qp, cp: ops.paged_attention(
        q, k, v, bt, qp, cp, scale=0.125, policy=PALLAS),
        (S((DECODE_B, 1, 32, 64), BF16), S((n_pages, PAGE, 8, 64), BF16),
         S((n_pages, PAGE, 8, 64), BF16), S((DECODE_B, PAGES), I32),
         S((DECODE_B,), I32), S((DECODE_B,), I32)))


def _megakernel(S, _):
    n_pages = DECODE_B * PAGES + 1
    return (lambda x, mq, wo, k, v, bt, qp, cp: ops.decode_step_megakernel(
        x, mq, wo, k, v, bt, qp, cp, head_dim=64, dims=(2048, 512),
        theta=5e5, scale=0.125, policy=PALLAS),
        (S((DECODE_B, 2048), BF16), _linear(S, 2048, 2048, 992, groups=3),
         _linear(S, 2048, 2048, 992), S((n_pages, PAGE, 8, 64), BF16),
         S((n_pages, PAGE, 8, 64), BF16), S((DECODE_B, PAGES), I32),
         S((DECODE_B,), I32), S((DECODE_B,), I32)))


# name -> (kernel expected in the compiled HLO, case builder)
CASES = {
    "fused_wo": ("nq_fused_lowrank_matmul", _single(PALLAS, 2048, 2048, 992)),
    "fused_down": ("nq_fused_lowrank_matmul",
                   _single(PALLAS, 8192, 2048, 1600)),
    "grouped_qkv": ("nq_fused_lowrank_matmul",
                    _merged(2048, 992, (2048, 512, 512))),
    "grouped_gate_up": ("nq_fused_lowrank_matmul",
                        _merged(2048, 1600, (8192, 8192))),
    "two_call_down": ("nq_packed_matmul",
                      _single(TWO_CALL, 8192, 2048, 1600)),
    "qwen_up_2816": ("nq_fused_lowrank_matmul",
                     _single(PALLAS, 1024, 2816, 704)),
    "qwen_down_2816": ("nq_fused_lowrank_matmul",
                       _single(PALLAS, 2816, 1024, 704)),
    "paged_gather": ("nq_paged_attention", _paged),
    "decode_megakernel": ("nq_decode_megakernel", _megakernel),
}
# decode slots, and a prefill-sized row block; the gather and the
# megakernel are decode-only
ROWS = {"decode": DECODE_B, "prefill": 128}
PARAMS = [(name, rows) for name in sorted(CASES) for rows in sorted(ROWS)
          if rows == "decode"
          or name not in ("paged_gather", "decode_megakernel")]


@pytest.mark.parametrize("name,rows", PARAMS)
def test_kernel_compiles_for_v5e(shape, name, rows):
    kernel, build = CASES[name]
    fn, args = build(shape, ROWS[rows])
    found = pallas_kernels(jax.jit(fn).lower(*args).compile().as_text())
    assert kernel in found, found

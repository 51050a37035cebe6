"""Partition-spec rules for every parallelism axis (DP / FSDP / TP /
EP), shared by training cells, the multi-pod dry-run and the
tensor-parallel serving engine — see :mod:`repro.sharding.rules` for
the mesh contract and the Megatron col/row pairing of packed
(quantized) linears, and docs/serving.md for how the engine consumes
these placements.
"""
from repro.sharding.rules import (  # noqa: F401
    batch_pspecs, cache_pspecs, data_axes, param_pspecs, replicate_specs,
    to_shardings, tp_role, ShardingPolicy, DEFAULT, SERVE)

__all__ = [
    "ShardingPolicy", "DEFAULT", "SERVE",
    "param_pspecs", "batch_pspecs", "cache_pspecs", "replicate_specs",
    "to_shardings", "data_axes", "tp_role",
]

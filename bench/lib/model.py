"""The model a cell serves: its configuration and its packed weights,
made on the device from the run's seed.

The weights take the shapes ``repro.quant.surgery.abstract_quantized_params``
gives for the configuration at its target bits per weight, the layout the
program serves. Every leaf is drawn in one jitted call: packed sign words
uniform over uint32, channel scales that keep each packed linear at its
input's scale, and the rest as ``assumed`` in the configuration's file says.
A cell on several chips draws each leaf into the shards the program's
``sharding.rules`` give it on the serving mesh.
The same seed gives the same bits, so the reference can draw them again
after the program's state is gone.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SCALE_LO, SCALE_HI = 0.5, 1.5
NORM_JITTER = 0.1
BIAS_STD = 0.1


def model_config(conf: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(**conf["model_config"])


def key_for(seed: int, stream: int = 0):
    """A PRNG key from any whole seed, also one past 32 bits."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def weight_shapes(conf: dict):
    from repro.quant.surgery import abstract_quantized_params
    return abstract_quantized_params(model_config(conf), conf["target_bpw"])


def _leaf(key, path, sds, conf):
    names = [getattr(p, "key", str(p)) for p in path]
    leaf = names[-1]
    shape, dtype = sds.shape, sds.dtype
    if leaf in ("qu_t", "qv"):
        return jax.random.bits(key, shape, jnp.uint32)
    if leaf == "b":
        return (BIAS_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if leaf == "embed":
        std = conf["assumed"]["embed_std"] / math.sqrt(shape[-1])
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if leaf == "w" and len(shape) >= 2:
        # a linear kept in full precision (the untied head, a router, an
        # MLA up-projection): N(0, 1)/sqrt(d_in), d_in = shape[-2]
        std = 1.0 / math.sqrt(shape[-2])
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if len(shape) >= 1 and ("ln" in leaf or "norm" in leaf):
        return (1.0 + NORM_JITTER * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    raise ValueError(f"no rule to draw weight leaf {'/'.join(names)}")


def shardings(conf: dict, mesh):
    """Where each leaf lives on the program's serving mesh: the
    program's own rules (``sharding.rules``, its serving policy)."""
    from repro.sharding import rules
    specs = rules.param_pspecs(model_config(conf), weight_shapes(conf), mesh,
                               rules.SERVE)
    return rules.to_shardings(mesh, specs)


def make_weights(conf: dict, seed: int, mesh=None):
    """The whole parameter tree on the device, from the seed; with a
    mesh, each leaf drawn straight into its shards, so no chip ever
    holds the whole tree. The bits are the same either way: JAX's
    threefry is partitionable (``jax_threefry_partitionable``)."""
    shapes = weight_shapes(conf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    # sizes of each packed linear, read from its own packed leaves
    dims = {}
    for path, sds in flat:
        names = tuple(getattr(p, "key", str(p)) for p in path)
        if names[-1] == "qv":
            dims[names[:-1]] = (sds.shape[-2] * 32, sds.shape[-1])

    def draw(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, sds) in zip(keys, flat):
            names = tuple(getattr(p, "key", str(p)) for p in path)
            if names[-1] in ("s1", "s2"):
                d_in, r = dims[names[:-1]]
                u = jax.random.uniform(k, sds.shape, jnp.float32,
                                       SCALE_LO, SCALE_HI)
                norm = 1.0 / math.sqrt(d_in if names[-1] == "s2" else r)
                out.append((u * norm).astype(sds.dtype))
            else:
                out.append(_leaf(k, path, sds, conf))
        return jax.tree_util.tree_unflatten(treedef, out)

    if mesh is None:
        return jax.jit(draw)(key_for(seed, 1))
    return jax.jit(draw, out_shardings=shardings(conf, mesh))(
        key_for(seed, 1))

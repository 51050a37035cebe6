"""Compile rehearsal: each cell's programs at their real sizes for a
described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--cells a,b] [--all-buckets]

For every cell it compiles, with the Pallas kernels on, the engine's
decode step at the cell's batch and paged pool, its largest admission
prefill bucket (all buckets with ``--all-buckets``), and the output
check's reference and control at ``max_len`` rows, for the
cell's chips of a described ``v5e:2x2`` (a cell on several chips over
the program's serving mesh, its arguments placed as ``sharding.rules``
places them), and prints ``memory_analysis()`` of each (per chip) and
the Pallas kernels the decode step holds. Nothing runs: it says
what the chip's compiler refuses and how much memory each program asks
for, not how fast anything is. The programs are built as the engine
builds them (``repro.serve.engine``), from shapes alone.
"""
import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def programs(conf, cellp, mix, all_buckets, mesh=None):
    import contextlib
    import json
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.models import transformer as T
    from repro.quant.surgery import merge_projection_groups
    from repro.serve import paging
    from repro.serve.engine import (ServeConfig, make_slot_prefill_step,
                                    sample_token)
    from bench.lib import model as bmodel
    from bench.lib import serve as bserve

    cfg = bmodel.model_config(conf)
    B, max_len, ps = cellp["max_batch"], cellp["max_len"], cellp["page_size"]
    kv = paging.PagedKVState(cfg, B, max_len, ps, cellp.get("kv_pool_pages"))
    policy = kops.KernelPolicy(mode="pallas", interpret=False, mesh=mesh,
                               tp_axis="model")
    with kops.kernel_policy(policy):
        params = jax.eval_shape(merge_projection_groups,
                                bmodel.weight_shapes(conf))
    cache = jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, B, max_len, kv.n_pages, kv.page_size))
    scfg = ServeConfig(greedy=True)

    def scope():
        """The engine's trace-time scope (``_policy_scope``)."""
        if mesh is None:
            return kops.kernel_policy(policy)
        from repro.models import layers as L
        stack = contextlib.ExitStack()
        stack.enter_context(L.activation_sharding(mesh, ("data",), "model"))
        stack.enter_context(kops.kernel_policy(policy))
        return stack

    def decode_fn(params, tokens, cache, pos, active, key, tables):
        with scope():
            logits, new = T.decode_step(params, cfg, tokens, cache, pos,
                                        block_tables=tables)
            new = paging.paged_select_active(new, cache, active)
            tok = sample_token(logits, key, scfg)
        return jnp.where(active[:, None], tok, 0), new

    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    yield ("decode_step", jax.jit(decode_fn, donate_argnums=(2,)),
           (params, S((B, 1), i32), cache, S((B,), i32), S((B,), jnp.bool_),
            S((2,), jnp.uint32), {"linear": S((B, kv.lin_pages), i32)}),
           ("params", None, "cache", None, None, None, None))
    prefill = make_slot_prefill_step(cfg, max_len)

    def prefill_fn(params, tokens, last_idx):
        with scope():
            return prefill(params, tokens, last_idx)

    from repro.serve.scheduler import bucket_length
    buckets = [bucket_length(n, max_len)
               for n in bserve.warm_lengths(mix, cellp)]
    for b in (buckets if all_buckets else buckets[-1:]):
        yield (f"prefill_{b}", jax.jit(prefill_fn),
               (params, S((1, b), i32), S((), i32)), ("params", None, None))
    # the output check's reference and control, on the drawn tree
    from bench.lib import check
    key = json.dumps(conf, sort_keys=True)
    for what, n_in in (("served", 2), ("control", 1)):
        yield (f"check_{what}", check._jitted(conf["reference"], key, what),
               (bmodel.weight_shapes(conf),) + (S((max_len,), i32),) * n_in,
               ("params",) + (None,) * n_in)


def serving_mesh(devices):
    """The layout of ``launch.mesh.make_serving_mesh`` over described
    devices; None for one."""
    import numpy as np
    from jax.sharding import Mesh
    if len(devices) == 1:
        return None
    return Mesh(np.array(devices).reshape(1, -1), ("data", "model"))


def placed(conf, args, kinds, devices, mesh):
    """The arguments' shapes on `devices`: one device as it is, a mesh
    with parameters and the paged pool placed by ``sharding.rules`` and
    the rest replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding import rules
    from bench.lib import model as bmodel
    if mesh is None:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), args)
    cfg = bmodel.model_config(conf)
    out = []
    for a, kind in zip(args, kinds):
        if kind == "params":
            specs = rules.param_pspecs(cfg, a, mesh, rules.SERVE)
        elif kind == "cache":
            specs = rules.cache_pspecs(cfg, a, mesh, rules.SERVE, paged=True)
        else:
            specs = rules.replicate_specs(a)
        out.append(jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            a, specs, is_leaf=lambda x: isinstance(x, P)))
    return tuple(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default="")
    ap.add_argument("--all-buckets", action="store_true")
    a = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from bench.lib import spec
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = spec.benchmark()
    names = [c for c in a.cells.split(",") if c] or \
        [w["name"] for w in bench["workloads"]]
    for name in names:
        w = spec.workload(name, bench)
        conf, cellp = spec.config(w["config"]), spec.cell(name)
        mix = spec.traffic(w["traffic"])
        devices = topo.devices[:w["chips"]]
        mesh = serving_mesh(devices)
        for prog, fn, args, kinds in programs(conf, cellp, mix,
                                              a.all_buckets, mesh):
            args = placed(conf, args, kinds, devices, mesh)
            t = time.perf_counter()
            compiled = fn.lower(*args).compile()
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            kernels = {k: text.count(k) for k in (
                "nq_fused_lowrank_matmul", "nq_packed_matmul",
                "nq_paged_attention", "nq_decode_megakernel",
                "all-reduce", "all-gather")}
            print(f"{name} {prog}: compiled in "
                  f"{time.perf_counter() - t:.1f} s; "
                  f"arguments {ma.argument_size_in_bytes}, "
                  f"outputs {ma.output_size_in_bytes}, "
                  f"temp {ma.temp_size_in_bytes}, "
                  f"alias {ma.alias_size_in_bytes}, "
                  f"code {ma.generated_code_size_in_bytes} bytes; "
                  f"kernel names in HLO text {kernels}", flush=True)


if __name__ == "__main__":
    main()

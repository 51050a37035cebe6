"""Compile rehearsal: each cell's programs at their real sizes for a
described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--cells a,b] [--all-buckets]

For every cell it compiles, with the Pallas kernels on, the engine's
decode step at the cell's batch and paged pool, and its largest
admission prefill bucket (all buckets with ``--all-buckets``), for one
chip of a described ``v5e:2x2``, and prints ``memory_analysis()`` of
each and the Pallas kernels the decode step holds. Nothing runs: it says
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


def programs(conf, cellp, mix, all_buckets):
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
    policy = kops.KernelPolicy(mode="pallas", interpret=False)
    with kops.kernel_policy(policy):
        params = jax.eval_shape(merge_projection_groups,
                                bmodel.weight_shapes(conf))
    cache = jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, B, max_len, kv.n_pages, kv.page_size))
    scfg = ServeConfig(greedy=True)

    def decode_fn(params, tokens, cache, pos, active, key, tables):
        with kops.kernel_policy(policy):
            logits, new = T.decode_step(params, cfg, tokens, cache, pos,
                                        block_tables=tables)
            new = paging.paged_select_active(new, cache, active)
            tok = sample_token(logits, key, scfg)
        return jnp.where(active[:, None], tok, 0), new

    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    yield ("decode_step", jax.jit(decode_fn, donate_argnums=(2,)),
           (params, S((B, 1), i32), cache, S((B,), i32), S((B,), jnp.bool_),
            S((2,), jnp.uint32), {"linear": S((B, kv.lin_pages), i32)}))
    prefill = make_slot_prefill_step(cfg, max_len)

    def prefill_fn(params, tokens, last_idx):
        with kops.kernel_policy(policy):
            return prefill(params, tokens, last_idx)

    from repro.serve.scheduler import bucket_length
    buckets = [bucket_length(n, max_len)
               for n in bserve.warm_lengths(mix, cellp)]
    for b in (buckets if all_buckets else buckets[-1:]):
        yield (f"prefill_{b}", jax.jit(prefill_fn),
               (params, S((1, b), i32), S((), i32)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default="")
    ap.add_argument("--all-buckets", action="store_true")
    a = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench.lib import spec
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = spec.benchmark()
    names = [c for c in a.cells.split(",") if c] or \
        [w["name"] for w in bench["workloads"]]
    for name in names:
        w = spec.workload(name, bench)
        conf, cellp = spec.config(w["config"]), spec.cell(name)
        mix = spec.traffic(w["traffic"])
        for prog, fn, args in programs(conf, cellp, mix, a.all_buckets):
            args = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one), args)
            t = time.perf_counter()
            compiled = fn.lower(*args).compile()
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            kernels = {k: text.count(k) for k in (
                "nq_fused_lowrank_matmul", "nq_paged_attention",
                "nq_decode_megakernel")}
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

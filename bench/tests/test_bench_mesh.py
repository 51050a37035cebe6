"""A cell on four chips, at smoke size on four CPU devices: the weights
drawn into their shards are the one-device draw bit for bit and no
device holds the whole tree, the program's placement then moves
nothing, the engine serves over ``make_serving_mesh(4)`` and passes the
output check, the check's gap on sharded weights is the one-device gap,
and a run with the exchange between chips left out is not correct.

The four devices exist only in a child process
(``--xla_force_host_platform_device_count=4``), which runs all of it
once and prints one JSON line; the tests read that line. The one-chip
draw is pinned here too, by a checksum taken before the benchmark knew
of meshes.
"""
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import _paths  # noqa: F401
import jax
import numpy as np
import pytest

from bench.lib import model, spec

SEED = 2**33 + 5
# qwen1.5-110b's own smoke widths (src/repro/configs/qwen1p5_110b.py):
# one kv head and a 64-wide model, so the pool, wo and w_down stay whole
PROGRAM_SMOKE = dict(n_layers=2, d_model=64, n_heads=8, n_kv_heads=1,
                     d_ff=192, vocab_size=256, head_dim=8)
# widths at which every rule of the 4-way split engages: 4 kv heads,
# packed d_in of wo and w_down divisible by 4, vocabulary by 4
TP_SMOKE = dict(n_layers=2, d_model=128, n_heads=8, n_kv_heads=4,
                d_ff=256, vocab_size=256, head_dim=16)
MIX = {"loop": "closed", "pool": 256,
       "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                  "min": 4, "max": 40},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                  "min": 4, "max": 20}}
CELL = {"max_batch": 4, "max_len": 64, "page_size": 16,
        "kv_pool_pages": None, "clients": 4, "ramp_steps": 4,
        "check": {"min_tokens": 64, "max_requests": 16},
        "limits": {"logit_gap": 0.3}}
BENCH = {"workloads": [{"name": "smoke", "config": "smoke",
                        "traffic": "smoke", "chips": 4}],
         "end_to_end": [{"name": "decode_tok_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}
# sha256 of every leaf's bytes, in tree order, of the one-chip smoke draw
# at SEED (bench/tests/test_bench_check.py's widths), as the parent of
# the mesh-aware draw made it
ONE_CHIP_SHA256 = {
    "qwen1.5-0.5b":
        "e5d7b78be1a491863162da856ae3425d012a0ddff7e92edef7dec2d2e20dc1e1",
    "qwen3-4b":
        "be8dd681cd7765200109e2038ca2fa0faf69999f6cb3f6842f929c011b722e6a",
}
ONE_CHIP_SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab_size=256, head_dim=32)

CHILD = r"""
import io, json, sys
import jax, numpy as np
from bench.lib import check, harness, model, serve, spec

a = json.loads(sys.argv[1])
out = {"partitionable": bool(jax.config.jax_threefry_partitionable),
       "devices": len(jax.devices())}
mesh = serve.serving_mesh(4)


def conf_of(widths):
    conf = spec.config("qwen1.5-110b")
    conf["model_config"].update(widths)
    return conf


for name in ("program", "tp"):
    conf = conf_of(a[name])
    whole = model.make_weights(conf, a["seed"])
    shard = model.make_weights(conf, a["seed"], mesh)
    leaves = jax.tree.leaves(shard)
    per_dev = {}
    for x in leaves:
        for s in x.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    out[name] = {
        "bits_equal": all(np.array_equal(np.asarray(x), np.asarray(y))
                          for x, y in zip(jax.tree.leaves(whole), leaves)),
        "tree_bytes": sum(x.nbytes for x in leaves),
        "max_device_bytes": max(per_dev.values()),
        "sharded_leaves": sum(
            x.addressable_shards[0].data.shape != x.shape for x in leaves),
        "placed_as_rules": all(jax.tree.leaves(jax.tree.map(
            lambda x, s: x.sharding.is_equivalent_to(s, x.ndim), shard,
            model.shardings(conf, mesh)))),
    }

conf = conf_of(a["tp"])
w = model.make_weights(conf, a["seed"], mesh)
from repro.quant.surgery import place_on_mesh
placed = place_on_mesh(w, model.model_config(conf), mesh)
out["placement_moves_nothing"] = all(
    [s.data.unsafe_buffer_pointer() for s in x.addressable_shards]
    == [s.data.unsafe_buffer_pointer() for s in y.addressable_shards]
    for x, y in zip(jax.tree.leaves(w), jax.tree.leaves(placed)))
placed = None
engine = serve.build_engine(conf, a["cell"], a["seed"], mesh)
out["engine"] = {
    "mesh_devices": int(engine.mesh.size),
    "pool_sharded": all(x.addressable_shards[0].data.shape != x.shape
                        for x in jax.tree.leaves(engine.cache)),
}
engine = None

rng = np.random.default_rng(3)
samples = [(rng.integers(0, 256, 20).astype(np.int32),
            rng.integers(0, 256, 12).astype(np.int32)),
           (rng.integers(0, 256, 35).astype(np.int32),
            rng.integers(0, 256, 20).astype(np.int32))]
out["gap_one_device"] = check.served_gap(conf, a["cell"], a["seed"], samples)
out["gap_mesh"] = check.served_gap(conf, a["cell"], a["seed"], samples, mesh)


def run():
    buf = io.StringIO()
    harness.run("smoke", a["seed"], 1.5, False, bench=a["bench"], conf=conf,
                cellp=a["cell"], mix=a["mix"], need_accelerator=False,
                out=buf, err=io.StringIO())
    return json.loads(buf.getvalue().strip().splitlines()[-1])


out["run"] = run()
# the exchange between chips left out: row-parallel linears skip their
# psum, so each chip goes on with its own partial sum
from repro.kernels import ops
launch = ops._shard_launch
ops._shard_launch = lambda *args, reduce_axis=None, **kw: launch(*args, **kw)
out["run_without_exchange"] = run()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(spec.ROOT),
                                           str(spec.ROOT / "src")]))
    args = json.dumps({"seed": SEED, "program": PROGRAM_SMOKE,
                       "tp": TP_SMOKE, "cell": CELL, "mix": MIX,
                       "bench": BENCH})
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD), args],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=str(spec.ROOT))
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["program", "tp"])
def test_sharded_draw_is_the_one_device_draw(child, name):
    assert child["devices"] == 4
    assert child["partitionable"]
    assert child[name]["bits_equal"]
    assert child[name]["placed_as_rules"]


def test_no_device_holds_the_whole_tree(child):
    tp = child["tp"]
    assert tp["sharded_leaves"] > 0
    assert tp["max_device_bytes"] < tp["tree_bytes"] / 2


def test_engine_serves_over_the_mesh(child):
    assert child["placement_moves_nothing"]
    assert child["engine"] == {"mesh_devices": 4, "pool_sharded": True}
    run = child["run"]
    assert run["correct"], run["checks"]
    assert run["failed"] == 0
    assert run["checks"]["checked_tokens"]["value"] >= 64


def test_served_gap_on_the_mesh_is_the_one_device_gap(child):
    a, b = child["gap_one_device"], child["gap_mesh"]
    assert a > 0
    assert abs(a - b) <= 1e-5 * max(1.0, abs(a))


def test_exchange_left_out_is_not_correct(child):
    run = child["run_without_exchange"]
    assert not run["correct"], run["checks"]
    assert run["checks"]["logit_gap"]["value"] > CELL["limits"]["logit_gap"]


@pytest.mark.parametrize("name", sorted(ONE_CHIP_SHA256))
def test_one_chip_draw_is_pinned(name):
    assert jax.config.jax_threefry_partitionable
    conf = spec.config(name)
    conf["model_config"].update(ONE_CHIP_SMOKE)
    h = hashlib.sha256()
    for x in jax.tree.leaves(model.make_weights(conf, SEED)):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == ONE_CHIP_SHA256[name]


def test_every_leaf_of_a_moe_mla_tree_is_drawn(monkeypatch):
    """Routers and MLA up-projections are linears kept in full precision:
    drawn N(0, 1)/sqrt(d_in) in their own dtype."""
    from repro import configs
    from repro.quant.surgery import abstract_quantized_params
    tree = abstract_quantized_params(
        configs.get_smoke("deepseek-v2-lite-16b"), 1.0)
    names = {"/".join(str(getattr(p, "key", p)) for p in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert {"layers/moe/router/w", "layers/attn/w_uk/w",
            "layers/attn/w_uv/w"} <= names
    monkeypatch.setattr(model, "weight_shapes", lambda conf: tree)
    w = model.make_weights({"assumed": {"embed_std": 3.0}}, SEED)
    for x, sds in zip(jax.tree.leaves(w), jax.tree.leaves(tree)):
        assert x.shape == sds.shape and x.dtype == sds.dtype
    for leaf in (w["layers"]["moe"]["router"]["w"],
                 w["layers"]["attn"]["w_uk"]["w"]):
        x = np.asarray(leaf, np.float64)
        assert abs(x.std() * np.sqrt(x.shape[-2]) - 1.0) < 0.2

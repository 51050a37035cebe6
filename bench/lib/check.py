"""Whether the timed path served the right tokens.

Once the window has closed, a sample of the requests the engine finished
is drawn from the seed, the longest among them, until it holds the
cell's ``check.min_tokens`` served tokens. The configuration's plain
reference then reads each prompt with its served tokens in one pass, and
the number compared is the widest gap by which a served token's logit
lies below the reference's best at that position. Greedy decoding makes
that gap zero up to the rounding of the program's bfloat16 path.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import numpy as np

from bench.lib import model as bmodel
from bench.lib import spec


def pick(driver, cellp: dict, seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """(prompt, served tokens) of the sampled finished requests."""
    done = [r for r in driver.records.values()
            if r.status == "done" and len(r.tokens) > 1]
    if not done:
        return []
    done.sort(key=lambda r: r.item.index)
    longest = max(done, key=lambda r: len(r.tokens))
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
    order = [longest] + [done[i] for i in rng.permutation(len(done))
                         if done[i] is not longest]
    out, n = [], 0
    for r in order:
        if n >= cellp["check"]["min_tokens"] \
                or len(out) >= cellp["check"]["max_requests"]:
            break
        out.append((np.asarray(r.prompt), np.asarray(r.tokens, np.int32)))
        n += len(r.tokens)
    return out


def _inputs(prompt, served, length):
    """Reference input (prompt + served tokens but the last) padded to
    `length`, and per position the served token it must predict, -1
    where nothing was served."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    tokens = np.zeros((length,), np.int32)
    tokens[:seq.size] = seq
    targets = np.full((length,), -1, np.int32)
    targets[prompt.size - 1: prompt.size - 1 + served.size] = served
    return tokens, targets


@functools.lru_cache(maxsize=None)
def _jitted(ref_name: str, conf_json: str, what: str):
    import json
    import jax
    ref = spec.load_module("reference", ref_name)
    conf = json.loads(conf_json)
    if what == "served":
        return jax.jit(functools.partial(ref.served_gaps, conf))
    return jax.jit(functools.partial(ref.control_gaps, conf))


def _gaps(conf, cellp, seed, samples, what, mesh):
    import json
    import jax
    fn = _jitted(conf["reference"], json.dumps(conf, sort_keys=True), what)
    w = bmodel.make_weights(conf, seed, mesh)
    widest = []
    for prompt, served in samples:
        tokens, targets = _inputs(prompt, served, cellp["max_len"])
        if what == "served":
            g = fn(w, tokens, targets)
        else:
            g = fn(w, tokens)
        g = np.asarray(jax.device_get(g))[targets >= 0]
        widest.append(float(g.max()))
    return widest


def served_gap(conf: dict, cellp: dict, seed: int, samples,
               mesh=None) -> float:
    """The widest gap over every sampled served token (None if none).
    With the cell's mesh, the reference reads the weights drawn into
    the same shards as the program's and runs over the same chips."""
    if not samples:
        return None
    return max(_gaps(conf, cellp, seed, samples, "served", mesh))


def control_gap(conf: dict, cellp: dict, seed: int, samples,
                mesh=None) -> float:
    """The same reading for the fp8 control's first choices at the same
    positions of the same prompts and tokens."""
    if not samples:
        return None
    return max(_gaps(conf, cellp, seed, samples, "control", mesh))


def tokens(samples) -> int:
    return sum(len(s[1]) for s in samples)


def judge(cellp: dict, gap, checked: int, compiles: int, failed: int):
    """(checks, correct): every number a run compares, beside its limit,
    and whether each keeps to it. The one verdict of a run, and of the
    control put in the program's place."""
    checks = {
        "logit_gap": {"value": gap, "limit": cellp["limits"]["logit_gap"]},
        "window_compiles": {"value": compiles, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "checked_tokens": {"value": checked,
                           "limit": cellp["check"]["min_tokens"]},
    }
    correct = bool(gap is not None and gap <= checks["logit_gap"]["limit"]
                   and compiles == 0 and failed == 0
                   and checked >= checks["checked_tokens"]["limit"])
    return checks, correct


def print_checks(checks: dict, err) -> None:
    for k, v in checks.items():
        cmp = ">=" if k == "checked_tokens" else "<="
        print(f"check {k}: {v['value']} (limit {cmp} {v['limit']})",
              file=err, flush=True)

"""Plain reference of the Qwen2 / Qwen3 dense decoder, in float32.

It follows the published architecture: token embedding; per layer an
RMSNorm, grouped-query attention with rotate-half RoPE (Qwen3: per-head
RMSNorm on q and k; Qwen2: bias on q, k and v), a residual, an RMSNorm
and a SwiGLU MLP with a residual; a final RMSNorm and the head (the
embedding, transposed, where the configuration ties them). Every linear
is NanoQuant's packed form, paper Eq. 1:
``y = s1 * (((x * s2) @ V) @ U^T)`` with V and U matrices of +-1, read
from sign bits packed 32 to a uint32 word along the first axis (bit b of
word i is row 32 i + b, set for +1).

It imports nothing of the program. Its input is the weight tree that
``bench/lib/model.py`` draws from the seed, read by the names of its
leaves, and it runs one sequence at a time over the whole prompt and the
served tokens, with no cache and no kernels, every matmul at HIGHEST
precision.

``precision="fp8"`` is the control: the same computation with every
matmul operand rounded to float8 e4m3 first, the step below bfloat16
that the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
HEAD_ROWS = 512


def _rounder(precision: str):
    if precision == "f32":
        return lambda a: a
    if precision == "fp8":
        return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def unpack(words):
    """(K/32, N) uint32 -> (K, N) float32 of +-1."""
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    bits = (words[:, None, :] >> shifts) & jnp.uint32(1)
    k32, n = words.shape
    return bits.reshape(k32 * 32, n).astype(jnp.float32) * 2.0 - 1.0


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x (L, H, D); rotate-half RoPE at integer positions pos (L,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def linear(p, x, rnd):
    d_in = x.shape[-1]
    s2 = p["s2"].astype(jnp.float32)[:d_in]
    v = unpack(p["qv"])[:d_in]
    t = jnp.matmul(rnd(x * s2), v, precision=HI)
    y = jnp.matmul(rnd(t), unpack(p["qu_t"]), precision=HI) \
        * p["s1"].astype(jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


def layer(conf, lp, x, rnd):
    mc = conf["model_config"]
    eps, hd = mc["norm_eps"], mc["head_dim"]
    hq, hkv = mc["n_heads"], mc["n_kv_heads"]
    n = x.shape[0]
    pos = jnp.arange(n)
    a = lp["attn"]
    h = rms_norm(x, lp["ln1"], eps)
    q = linear(a["wq"], h, rnd).reshape(n, hq, hd)
    k = linear(a["wk"], h, rnd).reshape(n, hkv, hd)
    v = linear(a["wv"], h, rnd).reshape(n, hkv, hd)
    if mc["qk_norm"]:
        q = rms_norm(q, a["q_norm"], eps)
        k = rms_norm(k, a["k_norm"], eps)
    q = rope(q, pos, mc["rope_theta"])
    k = rope(k, pos, mc["rope_theta"])
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k), precision=HI) \
        / math.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", rnd(pr), rnd(v), precision=HI)
    x = x + linear(a["wo"], o.reshape(n, hq * hd), rnd)
    h = rms_norm(x, lp["ln2"], eps)
    f = lp["ffn"]
    gate = linear(f["w_gate"], h, rnd)
    up = linear(f["w_up"], h, rnd)
    return x + linear(f["w_down"], jax.nn.silu(gate) * up, rnd)


def hidden(conf, w, tokens, precision="f32"):
    """Final normed hidden states (L, d) of one sequence."""
    rnd = _rounder(precision)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        return layer(conf, lp, x, rnd), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    return rms_norm(x, w["ln_f"], conf["model_config"]["norm_eps"])


def head_matrix(conf, w):
    if conf["model_config"]["tie_embeddings"]:
        return w["embed"].T
    return w["lm_head"]["w"]


def _by_rows(fn, *arrays):
    """Apply fn to HEAD_ROWS-row blocks (bounds the (rows, vocab) logits)."""
    n = arrays[0].shape[0]
    pad = -n % HEAD_ROWS
    blocks = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (-1, HEAD_ROWS) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda b: fn(*b), tuple(blocks))
    return out.reshape((-1,) + out.shape[2:])[:n]


def served_gaps(conf, w, tokens, targets):
    """Per position: how far the reference's logit of the served token
    (``targets``) lies below its best logit. tokens, targets: (L,)."""
    e = head_matrix(conf, w).astype(jnp.float32)
    h = hidden(conf, w, tokens)

    def gap(hb, tb):
        lg = jnp.matmul(hb, e, precision=HI)
        mine = jnp.take_along_axis(lg, jnp.maximum(tb, 0)[:, None], 1)[:, 0]
        return lg.max(-1) - mine

    return _by_rows(gap, h, targets)


def control_gaps(conf, w, tokens):
    """Per position: the reference's gap of the token that the fp8
    control puts first."""
    e = head_matrix(conf, w).astype(jnp.float32)
    h = hidden(conf, w, tokens)
    h8 = hidden(conf, w, tokens, "fp8")
    rnd = _rounder("fp8")

    def gap(hb, h8b):
        lg = jnp.matmul(hb, e, precision=HI)
        pick = jnp.argmax(jnp.matmul(rnd(h8b), rnd(e), precision=HI), -1)
        return lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]

    return _by_rows(gap, h, h8)

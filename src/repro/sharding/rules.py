"""Per-family PartitionSpec rules (DP / FSDP / TP / EP / pod axis).

Mesh contract (see ``repro.launch.mesh``): axes are ``("data", "model")``
single-pod or ``("pod", "data", "model")`` multi-pod. Policy:

- **batch**  is sharded over every data-parallel axis (``pod`` + ``data``).
- **params** are FSDP-sharded over ``data`` on one dim and tensor-parallel
  over ``model`` on the other (Megatron pairing: column-parallel
  wq/wk/wv/w_gate/w_up, row-parallel wo/w_down); replicated across pods
  (pure DP on the DCN-mapped ``pod`` axis; gradient all-reduce is
  hierarchical).
- **MoE experts** are expert-parallel on ``model``; router stays
  replicated (it is <0.01% of params).
- **Mamba2 mixers** use split z/x/B/C/dt projections (see
  ``layers.init_mamba2``): the wide z/x streams are TP-sharded on
  ``model`` (columns == SSD heads, so the chunked SSD shards by head);
  B/C/dt are small and replicated; out_proj is row-parallel.
- **Quantized linears** (packed low-rank binary) follow the same
  Megatron pairing as their FP counterparts: column-parallel projections
  (wq/wk/wv/w_gate/w_up, mamba wz/wx) shard U with its s1 scale on
  d_out over ``model`` (V/s2 replicated — each device runs the whole
  fused kernel on its output shard, no collective); row-parallel
  projections (wo/w_down/out_proj) shard V on packed d_in with its s2
  scale (U/s1 replicated — partial outputs finish with ONE psum).
  ``qv_sharded`` additionally r-shards V on column-parallel linears
  (residency optimization for training/FSDP; the serving launch keeps V
  replicated, see :data:`SERVE`).
- **KV caches**: kv-head dim on ``model`` when divisible, else the
  sequence dim (GSPMD handles softmax/contraction over a sharded
  sequence with small all-reduces); batch on data axes.

Every rule checks divisibility against the mesh axis size and falls back
to ``None`` (replicated) — uneven shardings are never emitted, so
``.lower().compile()`` is deterministic across all 10 archs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All data-parallel axes, outermost first ((pod, data) or (data,))."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Tunable knobs, exercised by the §Perf hillclimb. qv_sharded
    defaults ON after §Perf iteration 4 (r-dim TP of the packed V factor
    — halves quantized-param residency for ~1ms of extra all-gather);
    set False to reproduce the paper-faithful replicated-V baseline."""
    fsdp: bool = True              # shard params over `data` (ZeRO-3 style)
    fsdp_pod: bool = False         # extend FSDP over the pod axis too
    qv_sharded: bool = True        # shard packed V on r (beyond-paper TP)
    seq_shard_cache: bool = True   # allow sequence-sharded KV caches


DEFAULT = ShardingPolicy()

# Serving placement (InferenceEngine): tensor-parallel only. No FSDP —
# there is no optimizer state to amortize and decode activations are
# tiny — and V stays replicated so every device can run the whole fused
# kernel on its local shard (the paper-faithful baseline layout).
SERVE = ShardingPolicy(fsdp=False, qv_sharded=False)

# Megatron pairing for quantized linears, keyed on the parent linear
# name (the packed leaves live one level below, e.g. layers/attn/wq/qu_t).
# Column-parallel: output dim sharded, input replicated. Row-parallel:
# input dim sharded, output reduced with one psum. Shared with
# kernels.ops, which mirrors this pairing in its shard_map launch.
COL_PARALLEL = ("wq", "wk", "wv", "w_gate", "w_up", "wz", "wx",
                "wqkv", "wgu")
ROW_PARALLEL = ("wo", "w_down", "out_proj")


def tp_role(name) -> Optional[str]:
    """'col' | 'row' | None for a linear's name. Accepts bare parent
    keys ('wo'), rule paths ('layers/attn/wo') and tap names
    ('attn.wo')."""
    if not name:
        return None
    leaf = str(name).replace(".", "/").rsplit("/", 1)[-1]
    if leaf in COL_PARALLEL:
        return "col"
    if leaf in ROW_PARALLEL:
        return "row"
    return None


def _fit(dim: int, axis, mesh: Mesh):
    """axis if dim divides evenly over it, else None."""
    return axis if axis is not None and dim % _axis_size(mesh, axis) == 0 \
        else None


class _Ruler:
    def __init__(self, cfg, mesh: Mesh, policy: ShardingPolicy):
        self.cfg = cfg
        self.mesh = mesh
        self.policy = policy
        self.tp = "model" if "model" in mesh.axis_names else None
        fsdp: Any = None
        if policy.fsdp and "data" in mesh.axis_names:
            fsdp = ("pod", "data") if (policy.fsdp_pod
                                       and "pod" in mesh.axis_names) else "data"
        self.fsdp = fsdp

    # -- helpers ----------------------------------------------------------

    def _two(self, shape, a0, a1):
        """Spec for the trailing 2 dims; leading dims -> None (scan axes)."""
        lead = (None,) * (len(shape) - 2)
        return P(*lead, _fit(shape[-2], a0, self.mesh),
                 _fit(shape[-1], a1, self.mesh))

    def _one(self, shape, a0):
        lead = (None,) * (len(shape) - 1)
        return P(*lead, _fit(shape[-1], a0, self.mesh))

    # -- the rule table ----------------------------------------------------

    def spec(self, path: str, leaf) -> P:
        shape = leaf.shape
        name = path.rsplit("/", 1)[-1]
        cfg, mesh = self.cfg, self.mesh
        tp, fsdp = self.tp, self.fsdp

        if len(shape) == 0:
            return P()

        # ---- quantized leaves (packed low-rank binary) --------------------
        # Leading dims are scan stacks (layers / vlm groups) and stay
        # unsharded EXCEPT the expert dim of MoE leaves, which is
        # expert-parallel on the model axis (per-expert factors whole).
        if name in ("qu_t", "qv", "s1", "s2", "rmask"):
            base = 2 if name in ("qu_t", "qv") else 1
            lead = len(shape) - base
            spec = [None] * len(shape)
            # expert-parallel applies to true expert stacks only; the
            # dense *shared*-expert FFN under /moe/ is a plain linear
            # and takes the Megatron col/row pairing below (matching
            # the role layers.dense launches it with).
            expert = ("/moe/" in path or path.startswith("moe/")) \
                and "/shared/" not in path
            parent = path.split("/")[-2] if "/" in path else ""
            role = tp_role(parent)
            if expert and lead >= 1:
                spec[lead - 1] = _fit(shape[lead - 1], tp, mesh)
            elif role == "row":
                # row-parallel: V/s2 shard on (packed) d_in; U/s1 stay
                # replicated and the launch finishes with one psum. The
                # s2 check mirrors qv's packed dim so the pair never
                # shards inconsistently (kp % 32N == 0 <=> kp//32 % N).
                if name == "qv":                  # (..., d_in//32, r)
                    spec[-2] = _fit(shape[-2], tp, mesh)
                elif name == "s2":                # (..., d_in)
                    spec[-1] = tp if tp is not None and \
                        shape[-1] % (32 * _axis_size(mesh, tp)) == 0 \
                        else None
            elif role == "col":
                # column-parallel: U/s1 shard on d_out, shard-local
                # launch. Role-less packed linears (MLA w_dkv/w_kr,
                # mamba wB/wC/wdt) stay fully replicated — their FP
                # counterparts are not TP-sharded either, and the
                # kernel launch in ops dispatches them single-device,
                # so placement and launch always agree.
                if name == "qu_t":            # (..., r//32, d_out)
                    spec[-1] = _fit(shape[-1], tp, mesh)
                elif name == "qv" and self.policy.qv_sharded:
                    spec[-1] = _fit(shape[-1], tp, mesh)  # (.., K//32, r)
                elif name == "s1":
                    spec[-1] = _fit(shape[-1], tp, mesh)
            return P(*spec)
        # STE latents (block reconstruction runs single-host; replicate)
        if name in ("lu", "lv"):
            return P(*(None,) * len(shape))

        # ---- embeddings / head --------------------------------------------
        if name == "embed":
            if cfg.family == "audio":  # (K, V, d)
                return P(None, _fit(shape[-2], tp, mesh),
                         _fit(shape[-1], fsdp, mesh))
            return self._two(shape, tp, fsdp)        # (V, d)
        if "lm_head" in path and name == "w":        # (d, V)
            return self._two(shape, fsdp, tp)

        # ---- MoE -----------------------------------------------------------
        if "/moe/" in path or path.startswith("moe/"):
            if "router" in path:
                return P(*(None,) * len(shape))
            if "shared" in path:                     # dense shared expert FFN
                if name == "w" and ("w_down" in path):
                    return self._two(shape, tp, fsdp)
                if name == "w":
                    return self._two(shape, fsdp, tp)
                return self._one(shape, tp) if name == "b" \
                    else P(*(None,) * len(shape))
            if name == "w":                          # (..., E, d, f) experts
                lead = (None,) * (len(shape) - 3)
                ep = _fit(shape[-3], tp, mesh)
                return P(*lead, ep, _fit(shape[-2], fsdp, mesh), None)

        # ---- attention (incl. MLA / cross-attn) ----------------------------
        if name == "w":
            col = any(s in path for s in
                      ("/wq/", "/wk/", "/wv/", "/w_uk/", "/w_uv/",
                       "/w_gate/", "/w_up/"))
            row = any(s in path for s in ("/wo/", "/w_down/"))
            if col:
                # MLA up-projections contract over the small lora rank; only
                # the wide output dim is TP-sharded.
                a0 = fsdp if not any(s in path for s in ("/w_uk/", "/w_uv/")) \
                    else None
                return self._two(shape, a0, tp)
            if row:
                return self._two(shape, tp, fsdp)
            if any(s in path for s in ("/w_dkv/", "/w_kr/")):
                return self._two(shape, fsdp, None)
            # mamba2 split projections: z/x wide streams are TP-sharded
            # (columns == SSD heads); B/C/dt streams stay replicated.
            if any(s in path for s in ("/wz/", "/wx/")):
                return self._two(shape, fsdp, tp)
            if any(s in path for s in ("/wB/", "/wC/", "/wdt/")):
                return self._two(shape, fsdp, None)
            if "out_proj" in path:                   # row-parallel
                return self._two(shape, tp, fsdp)
        if name == "b":
            col = any(s in path for s in ("/wq/", "/wk/", "/wv/"))
            return self._one(shape, tp if col else None)

        # ---- mamba conv / gated-norm ride with the TP-sharded d_inner ------
        if name in ("conv_x", "conv_bx") or (name == "norm_w"
                                             and "mixer" in path):
            return self._one(shape, tp)

        # ---- everything else (norms, gates, conv, SSM params) -------------
        return P(*(None,) * len(shape))


def _path_str(kp) -> str:
    parts = []
    for p in kp:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(cfg, params, mesh: Mesh,
                 policy: ShardingPolicy = DEFAULT):
    """PartitionSpec tree mirroring `params` (works on SDS trees too)."""
    ruler = _Ruler(cfg, mesh, policy)
    return jax.tree_util.tree_map_with_path(
        lambda kp, l: ruler.spec(_path_str(kp), l), params)


def batch_pspecs(cfg, batch, mesh: Mesh, grad_accum: int = 1):
    """Batch dim -> all DP axes; everything else replicated. With
    grad_accum > 1 the leading dim is the microbatch scan axis
    (replicated) and the *second* dim is the sharded batch."""
    dp = data_axes(mesh)
    bdim = 1 if grad_accum > 1 else 0

    def spec(leaf):
        if len(leaf.shape) <= bdim:
            return P(*(None,) * len(leaf.shape))
        b = leaf.shape[bdim]
        a = dp if dp and b % _axis_size(mesh, dp) == 0 else None
        out = [None] * len(leaf.shape)
        out[bdim] = a
        return P(*out)

    return jax.tree.map(spec, batch)


def cache_pspecs(cfg, cache, mesh: Mesh,
                 policy: ShardingPolicy = DEFAULT, paged: bool = False):
    """KV / SSM cache sharding: batch on DP axes; heads (or sequence) on
    model. Cache leaves carry a leading layer-stack dim.

    paged: the attention leaves are page pools (serve.paging —
    ``(L, n_pages, page_size, Hkv, hd)`` instead of a batch-indexed
    rectangle). They shard the kv-head dim on ``model`` exactly like
    the rectangular pool; there is no sequence-dim fallback (the page
    dims must stay whole for block-table addressing), so non-divisible
    head counts replicate — matching the replicated single-device
    launch fallback of ``kernels.ops.paged_attention``. State leaves
    (SSM / conv / image KV) stay batch-indexed and keep their specs."""
    dp = data_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None

    def spec(path: str, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        name = path.rsplit("/", 1)[-1]
        # (L, B, ...) — batch at dim 1
        def b_axis(i=1):
            return dp if dp and shape[i] % _axis_size(mesh, dp) == 0 else None

        if paged and name in ("k", "v", "c_kv", "k_rope") \
                and "cross_kv" not in path:
            if name in ("k", "v"):        # (L[, G], NP, PS, Hkv, hd)
                lead = len(shape) - 4
                return P(*((None,) * lead), None, None,
                         _fit(shape[-2], tp, mesh), None)
            return P(*(None,) * len(shape))   # MLA pools: latent dims small

        if name in ("k", "v"):            # (L[, G], B, S, Hkv, hd)
            lead = len(shape) - 4            # layer-stack dims before batch
            h_ax = _fit(shape[-2], tp, mesh)
            s_ax = None
            if h_ax is None and policy.seq_shard_cache:
                s_ax = _fit(shape[-3], tp, mesh)
            return P(*((None,) * lead), b_axis(lead), s_ax, h_ax, None)
        if name == "c_kv":                # (L, B, S, dc)
            return P(None, b_axis(), _fit(shape[-2], tp, mesh), None)
        if name == "k_rope":              # (L, B, S, 1, dr)
            return P(None, b_axis(), _fit(shape[-3], tp, mesh), None, None)
        if name == "ssm":                 # (L, B, H, P, N)
            return P(None, b_axis(), _fit(shape[-3], tp, mesh), None, None)
        if name == "conv_x":              # (L, B, K-1, d_inner)
            return P(None, b_axis(), None, _fit(shape[-1], tp, mesh))
        if name in ("conv_B", "conv_C"):  # small replicated streams
            return P(None, b_axis(), None, None)
        return P(*(None,) * len(shape))

    return jax.tree_util.tree_map_with_path(
        lambda kp, l: spec(_path_str(kp), l), cache)


def replicate_specs(tree):
    return jax.tree.map(lambda l: P(*(None,) * len(l.shape)), tree)


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))

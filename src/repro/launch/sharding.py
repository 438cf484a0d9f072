"""Divisibility-aware sharding policy.

Two halves:

* **Activations** — model code annotates tensors with logical axis names
  (``policy.constrain(x, ("batch", "seq", "heads", None))``); MeshPolicy
  resolves each name through LOGICAL_RULES, dropping any assignment that
  does not divide the dimension or would reuse a mesh axis twice.  On a
  single device (smoke tests) the default no-op Policy is used instead.

* **Parameters / caches** — ``param_specs`` and ``cache_specs`` walk the
  pytrees and classify leaves by their key-path (wq/wk/wv/wo, mlp up/down,
  MoE experts, recurrent states, KV caches...), producing a PartitionSpec
  tree for ``jax.jit(in_shardings=...)``.

Per-arch quirks are driven by the config (``attn_shard``):
``replicate`` (heads don't divide the 16-way model axis: recurrentgemma
10H, gemma2/gemma3 8H), ``head_dim`` (llava 56H/8kv: shard the 128-wide
head dim; pjit input shardings cannot pad), and the beyond-paper perf
variants ``seq2d`` / ``dp2d`` / ``seq2d_fsdp`` (EXPERIMENTS.md §Perf).
``shard_experts_2d`` (kimi-k2): expert weights sharded over model AND
data, ZeRO-style, to fit 1T params.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import Policy

Tree = Any


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1)
    return n


class MeshPolicy(Policy):
    """Activation-constraint resolver for a (pod,) data, model mesh."""

    def __init__(self, mesh: Mesh, cfg: ModelConfig):
        self.mesh = mesh
        self.cfg = cfg
        data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        self.data_axes = data
        heads_rule = "model"
        if cfg.attn_shard in ("replicate", "head_dim", "seq2d",
                              "seq2d_fsdp", "dp2d"):
            heads_rule = None
        # seq2d ("2D token sharding"): tokens shard over data x model and
        # weights replicate — the fix for archs whose heads don't divide
        # the model axis (see EXPERIMENTS.md §Perf H2).  dp2d goes further
        # when global_batch >= chips: batch shards over BOTH axes and
        # attention is fully local (H2 iteration 3).
        self.seq2d = cfg.attn_shard in ("seq2d", "seq2d_fsdp")
        self.dp2d = cfg.attn_shard == "dp2d"
        self.rules = {
            "batch": data + ("model",) if self.dp2d else data,
            "seq": "model" if self.seq2d else None,
            "seq_chunks": "model" if self.seq2d else None,
            "heads": heads_rule,
            "kv_heads": heads_rule,
            "head_dim": "model" if cfg.attn_shard == "head_dim" else None,
            "ffn": None if (self.seq2d or self.dp2d) else "model",
            "experts": "model",
            "expert_ffn": "model",
            "vocab": None if self.dp2d else "model",
            "rnn": "model",
            # xLSTM: sharding the inner head dim causes SPMD resharding
            # storms through the chunked reshapes (measured 1.7 TB/chip of
            # collectives); baseline replicates the mixer over `model`.
            "mlstm_dh": None,
            # decode KV caches: shard the key/value sequence over `model`
            # when the kv heads cannot use it (context-parallel decode)
            "kv_seq": "model",
            # federated cohort chunk axis (one client per data slice): the
            # streaming round engine scans over chunks and each chunk's
            # client axis shards over data/pod, so the per-chunk masked
            # aggregation fold lowers to the round's all-reduce
            "cohort": data,
        }
        # resolution priority when two logical names want the same mesh axis
        self.priority = {"kv_seq": 1, "seq": 1}  # vocab/heads first

    def spec(self, x_shape: Sequence[int],
             axes: Sequence[Optional[str]]) -> P:
        used = set()
        out: list = [None] * len(tuple(axes))
        order = sorted(range(len(out)),
                       key=lambda i: self.priority.get(tuple(axes)[i], 0)
                       if tuple(axes)[i] else 9)
        axes_t = tuple(axes)
        for i in order:
            name = axes_t[i]
            dim = x_shape[i]
            assign = self.rules.get(name) if name else None
            if assign is None:
                continue
            assign_t = (assign,) if isinstance(assign, str) else tuple(assign)
            # longest usable prefix: lets dp2d's ("data","model") batch rule
            # fall back to plain data parallelism when batch < chips
            while assign_t:
                if (not any(a in used for a in assign_t)
                        and _axis_size(self.mesh, assign_t) > 1
                        and dim % _axis_size(self.mesh, assign_t) == 0):
                    out[i] = (assign_t if len(assign_t) > 1
                              else assign_t[0])
                    used.update(assign_t)
                    break
                assign_t = assign_t[:-1]
        return P(*out)

    def constrain(self, x: jax.Array, axes: Sequence[Optional[str]]):
        spec = self.spec(x.shape, axes)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "idx"):
            keys.append(f"#{p.idx}")
    return tuple(keys)


def _div(mesh: Mesh, dim: int, axis) -> bool:
    return dim % _axis_size(mesh, axis) == 0


def _leaf_param_spec(keys: Tuple[str, ...], shape: Tuple[int, ...],
                     cfg: ModelConfig, mesh: Mesh, stacked: bool) -> P:
    """Spec for one parameter leaf; ``stacked`` means a leading period axis."""
    body = shape[1:] if stacked else shape
    name = keys[-1]
    parent = keys[-2] if len(keys) > 1 else ""
    spec: Tuple = (None,) * len(body)
    m = "model"
    ms = _axis_size(mesh, m)

    def ok(i, axis=m):
        return _div(mesh, body[i], axis)

    in_mixer = "mixer" in keys or "cross" in keys   # cross: same layout
    in_experts = "experts" in keys
    in_embed = "embed" in keys

    # SSM (xLSTM) mixers stay replicated at baseline — see MeshPolicy note
    if in_mixer and cfg.arch_type == "ssm":
        return P(*((None,) + spec if stacked else spec))

    # seq2d/dp2d (H2): weights replicate, tokens shard 2D.  seq2d keeps
    # the embedding vocab-sharded (seq chunks can use it); dp2d replicates
    # it too — batch holds the model axis, so a vocab-sharded table would
    # be re-gathered per CE chunk (measured 150 GiB/step, H2 iter 3).
    if cfg.attn_shard == "seq2d" and not in_embed:
        return P(*((None,) + spec if stacked else spec))
    if cfg.attn_shard == "dp2d":
        return P(*((None,) + spec if stacked else spec))
    # seq2d_fsdp (H1, llava-class): tokens shard 2D like seq2d, and the
    # weights shard over `data` (ZeRO-3: all-gathered per layer use) since
    # a 34B model cannot replicate into 16 GiB chips.
    if cfg.attn_shard == "seq2d_fsdp" and not in_embed:
        fs = [None] * len(body)
        for i, dim in enumerate(body):
            if _div(mesh, dim, "data") and dim >= 64:
                fs[i] = "data"
                break
        fs = tuple(fs)
        return P(*((None,) + fs if stacked else fs))

    if in_embed and name in ("table",):
        if ok(0):
            spec = (m, None)
    elif in_embed and name == "tables":
        if ok(1):
            spec = (None, m, None)
    elif name == "w" and parent == "unembed":
        if ok(len(body) - 1):                          # ([NC,] D, V)
            spec = (None,) * (len(body) - 1) + (m,)
    elif in_experts and name in ("gate", "up"):        # (E, D, F)
        if cfg.shard_experts_2d and ok(0) and _div(mesh, body[2], "data"):
            spec = (m, None, "data")
        elif ok(0):
            spec = (m, None, None)
        elif ok(2):
            spec = (None, None, m)
    elif in_experts and name == "down":                # (E, F, D)
        if cfg.shard_experts_2d and ok(0) and _div(mesh, body[1], "data"):
            spec = (m, "data", None)
        elif ok(0):
            spec = (m, None, None)
        elif ok(1):
            spec = (None, m, None)
    elif name == "router":
        spec = (None, None)
    elif in_mixer and name == "wq":                    # (D, H, Dh)
        if cfg.attn_shard == "head_dim" and ok(2):
            spec = (None, None, m)
        elif ok(1) and cfg.attn_shard != "replicate":
            spec = (None, m, None)
    elif in_mixer and name in ("wk", "wv"):            # (D, Kh, Dh)
        if cfg.attn_shard == "head_dim" and ok(2):
            spec = (None, None, m)
        elif ok(1) and cfg.attn_shard not in ("replicate",):
            spec = (None, m, None)
    elif in_mixer and name == "wo":                    # (H, Dh, D)
        if cfg.attn_shard == "head_dim" and ok(1):
            spec = (None, m, None)
        elif ok(0) and cfg.attn_shard != "replicate":
            spec = (m, None, None)
    elif in_mixer and name in ("w_in", "w_gate", "w_up"):   # (D, Dr/Di)
        if ok(1):
            spec = (None, m)
    elif in_mixer and name in ("w_out", "w_down"):     # (Dr/Di, D)
        if ok(0):
            spec = (m, None)
    elif in_mixer and name == "conv":                  # (tw, Dr/Di)
        if ok(1):
            spec = (None, m)
    elif in_mixer and name in ("w_r", "b_r", "w_i", "b_i", "lam"):  # (Dr,)
        if ok(0):
            spec = (m,)
    elif in_mixer and name in ("wq", "wk", "wv") and len(body) == 3:
        pass  # handled above (attention); mlstm variant below
    elif in_mixer and len(body) == 3 and name in ("r",):
        spec = (None, None, None, None)[:len(body)]
    elif "mlp" in keys or "shared" in keys:
        if name in ("gate", "up") and ok(1):           # (D, F)
            spec = (None, m)
        elif name == "down" and ok(0):                 # (F, D)
            spec = (m, None)
    elif name == "w" and parent == "frontend_proj":
        spec = (None, None)

    # mLSTM block-diagonal qkv: (NH, DH, DH) -> shard output DH
    if in_mixer and name in ("wq", "wk", "wv") and len(body) == 3 \
            and body[0] == cfg.n_heads and body[1] == body[2]:
        spec = (None, None, m) if _div(mesh, body[2], m) else (None,) * 3

    if stacked:
        spec = (None,) + tuple(spec)
    return P(*spec)


def param_specs(params: Tree, cfg: ModelConfig, mesh: Mesh) -> Tree:
    """PartitionSpec tree matching ``params`` (works on ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        keys = _path_keys(path)
        stacked = "periods" in keys
        specs.append(_leaf_param_spec(keys, tuple(leaf.shape), cfg, mesh,
                                      stacked))
    return jax.tree_util.tree_unflatten(treedef, specs)


def cohort_specs(params: Tree, cfg: ModelConfig, mesh: Mesh) -> Tree:
    """NamedSharding tree for a *stacked cohort* of client models.

    The leading client axis shards over ``data``/``pod`` (one client per
    data slice); each client's parameters keep their model-parallel layout
    from :func:`param_specs` within.  The streaming round engine reshapes
    to ``(n_chunks, chunk, ...)`` inside the jit, so each scan step is one
    data-parallel cohort chunk of this layout.
    """
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, P(data, *tuple(s))),
        param_specs(params, cfg, mesh),
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Cache specs (decode)
# ---------------------------------------------------------------------------

def _leaf_cache_spec(keys: Tuple[str, ...], shape: Tuple[int, ...],
                     cfg: ModelConfig, mesh: Mesh, stacked: bool,
                     data_axes) -> P:
    body = shape[1:] if stacked else shape
    name = keys[-1]
    m = "model"
    batch = body[0]
    batch_ok = _div(mesh, batch, data_axes)
    spec = [data_axes if batch_ok else None] + [None] * (len(body) - 1)

    if name in ("k", "v") and len(body) == 4:          # (B, S, Kh, Dh)
        if not batch_ok and _div(mesh, body[1], data_axes):
            spec[1] = data_axes                        # context-parallel cache
        if cfg.attn_shard == "head_dim" and _div(mesh, body[3], m):
            spec[3] = m
        elif _div(mesh, body[2], m) and cfg.attn_shard != "replicate":
            spec[2] = m
        elif spec[1] is None and _div(mesh, body[1], m):
            spec[1] = m                                # kv-seq over model
    elif name == "C" and len(body) == 4:               # (B, NH, DH, DH)
        if _div(mesh, body[2], m):
            spec[2] = m                                # value index
    elif name in ("y",) and len(body) == 2:            # rglru (B, Dr)
        if _div(mesh, body[1], m):
            spec[1] = m
    elif name == "conv" and len(body) == 3:            # (B, tw-1, Dr/Di)
        if _div(mesh, body[2], m):
            spec[2] = m
    elif name == "n" and len(body) == 3:               # mlstm (B, NH, DH)
        if _div(mesh, body[2], m):
            spec[2] = m

    if stacked:
        spec = [None] + spec
    return P(*spec)


def cache_specs(cache: Tree, cfg: ModelConfig, mesh: Mesh) -> Tree:
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, leaf in flat:
        keys = _path_keys(path)
        stacked = "periods" in keys
        specs.append(_leaf_cache_spec(keys, tuple(leaf.shape), cfg, mesh,
                                      stacked, data))
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Input (batch) specs
# ---------------------------------------------------------------------------

def batch_specs(batch: Tree, mesh: Mesh, policy=None) -> Tree:
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if policy is not None and getattr(policy, "dp2d", False):
        data = data + ("model",)

    def leaf(x):
        if x.ndim == 0:
            return P()
        if _div(mesh, x.shape[0], data):
            return P(data, *([None] * (x.ndim - 1)))
        return P(*([None] * x.ndim))

    return jax.tree.map(leaf, batch)


def to_named(tree_of_specs: Tree, mesh: Mesh) -> Tree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_of_specs,
        is_leaf=lambda x: isinstance(x, P))


def bytes_per_chip(tree: Tree, specs: Tree, mesh: Mesh) -> int:
    """Per-device bytes of a sharded tree (ceil for uneven shards)."""
    import math
    total = 0
    for leaf, spec in zip(jax.tree.leaves(tree),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda x: isinstance(x, P))):
        per = leaf.dtype.itemsize
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            per *= math.ceil(dim / _axis_size(mesh, axes))
        total += per
    return total

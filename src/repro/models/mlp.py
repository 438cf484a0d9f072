"""Dense GLU MLP and Mixture-of-Experts layers.

MoE uses capacity-based top-k routing with a *per-sequence* routing group:
each batch element routes its own tokens into an ``(E, C, D)`` buffer via a
one-hot-free gather.  This keeps the dispatch local to the ``data`` mesh
shards (batch-aligned gather), so under pjit the only cross-shard collective
the layer needs is the expert-output combine (an all-reduce over ``model``
when experts or expert-ffn dims are model-sharded) — the classic
expert/tensor-parallel hybrid.  Dropped tokens (over capacity) fall into a
garbage slot and are zero-combined, as in Switch/GShard.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import common
from repro.models.common import Policy, NO_POLICY


# ---------------------------------------------------------------------------
# Dense GLU MLP (gate, up, down)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = cfg.jnp_param_dtype()
    kg, ku, kd = jax.random.split(key, 3)
    p = {
        "up": common.dense_init(ku, (d, f), dt),
        "down": common.dense_init(kd, (f, d), dt, fan_in=f),
    }
    if cfg.mlp_glu:
        p["gate"] = common.dense_init(kg, (d, f), dt)
    return p


def apply_mlp(p: dict, x: jax.Array, policy: Policy = NO_POLICY, *,
              gelu_exact: bool = False) -> jax.Array:
    u = jnp.einsum("...d,df->...f", x, p["up"].astype(x.dtype))
    if "gate" in p:
        g = jnp.einsum("...d,df->...f", x, p["gate"].astype(x.dtype))
        h = jax.nn.gelu(g, approximate=not gelu_exact) * u
    else:
        h = jax.nn.gelu(u, approximate=not gelu_exact)
    h = policy.constrain(h, ("batch", "seq", "ffn"))
    return jnp.einsum("...f,fd->...d", h, p["down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    de = m.d_expert or cfg.d_ff
    dt = cfg.jnp_param_dtype()
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    # router always spans the REAL experts; only the weight tensors pad
    e = max(m.pad_to, m.n_experts) if m.pad_to else m.n_experts
    p = {
        "router": common.dense_init(kr, (d, m.n_experts), jnp.float32),
        "experts": {
            "gate": common.dense_init(kg, (e, d, de), dt, fan_in=d),
            "up": common.dense_init(ku, (e, d, de), dt, fan_in=d),
            "down": common.dense_init(kd, (e, de, d), dt, fan_in=de),
        },
    }
    if m.n_shared:
        sub = jax.random.split(ks, m.n_shared)
        p["shared"] = [init_mlp(sub[i], cfg, d_ff=de) for i in range(m.n_shared)]
    return p


def _capacity(moe: MoEConfig, tokens_per_group: int) -> int:
    c = int(moe.top_k * tokens_per_group * moe.capacity_factor / moe.n_experts)
    return max(min(c, tokens_per_group), 1)


def route_topk(router_logits: jax.Array, moe: MoEConfig,
               capacity: int, e_pad: int = 0
               ) -> Tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Top-k routing with per-group capacity.

    router_logits: (B, S, E).  Returns
      slot_idx  (B, E, C) int32 token index per expert slot (S = garbage),
      slot_gate (B, E, C) f32 combine weight per slot (0 for empty),
      token_expert (B, S, K) chosen expert per token (diagnostics),
      aux: router z-loss and load-balance loss terms.
    """
    b, s, e = router_logits.shape
    logits = router_logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    e_out = max(e_pad, e)

    topk_prob, topk_idx = jax.lax.top_k(probs, moe.top_k)       # (B, S, K)
    # normalize the combine weights over the selected experts
    topk_prob = topk_prob / jnp.maximum(
        jnp.sum(topk_prob, axis=-1, keepdims=True), 1e-9)

    # position of each (token, k) within its expert's queue
    onehot = jax.nn.one_hot(topk_idx, e, dtype=jnp.int32)       # (B, S, K, E)
    flat = onehot.reshape(b, s * moe.top_k, e)
    rank = jnp.cumsum(flat, axis=1) - flat                      # (B, S*K, E)
    rank = jnp.sum(rank * flat, axis=-1).reshape(b, s, moe.top_k)
    within = rank < capacity

    # scatter token indices into (B, E, C) slots
    tok_ids = jnp.broadcast_to(jnp.arange(s)[None, :, None], (b, s, moe.top_k))
    # buffers sized to the (possibly padded) expert axis; pad experts can
    # never appear in topk_idx so their slots stay at the garbage index
    slot_idx = jnp.full((b, e_out, capacity), s, dtype=jnp.int32)
    slot_gate = jnp.zeros((b, e_out, capacity), dtype=jnp.float32)

    flat_e = topk_idx.reshape(b, -1)
    flat_r = rank.reshape(b, -1)
    flat_t = tok_ids.reshape(b, -1)
    flat_g = jnp.where(within, topk_prob, 0.0).reshape(b, -1)
    flat_keep = within.reshape(b, -1)
    # out-of-capacity entries scatter to a dummy slot via clamped rank? No:
    # drop them by redirecting to expert-slot (e-1, capacity-1)? Cleaner: use
    # mode="drop" — JAX scatters with out-of-bound indices are dropped.
    flat_r = jnp.where(flat_keep, flat_r, capacity)             # OOB -> dropped

    def scatter_one(si, sg, te, tr, tt, tg):
        idx = jnp.stack([te, tr], axis=-1)                      # (S*K, 2)
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1))
        si = jax.lax.scatter(si, idx, tt, dnums,
                             mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
        sg = jax.lax.scatter(sg, idx, tg, dnums,
                             mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
        return si, sg

    slot_idx, slot_gate = jax.vmap(scatter_one)(
        slot_idx, slot_gate, flat_e, flat_r, flat_t, flat_g)

    # aux losses (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))                           # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(topk_idx[..., 0], e), axis=1) / s, axis=0)
    load_balance = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"load_balance": load_balance * moe.load_balance_loss,
           "router_z": z_loss * moe.router_z_loss}
    return slot_idx, slot_gate, topk_idx, aux


def apply_moe(p: dict, x: jax.Array, cfg: ModelConfig,
              policy: Policy = NO_POLICY) -> Tuple[jax.Array, dict]:
    """x: (B, S, D) -> (out, aux_losses)."""
    m = cfg.moe
    b, s, d = x.shape
    capacity = _capacity(m, s)
    e_pad = max(m.pad_to, m.n_experts) if m.pad_to else m.n_experts

    router_logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                               p["router"])
    slot_idx, slot_gate, _, aux = route_topk(router_logits, m, capacity,
                                             e_pad=e_pad)

    # dispatch: gather tokens into (B, E, C, D); garbage index S reads zeros
    xp = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)
    dispatched = jnp.take_along_axis(
        xp[:, None, :, :],                                      # (B, 1, S+1, D)
        slot_idx[..., None].clip(0, s),                         # (B, E, C, 1)
        axis=2)                                                 # (B, E, C, D)
    dispatched = policy.constrain(dispatched, ("batch", "experts", None, None))

    w = p["experts"]
    g = jnp.einsum("becd,edf->becf", dispatched, w["gate"].astype(x.dtype))
    u = jnp.einsum("becd,edf->becf", dispatched, w["up"].astype(x.dtype))
    h = jax.nn.gelu(g) * u
    h = policy.constrain(h, ("batch", "experts", None, "expert_ffn"))
    y = jnp.einsum("becf,efd->becd", h, w["down"].astype(x.dtype))

    # combine: scatter-add back to token positions, weighted by gate
    y = y * slot_gate[..., None].astype(y.dtype)
    flat_y = y.reshape(b, e_pad * capacity if m.pad_to else
                       m.n_experts * capacity, d)
    flat_i = slot_idx.reshape(b, -1)

    def combine_one(buf, idx, vals):
        return buf.at[idx].add(vals, mode="drop")

    out = jax.vmap(combine_one)(jnp.zeros((b, s, d), y.dtype), flat_i, flat_y)
    out = policy.constrain(out, ("batch", "seq", None))

    for shared in p.get("shared", []):
        out = out + apply_mlp(shared, x, policy)
    return out, aux

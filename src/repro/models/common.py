"""Shared building blocks: norms, embeddings, init helpers, sharding hooks.

Parameters are plain nested dicts of ``jnp`` arrays.  Every ``init_*``
function takes an explicit PRNG key; every ``apply_*`` function is pure.

Sharding is threaded through a :class:`Policy` object: model code annotates
activations with *logical axis names* and the policy (installed by
``launch/sharding.py``) resolves them to ``with_sharding_constraint`` under a
mesh, or to the identity on a single device (smoke tests).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Sharding policy hook
# ---------------------------------------------------------------------------

class Policy:
    """No-op default policy (single device).  See launch/sharding.py."""

    def constrain(self, x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
        return x


NO_POLICY = Policy()


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    """Truncated-normal fan-in init (LeCun-ish), matching common LLM practice."""
    if fan_in is None:
        fan_in = shape[0] if len(shape) > 1 else shape[-1]
    std = 1.0 / jnp.sqrt(jnp.maximum(fan_in, 1)).astype(jnp.float32)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm (gemma-style: weight is a residual around 1)
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.zeros((d,), dtype)}


def apply_rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    out = x * (1.0 + p["scale"].astype(jnp.float32))
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# LayerNorm (with bias) and the config's choice of norm
# ---------------------------------------------------------------------------

def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def apply_layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    out = x * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(dtype)


def init_norm(cfg, d: int, dtype) -> dict:
    """The config's norm: ``cfg.norm`` is "rms" or "layer"."""
    if cfg.norm == "layer":
        return init_layernorm(d, dtype)
    return init_rmsnorm(d, dtype)


def apply_norm(cfg, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "layer":
        return apply_layernorm(p, x, cfg.norm_eps)
    return apply_rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# GroupNorm (paper footnote 1: replaces BatchNorm in all ResNets)
# ---------------------------------------------------------------------------

def init_groupnorm(channels: int, dtype) -> dict:
    return {"scale": jnp.ones((channels,), dtype),
            "bias": jnp.zeros((channels,), dtype)}


def apply_groupnorm(p: dict, x: jax.Array, groups: int = 8,
                    eps: float = 1e-5) -> jax.Array:
    """x: (B, H, W, C) channels-last."""
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    dtype = x.dtype
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mean = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xf, axis=(1, 2, 4), keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(b, h, w, c)
    return (xf * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Softcap (gemma-2)
# ---------------------------------------------------------------------------

def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)                       # (head_dim // 2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, *,
               seq_axis: int = 1) -> jax.Array:
    """x: (B, S, N, Dh), or (B, N, S, Dh) with ``seq_axis`` 2;
    positions: (B, S) or (S,) int32."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                    # (dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
    heads_axis = 3 - seq_axis
    cos = jnp.expand_dims(jnp.cos(angles), heads_axis)
    sin = jnp.expand_dims(jnp.sin(angles), heads_axis)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions: jax.Array, dim: int,
                         max_period: float) -> jax.Array:
    """Audiocraft's absolute position embedding: ``[cos(p w), sin(p w)]``
    with ``w_i = max_period ** (-i / (dim / 2 - 1))``.  positions: (S,)
    -> (S, dim) float32."""
    half = dim // 2
    freqs = max_period ** -(jnp.arange(half, dtype=jnp.float32)
                            / (half - 1))
    phase = positions.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.cos(phase), jnp.sin(phase)], axis=-1)


# ---------------------------------------------------------------------------
# Token embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype) -> dict:
    return {"table": embed_init(key, (vocab, d_model), dtype)}


def apply_embedding(p: dict, tokens: jax.Array, *, scale: bool = True) -> jax.Array:
    h = jnp.take(p["table"], tokens, axis=0)
    if scale:
        h = h * jnp.asarray(jnp.sqrt(p["table"].shape[-1]), h.dtype)
    return h


def apply_unembedding(p: dict, h: jax.Array) -> jax.Array:
    return jnp.einsum("...d,vd->...v", h, p["table"])


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_cross_entropy_sum(logits: jax.Array, labels: jax.Array,
                              mask: Optional[jax.Array] = None
                              ) -> jax.Array:
    """Sum (not mean) of per-position NLL, over the positions where
    ``mask`` is true if given; sharding-friendly (see below)."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - m).astype(jnp.float32)
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) \
        + m[..., 0].astype(jnp.float32)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1).astype(jnp.float32)
    if mask is not None:
        return jnp.sum(jnp.where(mask, logz - gold, 0.0))
    return jnp.sum(logz - gold)


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean CE over (optionally masked) positions.  logits: (..., V).

    Written to stay efficient when the vocab axis is model-sharded: the
    gold logit is picked with a one-hot contraction (local + all-reduce)
    rather than take_along_axis (which would all-gather the full logits),
    and reductions accumulate in f32 while logits stay in their compute
    dtype.
    """
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - m).astype(jnp.float32)
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) \
        + m[..., 0].astype(jnp.float32)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1).astype(jnp.float32)
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)

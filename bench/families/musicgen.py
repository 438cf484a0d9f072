"""MusicGen (a decoder over EnCodec tokens with cross-attention to the
text conditioning): its adapter, its initial weights, its client data and
its training FLOPs per crop."""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp


def model_config(cfg: dict):
    """The program's registry entry (``program_config``) at the widths,
    depth and numerics the configuration file states."""
    from repro import configs
    from repro.configs.base import StubFrontend
    base = configs.get_config(cfg["program_config"])
    return base.with_overrides(
        n_layers=cfg["n_layers"], exit_layer=cfg["exit_layer"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab_size=cfg["card"],
        n_codebooks=cfg["n_codebooks"],
        frontend=StubFrontend(kind=base.frontend.kind,
                              n_tokens=cfg["cond_tokens"],
                              d_in=cfg["cond_dim"]),
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def adapter(cfg: dict):
    from repro.core.adapters import LMAdapter
    return LMAdapter(model_config(cfg))


def _fan_in(name: str, shape) -> int:
    if name in ("wq", "wk", "wv"):          # (..., D, H, Dh)
        return shape[-3]
    if name == "wo":                        # (..., H, Dh, D)
        return shape[-3] * shape[-2]
    if name == "tables":                    # (NC, V + 1, D)
        return shape[-1]
    return shape[-2]                        # (..., in, out)


def rule(name: str, key, s):
    """LayerNorm scales 1, biases 0, every matrix and embedding truncated
    normal (2 sigma) with sigma 1/sqrt(fan-in)."""
    if name == "scale":
        return jnp.ones(s.shape, s.dtype)
    if name in ("bias", "b"):
        return jnp.zeros(s.shape, s.dtype)
    return (jax.random.truncated_normal(key, -2.0, 2.0, s.shape, jnp.float32)
            / math.sqrt(_fan_in(name, s.shape))).astype(s.dtype)


def delay_grid(codes, card: int, steps: int):
    """The delay pattern of ``codes`` (n, T, K) over ``steps`` steps:
    codebook k's frame f at step f + 1 + k, the special token ``card``
    everywhere else (the start step, the delays, the padding)."""
    t, k = codes.shape[1], codes.shape[2]
    frame = jnp.arange(steps)[:, None] - 1 - jnp.arange(k)[None, :]
    valid = (frame >= 0) & (frame < t)
    picked = jnp.take_along_axis(codes, jnp.clip(frame, 0, t - 1)[None],
                                 axis=1)
    return jnp.where(valid[None], picked, card).astype(jnp.int32)


def data(cfg: dict, traffic: dict, key) -> List[dict]:
    """Per client, ``points_per_client`` crops: uniform EnCodec codes of
    ``crop_frames`` frames in the delay pattern over ``seq_len + 1``
    steps, and one conditioning per crop (standard normal embeddings, a
    random 1..``cond_tokens`` of them valid).  One jitted call, the
    clients drawn under ``vmap``."""
    clients, n = traffic["clients"], traffic["points_per_client"]
    t, k, card = cfg["crop_frames"], cfg["n_codebooks"], cfg["card"]
    n_cond, d_cond = cfg["cond_tokens"], cfg["cond_dim"]

    @jax.jit
    def make(key):
        def client(key):
            kc, ke, kl = jax.random.split(key, 3)
            codes = jax.random.randint(kc, (n, t, k), 0, card, jnp.int32)
            lengths = jax.random.randint(kl, (n,), 1, n_cond + 1)
            return {"tokens": delay_grid(codes, card, cfg["seq_len"] + 1),
                    "cond": jax.random.normal(ke, (n, n_cond, d_cond),
                                              jnp.float32),
                    "cond_mask": jnp.arange(n_cond)[None, :]
                    < lengths[:, None]}

        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(clients))
        out = jax.vmap(client)(keys)
        return [{name: v[i] for name, v in out.items()}
                for i in range(clients)]

    return make(key)


def _forward(cfg: dict, layers: int, heads: int) -> float:
    """Forward FLOPs of one crop through ``layers`` layers and ``heads``
    sets of codebook heads.  The self-attention scores are counted over
    all ``seq_len`` x ``seq_len`` pairs, as the program computes them (its
    causal mask selects, it does not skip)."""
    s, d, f = cfg["seq_len"], cfg["d_model"], cfg["d_ff"]
    a = cfg["n_heads"] * cfg["head_dim"]
    n = cfg["cond_tokens"]
    layer = (2 * s * d * a * 4            # self-attention q, k, v, o
             + 2 * 2 * s * s * a          # its scores and their values
             + 2 * s * d * a * 2          # cross-attention q, o
             + 2 * n * d * a * 2          # its k, v of the conditioning
             + 2 * 2 * s * n * a          # its scores and values
             + 2 * 2 * s * d * f)         # FFN up, down
    return layers * layer + heads * 2.0 * s * d * cfg["card"] * \
        cfg["n_codebooks"]


def client_flops(cfg: dict, simple: bool) -> float:
    """Training FLOPs of one crop of a simple or a complex client: three
    times the forward, and twice the conditioning projection's (its input
    is data, so no gradient flows into it)."""
    proj = 2.0 * cfg["cond_tokens"] * cfg["cond_dim"] * cfg["d_model"]
    if simple:
        return 3.0 * _forward(cfg, cfg["exit_layer"], 1) + 2.0 * proj
    return 3.0 * _forward(cfg, cfg["n_layers"], 2) + 2.0 * proj

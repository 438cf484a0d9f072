"""PreActResNet with GroupNorm (FedHeN's model): its adapter, its initial
weights, its client data and its training FLOPs per image."""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from bench import flops


def adapter(cfg: dict):
    from repro.core.adapters import ResNetAdapter
    return ResNetAdapter(cfg["n_classes"])


def rule(name: str, key, s):
    """GroupNorm scales 1, biases and the mix-pool logit 0, convolutions
    He-normal, dense layers truncated normal over the fan-in."""
    if name == "scale":
        return jnp.ones(s.shape, s.dtype)
    if name in ("bias", "b", "alpha"):
        return jnp.zeros(s.shape, s.dtype)
    if len(s.shape) == 4:                      # HWIO convolution
        fan_in = s.shape[0] * s.shape[1] * s.shape[2]
        return (jax.random.normal(key, s.shape, jnp.float32)
                * math.sqrt(2.0 / fan_in)).astype(s.dtype)
    return (jax.random.truncated_normal(key, -2.0, 2.0, s.shape, jnp.float32)
            / math.sqrt(s.shape[0])).astype(s.dtype)


def data(cfg: dict, traffic: dict, key) -> List[dict]:
    """Class-conditional CIFAR-shaped images: a smooth prototype per class
    plus noise; each client's labels follow its own Dirichlet draw of class
    proportions (concentration ``dirichlet_alpha``).  One jitted call, the
    clients drawn under ``vmap``."""
    n_cls, size = cfg["n_classes"], cfg["image_size"]
    ch = cfg["image_channels"]
    clients, n = traffic["clients"], traffic["points_per_client"]
    alpha = traffic["dirichlet_alpha"]

    @jax.jit
    def make(key):
        kp, kc = jax.random.split(key)
        base = jax.random.normal(kp, (n_cls, 8, 8, ch), jnp.float32)
        protos = jnp.repeat(jnp.repeat(base, size // 8, 1), size // 8, 2)

        def client(k):
            kd, kl, kn = jax.random.split(k, 3)
            props = jax.random.dirichlet(kd, jnp.full((n_cls,), alpha))
            labels = jax.random.categorical(
                kl, jnp.log(jnp.maximum(props, 1e-30)), shape=(n,)
            ).astype(jnp.int32)
            noise = jax.random.normal(kn, (n, size, size, ch), jnp.float32)
            return {"images": protos[labels] + 0.6 * noise, "labels": labels}

        keys = jax.vmap(lambda i: jax.random.fold_in(kc, i))(
            jnp.arange(clients))
        out = jax.vmap(client)(keys)
        return [{k: v[i] for k, v in out.items()} for i in range(clients)]

    return make(key)


def _forward(cfg: dict, n_stages: int, final_head: bool) -> float:
    """Forward FLOPs of one image through the first ``n_stages`` stages and
    the exit head (and the final head where ``final_head``)."""
    size, cls = cfg["image_size"], cfg["n_classes"]
    chans = cfg["stage_channels"]
    total = flops.conv(size, 3, 1, cfg["image_channels"], chans[0])  # stem
    cin, hw = chans[0], size
    for s in range(n_stages):
        cout = chans[s]
        for b in range(cfg["blocks_per_stage"]):
            stride = 2 if (s > 0 and b == 0) else 1
            total += flops.conv(hw, 3, stride, cin, cout)            # conv1
            if stride != 1 or cin != cout:
                total += flops.conv(hw, 1, stride, cin, cout)        # 1x1
            hw = -(-hw // stride)
            total += flops.conv(hw, 3, 1, cout, cout)                # conv2
            cin = cout
    total += 2.0 * chans[cfg["simple_stages"] - 1] * cls             # exit
    if final_head:
        total += 2.0 * chans[-1] * cls
    return total


def client_flops(cfg: dict, simple: bool) -> float:
    """Training FLOPs of one image of a simple or a complex client."""
    if simple:
        return 3.0 * _forward(cfg, cfg["simple_stages"], False)
    return 3.0 * _forward(cfg, len(cfg["stage_channels"]), True)

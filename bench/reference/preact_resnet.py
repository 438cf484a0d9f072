"""Plain reference of PreActResNet with GroupNorm (FedHeN's model): the two
client objectives and the index set M.  Imports nothing of the program."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import Numerics, ce_mean


def _groupnorm(p, x, groups: int, eps: float = 1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mean = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=(1, 2, 4), keepdims=True)
    xf = ((xf - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _block(p, x, stride, cfg, num: Numerics):
    groups = cfg["groupnorm_groups"]
    h = jax.nn.relu(_groupnorm(p["gn1"], x, groups))
    shortcut = num.conv(h, p["shortcut"], stride) if "shortcut" in p else x
    h = num.conv(h, p["conv1"], stride)
    h = jax.nn.relu(_groupnorm(p["gn2"], h, groups))
    return num.conv(h, p["conv2"], 1) + shortcut


def _mixpool_logits(p, x, num: Numerics):
    a = jax.nn.sigmoid(p["alpha"].astype(jnp.float32))
    pooled = a * jnp.mean(x, axis=(1, 2)) + (1.0 - a) * jnp.max(x, axis=(1, 2))
    return num.einsum("bc,ck->bk", pooled, p["w"]) + p["b"].astype(jnp.float32)


def losses(cfg: dict, num: Numerics):
    """(simple loss, complex side-objective loss) of a batch."""
    n_stages = len(cfg["stage_channels"])

    def stages(params, images, upto):
        h = num.conv(images, params["stem"], 1)
        exit_h = None
        for s in range(upto):
            for b, blk in enumerate(params[f"stage{s + 1}"]):
                h = _block(blk, h, 2 if (s > 0 and b == 0) else 1, cfg, num)
            if s + 1 == cfg["simple_stages"]:
                exit_h = h
        return h, exit_h

    def simple(params, batch):
        _, exit_h = stages(params, batch["images"], cfg["simple_stages"])
        return ce_mean(_mixpool_logits(params["exit_head"], exit_h, num),
                       batch["labels"])

    def side(params, batch):
        h, exit_h = stages(params, batch["images"], n_stages)
        h = jax.nn.relu(_groupnorm(params["final_gn"], h,
                                   cfg["groupnorm_groups"]))
        final = (num.einsum("bc,ck->bk", jnp.mean(h, axis=(1, 2)),
                            params["head"]["w"])
                 + params["head"]["b"].astype(jnp.float32))
        exit_logits = _mixpool_logits(params["exit_head"], exit_h, num)
        return (ce_mean(final, batch["labels"])
                + ce_mean(exit_logits, batch["labels"]))

    return simple, side


def subnet(cfg: dict, params) -> Dict:
    """Index set M as a tree of bools broadcastable against each leaf: stem,
    the simple stages, exit head."""
    keep = {"stem", "exit_head"} | {f"stage{s + 1}"
                                   for s in range(cfg["simple_stages"])}
    return {k: jax.tree.map(lambda x, k=k: np.asarray(k in keep), v)
            for k, v in params.items()}

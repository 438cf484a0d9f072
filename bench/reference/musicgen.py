"""Plain reference of MusicGen (arXiv:2306.05284; Audiocraft's LM): the
two client objectives and the index set M.  Imports nothing of the
program; reads the parameter tree it builds.

Per crop: the 4 codebook embeddings summed unscaled, plus the sinusoidal
position embedding (cos then sin, ``max_period ** (-i / (D/2 - 1))``);
each layer LayerNorm -> causal self-attention, LayerNorm ->
cross-attention to the projected conditioning (padding zeroed and
masked), LayerNorm -> exact-GELU FFN, each added to the stream; a final
LayerNorm and a linear head per codebook.  The loss is next-step CE over
the delay pattern: codebook k's label at step t + 1 counts where it holds
a frame (``k <= t < crop_frames + k``), each codebook's CE is its mean
over those labels, and the loss is the mean over codebooks.

Attention is full (every query against every key, masked), in blocks of
heads so that the score tensor of a layer stays small; each layer is
recomputed in the backward pass (``jax.checkpoint``).  Neither changes
the arithmetic.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import Numerics

HEAD_BLOCK = 8          # heads per block of the attention


def _layernorm(p, x, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))


def _sinusoid(steps: int, dim: int, max_period: float):
    half = dim // 2
    pos = jnp.arange(steps, dtype=jnp.float32)[:, None]
    phase = pos / max_period ** (jnp.arange(half, dtype=jnp.float32)
                                 / (half - 1))
    return jnp.concatenate([jnp.cos(phase), jnp.sin(phase)], axis=-1)


def _attention(num: Numerics, q, k, v, mask):
    """q (B, S, H, Dh), k/v (B, N, H, Dh), mask (B|1, S, N) -> (B, S, H,
    Dh), a block of ``HEAD_BLOCK`` heads at a time."""
    b, s, h, dh = q.shape
    blk = math.gcd(h, HEAD_BLOCK)

    def blocks(x):
        return jnp.moveaxis(x.reshape(x.shape[:2] + (h // blk, blk, dh)),
                            2, 0)

    @jax.checkpoint
    def one(qkv):
        qb, kb, vb = qkv
        logits = num.einsum("bqhd,bkhd->bhqk", qb, kb) / np.sqrt(dh)
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return num.einsum("bhqk,bkhd->bqhd", probs, vb)

    out = jax.lax.map(one, (blocks(q), blocks(k), blocks(v)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h, dh)


def _layer(cfg: dict, num: Numerics, p, h, src, src_mask):
    eps, s = cfg["norm_eps"], h.shape[1]
    x = _layernorm(p["pre_norm"], h, eps)
    a = p["mixer"]
    q, k, v = (num.einsum("bsd,dhk->bshk", x, a[w]) for w in ("wq", "wk",
                                                               "wv"))
    causal = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])[None]
    h = h + num.einsum("bshk,hkd->bsd", _attention(num, q, k, v, causal),
                       a["wo"])
    x = _layernorm(p["cross_norm"], h, eps)
    c = p["cross"]
    q = num.einsum("bsd,dhk->bshk", x, c["wq"])
    k, v = (num.einsum("bnd,dhk->bnhk", src, c[w]) for w in ("wk", "wv"))
    h = h + num.einsum("bshk,hkd->bsd",
                       _attention(num, q, k, v, src_mask[:, None, :]),
                       c["wo"])
    x = _layernorm(p["mlp_norm"], h, eps)
    u = _gelu(num.einsum("bsd,df->bsf", x, p["mlp"]["up"]))
    return h + num.einsum("bsf,fd->bsd", u, p["mlp"]["down"])


def _codebook_ce(cfg: dict, logits, labels):
    """Mean over codebooks of each codebook's mean CE over the labels that
    hold a frame of the delay pattern.  logits (B, S, K, V), labels
    (B, S, K) at steps 1..S."""
    t = jnp.arange(labels.shape[1])[:, None]
    k = jnp.arange(labels.shape[2])[None, :]
    valid = (t >= k) & (t < cfg["crop_frames"] + k)           # (S, K)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.minimum(labels, cfg["card"] - 1)[..., None], axis=-1)
    nll = jnp.where(valid[None], logz - gold[..., 0], 0.0)
    per_codebook = jnp.sum(nll, axis=(0, 1)) / (labels.shape[0]
                                                * jnp.sum(valid, axis=0))
    return jnp.mean(per_codebook)


def losses(cfg: dict, num: Numerics):
    """(simple loss, complex side-objective loss) of a batch."""
    n_layers, exit_layer = cfg["n_layers"], cfg["exit_layer"]
    eps = cfg["norm_eps"]

    def trunk(params, batch, upto):
        tokens = batch["tokens"][:, :-1]
        tabs = params["embed"]["tables"]
        h = sum(tabs[c].astype(jnp.float32)[tokens[..., c]]
                for c in range(tokens.shape[-1]))
        h = h + _sinusoid(tokens.shape[1], cfg["d_model"],
                          cfg["max_period"])[None]
        proj = params["frontend_proj"]
        mask = batch["cond_mask"]
        src = num.einsum("bnd,dk->bnk", batch["cond"], proj["w"]) + \
            proj["b"].astype(jnp.float32)
        src = jnp.where(mask[..., None], src, 0.0)
        layer = jax.checkpoint(lambda p, h: _layer(cfg, num, p, h, src,
                                                   mask))
        exit_h = None
        for i in range(upto):
            h = layer(jax.tree.map(lambda x: x[i], params["periods"][0]), h)
            if i + 1 == exit_layer:
                exit_h = h
        return h, exit_h

    def head_ce(params, norm, h, batch):
        x = _layernorm(params[norm], h, eps)
        logits = num.einsum("bsd,cdv->bscv", x, params["unembed"]["w"])
        return _codebook_ce(cfg, logits, batch["tokens"][:, 1:])

    def simple(params, batch):
        _, exit_h = trunk(params, batch, exit_layer)
        return head_ce(params, "exit_norm", exit_h, batch)

    def side(params, batch):
        h, exit_h = trunk(params, batch, n_layers)
        return (head_ce(params, "final_norm", h, batch)
                + head_ce(params, "exit_norm", exit_h, batch))

    return simple, side


def subnet(cfg: dict, params) -> Dict:
    """Index set M as a tree of bools broadcastable against each leaf: the
    embeddings, the conditioning projection, the first ``exit_layer``
    layers (leading axis of the stacked layers), the exit norm and the
    codebook heads that the exit head shares."""
    keep = {"embed", "frontend_proj", "exit_norm", "unembed"}
    out = {}
    for name, sub in params.items():
        if name == "periods":
            out[name] = jax.tree.map(
                lambda x: (np.arange(x.shape[0]) < cfg["exit_layer"]
                           ).reshape((-1,) + (1,) * (x.ndim - 1)), sub)
        else:
            out[name] = jax.tree.map(lambda x, k=name: np.asarray(k in keep),
                                     sub)
    return out

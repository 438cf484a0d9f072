"""Index set M (FedHeN Assumption 2.1) as broadcastable mask pytrees.

A *mask tree* has the same treedef as the parameter tree; each leaf is a
boolean array broadcastable against the corresponding parameter leaf:

* fully-included / fully-excluded leaves -> scalar ``True`` / ``False``
* period-stacked transformer leaves (leading axis = n_periods) -> shape
  ``(n_periods, 1, 1, ...)`` with ``True`` for periods < exit_period.

This representation makes every FedHeN tree operation a single broadcasted
``where``/multiply — which is also what the ``masked_agg`` Pallas kernel
implements for the server hot path.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Tree = Any


def _const_mask(tree: Tree, value: bool) -> Tree:
    return jax.tree.map(lambda _: jnp.asarray(value), tree)


# top-level subtrees the simple model uses whole: the embeddings, the
# frontend projector (prepended tokens or the cross-attention source), and
# the exit head: its norm and the output heads it shares with the final
# head (the embedding where tied, ``unembed`` where not)
SIMPLE_SHARED = ("embed", "frontend_proj", "exit_norm", "unembed")


def transformer_subnet_mask(params: Tree, cfg: ModelConfig) -> Tree:
    """M for the decoder zoo: :data:`SIMPLE_SHARED` + blocks[:K] (their
    cross-attention included)."""
    mask: Dict[str, Tree] = {}
    for name, sub in params.items():
        if name == "periods":
            kp = cfg.exit_period
            stacks = []
            for stacked in sub:
                def leaf_mask(x):
                    m = jnp.arange(x.shape[0]) < kp
                    return m.reshape((x.shape[0],) + (1,) * (x.ndim - 1))
                stacks.append(jax.tree.map(leaf_mask, stacked))
            mask[name] = tuple(stacks)
        elif name == "rem":
            # remainder layers sit at the tail -> never in the prefix subnet
            mask[name] = _const_mask(sub, False)
        elif name in SIMPLE_SHARED:
            mask[name] = _const_mask(sub, True)
        else:  # final_norm
            mask[name] = _const_mask(sub, False)
    return mask


def resnet_subnet_mask(params: Tree) -> Tree:
    from repro.models import resnet
    mask = {}
    for name, sub in params.items():
        keep = name in ("stem", "stage1", "stage2", "exit_head")
        mask[name] = _const_mask(sub, keep)
    return mask


# ---------------------------------------------------------------------------
# Tree ops over masks
# ---------------------------------------------------------------------------

def where_mask(mask: Tree, a: Tree, b: Tree) -> Tree:
    """leafwise: mask ? a : b."""
    return jax.tree.map(lambda m, x, y: jnp.where(m, x, y), mask, a, b)


def apply_mask(mask: Tree, tree: Tree) -> Tree:
    """Zero out the complement of M (used to isolate [w]_M)."""
    return jax.tree.map(lambda m, x: jnp.where(m, x, jnp.zeros_like(x)),
                        mask, tree)


def mask_size(mask: Tree, params: Tree) -> int:
    """Number of scalar parameters inside M."""
    total = 0
    for m, x in zip(jax.tree.leaves(mask), jax.tree.leaves(params)):
        total += int(jnp.sum(jnp.broadcast_to(m, x.shape)))
    return total


def extract_simple(params: Tree, cfg: ModelConfig) -> Tree:
    """Materialize the simple model's own (smaller) parameter tree.

    The result is directly consumable by ``transformer.forward_simple`` —
    period stacks are truncated to ``exit_period``; complex-only subtrees
    are dropped.
    """
    kp = cfg.exit_period
    out: Dict[str, Tree] = {}
    for name, sub in params.items():
        if name == "periods":
            out[name] = tuple(jax.tree.map(lambda x: x[:kp], s) for s in sub)
        elif name in SIMPLE_SHARED:
            out[name] = sub
        # rem / final_norm are complex-only
    return out


def embed_simple(simple: Tree, complex_params: Tree, cfg: ModelConfig) -> Tree:
    """Write a simple tree back into the complex one ([w_c]_M := w_s)."""
    kp = cfg.exit_period
    out = dict(complex_params)
    for name, sub in simple.items():
        if name == "periods":
            merged = []
            for s_stk, c_stk in zip(sub, complex_params["periods"]):
                merged.append(jax.tree.map(
                    lambda s, c: jnp.concatenate([s.astype(c.dtype), c[kp:]],
                                                 axis=0),
                    s_stk, c_stk))
            out[name] = tuple(merged)
        else:
            out[name] = sub
    return out


def tree_isfinite(tree: Tree) -> jax.Array:
    """Scalar bool: every leaf fully finite (paper's NaN-device check)."""
    flags = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)
             if jnp.issubdtype(x.dtype, jnp.floating)]
    return jnp.all(jnp.stack(flags)) if flags else jnp.asarray(True)

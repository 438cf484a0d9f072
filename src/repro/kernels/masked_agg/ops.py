"""Masked cohort aggregation over parameter pytrees + backend dispatch.

The server hot path: ``core.aggregate.streaming_fold`` owns the flat
engine's dispatch — on the kernel path it packs each chunk into one
contiguous ``(Z, n_flat)`` buffer and calls ``masked_agg_acc_pallas``
(re-exported here) with *raw* unnormalized weights, accumulating into one
flat f32 running sum divided once per round: one launch per fold, updated
in place via ``input_output_aliases``; on CPU it folds per leaf directly
into the flat accumulator's slices.  Under an int8 wire
(``FedConfig.comm_dtype``) the fold instead calls
``masked_agg_acc_deq_pallas`` — the dequantizing accumulate that consumes
the wire payload + per-group scales directly (``masked_agg_acc_deq_ref``
is its CPU/oracle form).  ``masked_agg_tree`` below keeps the PR 2
per-leaf path (one launch per leaf) as the parity engine.

Backend selection (``use_pallas``): the Pallas kernels run on TPU and
nowhere else; on any other backend the XLA reference path runs — set
``force_pallas_interpret=True`` to exercise the kernel body in interpret
mode (tests do).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.masked_agg.kernel import (masked_agg_acc_deq_pallas,
                                             masked_agg_acc_pallas,
                                             masked_agg_pallas,
                                             masked_scatter_acc_pallas)
from repro.kernels.masked_agg.ref import (masked_agg_acc_deq_ref,
                                          masked_agg_acc_ref,
                                          masked_agg_ref,
                                          masked_scatter_acc_ref)

Tree = Any


def use_pallas() -> bool:
    """True when the Pallas kernel (not the XLA reference) should run."""
    return jax.default_backend() == "tpu"


def masked_agg_leaf(x: jax.Array, mask: jax.Array, w_m: jax.Array,
                    w_rest: jax.Array, *, block_n: int = 2048,
                    force_pallas_interpret: bool = False) -> jax.Array:
    """One stacked leaf: x (Z, ...) + broadcastable mask -> aggregated (…)."""
    z = x.shape[0]
    body = x.reshape(z, -1)
    # mask is broadcastable against one cohort member's shape (x.shape[1:])
    mask_flat = jnp.broadcast_to(jnp.asarray(mask),
                                 x.shape[1:]).reshape(-1)
    if force_pallas_interpret:
        out = masked_agg_pallas(body, mask_flat, w_m, w_rest,
                                block_n=block_n, interpret=True)
    elif use_pallas():
        out = masked_agg_pallas(body, mask_flat, w_m, w_rest,
                                block_n=block_n)
    else:
        out = masked_agg_ref(body, mask_flat, w_m, w_rest)
    return out.reshape(x.shape[1:])


def masked_agg_tree(cohort: Tree, mask_tree: Tree, w_m: jax.Array,
                    w_rest: jax.Array, **kw) -> Tree:
    """Apply the aggregation across a stacked cohort pytree (per leaf).

    Weights are RAW per-client coefficients (a weighted *sum*, not a
    mean): the streaming server step passes unnormalized validity weights
    per chunk and divides by the running totals once per round.  Callers
    wanting a mean must normalize w_m/w_rest themselves."""
    return jax.tree.map(
        lambda x, m: masked_agg_leaf(x, m, w_m, w_rest, **kw),
        cohort, mask_tree)

"""Backend dispatch for the masked cohort aggregation kernels.

The server hot path: ``core.aggregate.streaming_fold`` owns the fold's
dispatch — on the kernel path it packs each chunk into one contiguous
``(Z, n_flat)`` buffer and calls ``masked_agg_acc_pallas`` (re-exported
here) with *raw* unnormalized weights, accumulating into one flat f32
running sum divided once per round: one launch per fold, updated in place
via ``input_output_aliases``; on CPU it folds per leaf directly into the
flat accumulator's slices.  Under an int8 wire (``FedConfig.comm_dtype``)
the fold instead calls ``masked_agg_acc_deq_pallas`` — the dequantizing
accumulate that consumes the wire payload + per-group scales directly
(``masked_agg_acc_deq_ref`` is its CPU/oracle form) — and top-k uploads
go through ``masked_scatter_acc_pallas``.

Backend selection (``use_pallas``): the Pallas kernels run on TPU and
nowhere else; on any other backend the XLA reference path runs — set
``force_pallas_interpret=True`` to exercise the kernel body in interpret
mode (tests do).
"""

from __future__ import annotations

import jax

from repro.kernels.masked_agg.kernel import (masked_agg_acc_deq_pallas,
                                             masked_agg_acc_pallas,
                                             masked_scatter_acc_pallas)
from repro.kernels.masked_agg.ref import (masked_agg_acc_deq_ref,
                                          masked_agg_acc_ref,
                                          masked_scatter_acc_ref)


def use_pallas() -> bool:
    """True when the Pallas kernel (not the XLA reference) should run."""
    return jax.default_backend() == "tpu"

"""Backend dispatch for the causal flash attention kernel.

``models/attention.py`` asks :func:`use_pallas` and :func:`block_for`
whether the kernel takes a train/prefill call and calls
:func:`flash_attention` (re-exported from ``kernel.py``) when it does;
every other call keeps the XLA path there.  The kernel runs on TPU and
nowhere else: on another backend ``use_pallas`` is false, and tests
exercise the kernel body with ``interpret=True``.
"""

from __future__ import annotations

import jax

from repro.kernels.flash_attention.kernel import flash_attention

__all__ = ["block_for", "flash_attention", "use_pallas"]

BLOCKS = (512, 256, 128)   # largest first


def use_pallas() -> bool:
    """True when the Pallas kernel (not the XLA path) should run."""
    return jax.default_backend() == "tpu"


def block_for(s: int) -> int:
    """The q- and k-block for sequence length ``s``: the largest of
    :data:`BLOCKS` that divides it, or 0 when none does."""
    return next((b for b in BLOCKS if s % b == 0), 0)

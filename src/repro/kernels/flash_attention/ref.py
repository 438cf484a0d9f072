"""Pure-jnp oracle for the causal flash attention kernel.

Semantics identical to ``models/attention.chunked_causal_attention`` but
restated independently (naive O(S^2) masked softmax in f32) so the kernel
test has an oracle that shares no code with either implementation.  Heads
first, like the kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array,
                        v: jax.Array) -> jax.Array:
    """q: (B, H, S, Dh); k/v: (B, Kh, S, Dh); causal -> like q."""
    b, h, s, dh = q.shape
    kh = k.shape[1]
    qg = q.reshape(b, kh, h // kh, s, dh).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32),
                        precision=hi) * dh ** -0.5
    pos = jnp.arange(s)
    logits = jnp.where(pos[:, None] >= pos[None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs, v.astype(jnp.float32),
                     precision=hi)
    return out.reshape(b, h, s, dh).astype(q.dtype)

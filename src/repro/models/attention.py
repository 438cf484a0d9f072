"""GQA attention: global/sliding-window, RoPE, softcap, KV caches, decode.

Two execution regimes, sharing the same parameters:

* ``train/prefill`` — causal attention over the whole sequence.  On a TPU
  a call the flash kernel takes (``kernels/flash_attention``: global
  causal, no softcap, S a multiple of 128, no mesh) runs it: q/k/v are
  projected straight into its heads-first (B, H, S, Dh) layout, no score
  tile leaves VMEM forward or backward, and blocks above the diagonal are
  skipped.  Every other call runs chunked causal attention in XLA:
  queries in chunks of ``q_chunk`` via ``lax.scan``, so the score matrix
  is O(chunk x keys) rather than O(S^2) memory; local layers slice only
  the ``chunk + window`` keys they can see, so their FLOPs are
  O(S * window).  Under a ``seq2d`` policy, ``chunk2d_attention`` shards
  the query chunks over the mesh.
* ``decode`` — one query token against a KV cache.  Local layers keep a
  ring-buffer cache of size ``window`` (RoPE is applied at write time, so
  ring rotation is harmless); global layers keep the full ``S`` cache.

Cross-attention (musicgen) attends from the decoder stream to a fixed
source, the projected conditioning: no causal mask, a key-padding mask
over the source tokens, and K/V that a decode cache computes once.

Shapes: hidden (B, S, D); q (B, S, H, Dh); k/v (B, S, Kh, Dh) — heads
first, (B, H, S, Dh), on the flash kernel's path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as flash_ops
from repro.models import common
from repro.models.common import Policy, NO_POLICY

NEG_INF = -2.0 ** 30  # large-but-finite: keeps fully-masked rows NaN-free


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> dict:
    d, h, kh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    dt = cfg.jnp_param_dtype()
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": common.dense_init(kq, (d, h, dh), dt, fan_in=d),
        "wk": common.dense_init(kk, (d, kh, dh), dt, fan_in=d),
        "wv": common.dense_init(kv, (d, kh, dh), dt, fan_in=d),
        "wo": common.dense_init(ko, (h, dh, d), dt, fan_in=h * dh),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = common.init_rmsnorm(dh, dt)
        p["k_norm"] = common.init_rmsnorm(dh, dt)
    return p


# ---------------------------------------------------------------------------
# Core masked attention over an explicit key block
# ---------------------------------------------------------------------------

def _attend(q, k, v, mask, softcap_val: float):
    """q: (B, Sq, Kh, G, Dh); k/v: (B, Sk, Kh, Dh); mask: (B|1, Sq, Sk)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = common.softcap(logits, softcap_val)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out


def _split_gqa(q, n_kv: int):
    b, s, h, dh = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, dh)


def _merge_gqa(o):
    b, s, kh, g, dh = o.shape
    return o.reshape(b, s, kh * g, dh)


# ---------------------------------------------------------------------------
# Chunked causal attention (train / prefill)
# ---------------------------------------------------------------------------

def chunked_causal_attention(q, k, v, *, window: int = 0,
                             softcap_val: float = 0.0,
                             q_chunk: int = 512) -> jax.Array:
    """Causal (optionally sliding-window) attention without an S^2 buffer.

    q: (B, S, H, Dh); k, v: (B, S, Kh, Dh).  ``window`` == 0 means global
    causal.  A query at position i sees keys j with j <= i and, when
    windowed, i - j < window.
    """
    b, s, h, dh = q.shape
    kh = k.shape[2]
    qg = _split_gqa(q, kh)

    if s <= q_chunk:
        pos = jnp.arange(s)
        mask = pos[None, :, None] >= pos[None, None, :]
        if window:
            mask &= (pos[None, :, None] - pos[None, None, :]) < window
        return _merge_gqa(_attend(qg, k, v, mask, softcap_val))

    if s % q_chunk:
        raise ValueError(f"seq {s} not divisible by q_chunk {q_chunk}")
    n_chunks = s // q_chunk

    if window and window + q_chunk < s:
        # Local: each chunk sees a static slice of window + chunk keys.
        span = window + q_chunk
        pad = window
        kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        qc = qg.reshape(b, n_chunks, q_chunk, kh, -1, dh)

        @jax.checkpoint  # flash-style: recompute chunk attention in backward
        def body(c, q_blk):
            start = c * q_chunk                      # in padded coords
            kb = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
            q_pos = start + pad + jnp.arange(q_chunk)    # padded coords
            k_pos = start + jnp.arange(span)
            delta = q_pos[:, None] - k_pos[None, :]
            mask = (delta >= 0) & (delta < window) & (k_pos[None, :] >= pad)
            out = _attend(q_blk, kb, vb, mask[None], softcap_val)
            return c + 1, out

        _, outs = jax.lax.scan(body, 0, qc.transpose(1, 0, 2, 3, 4, 5))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, kh, -1, dh)
        return _merge_gqa(out)

    # Global causal: chunked queries against all keys.
    qc = qg.reshape(b, n_chunks, q_chunk, kh, -1, dh)
    k_pos = jnp.arange(s)

    @jax.checkpoint  # flash-style: recompute chunk attention in backward
    def body(c, q_blk):
        q_pos = c * q_chunk + jnp.arange(q_chunk)
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        out = _attend(q_blk, k, v, mask[None], softcap_val)
        return c + 1, out

    _, outs = jax.lax.scan(body, 0, qc.transpose(1, 0, 2, 3, 4, 5))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, kh, -1, dh)
    return _merge_gqa(out)


def chunk2d_attention(q, k, v, *, window: int = 0, softcap_val: float = 0.0,
                      q_chunk: int = 512, k_chunk: int = 2048,
                      policy: Policy = NO_POLICY) -> jax.Array:
    """Sequence-parallel flash attention (XLA level).

    q is reshaped to (B, NC, Lq, H, Dh) and the CHUNK axis is sharded over
    `model` (logical name "seq_chunks"), so the quadratic score work spreads
    over data x model; k/v are consumed whole (the policy leaves them
    batch-sharded only -> one all-gather each).  An online-softmax scan over
    k-blocks bounds the live score tile, exactly like the Pallas kernel in
    repro/kernels/flash_attention — this is its pjit/SPMD twin for meshes
    where heads cannot shard (llava 56H; H1 in EXPERIMENTS.md §Perf).
    """
    b, s, h, dh = q.shape
    kh = k.shape[2]
    g = h // kh
    if s % q_chunk or s % k_chunk:
        return chunked_causal_attention(q, k, v, window=window,
                                        softcap_val=softcap_val,
                                        q_chunk=min(q_chunk, s))
    nc = s // q_chunk
    nk = s // k_chunk
    qc = q.reshape(b, nc, q_chunk, kh, g, dh)
    qc = policy.constrain(qc, ("batch", "seq_chunks", None, None, None, None))
    scale = dh ** -0.5

    def body(carry, kc):
        m_prev, l_prev, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, kc * k_chunk, k_chunk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, kc * k_chunk, k_chunk, axis=1)
        logits = jnp.einsum("bnqkgd,bskd->bnqkgs", qc, kb,
                            preferred_element_type=jnp.float32) * scale
        logits = common.softcap(logits, softcap_val)
        q_pos = (jnp.arange(nc)[:, None] * q_chunk
                 + jnp.arange(q_chunk)[None, :])          # (NC, Lq)
        k_pos = kc * k_chunk + jnp.arange(k_chunk)        # (Lk,)
        delta = q_pos[..., None] - k_pos[None, None, :]
        mask = delta >= 0
        if window:
            mask &= delta < window
        logits = jnp.where(mask[None, :, :, None, None, :], logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        # p joins v's storage dtype (standard flash practice) so XLA
        # all-gathers v in bf16, not f32 — accumulation stays f32
        acc = (acc * alpha[..., None]
               + jnp.einsum("bnqkgs,bskd->bnqkgd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32))
        return (m_new, l_new, acc), None

    shape5 = (b, nc, q_chunk, kh, g)
    init = (jnp.full(shape5, NEG_INF, jnp.float32),
            jnp.zeros(shape5, jnp.float32),
            jnp.zeros(shape5 + (dh,), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.astype(q.dtype).reshape(b, s, kh, g, dh)
    return _merge_gqa(out)


# ---------------------------------------------------------------------------
# Full layer application
# ---------------------------------------------------------------------------

def _project_qkv(p, h_in, cfg: ModelConfig, positions,
                 heads_first: bool = False):
    """q (B, S, H, Dh), k/v (B, S, Kh, Dh); (B, H|Kh, S, Dh) when
    ``heads_first``."""
    spec = "bsd,dhk->bhsk" if heads_first else "bsd,dhk->bshk"
    q = jnp.einsum(spec, h_in, p["wq"].astype(h_in.dtype))
    k = jnp.einsum(spec, h_in, p["wk"].astype(h_in.dtype))
    v = jnp.einsum(spec, h_in, p["wv"].astype(h_in.dtype))
    if cfg.use_qk_norm:
        q = common.apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = common.apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.positions == "rope":
        seq_axis = 2 if heads_first else 1
        q = common.apply_rope(q, positions, cfg.rope_theta, seq_axis=seq_axis)
        k = common.apply_rope(k, positions, cfg.rope_theta, seq_axis=seq_axis)
    return q, k, v


def _flash_block(s: int, window: int, softcap_val: float,
                 policy: Policy) -> int:
    """The flash kernel's block for a train/prefill call of length ``s``,
    or 0 when the call keeps the XLA path: off a TPU, under a sliding
    window or a softcap (the kernel has neither), on a mesh (SPMD does not
    partition a Pallas call), or where no block divides ``s``."""
    if (window or softcap_val or getattr(policy, "mesh", None) is not None
            or not flash_ops.use_pallas()):
        return 0
    return flash_ops.block_for(s)


def apply_attention(p: dict, h_in: jax.Array, cfg: ModelConfig, *,
                    window: int = 0, policy: Policy = NO_POLICY,
                    positions: Optional[jax.Array] = None,
                    q_chunk: int = 512, return_kv: bool = False):
    """Train/prefill path.  h_in: (B, S, D) -> (B, S, D).

    ``return_kv=True`` additionally returns the (RoPE'd) K/V tensors so the
    caller can build a decode cache (prefill -> decode handoff)."""
    b, s, _ = h_in.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    h_in = policy.constrain(h_in, ("batch", "seq", None))
    block = _flash_block(s, window, cfg.attn_logit_softcap, policy)
    if block:
        q, k, v = _project_qkv(p, h_in, cfg, positions, heads_first=True)
        out = flash_ops.flash_attention(q, k, v, block=block)
        out = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(out.dtype))
        if return_kv:
            return out, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        return out
    q, k, v = _project_qkv(p, h_in, cfg, positions)
    if getattr(policy, "seq2d", False):
        # 2D token sharding: q-chunks sharded over `model`; k/v consumed
        # whole (batch-sharded) — the SPMD twin of the flash kernel.
        # Constrain k/v seq-sharded FIRST so the projection dot computes
        # locally and only the small k/v get gathered — otherwise SPMD
        # replicates the (much larger) hidden-state input instead.
        q = policy.constrain(q, ("batch", "seq", None, None))
        k = policy.constrain(k, ("batch", "seq", None, None))
        v = policy.constrain(v, ("batch", "seq", None, None))
        k = policy.constrain(k, ("batch", None, None, None))
        v = policy.constrain(v, ("batch", None, None, None))
        out = chunk2d_attention(q, k, v, window=window,
                                softcap_val=cfg.attn_logit_softcap,
                                q_chunk=q_chunk, policy=policy)
    else:
        q = policy.constrain(q, ("batch", "seq", "heads", "head_dim"))
        k = policy.constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
        v = policy.constrain(v, ("batch", "seq", "kv_heads", "head_dim"))
        out = chunked_causal_attention(q, k, v, window=window,
                                       softcap_val=cfg.attn_logit_softcap,
                                       q_chunk=q_chunk)
    out = policy.constrain(out, ("batch", "seq", "heads", None))
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(out.dtype))
    if return_kv:
        return out, k, v
    return out


def kv_to_cache(k: jax.Array, v: jax.Array, cfg: ModelConfig, *,
                window: int = 0, cache_len: Optional[int] = None) -> dict:
    """Arrange prefill K/V (B, S, Kh, Dh) into a decode cache.

    Windowed layers get a ring buffer laid out so that position p sits at
    slot p % size — exactly what ``apply_attention_decode`` expects when it
    continues from pos = S.  Global layers get a dense cache of
    ``cache_len`` (>= S) slots.
    """
    b, s, kh, dh = k.shape
    dt = cfg.jnp_compute_dtype()
    if window:
        size = min(window, cache_len or s)
        start = max(s - size, 0)
        slots = (start + jnp.arange(min(size, s))) % size
        ck = jnp.zeros((b, size, kh, dh), dt).at[:, slots].set(
            k[:, start:].astype(dt))
        cv = jnp.zeros((b, size, kh, dh), dt).at[:, slots].set(
            v[:, start:].astype(dt))
        return {"k": ck, "v": cv}
    size = cache_len or s
    ck = jnp.zeros((b, size, kh, dh), dt).at[:, :s].set(k.astype(dt))
    cv = jnp.zeros((b, size, kh, dh), dt).at[:, :s].set(v.astype(dt))
    return {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, *,
                  window: int = 0) -> dict:
    """window > 0 -> ring buffer of that size; else dense cache of seq_len."""
    size = min(window, seq_len) if window else seq_len
    dt = cfg.jnp_compute_dtype()
    shape = (batch, size, cfg.n_kv_heads, cfg.resolved_head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def apply_attention_decode(p: dict, h_in: jax.Array, cache: dict,
                           pos: jax.Array, cfg: ModelConfig, *,
                           window: int = 0,
                           policy: Policy = NO_POLICY):
    """One-token decode.  h_in: (B, 1, D); pos: scalar int32 (current index).

    Returns (out (B, 1, D), new_cache).
    """
    b = h_in.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(p, h_in, cfg, positions)

    size = cache["k"].shape[1]
    slot = pos % size if window else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    new_cache = {"k": k, "v": v}

    k = policy.constrain(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = policy.constrain(v, ("batch", "kv_seq", "kv_heads", "head_dim"))

    idx = jnp.arange(size)
    if window:
        # slot j holds logical position: the largest p' <= pos with p' % size == j
        logical = pos - ((pos - idx) % size)
        valid = (logical >= 0) & (logical <= pos) & (pos - logical < window)
    else:
        valid = idx <= pos
    mask = jnp.broadcast_to(valid[None, None, :], (1, 1, size))

    qg = _split_gqa(q, cfg.n_kv_heads)
    out = _attend(qg, k, v, mask, cfg.attn_logit_softcap)
    out = _merge_gqa(out)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(out.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (to a fixed source: the projected conditioning)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ModelConfig) -> dict:
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    dt = cfg.jnp_param_dtype()
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": common.dense_init(kq, (d, h, dh), dt, fan_in=d),
        "wk": common.dense_init(kk, (d, h, dh), dt, fan_in=d),
        "wv": common.dense_init(kv, (d, h, dh), dt, fan_in=d),
        "wo": common.dense_init(ko, (h, dh, d), dt, fan_in=h * dh),
    }


def cross_kv(p: dict, src: jax.Array):
    """K/V of the source (B, N, D) -> each (B, N, H, Dh): what a decode
    cache holds for the whole generation."""
    k = jnp.einsum("bnd,dhk->bnhk", src, p["wk"].astype(src.dtype))
    v = jnp.einsum("bnd,dhk->bnhk", src, p["wv"].astype(src.dtype))
    return k, v


def apply_cross_attention(p: dict, h_in: jax.Array, k: jax.Array,
                          v: jax.Array, src_mask: jax.Array) -> jax.Array:
    """h_in (B, S, D) attends to source K/V (B, N, H, Dh); ``src_mask``
    (B, N) bool leaves out the source's padding.  -> (B, S, D)."""
    q = jnp.einsum("bsd,dhk->bshk", h_in, p["wq"].astype(h_in.dtype))
    out = _attend(_split_gqa(q, k.shape[2]), k, v, src_mask[:, None, :], 0.0)
    out = _merge_gqa(out)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(out.dtype))

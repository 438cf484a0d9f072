"""Decode (serve_step) must reproduce prefill logits token-by-token.

This is the core serving invariant: for every mixer family, running the
model autoregressively with its cache yields the same logits as the full
parallel forward.  fp32 + no-drop MoE capacity so comparisons are exact-ish.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig, MoEConfig
from repro.models import transformer as tf

S = 16
B = 2


def _roundtrip(cfg, tol):
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    _, final_h, _ = tf.forward(params, cfg, tokens)
    ref = tf.logits_from_hidden(params, cfg, final_h, "final")

    cache = tf.init_cache(cfg, B, S)
    step = jax.jit(lambda c, t, p: tf.decode_step(params, c, cfg, t, p))
    outs = []
    for t in range(S):
        lg, cache = step(cache, tokens[:, t:t + 1], jnp.int32(t))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    err = float(jnp.max(jnp.abs(dec.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < tol, f"decode/prefill mismatch: {err}"
    assert not bool(jnp.isnan(dec).any())


def test_dense_gqa():
    cfg = ModelConfig(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab_size=97, pattern=(LayerSpec("attn"),),
                      exit_layer=2, compute_dtype="float32")
    _roundtrip(cfg, 2e-3)


def test_local_global_softcap():
    cfg = ModelConfig(n_layers=4, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=128, vocab_size=97, window=6,
                      attn_logit_softcap=50.0, final_logit_softcap=30.0,
                      pattern=(LayerSpec("local_attn"), LayerSpec("attn")),
                      exit_layer=2, compute_dtype="float32")
    _roundtrip(cfg, 2e-3)


def test_moe_no_drop():
    cfg = ModelConfig(n_layers=4, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=128, vocab_size=97,
                      pattern=(LayerSpec("attn", "moe"),),
                      moe=MoEConfig(n_experts=4, top_k=2, n_shared=1,
                                    d_expert=64, capacity_factor=64.0),
                      exit_layer=2, compute_dtype="float32")
    _roundtrip(cfg, 2e-3)


def test_hybrid_rglru():
    cfg = ModelConfig(n_layers=6, d_model=64, n_heads=4, n_kv_heads=1,
                      d_ff=128, vocab_size=97, window=6,
                      pattern=(LayerSpec("rglru"), LayerSpec("rglru"),
                               LayerSpec("local_attn")),
                      exit_layer=3, compute_dtype="float32")
    _roundtrip(cfg, 2e-3)


def test_xlstm():
    cfg = ModelConfig(n_layers=4, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=0, vocab_size=97, mlstm_chunk=4,
                      pattern=(LayerSpec("mlstm", "none"),
                               LayerSpec("mlstm", "none"),
                               LayerSpec("mlstm", "none"),
                               LayerSpec("slstm", "none")),
                      exit_layer=4, compute_dtype="float32")
    _roundtrip(cfg, 5e-3)


def test_musicgen_codebooks():
    """MusicGen's four codebooks in the delay pattern (reduced published
    block): from the start step alone, decoding every later step through
    the cache gives each codebook's head the full forward's logits."""
    _musicgen_prefill_decode(prompt=1)


def test_ring_buffer_past_window():
    """Decode beyond the window: ring buffer must match windowed prefill."""
    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab_size=31, window=5,
                      pattern=(LayerSpec("local_attn"),),
                      exit_layer=1, compute_dtype="float32")
    _roundtrip(cfg, 2e-3)


def _musicgen_prefill_decode(prompt):
    """Prefill ``prompt`` steps of the reduced MusicGen block at four
    codebooks, decode the rest through the cache, and compare both with
    the full forward's logits; returns the pieces for further checks."""
    from repro import configs
    cfg = configs.get_reduced("musicgen-large").with_overrides(
        n_codebooks=4)
    fe = cfg.frontend
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    tokens = jax.random.randint(k1, (B, S, cfg.n_codebooks), 0,
                                cfg.vocab_size + 1)      # special included
    cond = jax.random.normal(k2, (B, fe.n_tokens, fe.d_in))
    cond_mask = jnp.arange(fe.n_tokens)[None, :] < jnp.array([[1], [3]])
    _, final_h, _ = tf.forward(params, cfg, tokens, cond=cond,
                               cond_mask=cond_mask)
    ref = tf.logits_from_hidden(params, cfg, final_h, "final")
    assert ref.shape == (B, S, cfg.n_codebooks, cfg.vocab_size)

    logits_p, cache = tf.prefill(params, cfg, tokens[:, :prompt], cond=cond,
                                 cond_mask=cond_mask, cache_len=S)
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(ref[:, :prompt]),
                               rtol=2e-3, atol=2e-3)
    step = jax.jit(lambda c, t, p: tf.decode_step(params, c, cfg, t, p))
    outs = []
    for t in range(prompt, S):
        lg, cache = step(cache, tokens[:, t:t + 1], jnp.int32(t))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref[:, prompt:]),
                               rtol=2e-3, atol=2e-3)
    return cfg, params, tokens, cond, cond_mask, ref


def test_musicgen_cross_attention_prefill_decode():
    """MusicGen's block (cross-attention to padded conditioning, LayerNorm,
    sinusoidal positions, delay-pattern special tokens, untied heads):
    prefill of a prompt, then decoding through the cache whose
    cross-attention K/V prefill computed once, agree with the full
    forward's logits."""
    cfg, params, tokens, cond, cond_mask, ref = _musicgen_prefill_decode(
        prompt=S // 2)
    # the conditioning matters: other conditioning, other logits
    _, other_h, _ = tf.forward(params, cfg, tokens, cond=-cond,
                               cond_mask=cond_mask)
    assert not np.allclose(np.asarray(tf.logits_from_hidden(
        params, cfg, other_h, "final")), np.asarray(ref), atol=1e-3)

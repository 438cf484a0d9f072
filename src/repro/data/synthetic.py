"""Synthetic datasets (deterministic, offline-friendly).

* ``synthetic_cifar`` — class-conditional images: each class has a smooth
  random prototype; samples are prototype + structured noise.  Learnable by
  both the simple and complex ResNets, separable enough that federated
  convergence ordering (the paper's claim) is measurable in tens of rounds.
* ``synthetic_lm`` — first-order Markov token streams with a class-dependent
  transition matrix; learnable by small decoder LMs.
* ``synthetic_frontend_embeds`` — stand-ins for the stubbed modality
  frontends (VLM patches / text conditioning); ``synthetic_conditioning``
  adds a padding mask of a random length per sequence.
* ``delay_pattern`` — MusicGen's interleaving of parallel codebooks.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def synthetic_cifar(n: int, n_classes: int, seed: int = 0,
                    image_size: int = 32) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    # smooth prototypes: low-frequency random fields per class
    base = rng.normal(size=(n_classes, 8, 8, 3)).astype(np.float32)
    protos = np.stack([
        np.kron(base[c], np.ones((image_size // 8, image_size // 8, 1)))
        for c in range(n_classes)])
    labels = rng.integers(0, n_classes, size=n).astype(np.int32)
    noise = rng.normal(scale=0.6, size=(n, image_size, image_size, 3))
    images = protos[labels] + noise.astype(np.float32)
    return {"images": images.astype(np.float32), "labels": labels}


def delay_pattern(codes: np.ndarray, special: int,
                  length: int) -> np.ndarray:
    """MusicGen's delay pattern of ``codes`` (n, T, K): step 0 is the
    special token, codebook k's frame f sits at step f + 1 + k, and every
    other step holds ``special``; ``length`` (>= T + K) steps."""
    n, t, k = codes.shape
    if length < t + k:
        raise ValueError(f"{length} steps cannot hold {t} frames of {k} "
                         f"delayed codebooks")
    out = np.full((n, length, k), special, codes.dtype)
    for c in range(k):
        out[:, 1 + c:1 + c + t, c] = codes[:, :, c]
    return out


def synthetic_lm(n_seqs: int, seq_len: int, vocab: int,
                 seed: int = 0, n_codebooks: int = 1,
                 chain_seed: int = 1234) -> Dict[str, np.ndarray]:
    """``seed`` controls the sampled streams; ``chain_seed`` controls the
    transition structure — train/test splits must share the latter.
    Parallel codebooks' ``seq_len + 1`` steps are the delay pattern
    (special token ``vocab``) of ``seq_len + 1 - n_codebooks`` frames."""
    rng = np.random.default_rng(chain_seed)
    # peaked Markov chain: one dominant successor (p~0.75) + a runner-up,
    # so argmax accuracy is learnable (optimum ~0.75) and convergence
    # ordering between algorithms is measurable in tens of rounds
    probs = np.full((vocab, vocab), 0.1 / vocab, np.float32)
    succ = rng.permutation(vocab)
    succ2 = rng.permutation(vocab)
    for v in range(vocab):
        probs[v, succ[v]] += 0.75
        probs[v, succ2[v]] += 0.15
    probs /= probs.sum(1, keepdims=True)
    cdf = np.cumsum(probs, axis=1)

    def sample_stream(k):
        r = np.random.default_rng(seed * 7919 + k)
        out = np.empty(seq_len + 1, np.int32)
        out[0] = r.integers(vocab)
        u = r.random(seq_len)
        for t in range(seq_len):
            out[t + 1] = np.searchsorted(cdf[out[t]], u[t])
        return out

    tokens = np.stack([sample_stream(i) for i in range(n_seqs)])
    if n_codebooks > 1:
        shifted = [np.roll(tokens, c, axis=1) for c in range(n_codebooks)]
        tokens = delay_pattern(np.stack(shifted, axis=-1)[
            :, :seq_len + 1 - n_codebooks], vocab, seq_len + 1)
    # labels for dirichlet splitting: dominant token bucket
    labels = (tokens.reshape(n_seqs, -1)[:, 0] % 10).astype(np.int32)
    return {"tokens": tokens, "labels": labels}


def synthetic_frontend_embeds(n: int, n_tokens: int, d_in: int,
                              seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(scale=0.5, size=(n, n_tokens, d_in)).astype(np.float32)


def synthetic_conditioning(n: int, n_tokens: int, d_in: int,
                           seed: int = 0) -> Dict[str, np.ndarray]:
    """Stand-in text conditioning: ``cond`` (n, n_tokens, d_in) and its
    padding ``cond_mask`` (n, n_tokens), each row a random 1..n_tokens
    valid tokens followed by padding."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, n_tokens + 1, size=n)
    return {"cond": synthetic_frontend_embeds(n, n_tokens, d_in, seed),
            "cond_mask": np.arange(n_tokens)[None, :] < lengths[:, None]}

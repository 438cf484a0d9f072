"""Serving driver: batched prefill + decode with optional FedHeN early exit.

The FedHeN side objective trains the exit head jointly with the full model,
so at serving time the same checkpoint yields two operating points:
* full-depth decode (quality), and
* early-exit decode (the simple sub-network: ~simple/complex FLOPs ratio),
plus a **confidence-based adaptive mode** (Kaya et al.-style): emit the
exit head's token when its max probability clears a threshold, otherwise
run the remaining layers.  (On the batched path we compute both heads and
report how often the exit head would have sufficed.)

Example:
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
        --batch 4 --prompt-len 32 --gen 16 --adaptive-threshold 0.6
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.checkpoint import restore_tree
from repro.data.synthetic import synthetic_conditioning
from repro.models import transformer as tfm


def generate(params, cfg, prompts: jax.Array, gen: int, *,
             adaptive_threshold: float = 0.0, temperature: float = 0.0,
             rng=None, cond=None, cond_mask=None):
    """prompts: (B, S[, NC]); ``cond``/``cond_mask``: the conditioning of
    a cross-attention model, whose K/V prefill caches for every decoded
    token.  Returns (tokens, stats)."""
    b, s = prompts.shape[0], prompts.shape[1]
    total = s + gen
    logits, cache = tfm.prefill(params, cfg, prompts, cond=cond,
                                cond_mask=cond_mask, cache_len=total)
    last = logits[:, -1]

    step = jax.jit(lambda c, t, p: tfm.decode_step(
        params, c, cfg, t, p, with_exit_head=True))

    out = [prompts]
    exit_agree = 0
    exit_confident = 0

    def pick(lg, key):
        if temperature > 0:
            return jax.random.categorical(key, lg / temperature, axis=-1)
        return jnp.argmax(lg, axis=-1)

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if cfg.n_codebooks > 1:
        tok = pick(last, rng)[:, None]                  # (B, 1, NC)
    else:
        tok = pick(last, rng)[:, None]                  # (B, 1)
    out.append(tok)

    for i in range(gen - 1):
        rng, key = jax.random.split(rng)
        logits, cache, exit_logits = step(cache, tok, jnp.int32(s + i))
        full_tok = pick(logits[:, -1], key)
        exit_tok = pick(exit_logits[:, -1], key)
        if adaptive_threshold > 0:
            probs = jax.nn.softmax(exit_logits[:, -1].astype(jnp.float32),
                                   axis=-1)
            conf = jnp.max(probs, axis=-1)
            confident = conf >= adaptive_threshold
            chosen = jnp.where(confident[..., None] if full_tok.ndim > 1
                               else confident, exit_tok, full_tok)
            exit_confident += int(jnp.sum(confident))
        else:
            chosen = full_tok
        exit_agree += int(jnp.sum(exit_tok == full_tok))
        tok = chosen[:, None]
        out.append(tok)

    tokens = jnp.concatenate(out, axis=1)
    n = b * max(gen - 1, 1) * (cfg.n_codebooks if cfg.n_codebooks > 1 else 1)
    stats = {"exit_agreement": exit_agree / n,
             "exit_confident_frac": exit_confident / max(b * (gen - 1), 1)}
    return tokens, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--adaptive-threshold", type=float, default=0.0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    params = tfm.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.checkpoint:
        params, _ = restore_tree(args.checkpoint, params)

    shape = ((args.batch, args.prompt_len, cfg.n_codebooks)
             if cfg.n_codebooks > 1 else (args.batch, args.prompt_len))
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1), shape,
                                 0, cfg.vocab_size)
    cond = {}
    if cfg.cross_attention:
        cond = {k: jnp.asarray(v) for k, v in synthetic_conditioning(
            args.batch, cfg.frontend.n_tokens, cfg.frontend.d_in,
            seed=args.seed).items()}

    t0 = time.time()
    tokens, stats = generate(params, cfg, prompts, args.gen,
                             adaptive_threshold=args.adaptive_threshold,
                             temperature=args.temperature, **cond)
    dt = time.time() - t0
    n_new = args.batch * args.gen
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"generated {n_new} tokens in {dt:.2f}s "
          f"({n_new / dt:.1f} tok/s on CPU)")
    print(f"exit-head agreement with full model: "
          f"{stats['exit_agreement']:.2%}")
    if args.adaptive_threshold > 0:
        print(f"tokens the exit head was confident on: "
              f"{stats['exit_confident_frac']:.2%} "
              f"(these skip {cfg.n_layers - cfg.resolved_exit_layer} of "
              f"{cfg.n_layers} layers)")
    print("sample tokens:", np.asarray(tokens[0, :24]).tolist())
    return stats


if __name__ == "__main__":
    main()

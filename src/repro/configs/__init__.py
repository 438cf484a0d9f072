"""Architecture registry: the 10 assigned architectures + the paper's own
PreActResNet18/CIFAR setting, with ``input_specs`` ShapeDtypeStruct
stand-ins for the dry-run."""

from __future__ import annotations

import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (FedConfig, InputShape, ModelConfig,
                                INPUT_SHAPES, TRAIN_4K, PREFILL_32K,
                                DECODE_32K, LONG_500K)

_MODULES = {
    "recurrentgemma-2b": "recurrentgemma_2b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "starcoder2-15b": "starcoder2_15b",
    "gemma2-2b": "gemma2_2b",
    "xlstm-1.3b": "xlstm_1_3b",
    "llava-next-34b": "llava_next_34b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "gemma3-4b": "gemma3_4b",
    "musicgen-large": "musicgen_large",
    "minitron-8b": "minitron_8b",
}

ARCH_NAMES = tuple(_MODULES)

# Archs whose paper config is natively sub-quadratic (bounded state / local
# window): run long_500k as configured.  The rest use the sliding-window
# longctx variant (cfg.longctx_window), flagged in the dry-run record.
NATIVE_LONGCTX = ("recurrentgemma-2b", "xlstm-1.3b", "gemma2-2b", "gemma3-4b")


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


def get_reduced(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.reduced()


def needs_longctx_variant(cfg: ModelConfig, shape: InputShape) -> bool:
    return (shape.name == "long_500k"
            and cfg.name not in NATIVE_LONGCTX)


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins (no allocation) for every input a
# step function takes, per (arch x input shape).
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: InputShape,
                batch_override: Optional[int] = None
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    b = batch_override or shape.global_batch
    s = shape.seq_len
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    tok_shape = (b, s + 1) if shape.kind == "train" else (b, s)
    if shape.kind == "decode":
        tok_shape = (b, 1)
    if cfg.n_codebooks > 1:
        tok_shape = tok_shape + (cfg.n_codebooks,)
    specs["tokens"] = jax.ShapeDtypeStruct(tok_shape, jnp.int32)

    if cfg.cross_attention and shape.kind != "decode":
        # the conditioning is the cross-attention source, not part of the
        # sequence; decode reads its K/V from the cache
        specs["cond"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend.n_tokens, cfg.frontend.d_in),
            jnp.dtype(cfg.compute_dtype))
        specs["cond_mask"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend.n_tokens), jnp.bool_)
    elif cfg.frontend is not None and shape.kind != "decode":
        # frontend embeddings occupy the head of the sequence; the token part
        # shrinks so total length stays seq_len (handled by the step fns)
        specs["extra_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend.n_tokens, cfg.frontend.d_in),
            jnp.dtype(cfg.compute_dtype))
        t = specs["tokens"].shape
        specs["tokens"] = jax.ShapeDtypeStruct(
            (b, t[1] - cfg.frontend.n_tokens) + t[2:], jnp.int32)
    return specs

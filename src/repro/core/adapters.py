"""Model adapters: bind a concrete architecture to the FedHeN machinery.

An adapter exposes the paper's three client objectives over a *complex*
parameter tree:

* ``loss_complex``            — f_j(w_c)                      (ClientTraining)
* ``loss_simple``             — f_i([w_c]_M)                  (simple devices;
  touches only M-parameters, so its gradient is zero outside M)
* ``loss_side``               — f_j(w_c) + f_j([w_c]_M)       (ClientTrainingSideObj)

plus ``subnet_mask`` (index set M) and evaluation metrics for both heads.
``loss_side`` is computed in ONE forward pass (the subnet is a depth
prefix -> early-exit head), matching the paper's "side objective adds
minimal cost" property.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import masking
from repro.models import common, resnet
from repro.models import transformer as tfm
from repro.models.common import NO_POLICY, Policy

Tree = Any
Batch = Dict[str, jax.Array]


def _ce(logits, labels):
    return common.softmax_cross_entropy(logits, labels)


def _acc(logits, labels):
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


# ---------------------------------------------------------------------------
# ResNet / CIFAR (the paper's own experimental setting)
# ---------------------------------------------------------------------------

class ResNetAdapter:
    """PreActResNet18-GN complex / 2-stage+mixpool simple (paper §3)."""

    def __init__(self, n_classes: int = 10):
        self.n_classes = n_classes

    def init(self, key) -> Tree:
        return resnet.init_params(key, self.n_classes)

    def subnet_mask(self, params: Tree) -> Tree:
        return masking.resnet_subnet_mask(params)

    def loss_complex(self, params: Tree, batch: Batch) -> jax.Array:
        _, final = resnet.forward(params, batch["images"])
        return _ce(final, batch["labels"])

    def loss_simple(self, params: Tree, batch: Batch) -> jax.Array:
        logits = resnet.forward_simple(params, batch["images"])
        return _ce(logits, batch["labels"])

    def loss_side(self, params: Tree, batch: Batch) -> jax.Array:
        exit_logits, final = resnet.forward(params, batch["images"])
        return _ce(final, batch["labels"]) + _ce(exit_logits, batch["labels"])

    def evaluate(self, params: Tree, batch: Batch) -> Dict[str, jax.Array]:
        exit_logits, final = resnet.forward(params, batch["images"])
        return {"acc_complex": _acc(final, batch["labels"]),
                "acc_simple": _acc(exit_logits, batch["labels"])}


# ---------------------------------------------------------------------------
# Decoder LM zoo
# ---------------------------------------------------------------------------

class LMAdapter:
    """Any ModelConfig from the zoo.  Batch: tokens (B, S+1)
    [, extra_embeds | cond, cond_mask].

    For multi-codebook (musicgen) tokens are (B, S+1, n_codebooks) in the
    delay pattern and the loss averages codebook CEs, each codebook's CE
    its mean over the labels that are not the special token.  For VLM,
    ``extra_embeds`` are prepended and the loss covers text positions
    only; with cross-attention ``cond`` (B, N, d_in) is the conditioning
    that every layer attends to, ``cond_mask`` (B, N) its padding.
    """

    def __init__(self, cfg: ModelConfig, policy: Policy = NO_POLICY,
                 remat: bool = False):
        self.cfg = cfg
        self.policy = policy
        self.remat = remat

    def init(self, key) -> Tree:
        return tfm.init_params(key, self.cfg)

    def subnet_mask(self, params: Tree) -> Tree:
        return masking.transformer_subnet_mask(params, self.cfg)

    def geometry(self, data: Batch, batch_size: int) -> Dict[str, Any]:
        """The client model's shape on one client's dataset ``data``
        (leading axis: its sequences) at SGD batches of ``batch_size``,
        for the run's ``run_config`` ledger.  Loss tokens count the labels
        the loss covers, summed over codebooks."""
        cfg = self.cfg
        tokens = data["tokens"]
        seq = tokens.shape[1] - 1
        labels = np.asarray(tokens[:, 1:])
        if cfg.delay_pattern:
            per_seq = np.mean(np.sum(labels != cfg.vocab_size,
                                     axis=tuple(range(1, labels.ndim))))
        else:
            per_seq = seq
        out = {"model_layers": cfg.n_layers,
               "model_exit_layer": cfg.resolved_exit_layer,
               "model_codebooks": cfg.n_codebooks, "model_seq": seq,
               "model_loss_tokens_per_step": float(batch_size * per_seq)}
        if cfg.cross_attention:
            out["model_cond_tokens"] = data["cond"].shape[1]
        return out

    # -- loss plumbing -----------------------------------------------------

    def _inputs(self, batch: Batch):
        tokens = batch["tokens"]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        extra = batch.get("extra_embeds")
        return inputs, labels, extra

    def _forward(self, params, batch, inputs, **kw):
        return tfm.forward(params, self.cfg, inputs,
                           extra_embeds=batch.get("extra_embeds"),
                           cond=batch.get("cond"),
                           cond_mask=batch.get("cond_mask"),
                           policy=self.policy, **kw)

    def _head_loss(self, params, h, labels, extra, head, chunk: int = 256):
        """CE between head logits and labels.

        Long sequences are processed in remat'd chunks so the (B, S, V)
        logits tensor is never materialized at once (the unembedding is
        recomputed per chunk in the backward pass) — essential at
        vocab >= 256k x seq 4k on 16 GB chips.
        """
        if extra is not None:
            # VLM: frontend tokens are prepended; loss on text positions only
            h = h[:, extra.shape[1]:]
        b, s = h.shape[0], h.shape[1]

        if getattr(self.policy, "dp2d", False):
            # 2D data parallel: per-chip batch is ~1, so full-length logits
            # are small per chip AND chunk-scanned CE would pin a tied-
            # embedding grad all-reduce inside the loop (measured
            # 70 GiB/step).  Compute CE in one piece.
            chunk = s

        def chunk_nll_sum(h_c, lab_c):
            return tfm.head_nll(params, self.cfg, h_c, lab_c, head,
                                self.policy)

        def mean(total):
            if isinstance(total, dict):     # delay pattern: per codebook
                return jnp.mean(total["nll"]
                                / jnp.maximum(total["count"], 1.0))
            return total / (b * s)

        if s <= 2 * chunk or s % chunk:
            return mean(chunk_nll_sum(h, labels))

        nc = s // chunk
        h_c = h.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
        lab_c = labels.reshape((b, nc, chunk) + labels.shape[2:]
                               ).transpose(1, 0, 2, *range(3, labels.ndim + 1))

        @jax.checkpoint
        def body(acc, xs):
            hc, lc = xs
            return jax.tree.map(jnp.add, acc, chunk_nll_sum(hc, lc)), None

        zero = jax.tree.map(jnp.zeros_like,
                            jax.eval_shape(chunk_nll_sum, h_c[0], lab_c[0]))
        total, _ = jax.lax.scan(body, zero, (h_c, lab_c))
        return mean(total)

    def loss_complex(self, params: Tree, batch: Batch) -> jax.Array:
        inputs, labels, extra = self._inputs(batch)
        _, final_h, aux = self._forward(params, batch, inputs,
                                        remat=self.remat)
        loss = self._head_loss(params, final_h, labels, extra, "final")
        return loss + aux["load_balance"] + aux["router_z"]

    def loss_simple(self, params: Tree, batch: Batch) -> jax.Array:
        inputs, labels, extra = self._inputs(batch)
        exit_h = tfm.forward_simple(params, self.cfg, inputs,
                                    extra_embeds=extra,
                                    cond=batch.get("cond"),
                                    cond_mask=batch.get("cond_mask"),
                                    policy=self.policy, remat=self.remat)
        return self._head_loss(params, exit_h, labels, extra, "exit")

    def loss_side(self, params: Tree, batch: Batch) -> jax.Array:
        """f(w_c) + f([w_c]_M) — one forward pass, two heads."""
        inputs, labels, extra = self._inputs(batch)
        exit_h, final_h, aux = self._forward(params, batch, inputs,
                                             remat=self.remat)
        loss = (self._head_loss(params, final_h, labels, extra, "final")
                + self._head_loss(params, exit_h, labels, extra, "exit"))
        return loss + aux["load_balance"] + aux["router_z"]

    def evaluate(self, params: Tree, batch: Batch) -> Dict[str, jax.Array]:
        inputs, labels, extra = self._inputs(batch)
        exit_h, final_h, _ = self._forward(params, batch, inputs)
        out = {}
        for head, h in (("complex", final_h), ("simple", exit_h)):
            name = "final" if head == "complex" else "exit"
            if self.cfg.delay_pattern:
                # every codebook, over the labels the loss covers
                logits = tfm.logits_from_hidden(params, self.cfg, h, name,
                                                self.policy)
                valid = labels != self.cfg.vocab_size
                hits = jnp.argmax(logits, -1) == labels
                out[f"acc_{head}"] = (jnp.sum(hits & valid)
                                      / jnp.maximum(jnp.sum(valid), 1))
                out[f"loss_{head}"] = self._head_loss(params, h, labels,
                                                      extra, name)
                continue
            logits = tfm.logits_from_hidden(params, self.cfg, h, name,
                                            self.policy)
            if extra is not None:
                logits = logits[:, extra.shape[1]:]
            out[f"acc_{head}"] = _acc(logits, labels)
            out[f"loss_{head}"] = _ce(logits, labels)
        return out

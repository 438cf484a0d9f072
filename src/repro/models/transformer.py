"""Decoder-stack assembly for the architecture zoo.

The layer stack is organised as ``n_periods`` repetitions of the config's
``pattern`` (compiled as ``lax.scan`` over stacked parameters, one stack per
pattern position) plus ``n_remainder`` unrolled tail layers.  The FedHeN
simple sub-network is the depth prefix ``blocks[:exit_layer]`` — the scan is
split at ``exit_period`` so the complex forward yields the exit activation
for the side objective in the same pass (one forward, two heads).

Parameter tree:

    {"embed":   {"table": (V, D)} | {"tables": (n_codebooks, V(+1), D)},
     "frontend_proj": {"w": (d_in, D), "b": (D,)?}?, # frontend stub projector
     "periods": (p0, p1, ... p_{period-1})          # leaves (n_periods, ...)
     "rem":     (layer trees ...),                  # unrolled tail
     "exit_norm":  norm,                            # FedHeN early-exit head
     "final_norm": norm,
     "unembed": {"w": (D, V) | (n_codebooks, D, V)}?}  # untied heads

A block is ``{"pre_norm", "mixer"[, "cross_norm", "cross"][, "mlp_norm",
"mlp"]}``; ``cross`` is the cross-attention to the projected frontend
embeddings (``cfg.cross_attention``, musicgen), where the frontend is not
prepended.  Norms are RMSNorm or LayerNorm with bias (``cfg.norm``).

Caches mirror the same periods/rem structure; with cross-attention each
block's cache also holds the source's K/V (computed once, by ``prefill``)
and the cache holds the source's padding mask, ``cond_mask``.

Each part of a block is tagged for the device trace (``obs/scopes.part``):
self-attention, cross-attention, the FFN, and the heads with their loss.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, MLP_DENSE, MLP_MOE,
                                MLSTM, RGLRU, SLSTM, LayerSpec, ModelConfig)
from repro.models import attention, common, mlp, rglru, xlstm
from repro.models.common import NO_POLICY, Policy
from repro.obs.scopes import part

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def init_block(key, spec: LayerSpec, cfg: ModelConfig) -> Params:
    km, kf = jax.random.split(key)
    dt = cfg.jnp_param_dtype()
    p: Params = {"pre_norm": common.init_norm(cfg, cfg.d_model, dt)}
    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        p["mixer"] = attention.init_attention(km, cfg)
    elif spec.mixer == RGLRU:
        p["mixer"] = rglru.init_rglru(km, cfg)
    elif spec.mixer == MLSTM:
        p["mixer"] = xlstm.init_mlstm(km, cfg)
    elif spec.mixer == SLSTM:
        p["mixer"] = xlstm.init_slstm(km, cfg)
    if cfg.cross_attention:
        p["cross_norm"] = common.init_norm(cfg, cfg.d_model, dt)
        p["cross"] = attention.init_cross_attention(
            jax.random.fold_in(key, 2), cfg)
    if spec.mlp == MLP_DENSE:
        p["mlp_norm"] = common.init_norm(cfg, cfg.d_model, dt)
        p["mlp"] = mlp.init_mlp(kf, cfg)
    elif spec.mlp == MLP_MOE:
        p["mlp_norm"] = common.init_norm(cfg, cfg.d_model, dt)
        p["mlp"] = mlp.init_moe(kf, cfg)
    return p


def _zero_aux() -> Dict[str, jax.Array]:
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32)}


def _mixer_part(spec: LayerSpec):
    """The part tag of a mixer: attention mixers are ``self_attn``; the
    recurrent ones carry none."""
    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        return part("self_attn")
    return contextlib.nullcontext()


def _window(spec: LayerSpec, cfg: ModelConfig,
            window_override: Optional[int]) -> int:
    window = cfg.window if spec.mixer == ATTN_LOCAL else 0
    return window if window_override is None else window_override


def _apply_cross(p: Params, h: jax.Array, cfg: ModelConfig, kv, src_mask):
    """h + cross-attention of ``h`` to the source K/V (``kv``)."""
    with part("cross_attn"):
        x = common.apply_norm(cfg, p["cross_norm"], h)
        return h + attention.apply_cross_attention(p["cross"], x, *kv,
                                                   src_mask)


def _apply_ffn(p: Params, spec: LayerSpec, h: jax.Array, cfg: ModelConfig,
               policy: Policy, *, decode: bool = False):
    """h + the block's FFN (dense or MoE), and the MoE's aux losses."""
    aux = _zero_aux()
    if "mlp" not in p:
        return h, aux
    with part("ffn"):
        x = common.apply_norm(cfg, p["mlp_norm"], h)
        if spec.mlp == MLP_MOE and decode:
            # decode: route across the batch (one group) so active-expert
            # FLOPs scale with top_k, not n_experts
            b, s, d = x.shape
            y, aux = mlp.apply_moe(p["mlp"], x.reshape(1, b * s, d), cfg,
                                   policy)
            y = y.reshape(b, s, d)
        elif spec.mlp == MLP_MOE:
            y, aux = mlp.apply_moe(p["mlp"], x, cfg, policy)
        else:
            y = mlp.apply_mlp(p["mlp"], x, policy,
                              gelu_exact=cfg.gelu_exact)
        return h + y, aux


def apply_block(p: Params, spec: LayerSpec, h: jax.Array, cfg: ModelConfig,
                policy: Policy, *, window_override: Optional[int] = None,
                src=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence (train/prefill) block application.  ``src``: the
    cross-attention source ``(embeddings (B, N, D), mask (B, N))``."""
    with _mixer_part(spec):
        x = common.apply_norm(cfg, p["pre_norm"], h)
        if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
            m = attention.apply_attention(
                p["mixer"], x, cfg, window=_window(spec, cfg, window_override),
                policy=policy)
        elif spec.mixer == RGLRU:
            m = rglru.apply_rglru(p["mixer"], x, cfg, policy)
        elif spec.mixer == MLSTM:
            m = xlstm.apply_mlstm(p["mixer"], x, cfg, policy)
        elif spec.mixer == SLSTM:
            m = xlstm.apply_slstm(p["mixer"], x, cfg, policy)
        h = h + m
    if "cross" in p:
        with part("cross_attn"):
            kv = attention.cross_kv(p["cross"], src[0])
        h = _apply_cross(p, h, cfg, kv, src[1])
    h, aux = _apply_ffn(p, spec, h, cfg, policy)
    h = policy.constrain(h, ("batch", "seq", None))
    return h, aux


# -- decode variant ---------------------------------------------------------

def init_block_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                     seq_len: int, *, window_override: Optional[int] = None
                     ) -> Params:
    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        cache = attention.init_kv_cache(
            cfg, batch, seq_len, window=_window(spec, cfg, window_override))
        if cfg.cross_attention:
            shape = (batch, cfg.frontend.n_tokens, cfg.n_heads,
                     cfg.resolved_head_dim)
            cache["cross_k"] = jnp.zeros(shape, cfg.jnp_compute_dtype())
            cache["cross_v"] = jnp.zeros(shape, cfg.jnp_compute_dtype())
        return cache
    if spec.mixer == RGLRU:
        return rglru.init_rglru_cache(cfg, batch)
    if spec.mixer == MLSTM:
        return xlstm.init_mlstm_cache(cfg, batch)
    if spec.mixer == SLSTM:
        return xlstm.init_slstm_cache(cfg, batch)
    raise ValueError(spec.mixer)


def apply_block_decode(p: Params, spec: LayerSpec, h: jax.Array, cache: Params,
                       pos: jax.Array, cfg: ModelConfig, policy: Policy, *,
                       window_override: Optional[int] = None,
                       src_mask: Optional[jax.Array] = None):
    with _mixer_part(spec):
        x = common.apply_norm(cfg, p["pre_norm"], h)
        if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
            kv = {k: cache[k] for k in ("k", "v")}
            m, kv = attention.apply_attention_decode(
                p["mixer"], x, kv, pos, cfg,
                window=_window(spec, cfg, window_override), policy=policy)
            cache = {**cache, **kv}
        elif spec.mixer == RGLRU:
            m, cache = rglru.apply_rglru_decode(p["mixer"], x, cache, cfg,
                                                policy)
        elif spec.mixer == MLSTM:
            m, cache = xlstm.apply_mlstm_decode(p["mixer"], x, cache, cfg,
                                                policy)
        elif spec.mixer == SLSTM:
            m, cache = xlstm.apply_slstm_decode(p["mixer"], x, cache, cfg,
                                                policy)
        h = h + m
    if "cross" in p:
        h = _apply_cross(p, h, cfg, (cache["cross_k"], cache["cross_v"]),
                         src_mask)
    h, aux = _apply_ffn(p, spec, h, cfg, policy, decode=True)
    return h, cache, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    dt = cfg.jnp_param_dtype()
    params: Params = {}

    if cfg.n_codebooks > 1:
        tables = jax.vmap(
            lambda k: common.embed_init(k, (cfg.embed_rows, cfg.d_model), dt)
        )(jax.random.split(keys[0], cfg.n_codebooks))
        params["embed"] = {"tables": tables}
    else:
        params["embed"] = common.init_embedding(keys[0], cfg.embed_rows,
                                                cfg.d_model, dt)
    if cfg.frontend is not None:
        params["frontend_proj"] = {
            "w": common.dense_init(keys[1], (cfg.frontend.d_in, cfg.d_model),
                                   dt)}
        if cfg.cross_attention:
            params["frontend_proj"]["b"] = jnp.zeros((cfg.d_model,), dt)

    # periodic stacks: one stacked tree per pattern position
    period_params = []
    for pos, spec in enumerate(cfg.pattern):
        pkeys = jax.random.split(jax.random.fold_in(keys[2], pos),
                                 cfg.n_periods)
        stacked = jax.vmap(lambda k, s=spec: init_block(k, s, cfg))(pkeys)
        period_params.append(stacked)
    params["periods"] = tuple(period_params)

    rem = []
    for i in range(cfg.n_remainder):
        spec = cfg.pattern[i % cfg.period]
        rem.append(init_block(jax.random.fold_in(keys[3], i), spec, cfg))
    params["rem"] = tuple(rem)

    params["exit_norm"] = common.init_norm(cfg, cfg.d_model, dt)
    params["final_norm"] = common.init_norm(cfg, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        shape = (cfg.d_model, cfg.vocab_size)
        if cfg.n_codebooks > 1:
            shape = (cfg.n_codebooks,) + shape
        params["unembed"] = {
            "w": common.dense_init(keys[4], shape, dt, fan_in=cfg.d_model)}
    return params


# -- embedding --------------------------------------------------------------

def embed_inputs(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 extra_embeds: Optional[jax.Array] = None,
                 policy: Policy = NO_POLICY, offset=0) -> jax.Array:
    """tokens: (B, S) or (B, S, n_codebooks).  extra_embeds: (B, N, d_in)
    precomputed frontend embeddings (VLM patches), prepended to the
    sequence after projection.  ``offset``: the position of the first
    token (decode), for sinusoidal positions."""
    cd = cfg.jnp_compute_dtype()
    if cfg.n_codebooks > 1:
        tabs = params["embed"]["tables"]                  # (NC, V, D)
        parts = [jnp.take(tabs[c], tokens[..., c], axis=0)
                 for c in range(cfg.n_codebooks)]
        h = sum(parts)
    else:
        h = common.apply_embedding(params["embed"], tokens,
                                   scale=cfg.embed_scale)
    h = h.astype(cd)
    if cfg.positions == "sin":
        pos = offset + jnp.arange(tokens.shape[1], dtype=jnp.int32)
        h = h + common.sinusoidal_embedding(pos, cfg.d_model,
                                            cfg.rope_theta).astype(cd)
    if extra_embeds is not None:
        proj = jnp.einsum("bnd,dk->bnk",
                          extra_embeds.astype(cd),
                          params["frontend_proj"]["w"].astype(cd))
        h = jnp.concatenate([proj, h], axis=1)
    return policy.constrain(h, ("batch", "seq", None))


def cross_source(params: Params, cfg: ModelConfig,
                 cond: Optional[jax.Array],
                 cond_mask: Optional[jax.Array] = None):
    """The cross-attention source of a batch: the frontend embeddings
    ``cond`` (B, N, d_in) projected (with bias) to d_model, zeroed where
    ``cond_mask`` (B, N) is false (padding), and that mask.  ``None``
    for a config without cross-attention."""
    if not cfg.cross_attention:
        return None
    if cond is None:
        raise ValueError(f"{cfg.name}: cross-attention needs the batch's "
                         f"conditioning ('cond')")
    if cond_mask is None:
        cond_mask = jnp.ones(cond.shape[:2], bool)
    cd = cfg.jnp_compute_dtype()
    w = params["frontend_proj"]
    with part("cross_attn"):
        src = (jnp.einsum("bnd,dk->bnk", cond.astype(cd), w["w"].astype(cd))
               + w["b"].astype(cd))
        src = jnp.where(cond_mask[..., None], src, 0.0)
    return src, cond_mask


def logits_from_hidden(params: Params, cfg: ModelConfig, h: jax.Array,
                       head: str, policy: Policy = NO_POLICY) -> jax.Array:
    """head: 'final' or 'exit' (FedHeN early-exit head, shared
    unembedding: the tied tables or the untied heads)."""
    norm = params["final_norm"] if head == "final" else params["exit_norm"]
    h = common.apply_norm(cfg, norm, h)
    if cfg.n_codebooks > 1:
        heads = params["unembed"]["w"].astype(h.dtype)     # (NC, D, V)
        logits = jnp.einsum("bsd,cdv->bscv", h, heads)
    elif cfg.tie_embeddings:
        logits = common.apply_unembedding(
            {"table": params["embed"]["table"].astype(h.dtype)}, h)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h,
                            params["unembed"]["w"].astype(h.dtype))
    logits = common.softcap(logits, cfg.final_logit_softcap)
    return policy.constrain(logits, ("batch", "seq", "vocab"))


def head_nll(params: Params, cfg: ModelConfig, h: jax.Array,
             labels: jax.Array, head: str, policy: Policy = NO_POLICY):
    """Summed next-token NLL of one head over ``h`` (B, S, D) against
    ``labels`` (B, S[, n_codebooks]), tagged ``heads``.

    A scalar sum, or with parallel codebooks (the delay pattern)
    ``{"nll": (NC,), "count": (NC,)}``: each codebook's sum and count over
    the labels that are not the special token, whose ratio is that
    codebook's mean (Audiocraft's per-codebook CE)."""
    with part("heads"):
        logits = logits_from_hidden(params, cfg, h, head, policy)
        if cfg.n_codebooks == 1:
            return common.softmax_cross_entropy_sum(logits, labels)
        valid = labels != cfg.vocab_size
        nll = jnp.stack([common.softmax_cross_entropy_sum(
            logits[..., c, :], labels[..., c], valid[..., c])
            for c in range(cfg.n_codebooks)])
        count = jnp.sum(valid.reshape(-1, cfg.n_codebooks), axis=0)
        return {"nll": nll, "count": count.astype(jnp.float32)}


# -- forward (train / prefill) -----------------------------------------------

def _merge_aux(a, b):
    return {k: a[k] + b[k] for k in a}


def _tree_slice(tree, start, stop):
    return jax.tree.map(lambda x: x[start:stop], tree)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            extra_embeds: Optional[jax.Array] = None,
            cond: Optional[jax.Array] = None,
            cond_mask: Optional[jax.Array] = None,
            policy: Policy = NO_POLICY, remat: bool = False,
            window_override: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (exit_hidden, final_hidden, aux_losses).

    ``exit_hidden`` is the activation after ``resolved_exit_layer`` blocks —
    the FedHeN simple sub-network's output stream.  One scan over all
    periods; the exit activation is captured in the carry with a select at
    the exit boundary (gradients from the exit head route through it), which
    keeps the layer stack a single while loop in HLO.  ``cond`` /
    ``cond_mask``: the cross-attention source (see :func:`cross_source`).
    """
    h = embed_inputs(params, cfg, tokens, extra_embeds, policy)
    src = cross_source(params, cfg, cond, cond_mask)
    kp = cfg.exit_period

    def period_body(carry, xs):
        h, exit_h, aux, idx = carry
        period_slice = xs
        for pos, spec in enumerate(cfg.pattern):
            h, a = apply_block(period_slice[pos], spec, h, cfg, policy,
                               window_override=window_override, src=src)
            aux = _merge_aux(aux, a)
        exit_h = jnp.where(idx == kp - 1, h, exit_h)
        return (h, exit_h, aux, idx + 1), None

    body = jax.checkpoint(period_body) if remat else period_body
    (h, exit_h, aux, _), _ = jax.lax.scan(
        body, (h, h, _zero_aux(), jnp.zeros((), jnp.int32)),
        params["periods"])
    for i, p_rem in enumerate(params["rem"]):
        spec = cfg.pattern[i % cfg.period]
        h, a = apply_block(p_rem, spec, h, cfg, policy,
                           window_override=window_override, src=src)
        aux = _merge_aux(aux, a)
    return exit_h, h, aux


def forward_simple(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
                   extra_embeds: Optional[jax.Array] = None,
                   cond: Optional[jax.Array] = None,
                   cond_mask: Optional[jax.Array] = None,
                   policy: Policy = NO_POLICY, remat: bool = False
                   ) -> jax.Array:
    """Forward of the *simple* architecture only (prefix blocks + exit head).

    ``params`` may be either full complex params or an extracted simple tree
    (see core/masking.py) — only the prefix stacks are touched.
    """
    h = embed_inputs(params, cfg, tokens, extra_embeds, policy)
    src = cross_source(params, cfg, cond, cond_mask)

    def period_body(carry, period_slice):
        h, aux = carry
        for pos, spec in enumerate(cfg.pattern):
            h, a = apply_block(period_slice[pos], spec, h, cfg, policy,
                               src=src)
            aux = _merge_aux(aux, a)
        return (h, aux), None

    body = jax.checkpoint(period_body) if remat else period_body
    kp = cfg.exit_period
    pre = tuple(_tree_slice(t, 0, kp) for t in params["periods"])
    (h, _), _ = jax.lax.scan(body, (h, _zero_aux()), pre)
    return h


# -- prefill (build cache + logits in one parallel pass) ---------------------

def apply_block_prefill(p: Params, spec: LayerSpec, h: jax.Array,
                        cfg: ModelConfig, policy: Policy, *,
                        window_override: Optional[int] = None,
                        cache_len: Optional[int] = None, src=None):
    with _mixer_part(spec):
        x = common.apply_norm(cfg, p["pre_norm"], h)
        x = policy.constrain(x, ("batch", "seq", None))
        if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
            window = _window(spec, cfg, window_override)
            m, k, v = attention.apply_attention(p["mixer"], x, cfg,
                                                window=window, policy=policy,
                                                return_kv=True)
            cache = attention.kv_to_cache(k, v, cfg, window=window,
                                          cache_len=cache_len)
        elif spec.mixer == RGLRU:
            m, cache = rglru.apply_rglru(p["mixer"], x, cfg, policy,
                                         return_state=True)
        elif spec.mixer == MLSTM:
            m, cache = xlstm.apply_mlstm(p["mixer"], x, cfg, policy,
                                         return_state=True)
        elif spec.mixer == SLSTM:
            m, cache = xlstm.apply_slstm(p["mixer"], x, cfg, policy,
                                         return_state=True)
        h = h + m
    if "cross" in p:
        with part("cross_attn"):
            kv = attention.cross_kv(p["cross"], src[0])
        cd = cfg.jnp_compute_dtype()
        cache["cross_k"], cache["cross_v"] = (t.astype(cd) for t in kv)
        h = _apply_cross(p, h, cfg, kv, src[1])
    h, aux = _apply_ffn(p, spec, h, cfg, policy)
    h = policy.constrain(h, ("batch", "seq", None))
    return h, cache, aux


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            extra_embeds: Optional[jax.Array] = None,
            cond: Optional[jax.Array] = None,
            cond_mask: Optional[jax.Array] = None,
            policy: Policy = NO_POLICY,
            window_override: Optional[int] = None,
            cache_len: Optional[int] = None):
    """Parallel prefill: returns (logits, cache) — the prefill -> decode
    handoff.  ``cache_len`` sizes the dense caches (>= prompt length) to
    leave room for decoded tokens.  With cross-attention the cache also
    holds each layer's K/V of the conditioning and its mask, so decoding
    never projects the conditioning again."""
    h = embed_inputs(params, cfg, tokens, extra_embeds, policy)
    src = cross_source(params, cfg, cond, cond_mask)

    def period_body(h, period_slice):
        caches = []
        for pos, spec in enumerate(cfg.pattern):
            h, c, _ = apply_block_prefill(
                period_slice[pos], spec, h, cfg, policy,
                window_override=window_override, cache_len=cache_len,
                src=src)
            caches.append(c)
        return h, tuple(caches)

    h, period_caches = jax.lax.scan(period_body, h, params["periods"])
    rem_caches = []
    for i, p_rem in enumerate(params["rem"]):
        spec = cfg.pattern[i % cfg.period]
        h, c, _ = apply_block_prefill(p_rem, spec, h, cfg, policy,
                                      window_override=window_override,
                                      cache_len=cache_len, src=src)
        rem_caches.append(c)
    cache = {"periods": period_caches, "rem": tuple(rem_caches)}
    if src is not None:
        cache["cond_mask"] = src[1]
    logits = logits_from_hidden(params, cfg, h, "final", policy)
    return logits, cache


# -- decode -------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int, *,
               window_override: Optional[int] = None) -> Params:
    cache: Params = {"periods": [], "rem": []}
    for pos, spec in enumerate(cfg.pattern):
        one = init_block_cache(spec, cfg, batch, seq_len,
                               window_override=window_override)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape)
            if cfg.n_periods else x[None][:0], one)
        cache["periods"].append(stacked)
    cache["periods"] = tuple(cache["periods"])
    for i in range(cfg.n_remainder):
        spec = cfg.pattern[i % cfg.period]
        cache["rem"].append(init_block_cache(spec, cfg, batch, seq_len,
                                             window_override=window_override))
    cache["rem"] = tuple(cache["rem"])
    if cfg.cross_attention:
        # no conditioning until prefill writes it: every source key masked
        cache["cond_mask"] = jnp.zeros((batch, cfg.frontend.n_tokens), bool)
    return cache


def decode_step(params: Params, cache: Params, cfg: ModelConfig,
                tokens: jax.Array, pos: jax.Array, *,
                policy: Policy = NO_POLICY,
                window_override: Optional[int] = None,
                with_exit_head: bool = False):
    """One decode step.  tokens: (B, 1) or (B, 1, n_codebooks); pos: scalar.

    Returns (logits, new_cache[, exit_logits]).
    """
    h = embed_inputs(params, cfg, tokens, None, policy, offset=pos)
    src_mask = cache.get("cond_mask")
    kp = cfg.exit_period

    def period_body(carry, period_slice):
        h, pcaches, exit_h, idx = carry
        new_caches = list(pcaches)
        for pos_i, spec in enumerate(cfg.pattern):
            c_i = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, idx, 0,
                                                       keepdims=False),
                pcaches[pos_i])
            h, c, _ = apply_block_decode(period_slice[pos_i], spec, h,
                                         c_i, pos, cfg, policy,
                                         window_override=window_override,
                                         src_mask=src_mask)
            # write back in place (while-loop carry -> no cache copy)
            new_caches[pos_i] = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), idx, 0),
                pcaches[pos_i], c)
            pcaches = tuple(new_caches)
        exit_h = jnp.where(idx == kp - 1, h, exit_h)
        return (h, pcaches, exit_h, idx + 1), None

    (h, new_periods, exit_h, _), _ = jax.lax.scan(
        period_body,
        (h, cache["periods"], h, jnp.zeros((), jnp.int32)),
        params["periods"])

    new_rem = []
    for i, p_rem in enumerate(params["rem"]):
        spec = cfg.pattern[i % cfg.period]
        h, c, _ = apply_block_decode(p_rem, spec, h, cache["rem"][i], pos,
                                     cfg, policy,
                                     window_override=window_override,
                                     src_mask=src_mask)
        new_rem.append(c)

    new_cache = {"periods": new_periods, "rem": tuple(new_rem)}
    if src_mask is not None:
        new_cache["cond_mask"] = src_mask

    logits = logits_from_hidden(params, cfg, h, "final", policy)
    if with_exit_head:
        exit_logits = logits_from_hidden(params, cfg, exit_h, "exit", policy)
        return logits, new_cache, exit_logits
    return logits, new_cache

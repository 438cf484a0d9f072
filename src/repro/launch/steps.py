"""Production step functions — what the dry-run lowers and the drivers run.

* ``train_step`` — the FedHeN complex-device step: one side-objective SGD
  step (final CE + early-exit CE, clip 10, eta).  This is the per-device
  inner step of Alg. 2 ``ClientTrainingSideObj`` at production scale; the
  cohort/round structure wraps it in core/federated.py.
* ``baseline_train_step`` — same without the side objective (NoSide /
  Decouple inner step) — used to measure the side objective's marginal cost.
* ``fed_round_step`` — one complete FedHeN round over a stacked cohort,
  streamed in ``cohort_chunk``-sized chunks (``lax.scan``) through the
  masked-aggregation fold; the chunk's client axis is policy-constrained to
  the ``data``/``pod`` mesh axes (the ``cohort`` logical rule), so the fold
  lowers to the round's all-reduce while memory stays O(chunk).
* ``prefill_step`` — logits + decode cache for a prompt batch.
* ``serve_step`` — ONE token against a seq_len cache (decode shapes).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.core import aggregate, async_rounds, comm, flatten, masking
from repro.core.adapters import LMAdapter
from repro.models import transformer as tfm
from repro.models.common import NO_POLICY, Policy
from repro.obs import telemetry as obslib
from repro.optim.sgd import sgd_update

Tree = Any


def make_train_step(cfg: ModelConfig, policy: Policy = NO_POLICY, *,
                    lr: float = 0.1, clip_norm: float = 10.0,
                    side_objective: bool = True, remat: bool = True):
    adapter = LMAdapter(cfg, policy=policy, remat=remat)
    loss_fn = adapter.loss_side if side_objective else adapter.loss_complex

    def train_step(params: Tree, batch: Dict[str, jax.Array]):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params = sgd_update(params, grads, lr, clip_norm)
        return new_params, {"loss": loss}

    return train_step


def make_fed_round_step(cfg: ModelConfig, policy: Policy = NO_POLICY, *,
                        local_steps: int, lr: float = 0.1,
                        clip_norm: float = 10.0, cohort_chunk: int = 0,
                        engine: Optional[aggregate.EngineSpec] = None,
                        staleness_scheme: str = "poly",
                        staleness_decay: float = 0.5,
                        telemetry: Optional[obslib.Telemetry] = None):
    """One FedHeN round over a stacked cohort, streaming in chunks.

    Returns ``round_step(cohort, data, is_simple, flat_mask=None,
    staleness=None, real=None) -> (new_complex, loss)`` with ``cohort``
    stacked client params (K, ...),
    ``data`` of shape (K, B, local_steps, S+1) and ``is_simple`` (K,).
    ``cohort_chunk`` must divide K (0 = one chunk); the engine scans chunk
    by chunk, folding each trained chunk into running masked sums — the
    launch-side mirror of core/federated.py's round, operating on an
    externally sharded cohort instead of tiling server params.
    ``engine`` is an :class:`repro.core.aggregate.EngineSpec` carrying
    the whole aggregation configuration — the fold packs each trained
    chunk through the static ``core.flatten`` layout and folds the whole
    model with one accumulating ``masked_agg_acc`` launch per chunk in
    ``block_n`` tiles; the upload wire (``spec.wire``, core/comm.py: the
    externally sharded cohort arrives already broadcast, so only the
    client->server direction crosses this step — the fold consumes the
    encoded uploads, int8 via the dequantizing masked_agg accumulate);
    and the stream dtype.  The spec's mask/layout/flat_mask fields are
    bound HERE at trace time (they depend on the cohort template), so
    pass a spec without them — ``EngineSpec(wire=...)`` — or ``None``
    for the all-defaults f32 fold.

    Pass the precomputed flat bitvector (``flatten.pack_mask`` over the
    same layout) as ``flat_mask`` so it enters the jit as a replicated
    argument; if left ``None`` it is derived inside the trace, which XLA
    constant-folds into a params-sized ``pred`` literal baked into the
    executable (measured on the reduced config) — fine for tests, wrong
    at production scale.  The dry-run passes it explicitly.

    ``staleness`` is the async driver's seam (core/async_rounds.py owns
    the versioning; a sharded launch driver passes the result here): a
    ``(K,)`` array of per-client broadcast staleness in rounds (0 =
    fresh).  Each upload's validity weight is multiplied by
    ``staleness_weight(s, scheme=staleness_scheme,
    decay=staleness_decay)`` on the same masked-weight path NaN exclusion
    uses; ``None`` (and all-zero staleness) is exactly the synchronous
    fold.

    ``real`` is the uniform super-cohort sampler's seam
    (``core/sampling.py`` draws the plan; a launch driver passes
    ``plan.simple_real``/``plan.complex_real`` concatenated in slot
    order): a ``(K,)`` bool marking slots that hold a distinct sampled
    client.  Pad slots (``False``) fold at weight 0 through the same
    path and are excluded from the loss mean; ``None`` (stratified
    cohorts) means every slot is real — the unchanged program.

    ``telemetry`` (repro/obs; default: disabled) records ONE
    ``round_step_build`` ledger with the step's static configuration —
    the launch-side counterpart of the trainer's ``run_config`` event.
    The returned ``round_step`` itself stays pure and jit-friendly:
    callers jit it, so per-execution spans belong to the caller's host
    loop, not inside the traced function.
    """
    adapter = LMAdapter(cfg, policy=policy, remat=True)
    spec = engine if engine is not None else aggregate.EngineSpec(
        algorithm="fedhen", wire=comm.WireSpec("float32", 128))
    if spec.wire is None:
        spec = spec.bind(wire=comm.WireSpec("float32", 128))
    wire = spec.wire
    obs = obslib.coalesce(telemetry)
    if obs.enabled:
        values = {"local_steps": int(local_steps), "lr": lr,
                  "clip_norm": clip_norm,
                  "cohort_chunk": int(cohort_chunk),
                  "staleness_scheme": staleness_scheme,
                  "staleness_decay": staleness_decay}
        values.update(aggregate.engine_attrs(spec))
        obs.ledger("round_step_build", values)

    def constrain_cohort(tree):
        return jax.tree.map(
            lambda x: policy.constrain(
                x, ("cohort",) + (None,) * (x.ndim - 1)), tree)

    def client_train(params, data, is_simple):
        """One client: local_steps of SGD (side objective for complex
        clients, subnet objective for simple ones — branchless select)."""
        def step(p, batch):
            loss_c, g_c = jax.value_and_grad(adapter.loss_side)(p, batch)
            loss_s, g_s = jax.value_and_grad(adapter.loss_simple)(p, batch)
            g = jax.tree.map(lambda a, b: jnp.where(is_simple, b, a),
                             g_c, g_s)
            return sgd_update(p, g, lr, clip_norm), loss_c
        for i in range(local_steps):
            batch = {"tokens": data[:, i]}
            params, loss = step(params, batch)
        return params, loss

    def round_step(cohort: Tree, data: jax.Array, is_simple: jax.Array,
                   flat_mask: Optional[jax.Array] = None,
                   staleness: Optional[jax.Array] = None,
                   real: Optional[jax.Array] = None):
        k = data.shape[0]
        chunk = k if cohort_chunk <= 0 else cohort_chunk
        if k % chunk:
            raise ValueError(
                f"cohort_chunk={chunk} does not divide cohort size {k}")
        n_chunks = k // chunk
        template = jax.tree.map(lambda x: x[0], cohort)
        mask = masking.transformer_subnet_mask(template, cfg)
        layout = flatten.layout_of(template, total_multiple=spec.block_n)
        if flat_mask is None:  # trace-time fallback; see docstring
            flat_mask = flatten.pack_mask(layout, mask)
        agg_init, agg_fold, agg_finalize = aggregate.make_engine(
            spec.bind(mask=mask, layout=layout, flat_mask=flat_mask))

        if staleness is None:
            st_w = jnp.ones((k,), jnp.float32)
        else:
            st_w = async_rounds.staleness_weight(
                staleness, scheme=staleness_scheme, decay=staleness_decay)
        if real is not None:
            # super-cohort pad slots: weight 0 in the fold, out of the loss
            st_w = st_w * real.astype(jnp.float32)
        denom = (jnp.asarray(k, jnp.float32) if real is None
                 else jnp.maximum(jnp.sum(real.astype(jnp.float32)), 1.0))

        to_chunks = lambda x: x.reshape((n_chunks, chunk) + x.shape[1:])
        xs = (jax.tree.map(to_chunks, cohort), to_chunks(data),
              to_chunks(is_simple), to_chunks(st_w))
        if real is not None:
            xs = xs + (to_chunks(real),)

        def fold_chunk(carry, xs):
            state, loss_sum = carry
            if real is None:
                cohort_i, data_i, simple_i, st_w_i = xs
            else:
                cohort_i, data_i, simple_i, st_w_i, real_i = xs
            cohort_i = constrain_cohort(cohort_i)
            trained, losses = jax.vmap(client_train)(
                cohort_i, data_i.transpose(0, 2, 1, 3), simple_i)
            valid = jax.vmap(masking.tree_isfinite)(trained)
            state = agg_fold(state, trained, simple_i,
                             valid.astype(jnp.float32) * st_w_i)
            if real is not None:
                losses = jnp.where(real_i, losses, 0.0)
            return (state, loss_sum + jnp.sum(losses)), None

        state = agg_init(template)
        (state, loss_sum), _ = jax.lax.scan(
            fold_chunk, (state, jnp.zeros((), jnp.float32)), xs)
        new_complex, _ = agg_finalize(state, template=template)
        return new_complex, loss_sum / denom

    return round_step


def make_prefill_step(cfg: ModelConfig, policy: Policy = NO_POLICY, *,
                      window_override: Optional[int] = None,
                      cache_len: Optional[int] = None):
    def prefill_step(params: Tree, batch: Dict[str, jax.Array]):
        logits, cache = tfm.prefill(params, cfg, batch["tokens"],
                                    extra_embeds=batch.get("extra_embeds"),
                                    cond=batch.get("cond"),
                                    cond_mask=batch.get("cond_mask"),
                                    policy=policy,
                                    window_override=window_override,
                                    cache_len=cache_len)
        return logits, cache

    return prefill_step


def make_serve_step(cfg: ModelConfig, policy: Policy = NO_POLICY, *,
                    window_override: Optional[int] = None,
                    with_exit_head: bool = False):
    def serve_step(params: Tree, cache: Tree, batch: Dict[str, jax.Array],
                   pos: jax.Array):
        return tfm.decode_step(params, cache, cfg, batch["tokens"], pos,
                               policy=policy,
                               window_override=window_override,
                               with_exit_head=with_exit_head)

    return serve_step


def step_for_shape(cfg: ModelConfig, shape: InputShape,
                   policy: Policy = NO_POLICY, *,
                   window_override: Optional[int] = None,
                   side_objective: bool = True):
    """The step function a given input shape exercises."""
    if shape.kind == "train":
        return make_train_step(cfg, policy, side_objective=side_objective)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, policy,
                                 window_override=window_override)
    return make_serve_step(cfg, policy, window_override=window_override)

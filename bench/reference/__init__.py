"""Plain reference of FedHeN federated rounds, independent of the program.

It imports nothing from ``src/repro``.  Given the cell's configuration and
traffic files, the seed, the initial weights and the client data that the
benchmark made (``bench/families/``), it computes what the server model
must be after each round:

* the round's cohort, by the same stratified draw as the program's sampler
  (a pure function of ``(seed, round)``: ``round(p * pop)`` ids per
  population from ``SeedSequence([seed, round])``);
* the round's keys (``PRNGKey(seed * 100003 + round)``, split into one key
  per population, ``fold_in`` per cohort slot, ``split`` per local epoch,
  one permutation of the client's data per epoch);
* local SGD per client, the clients of a population side by side under
  ``vmap``: E epochs of minibatch SGD, global-norm clip, the step
  ``w <- store(w - lr * g)`` with the arithmetic in float32 and the
  result stored in the parameter dtype.  Simple clients
  train the simple subnet's loss, complex clients the loss of the whole
  model plus the simple head's (FedHeN's side objective); each family's
  objectives and index set M are ``bench/reference/<family>.py``;
* the float32 wire, which carries the models unchanged;
* the fold and finalize: inside the subnet M, the mean over every client
  whose result is finite; outside M, the mean over the finite complex
  clients.

``precision`` says how the matmuls and convolutions are computed:

* ``"stated"``, the reference: float32 operands at the matmul precision
  that the configuration states (``matmul_precision``; ``default`` is one
  bfloat16 pass with float32 accumulation on the TPU);
* ``"control"``, one precision step below: bfloat16 operands and bfloat16
  parameter storage;
* ``"highest"``: float32 operands at ``highest``, to show how far the
  stated precision itself lies from exact float32.
"""

from __future__ import annotations

import functools
import importlib
import json
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_SEED_MASK = (1 << 64) - 1
PRECISIONS = ("stated", "control", "highest")


# ---------------------------------------------------------------------------
# Cohort and keys
# ---------------------------------------------------------------------------

def _draw(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``k`` distinct sorted ids of ``[0, n)``: a choice without replacement
    for dense draws, first-seen rejection sampling for sparse ones."""
    if k == n:
        return np.arange(n, dtype=np.int64)
    if 4 * k >= n:
        return np.sort(rng.choice(n, size=k, replace=False).astype(np.int64))
    chosen = np.empty((0,), dtype=np.int64)
    while chosen.size < k:
        need = k - chosen.size
        draw = rng.integers(0, n, size=2 * need + 8, dtype=np.int64)
        draw = draw[~np.isin(draw, chosen)]
        _, first = np.unique(draw, return_index=True)
        chosen = np.concatenate([chosen, draw[np.sort(first)][:need]])
    return np.sort(chosen)


def cohort_sizes(traffic: dict) -> Tuple[int, int]:
    p = traffic["participation"]
    n_s = traffic["simple_clients"]
    n_c = traffic["clients"] - n_s
    return max(int(round(p * n_s)), 1), max(int(round(p * n_c)), 1)


def cohort(traffic: dict, seed: int, round_index: int
           ) -> Tuple[np.ndarray, np.ndarray]:
    """(simple ids, complex ids) of one round."""
    k_s, k_c = cohort_sizes(traffic)
    n_s = traffic["simple_clients"]
    rng = np.random.default_rng(
        np.random.SeedSequence([seed & _SEED_MASK, round_index]))
    simple = _draw(rng, n_s, k_s)
    complex_ = n_s + _draw(rng, traffic["clients"] - n_s, k_c)
    return simple, complex_


def round_key(seed: int, round_index: int) -> jax.Array:
    return jax.random.PRNGKey(seed * 100003 + round_index)


# ---------------------------------------------------------------------------
# Precision
# ---------------------------------------------------------------------------

class Numerics:
    """How the reference (or its control) rounds operands and storage."""

    def __init__(self, param_dtype: str, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        store = jnp.dtype(param_dtype)
        if precision == "control":
            if store != jnp.float32:
                raise ValueError(f"no control precision below {param_dtype}")
            store = jnp.dtype(jnp.bfloat16)
        self.store = store
        self.act = jnp.bfloat16 if precision == "control" else jnp.float32

    def einsum(self, spec: str, a, b) -> jax.Array:
        return jnp.einsum(spec, a.astype(self.act), b.astype(self.act),
                          preferred_element_type=jnp.float32)

    def conv(self, x, w, stride: int) -> jax.Array:
        """A convolution with f32 accumulation; below f32 the result is
        rounded to the operand dtype first, as the transposed convolutions
        of the gradient need operands of one dtype."""
        x, w = x.astype(self.act), w.astype(self.act)
        out = jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32 if x.dtype == jnp.float32
            else None)
        return out.astype(jnp.float32)


def ce_mean(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def family(cfg: dict):
    """The module ``bench/reference/<family>.py`` of a configuration."""
    return importlib.import_module(f"bench.reference.{cfg['family']}")


def matmul_precision(cfg: dict, precision: str) -> str:
    return "highest" if precision == "highest" else cfg["matmul_precision"]


# ---------------------------------------------------------------------------
# Local SGD, fold, rounds
# ---------------------------------------------------------------------------

def _global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


@functools.lru_cache(maxsize=8)
def client_trainers(cfg_json: str, traffic_json: str, precision: str):
    """The jitted (simple, complex) client trainers of a cell, kept so
    that repeated reference runs in one process compile once."""
    cfg, traffic = json.loads(cfg_json), json.loads(traffic_json)
    num = Numerics(cfg["param_dtype"], precision)
    simple_loss, side_loss = family(cfg).losses(cfg, num)
    return (make_client_train(simple_loss, traffic, num),
            make_client_train(side_loss, traffic, num))


def make_client_train(loss, traffic: dict, num: Numerics):
    """train(params, data, keys) -> (params', mean loss over the steps) of
    each client: ``data`` and ``keys`` are stacked on a leading client
    axis, ``params`` is the one model they all start from."""
    epochs, bs = traffic["local_epochs"], traffic["batch_size"]
    lr, clip = traffic["lr"], traffic["clip_norm"]

    def train(params, data, key):
        n = jax.tree.leaves(data)[0].shape[0]
        steps = max(n // bs, 1)

        def step(p, idx):
            batch = jax.tree.map(lambda x: x[idx], data)
            value, g = jax.value_and_grad(loss)(p, batch)
            gn = _global_norm(g)
            scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
            p = jax.tree.map(lambda w, gi: (w.astype(jnp.float32) - lr * (
                gi.astype(jnp.float32) * scale)).astype(num.store), p, g)
            return p, value

        def epoch(p, k):
            perm = jax.random.permutation(k, n)[:steps * bs]
            return jax.lax.scan(step, p, perm.reshape(steps, bs))

        params, losses = jax.lax.scan(epoch, params,
                                      jax.random.split(key, epochs))
        return params, jnp.mean(losses)

    return jax.jit(jax.vmap(train, in_axes=(None, 0, 0)))


@jax.jit
def _fold(trained_s, trained_c, mask, store_template):
    """FedHeN's server step over the stacked trained clients; a client
    whose result is not finite is left out."""
    def finite(tree):
        return jnp.stack([jnp.all(jnp.isfinite(x.reshape(x.shape[0], -1)),
                                  axis=1) for x in jax.tree.leaves(tree)]
                         ).all(axis=0).astype(jnp.float32)

    w_s, w_c = finite(trained_s), finite(trained_c)
    tot_all = jnp.sum(w_s) + jnp.sum(w_c)
    tot_c = jnp.sum(w_c)

    def leaf(xs, xc, m, t):
        def wsum(x, w):
            wb = w.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.sum(jnp.where(wb > 0, x.astype(jnp.float32), 0.0),
                           axis=0)
        inside = (wsum(xs, w_s) + wsum(xc, w_c)) / jnp.maximum(tot_all, 1.0)
        outside = wsum(xc, w_c) / jnp.maximum(tot_c, 1.0)
        return jnp.where(m, inside, outside).astype(t.dtype)

    return jax.tree.map(leaf, trained_s, trained_c, mask, store_template)


def run_rounds(cfg: dict, traffic: dict, seed: int, weights, client_data,
               n_rounds: int, precision: str = "stated"):
    """The reference's server models after each of ``n_rounds`` rounds.

    ``weights``: the initial server model; ``client_data``: a callable
    ``ids -> stacked batch`` of those clients' data.  Returns ``(models,
    losses)``: ``models[r]`` is the server model after round ``r + 1`` as
    host arrays, ``losses[r]`` the round's ``(mean simple loss, mean
    complex loss)``.
    """
    if (traffic["algorithm"], traffic["comm_dtype"], traffic["async_lag"]) \
            != ("fedhen", "float32", 0):
        raise NotImplementedError(
            "the reference covers fedhen on the float32 wire without lag")
    num = Numerics(cfg["param_dtype"], precision)
    train_s, train_c = client_trainers(json.dumps(cfg, sort_keys=True),
                                       json.dumps(traffic, sort_keys=True),
                                       precision)
    mask = family(cfg).subnet(cfg, weights)
    server = jax.tree.map(lambda x: jnp.asarray(x).astype(num.store), weights)
    template = jax.tree.map(lambda x: jnp.zeros((), num.store), weights)
    models, losses = [], []
    with jax.default_matmul_precision(matmul_precision(cfg, precision)):
        for r in range(n_rounds):
            ids_s, ids_c = cohort(traffic, seed, r)
            rs, rc = jax.random.split(round_key(seed, r))
            outs = []
            for ids, key, train in ((ids_s, rs, train_s),
                                    (ids_c, rc, train_c)):
                keys = jnp.stack([jax.random.fold_in(key, i)
                                  for i in range(len(ids))])
                trained, ls = train(server, client_data(ids), keys)
                outs.append((trained, float(jnp.mean(ls))))
            server = _fold(outs[0][0], outs[1][0], mask, template)
            models.append(jax.tree.map(np.asarray, server))
            losses.append((outs[0][1], outs[1][1]))
    return models, losses

"""What the benchmark builds for a configuration, from the seed.

A configuration file names its ``family``; ``bench/families/<family>.py``
says how that family's weights and client data are drawn and which of the
program's adapters runs it.  This module turns the configuration and
traffic files into the system under test: the weights and the client data
(each made on the device from ``--seed`` in one jitted call), the program's
adapter, its ``FedConfig`` and its ``FederatedTrainer``.  The trainer is the
program's own; only the initial weights come from here, through a thin
adapter whose ``init`` returns them, so that the reference can start from
the very same weights without taking anything that the program made.

A family module defines ``adapter(cfg)``, ``rule(leaf name, key, shape)``
(one leaf of the initial weights), ``data(cfg, traffic, key)`` (every
client's data as a list of dicts) and ``client_flops(cfg, simple)``.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int, stream: int) -> jax.Array:
    """A key per use of the seed, so data and weights draw apart."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)), stream)


_WEIGHTS, _DATA = 1, 2


def family(cfg: dict):
    """The module ``bench/families/<family>.py`` of a configuration."""
    return importlib.import_module(f"bench.families.{cfg['family']}")


class SeededAdapter:
    """The program's adapter with ``init`` replaced by given weights."""

    def __init__(self, inner, weights):
        self._inner = inner
        self._weights = weights

    def init(self, key):
        del key
        return self._weights

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def weights(cfg: dict, seed: int):
    """The program's adapter and the initial server model, drawn in one
    jitted call in the shapes and dtypes the adapter builds."""
    fam = family(cfg)
    adapter = fam.adapter(cfg)
    shapes = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    if n != cfg["n_params"]:
        raise ValueError(f"{cfg['name']}: the program builds {n} parameters, "
                         f"the configuration states {cfg['n_params']}")
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        return jax.tree.unflatten(treedef, [
            fam.rule(_leaf_name(p), jax.random.fold_in(key, i), s)
            for i, (p, s) in enumerate(paths)])

    return adapter, make(_key(seed, _WEIGHTS))


def data(cfg: dict, traffic: dict, seed: int) -> List[dict]:
    """Every client's data, one dict of device arrays per client."""
    return family(cfg).data(cfg, traffic, _key(seed, _DATA))


def stack(client_data: List[dict], ids) -> Dict[str, jax.Array]:
    """The data of clients ``ids``, stacked on a leading axis."""
    return {k: jnp.stack([client_data[int(i)][k] for i in ids])
            for k in client_data[0]}


def fed_config(traffic: dict, seed: int):
    from repro.configs.base import FedConfig
    return FedConfig(
        n_devices=traffic["clients"], n_simple=traffic["simple_clients"],
        participation=traffic["participation"],
        local_epochs=traffic["local_epochs"], lr=traffic["lr"],
        clip_norm=traffic["clip_norm"], batch_size=traffic["batch_size"],
        iid=traffic["dirichlet_alpha"] <= 0,
        dirichlet_alpha=traffic["dirichlet_alpha"] or 0.3,
        algorithm=traffic["algorithm"], seed=seed,
        cohort_chunk=traffic["cohort_chunk"],
        comm_dtype=traffic["comm_dtype"], async_lag=traffic["async_lag"])


def build_trainer(cfg: dict, traffic: dict, seed: int, telemetry=None,
                  marks: Optional[Dict[str, float]] = None):
    """The program's trainer of one run, on the seed's weights and data.
    ``marks``, where given, gets the host clock after each part."""
    from repro.core.federated import FederatedTrainer
    marks = {} if marks is None else marks
    adapter, w0 = jax.block_until_ready(weights(cfg, seed))
    marks["weights"] = time.perf_counter()
    client_data = jax.block_until_ready(data(cfg, traffic, seed))
    marks["data"] = time.perf_counter()
    trainer = FederatedTrainer(SeededAdapter(adapter, w0),
                               fed_config(traffic, seed), client_data,
                               telemetry=telemetry)
    marks["trainer"] = time.perf_counter()
    return trainer

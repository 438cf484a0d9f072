"""FedHeN core: masking, aggregation (Alg. 1), algorithms end-to-end
(chunked rounds: tests/test_chunked_rounds.py)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core import aggregate, async_rounds, masking
from repro.core.adapters import LMAdapter, ResNetAdapter
from repro.core.federated import (FederatedTrainer, make_client_trainer,
                                  rounds_to_target, stream_population)
from repro.data.synthetic import synthetic_lm
from repro.data.federated import dirichlet_split, iid_split


TINY = ModelConfig(n_layers=4, d_model=32, n_heads=2, n_kv_heads=2,
                   d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                   exit_layer=2, compute_dtype="float32")


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def test_mask_size_matches_analytic():
    adapter = LMAdapter(TINY)
    params = adapter.init(jax.random.PRNGKey(0))
    mask = adapter.subnet_mask(params)
    got = masking.mask_size(mask, params)
    assert got == TINY.simple_param_count(), (got, TINY.simple_param_count())


def test_extract_embed_roundtrip():
    params = LMAdapter(TINY).init(jax.random.PRNGKey(0))
    simple = masking.extract_simple(params, TINY)
    rebuilt = masking.embed_simple(simple, params, TINY)
    for a, b in zip(jax.tree.leaves(rebuilt), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_extracted_simple_runs_forward_simple():
    from repro.models import transformer as tfm
    params = LMAdapter(TINY).init(jax.random.PRNGKey(0))
    simple = masking.extract_simple(params, TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    h_full = tfm.forward_simple(params, TINY, tokens)
    h_sub = tfm.forward_simple(simple, TINY, tokens)
    np.testing.assert_allclose(h_full, h_sub, rtol=1e-6)


def test_simple_loss_grad_zero_outside_mask():
    """f([w_c]_M)'s gradient must vanish on M' (the paper's simple-client
    update touches only shared weights)."""
    adapter = LMAdapter(TINY)
    params = adapter.init(jax.random.PRNGKey(0))
    mask = adapter.subnet_mask(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 64)
    grads = jax.grad(adapter.loss_simple)(params, {"tokens": tokens})
    for g, m in zip(jax.tree.leaves(grads), jax.tree.leaves(mask)):
        outside = jnp.where(jnp.broadcast_to(m, g.shape), 0.0,
                            g.astype(jnp.float32))
        assert float(jnp.max(jnp.abs(outside))) == 0.0


# ---------------------------------------------------------------------------
# Server aggregation (Alg. 1 ln. 16-22)
# ---------------------------------------------------------------------------

def _toy_cohort():
    # tree: {"a": scalar-ish leaf in M, "b": leaf outside M}
    cohort = {"a": jnp.array([[1.0], [2.0], [3.0], [4.0]]),
              "b": jnp.array([[10.0], [20.0], [30.0], [40.0]])}
    mask = {"a": jnp.asarray(True), "b": jnp.asarray(False)}
    is_simple = jnp.array([True, True, False, False])
    valid = jnp.array([True, True, True, True])
    return cohort, mask, is_simple, valid


def test_fedhen_server_update_lines_18_22():
    cohort, mask, is_simple, valid = _toy_cohort()
    new = aggregate.fedhen_server_update(cohort, is_simple, valid, mask)
    # ln.18: M slice averaged over ALL devices
    np.testing.assert_allclose(new["a"], [2.5])
    # ln.22: M' averaged over complex devices only
    np.testing.assert_allclose(new["b"], [35.0])


def test_decouple_server_update():
    cohort, mask, is_simple, valid = _toy_cohort()
    simple_host, complex_new = aggregate.decouple_server_update(
        cohort, is_simple, valid, mask)
    np.testing.assert_allclose(simple_host["a"], [1.5])   # simple-only mean
    np.testing.assert_allclose(complex_new["a"], [3.5])   # complex-only mean
    np.testing.assert_allclose(complex_new["b"], [35.0])


def test_nan_device_excluded():
    cohort, mask, is_simple, valid = _toy_cohort()
    cohort["a"] = cohort["a"].at[0, 0].set(jnp.nan)
    valid = jax.vmap(masking.tree_isfinite)(cohort)
    assert list(np.asarray(valid)) == [False, True, True, True]
    new = aggregate.fedhen_server_update(cohort, is_simple, valid, mask)
    np.testing.assert_allclose(new["a"], [3.0])  # mean of 2,3,4
    assert np.isfinite(new["a"]).all()


# ---------------------------------------------------------------------------
# End-to-end rounds (tiny LM, all three algorithms)
# ---------------------------------------------------------------------------

def _make_trainer(algorithm, rounds_data_seed=0):
    fed = FedConfig(n_devices=4, n_simple=2, participation=0.5, rounds=3,
                    local_epochs=1, lr=0.1, clip_norm=10.0, batch_size=4,
                    algorithm=algorithm, seed=rounds_data_seed)
    data = synthetic_lm(32, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    adapter = LMAdapter(TINY)
    return FederatedTrainer(adapter, fed, shards)


@pytest.mark.parametrize("algorithm", ["fedhen", "noside", "decouple"])
def test_algorithms_run_and_update(algorithm):
    tr = _make_trainer(algorithm)
    before = jax.tree.map(jnp.copy, tr.server.complex)
    m = tr.run_round()
    assert np.isfinite(m["loss_complex"]) and np.isfinite(m["loss_simple"])
    assert m["n_valid"] == tr.k_simple + tr.k_complex
    changed = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                              b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(before),
                        jax.tree.leaves(tr.server.complex)))
    assert changed
    test = {"tokens": jnp.asarray(synthetic_lm(8, 16, TINY.vocab_size,
                                               seed=9)["tokens"])}
    ev = tr.evaluate(test)
    assert 0.0 <= ev["acc_complex"] <= 1.0
    assert ev["mbytes"] > 0


def test_fedhen_loss_decreases():
    tr = _make_trainer("fedhen")
    losses = [tr.run_round()["loss_complex"] for _ in range(6)]
    assert losses[-1] < losses[0], losses


def test_fedhen_simple_host_is_m_slice():
    """Alg. 1 ln. 20 invariant: server simple model == complex M slice, so
    extract(complex) round-trips through a training round."""
    tr = _make_trainer("fedhen")
    tr.run_round()
    simple = masking.extract_simple(tr.server.complex, TINY)
    rebuilt = masking.embed_simple(simple, tr.server.complex, TINY)
    for a, b in zip(jax.tree.leaves(rebuilt),
                    jax.tree.leaves(tr.server.complex)):
        np.testing.assert_array_equal(a, b)


def test_comm_accounting():
    tr = _make_trainer("fedhen")
    per = tr.bytes_per_round
    simple_bytes = TINY.simple_param_count() * 4
    total_bytes = TINY.param_count() * 4
    expected = 2.0 * (tr.k_simple * simple_bytes + tr.k_complex * total_bytes)
    assert per == expected, (per, expected)


def _make_uniform_trainer(participation, seed=0, **fed_kw):
    fed = FedConfig(n_devices=8, n_simple=4, participation=participation,
                    rounds=3, local_epochs=1, lr=0.1, clip_norm=10.0,
                    batch_size=4, algorithm="fedhen", seed=seed,
                    sample_uniform=True, **fed_kw)
    data = synthetic_lm(32, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    return FederatedTrainer(LMAdapter(TINY), fed, shards)


def test_uniform_mode_runs_and_bills_realized_cohort():
    """Uniform super-cohort rounds: pad slots are weight-0 (they never
    reach the loss or the aggregate) and move no bytes — only the
    realized clients are billed."""
    tr = _make_uniform_trainer(0.5)         # k_super = 4 over 8 clients
    expect = 0.0
    for r in range(2):
        plan = tr.sampler.plan(r)
        expect += 2.0 * (plan.n_real_simple * tr.per_simple_bytes
                         + plan.n_real_complex * tr.per_complex_bytes)
        m = tr.run_round()
        assert np.isfinite(m["loss_complex"]) and np.isfinite(m["loss_simple"])
        # every valid device is a REAL sampled client, never a pad slot
        assert m["n_valid"] == plan.n_real_simple + plan.n_real_complex
    assert tr.total_bytes == expect, (tr.total_bytes, expect)
    # the matrix tracked exactly the sampled clients
    assert tr.client_state.tracked_clients() == len(np.unique(
        np.concatenate([tr.sampler.plan(r).real_ids() for r in range(2)])))


def test_uniform_full_participation_matches_stratified():
    """At participation=1.0 the uniform draw enumerates the population in
    the stratified order, so the two modes must produce bit-identical
    server params and metrics."""
    fed = FedConfig(n_devices=4, n_simple=2, participation=1.0, rounds=2,
                    local_epochs=1, lr=0.1, clip_norm=10.0, batch_size=4,
                    algorithm="fedhen", seed=0)
    data = synthetic_lm(32, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    import dataclasses
    tr_s = FederatedTrainer(LMAdapter(TINY), fed, shards)
    tr_u = FederatedTrainer(
        LMAdapter(TINY),
        dataclasses.replace(fed, sample_uniform=True), shards)
    for _ in range(2):
        ms, mu = tr_s.run_round(), tr_u.run_round()
        assert ms == mu, (ms, mu)
    for a, b in zip(jax.tree.leaves(tr_s.server.complex),
                    jax.tree.leaves(tr_u.server.complex)):
        np.testing.assert_array_equal(a, b)
    assert tr_s.total_bytes == tr_u.total_bytes


def test_rounds_to_target():
    hist = [{"round": 1, "acc_simple": 0.1}, {"round": 2, "acc_simple": 0.5},
            {"round": 3, "acc_simple": 0.7}]
    assert rounds_to_target(hist, "acc_simple", 0.5) == 2
    assert rounds_to_target(hist, "acc_simple", 0.9) == -1


def test_rounds_to_target_loss_direction():
    """Loss-style metrics decrease toward the target: the threshold is
    'at or UNDER', matching obs.report's direction inference."""
    hist = [{"round": 1, "loss_simple": 2.0}, {"round": 2, "loss_simple": 0.8},
            {"round": 3, "loss_simple": 0.3}]
    assert rounds_to_target(hist, "loss_simple", 1.0) == 2
    assert rounds_to_target(hist, "loss_simple", 0.3) == 3
    assert rounds_to_target(hist, "loss_simple", 0.1) == -1


# ---------------------------------------------------------------------------
# A chunk's clients train one after another
# ---------------------------------------------------------------------------

def _image_clients(n_clients, n=2, seed=0):
    """PreActResNet18 clients of ``n`` 8x8 images each."""
    rng = np.random.default_rng(seed)
    return [{"images": jnp.asarray(rng.normal(size=(n, 8, 8, 3)),
                                   jnp.float32),
             "labels": jnp.asarray(rng.integers(0, 10, n), jnp.int32)}
            for _ in range(n_clients)]


def _assert_chunk_matches_clients_alone(adapter, loss, clients, fed,
                                        rtol, atol):
    """Train ``clients`` as one chunk through ``stream_population``, then
    each alone with ``make_client_trainer`` from the same broadcast and
    per-client key: the trained trees and the mean loss agree."""
    z = len(clients)
    params = adapter.init(jax.random.PRNGKey(0))
    train = make_client_trainer(getattr(adapter, loss), fed)
    key = jax.random.PRNGKey(1)

    @jax.jit
    def chunk(params, data):
        # the fold hands back the stacked trained trees it was given
        state = jax.tree.map(lambda x: jnp.zeros((z,) + x.shape, x.dtype),
                             params)
        trained, mean_loss, n_valid, _, _ = stream_population(
            state, lambda _: params, train, data, key,
            lambda _, trained, *a, **kw: trained, k=z, chunk=z, n_chunks=1,
            is_simple_flag=loss == "loss_simple", skip_nan=True)
        return trained, mean_loss, n_valid

    trained, mean_loss, n_valid = chunk(
        params, jax.tree.map(lambda *x: jnp.stack(x), *clients))
    assert float(n_valid) == z
    losses = []
    for i, data in enumerate(clients):
        want, loss_i = jax.jit(train)(params, data,
                                      jax.random.fold_in(key, i))
        losses.append(float(loss_i))
        assert not all(np.array_equal(w, p0) for w, p0 in zip(
            jax.tree.leaves(want), jax.tree.leaves(params)))
        for got, w in zip(jax.tree.leaves(trained), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(got[i]), np.asarray(w),
                                       rtol=rtol, atol=atol)
    np.testing.assert_allclose(float(mean_loss), np.mean(losses),
                               rtol=1e-6)


@pytest.mark.parametrize("loss", ["loss_simple", "loss_side"])
def test_sequential_clients_match_each_client_alone(loss):
    """Each convolutional client of a chunk equals that client trained
    alone."""
    _assert_chunk_matches_clients_alone(
        ResNetAdapter(), loss, _image_clients(2),
        FedConfig(local_epochs=1, lr=0.1, batch_size=2),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("loss", ["loss_simple", "loss_side"])
def test_dense_clients_map_in_sequence(loss):
    """A decoder LM's clients train one after another too: each client of
    a chunk of three (two SGD steps each) equals that client trained
    alone."""
    data = synthetic_lm(12, 16, TINY.vocab_size, seed=1)
    _assert_chunk_matches_clients_alone(
        LMAdapter(TINY), loss, iid_split(data, 3, seed=2),
        FedConfig(local_epochs=1, lr=0.1, batch_size=2),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("engine", [
    pytest.param({"variance_reduction": "none"}, id="none"),
    pytest.param({"variance_reduction": "scaffold"}, id="scaffold"),
    pytest.param({"async_lag": 0}, id="async-lag0"),
    pytest.param({"async_lag": 1}, id="async-lag1"),
    pytest.param({"async_lag": 1, "comm_dtype": "int8"},
                 id="async-lag1-int8"),
])
def test_conv_clients_map_in_sequence(engine):
    """The round the paper's model lowers, in chunks of two clients,
    holds no grouped convolution, in either engine, on SCAFFOLD's branch
    and behind the int8 wire: vmapped over per-client weights every
    convolution would group over the client axis."""
    fed_kw = dict(engine)
    lag0 = fed_kw.get("async_lag") == 0
    data = _image_clients(4)
    fed = FedConfig(n_devices=4, n_simple=2, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=2, algorithm="fedhen",
                    seed=0, cohort_chunk=2, **fed_kw)
    tr = FederatedTrainer(ResNetAdapter(), fed, data)
    assert tr.k_simple == tr.k_complex == tr.cohort_chunk == 2
    if lag0:
        lowered = async_rounds.AsyncRoundEngine(tr, lag=0).lower_round()
    else:
        assert (tr.async_engine is not None) == ("async_lag" in fed_kw)
        lowered = tr.lower_round()
    hlo = lowered.as_text()
    groups = re.findall(r"(?:feature|batch)_group_count = (\d+)", hlo)
    assert groups and set(groups) == {"1"}, sorted(set(groups))


# ---------------------------------------------------------------------------
# Communication accounting
# ---------------------------------------------------------------------------

class _ToyAdapter:
    """Fixed tiny param tree with a known mask: 4 floats in M ("a"),
    3 floats outside ("b")."""

    def init(self, key):
        return {"a": jnp.zeros((2, 2), jnp.float32),
                "b": jnp.zeros((3,), jnp.float32)}

    def subnet_mask(self, params):
        return {"a": jnp.asarray(True), "b": jnp.asarray(False)}

    loss_simple = loss_complex = loss_side = staticmethod(
        lambda params, batch: jnp.zeros(()))


def test_bytes_per_round_hand_computed():
    """down+up x (k_s x |M| + k_c x |w_c|) x 4 bytes, by hand: k_s = k_c = 1,
    |M| = 16 B, |w_c| = 28 B -> 2 x (16 + 28) = 88 B."""
    fed = FedConfig(n_devices=4, n_simple=2, participation=0.5,
                    algorithm="fedhen")
    tr = FederatedTrainer(_ToyAdapter(), fed, client_data=[])
    assert tr.k_simple == 1 and tr.k_complex == 1
    assert tr.bytes_per_round == 2.0 * (1 * 16 + 1 * 28) == 88.0


# ---------------------------------------------------------------------------
# Splits
# ---------------------------------------------------------------------------

def test_dirichlet_split_is_skewed_but_complete():
    data = synthetic_lm(400, 8, 32, seed=3)
    shards_iid = iid_split(data, 10, seed=4)
    shards_nid = dirichlet_split(data, 10, alpha=0.3, seed=4)
    assert all(len(s["tokens"]) == 40 for s in shards_nid)
    from repro.data.federated import label_distribution
    d_iid = label_distribution(shards_iid, 10)
    d_nid = label_distribution(shards_nid, 10)
    # non-IID shards should be measurably more concentrated
    assert d_nid.max(1).mean() > d_iid.max(1).mean() + 0.1

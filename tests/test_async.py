"""Asynchronous round engine (core/async_rounds.py): bounded-lag
schedule, staleness weighting, lag=0 bit-parity with the synchronous
engine, and version-aware byte accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from billing_oracle import VersionCache
from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core import async_rounds
from repro.core.adapters import LMAdapter, ResNetAdapter
from repro.core.federated import FederatedTrainer
from repro.data.federated import iid_split
from repro.data.synthetic import synthetic_lm

TINY = ModelConfig(n_layers=4, d_model=32, n_heads=2, n_kv_heads=2,
                   d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                   exit_layer=2, compute_dtype="float32")


def _make_trainer(algorithm="fedhen", *, n_devices=12, chunk=2,
                  participation=0.5, **fed_kw):
    fed = FedConfig(n_devices=n_devices, n_simple=n_devices // 2,
                    participation=participation, rounds=3, local_epochs=1,
                    lr=0.1, batch_size=4, algorithm=algorithm, seed=0,
                    cohort_chunk=chunk, **fed_kw)
    data = synthetic_lm(n_devices * 4, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    return FederatedTrainer(LMAdapter(TINY), fed, shards)


def _max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# Schedule + weights (host-side units)
# ---------------------------------------------------------------------------

def test_fold_schedule_values():
    """The bounded-lag rule: position t is ceil((lag - t)/F) rounds stale,
    clamped by the round index."""
    np.testing.assert_array_equal(async_rounds.fold_schedule(4, 0, 10),
                                  [0, 0, 0, 0])
    np.testing.assert_array_equal(async_rounds.fold_schedule(4, 1, 10),
                                  [1, 0, 0, 0])
    np.testing.assert_array_equal(async_rounds.fold_schedule(4, 3, 10),
                                  [1, 1, 1, 0])
    np.testing.assert_array_equal(async_rounds.fold_schedule(4, 4, 10),
                                  [1, 1, 1, 1])
    np.testing.assert_array_equal(async_rounds.fold_schedule(4, 5, 10),
                                  [2, 1, 1, 1])
    # round 0 cannot train on a pre-init model: clamp to 0
    np.testing.assert_array_equal(async_rounds.fold_schedule(4, 5, 0),
                                  [0, 0, 0, 0])
    np.testing.assert_array_equal(async_rounds.fold_schedule(4, 5, 1),
                                  [1, 1, 1, 1])


def test_staleness_weight_monotone_and_exact_at_zero():
    s = np.arange(5)
    w = np.asarray(async_rounds.staleness_weight(s, decay=0.5))
    assert w[0] == 1.0                      # exact — the parity bit
    assert np.all(np.diff(w) < 0)           # strictly decaying
    np.testing.assert_allclose(w, (1.0 + s) ** -0.5, rtol=1e-6)
    ones = np.asarray(async_rounds.staleness_weight(s, scheme="none"))
    np.testing.assert_array_equal(ones, np.ones(5))
    with pytest.raises(ValueError):
        async_rounds.staleness_weight(s, scheme="exp")


def test_config_validation():
    with pytest.raises(ValueError):
        FedConfig(async_lag=-1)
    with pytest.raises(ValueError):
        FedConfig(async_staleness="exp")
    with pytest.raises(ValueError):
        FedConfig(async_decay=-0.5)


# ---------------------------------------------------------------------------
# lag=0 bit-parity with the synchronous engine (the parity oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["fedhen", "noside", "decouple"])
def test_lag0_bit_parity(algorithm):
    """The async engine at lag=0 IS the synchronous engine: identical
    server state bit-for-bit after multiple rounds, through the async
    code path (version stack, dynamic version select, float weights)."""
    sync = _make_trainer(algorithm)
    tr = _make_trainer(algorithm)
    eng = async_rounds.AsyncRoundEngine(tr, lag=0)
    for _ in range(2):
        m_sync = sync.run_round()
        m_async = eng.run_round()
    assert _max_abs_diff(sync.server.complex, tr.server.complex) == 0.0
    if algorithm == "decouple":
        assert _max_abs_diff(sync.server.simple_host,
                             tr.server.simple_host) == 0.0
    assert m_sync == m_async
    # byte accounting: every round publishes a fresh version at lag=0,
    # so the version-aware ledger reproduces the synchronous numbers
    assert tr.total_bytes_down == sync.total_bytes_down
    assert tr.total_bytes_up == sync.total_bytes_up


def test_lag0_bit_parity_int8_wire():
    """Parity holds through a quantized wire too: the version stack is
    encoded/decoded with the same bits as the sync broadcast_roundtrip."""
    sync = _make_trainer("fedhen", comm_dtype="int8")
    tr = _make_trainer("fedhen", comm_dtype="int8")
    eng = async_rounds.AsyncRoundEngine(tr, lag=0)
    for _ in range(2):
        sync.run_round()
        eng.run_round()
    assert _max_abs_diff(sync.server.complex, tr.server.complex) == 0.0


def test_lag0_bit_parity_conv_clients():
    """Parity holds for the paper's convolutional model (PreActResNet18 at
    8x8 images, chunks of two clients trained one after another): one
    round of each engine gives the same server model bit for bit."""
    rng = np.random.default_rng(0)
    data = [{"images": jnp.asarray(rng.normal(size=(2, 8, 8, 3)),
                                   jnp.float32),
             "labels": jnp.asarray(rng.integers(0, 10, 2), jnp.int32)}
            for _ in range(4)]

    def trainer():
        fed = FedConfig(n_devices=4, n_simple=2, participation=1.0,
                        local_epochs=1, lr=0.1, batch_size=2,
                        algorithm="fedhen", seed=0, cohort_chunk=2)
        return FederatedTrainer(ResNetAdapter(), fed, data)

    sync, tr = trainer(), trainer()
    eng = async_rounds.AsyncRoundEngine(tr, lag=0)
    m_sync = sync.run_round()
    m_async = eng.run_round()
    assert _max_abs_diff(sync.server.complex, tr.server.complex) == 0.0
    assert _max_abs_diff(sync.server.complex, trainer().server.complex) > 0
    assert m_sync == m_async


# ---------------------------------------------------------------------------
# Nonzero lag: engine wiring, staleness liveness, padding/NaN devices
# ---------------------------------------------------------------------------

def test_trainer_dispatches_to_async_engine():
    tr0 = _make_trainer("fedhen")
    assert tr0.async_engine is None
    tr = _make_trainer("fedhen", async_lag=2)
    assert tr.async_engine is not None
    assert tr.async_engine.lag == 2
    # k=3 per population at chunk 2 -> 2 chunks each, 4 folds/round,
    # lag=2 < F -> 2 versions (fresh + one round back)
    assert tr.async_engine.folds_per_round == 4
    assert tr.async_engine.n_versions == 2
    assert tr.async_engine.versions.shape == (2, tr.layout.n_flat)
    m = tr.run_round()
    assert np.isfinite(m["loss_complex"]) and np.isfinite(m["loss_simple"])
    assert tr.server.round == 1


@pytest.mark.parametrize("algorithm", ["fedhen", "decouple"])
def test_async_rounds_stay_on_reasonable_trajectory(algorithm):
    """Nonzero lag with zero-weight padding clients (chunk 2 over k=3):
    multiple rounds run finite, move the server, and count exactly the
    real clients as valid."""
    tr = _make_trainer(algorithm, async_lag=3)
    before = jax.tree.map(jnp.copy, tr.server.complex)
    for _ in range(3):
        m = tr.run_round()
        assert np.isfinite(m["loss_complex"])
        assert m["n_valid"] == tr.k_simple + tr.k_complex
    assert _max_abs_diff(before, tr.server.complex) > 0
    for leaf in jax.tree.leaves(tr.server.complex):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


def test_staleness_weighting_is_live():
    """poly vs none weighting must actually change the trajectory at
    nonzero lag (the decay coefficient reaches the fold)."""
    a = _make_trainer("fedhen", async_lag=3, async_decay=0.5)
    b = _make_trainer("fedhen", async_lag=3, async_staleness="none")
    for _ in range(3):
        a.run_round()
        b.run_round()
    assert _max_abs_diff(a.server.complex, b.server.complex) > 0


class _NanAdapter:
    """Tiny real-training adapter whose loss is NaN-poisoned by NaN data:
    params drift toward each client's data mean, so a NaN shard produces
    a NaN-trained device the fold must exclude."""

    def init(self, key):
        return {"a": jnp.zeros((4,), jnp.float32),
                "b": jnp.zeros((4,), jnp.float32)}

    def subnet_mask(self, params):
        return {"a": jnp.asarray(True), "b": jnp.asarray(False)}

    @staticmethod
    def _loss(params, batch):
        x = batch["x"]                       # (B, 4)
        err_a = params["a"][None] - x
        err_b = params["b"][None] - 2.0 * x
        return jnp.mean(err_a ** 2) + jnp.mean(err_b ** 2)

    loss_simple = loss_complex = loss_side = _loss


def test_nan_device_excluded_under_lag():
    """A NaN-training device under nonzero lag carries weight 0 through
    the staleness-weighted fold: the server stays finite and still
    moves."""
    fed = FedConfig(n_devices=8, n_simple=4, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=4,
                    algorithm="fedhen", seed=0, cohort_chunk=1,
                    async_lag=2)
    rng = np.random.default_rng(0)
    shards = [{"x": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))}
              for _ in range(fed.n_devices)]
    shards[1]["x"] = shards[1]["x"].at[0, 0].set(jnp.nan)  # poisoned client
    tr = FederatedTrainer(_NanAdapter(), fed, shards)
    assert tr.async_engine is not None
    saw_exclusion = False
    for _ in range(4):
        m = tr.run_round()
        saw_exclusion |= m["n_valid"] < tr.k_simple + tr.k_complex
        for leaf in jax.tree.leaves(tr.server.complex):
            assert np.isfinite(np.asarray(leaf)).all()
    assert saw_exclusion  # the poisoned client was sampled and excluded
    assert _max_abs_diff(jax.tree.map(jnp.zeros_like, tr.server.complex),
                         tr.server.complex) > 0


def test_server_replacement_resets_version_stack():
    """Checkpoint restore replaces trainer.server wholesale AFTER the
    engine is built; the version stack must follow, or every chunk keeps
    training on the discarded pre-restore broadcast."""
    from repro.core import flatten
    from repro.core.federated import ServerState

    tr = _make_trainer("fedhen", async_lag=2)
    eng = tr.async_engine
    tr.run_round()
    tr.run_round()                          # the stack now carries history
    restored = ServerState(
        complex=jax.tree.map(lambda x: jnp.ones_like(x), tr.server.complex),
        round=7)
    tr.server = restored                    # what train.py --resume does
    args, (_, _, _, r) = eng._round_args()
    assert r == 7
    want = np.asarray(flatten.pack(eng.layout, restored.complex))
    for v in range(eng.n_versions):
        np.testing.assert_array_equal(np.asarray(args[0][v]), want)
    m = tr.run_round()                      # and rounds continue from it
    assert np.isfinite(m["loss_complex"])
    assert tr.server.round == 8


# ---------------------------------------------------------------------------
# Version-aware byte accounting
# ---------------------------------------------------------------------------

def test_version_cache_bills_once_per_version():
    cache = VersionCache()
    assert cache.bill(7, 0, 100) == 100     # first fetch
    assert cache.bill(7, 0, 100) == 0       # cached
    assert cache.holds(7, 0) and not cache.holds(7, 1)
    assert cache.bill(7, 1, 100) == 100     # new version
    assert cache.bill(7, 0, 100) == 100     # old version evicted
    assert cache.bill(8, 0, 100) == 100     # per-client ledger


def test_stale_broadcast_reuse_saves_download_bytes():
    """With every client sampled every round (participation 1) and lag
    covering the first simple chunk, round >= 1 reuses the cached stale
    broadcast for that chunk — measured download drops below the
    synchronous constant by exactly that chunk's client downloads."""
    sync = _make_trainer("fedhen", participation=1.0)
    tr = _make_trainer("fedhen", participation=1.0, async_lag=1)
    eng = tr.async_engine
    tr.run_round()                           # round 0: cold cache
    assert tr.total_bytes_down == sync.bytes_down_per_round
    tr.run_round()                           # round 1: chunk 0 is stale
    expected_saving = eng.chunk_s * eng._per_simple
    assert eng.last_bytes_down == sync.bytes_down_per_round - expected_saving
    # uploads never shrink: every client uploads fresh params every round
    assert eng.last_bytes_up == sync.bytes_up_per_round


# ---------------------------------------------------------------------------
# Launch-side staleness seam (launch/steps.py)
# ---------------------------------------------------------------------------

def test_fed_round_step_staleness_weights():
    from repro.launch.steps import make_fed_round_step
    from repro.models import transformer as tfm
    from repro.models.common import NO_POLICY

    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                      exit_layer=1, compute_dtype="float32")
    k, batch, steps, seq = 4, 2, 2, 16
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    cohort = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), params)
    data = jax.random.randint(jax.random.PRNGKey(1),
                              (k, batch, steps, seq + 1), 0, 64)
    is_simple = jnp.array([True, True, False, False])
    step = make_fed_round_step(cfg, NO_POLICY, local_steps=steps,
                               cohort_chunk=2)
    ref_c, ref_loss = jax.jit(step)(cohort, data, is_simple)
    # all-zero staleness == no staleness argument, bit-for-bit
    zero_c, zero_loss = jax.jit(step)(cohort, data, is_simple, None,
                                      jnp.zeros((k,), jnp.int32))
    assert _max_abs_diff(ref_c, zero_c) == 0.0
    assert float(ref_loss) == float(zero_loss)
    # nonzero staleness reweights the fold (training is unchanged)
    stale_c, stale_loss = jax.jit(step)(cohort, data, is_simple, None,
                                        jnp.array([2, 0, 2, 0]))
    assert float(stale_loss) == float(ref_loss)
    assert _max_abs_diff(ref_c, stale_c) > 0
    for leaf in jax.tree.leaves(stale_c):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()

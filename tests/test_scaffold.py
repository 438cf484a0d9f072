"""SCAFFOLD variance reduction end-to-end (the state-store tentpole's
first consumer): round-1 bit-equality with the plain protocol, a
closed-form option-II oracle, the cv fold's two lowerings against numpy,
chunked/unchunked parity across all three algorithms, NaN/pad-slot row
hygiene, the async engine, wire dtypes, comm billing, and checkpoint
resume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import restore_trainer, save_trainer
from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core import aggregate, async_rounds, comm, flatten
from repro.core.adapters import LMAdapter
from repro.core.federated import (FederatedTrainer, local_step_count,
                                  make_client_trainer)
from repro.data.federated import iid_split
from repro.data.synthetic import synthetic_lm

TINY = ModelConfig(n_layers=4, d_model=32, n_heads=2, n_kv_heads=2,
                   d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                   exit_layer=2, compute_dtype="float32")

ALGOS = ["fedhen", "noside", "decouple"]


def _make_trainer(algorithm="fedhen", *, n_devices=4, participation=1.0,
                  variance_reduction="scaffold", **fed_kw):
    fed = FedConfig(n_devices=n_devices, n_simple=n_devices // 2,
                    participation=participation, rounds=3, local_epochs=1,
                    lr=0.1, batch_size=4, algorithm=algorithm, seed=0,
                    variance_reduction=variance_reduction, **fed_kw)
    data = synthetic_lm(n_devices * 8, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    return FederatedTrainer(LMAdapter(TINY), fed, shards)


def _max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _server_close(a, b, tol=0.0):
    d = _max_abs_diff(a.server.complex, b.server.complex)
    assert d <= tol, d
    if a.fed.algorithm == "decouple":
        d = _max_abs_diff(a.server.simple_host, b.server.simple_host)
        assert d <= tol, d


# ---------------------------------------------------------------------------
# Zero-init contract: round 1 is bit-identical to variance_reduction="none"
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ALGOS)
def test_round1_bit_identical_to_none(algorithm):
    """c = c_i = 0 means the correction and every gradient are untouched:
    the first SCAFFOLD round must reproduce the plain protocol exactly
    (same trained models, same aggregate, bit for bit)."""
    plain = _make_trainer(algorithm, variance_reduction="none")
    scaf = _make_trainer(algorithm)
    m_plain = plain.run_round()
    m_scaf = scaf.run_round()
    _server_close(plain, scaf, tol=0.0)
    assert m_plain == m_scaf
    # ... and the control variates MOVED (the second round diverges)
    assert float(jnp.linalg.norm(scaf.cv_global)) > 0.0
    plain.run_round()
    scaf.run_round()
    assert _max_abs_diff(plain.server.complex, scaf.server.complex) > 0.0


# ---------------------------------------------------------------------------
# Closed-form option-II oracle (one client per population, K static)
# ---------------------------------------------------------------------------

def test_option_ii_oracle_single_client_populations():
    """With one simple + one complex client at full participation, the
    round's store rows must equal the hand-computed
    ``dc = (x - y)/(K*lr) - c`` (c = 0 in round 1): ``y`` is recomputed
    here by invoking the same client trainer with the same derived key,
    so the test pins the packing, masking, weighting AND the per-client
    RNG derivation."""
    tr = _make_trainer("fedhen", n_devices=2)
    fed, layout = tr.fed, tr.layout
    server0 = jax.tree.map(jnp.copy, tr.server.complex)
    plan = tr.sampler.plan(0)
    assert list(plan.simple_ids) == [0] and list(plan.complex_ids) == [1]

    tr.run_round()

    # replicate the round's broadcast + per-client training exactly
    key = jax.random.PRNGKey(fed.seed * 100003 + 0)
    rs, rc = jax.random.split(key)
    bc = comm.broadcast_roundtrip(tr.wire, layout, server0)
    x_flat = flatten.pack(layout, bc)
    adapter = tr.adapter
    shard = lambda i: jax.tree.map(lambda v: v[0], tr._gather([i]))

    train_s = make_client_trainer(adapter.loss_simple, fed)
    y_s, _ = train_s(bc, shard(0), jax.random.fold_in(rs, 0))
    train_c = make_client_trainer(adapter.loss_side, fed)
    y_c, _ = train_c(bc, shard(1), jax.random.fold_in(rc, 0))

    k_steps = local_step_count(tr._gather([0]), fed)
    inv = 1.0 / (k_steps * fed.lr)
    dc_s = jnp.where(tr.flat_mask,
                     (x_flat - flatten.pack(layout, y_s)) * inv, 0.0)
    dc_c = (x_flat - flatten.pack(layout, y_c)) * inv

    # y is recomputed outside the round jit, where XLA fuses the local
    # SGD steps differently: y agrees to a few f32 ulps of the parameter
    # magnitude, and dc scales that by 1/(K*lr).  A wrong key derivation
    # moves dc by O(|dc|), some 1e5 times this tolerance.
    rows = tr.cv_store.to_array()
    tol = (4 * float(jnp.finfo(jnp.float32).eps)
           * float(jnp.max(jnp.abs(x_flat))) * inv)
    assert float(jnp.max(jnp.abs(rows[0] - dc_s))) <= tol
    assert float(jnp.max(jnp.abs(rows[1] - dc_c))) <= tol
    # server update: c += (1/N) * sum_i dc_i (raw sum, never normalized
    # by cohort weights — dc_s is zero outside M so the masked fold's
    # w_out gating changes nothing elementwise)
    want = (dc_s + dc_c) / fed.n_devices
    np.testing.assert_allclose(np.asarray(tr.cv_global), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# The cv fold on both lowerings; chunked vs unchunked rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ALGOS)
def test_cv_fold_lowerings_match_numpy(algorithm):
    """The control-variate deltas fold into ``StreamState.cv_acc`` as the
    masked weighted sum ``sum_z (M ? w_in : w_out)[z] * dc[z]`` — the
    same weights as the params — on the kernel path (interpret mode) and
    on the CPU path alike, with a NaN client gated out at weight 0."""
    rng = np.random.default_rng(3)
    z = 6
    template = {"a": jnp.zeros((4, 3), jnp.float32),
                "b": jnp.zeros((5,), jnp.float32)}
    mask = {"a": jnp.asarray(True), "b": jnp.asarray(False)}
    layout = flatten.layout_of(template, total_multiple=512)
    flat_mask = flatten.pack_mask(layout, mask)
    spec = aggregate.EngineSpec(
        algorithm=algorithm, mask=mask, layout=layout, flat_mask=flat_mask,
        block_n=512, variance_reduction="scaffold")
    cohort = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=(z,) + x.shape), jnp.float32),
        template)
    dc = rng.normal(size=(z, layout.n_flat)).astype(np.float32)
    dc[2] = np.nan
    is_simple = np.arange(z) < z // 2
    valid = np.array([1.0, 0.5, 0.0, 1.0, 0.25, 1.0], np.float32)

    w = np.where(valid > 0, valid, 0.0)
    w_in = w * is_simple if algorithm == "decouple" else w
    w_out = w * ~is_simple
    rows = np.where(np.asarray(flat_mask)[None], w_in[:, None],
                    w_out[:, None])
    want = np.where(rows > 0, np.nan_to_num(dc), 0.0) * rows

    for interpret in (False, True):
        state = aggregate.streaming_init(template, spec)
        for lo in range(0, z, 3):
            sl = slice(lo, lo + 3)
            state = aggregate.streaming_fold(
                state, jax.tree.map(lambda x: x[sl], cohort),
                jnp.asarray(is_simple[sl]), jnp.asarray(valid[sl]), spec,
                cv_chunk=jnp.asarray(dc[sl]),
                force_pallas_interpret=interpret)
        np.testing.assert_allclose(np.asarray(state.cv_acc), want.sum(0),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("algorithm", ALGOS)
def test_chunked_parity(algorithm):
    """Streaming the cohort one client at a time must fold the same cv
    state as the single-chunk round (the rows ride the scan outputs)."""
    whole = _make_trainer(algorithm, cohort_chunk=0)
    chunked = _make_trainer(algorithm, cohort_chunk=1)
    for _ in range(2):
        whole.run_round()
        chunked.run_round()
    _server_close(whole, chunked, tol=2e-5)
    np.testing.assert_allclose(np.asarray(whole.cv_global),
                               np.asarray(chunked.cv_global),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(whole.cv_store.to_array(),
                               chunked.cv_store.to_array(),
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# Row hygiene: NaN devices and uniform-sampling pad slots
# ---------------------------------------------------------------------------

class _NanAdapter:
    """Tiny real-training adapter (mirrors tests/test_async.py): params
    drift toward each client's data mean, so a NaN shard produces a
    NaN-trained device the fold — and the row scatter — must exclude."""

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


def test_nan_device_keeps_previous_row_and_finite_c():
    """A NaN device folds at weight 0 AND keeps its previous control
    variate: a NaN row must never persist in the store, and c stays
    finite."""
    fed = FedConfig(n_devices=4, n_simple=2, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=4,
                    algorithm="fedhen", seed=0,
                    variance_reduction="scaffold")
    rng = np.random.default_rng(0)
    shards = [{"x": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))}
              for _ in range(fed.n_devices)]
    shards[1]["x"] = shards[1]["x"].at[0, 0].set(jnp.nan)  # poisoned client
    tr = FederatedTrainer(_NanAdapter(), fed, shards)
    m = tr.run_round()
    assert m["n_valid"] == fed.n_devices - 1
    rows = tr.cv_store.to_array()
    assert np.isfinite(rows).all()
    np.testing.assert_array_equal(rows[1], 0.0)   # kept its (zero) row
    assert np.isfinite(np.asarray(tr.cv_global)).all()
    # the healthy clients' rows updated
    for i in (0, 2, 3):
        assert np.abs(rows[i]).max() > 0.0


def test_uniform_pad_slots_never_clobber_rows():
    """Uniform super-cohort mode: unfilled slots wrap real clients' ids —
    scattering them back would overwrite a row the wrapped client just
    wrote.  Only REAL slots may touch the store."""
    tr = _make_trainer("fedhen", n_devices=8, participation=0.25,
                       sample_uniform=True)
    # find a round whose plan actually has pad slots
    for r in range(20):
        plan = tr.sampler.plan(tr.server.round)
        if not plan.all_real:
            break
        tr.run_round()
    else:
        pytest.fail("no uniform round with pad slots in 20 draws")
    before = tr.cv_store.to_array().copy()
    tr.run_round()
    after = tr.cv_store.to_array()
    real = set(int(i) for i in plan.real_ids())
    changed = {i for i in range(tr.fed.n_devices)
               if np.abs(after[i] - before[i]).max() > 0.0}
    assert changed <= real, (changed, real)
    assert changed, "no real row updated"


# ---------------------------------------------------------------------------
# Async engine: lag=0 bit-parity, lag=1 liveness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ALGOS)
def test_async_lag0_bit_parity_with_scaffold(algorithm):
    """lag=0 through the async code path (version stack, float weights,
    shared scan) must reproduce the synchronous SCAFFOLD round bit for
    bit — server state, c, and every store row."""
    sync = _make_trainer(algorithm, n_devices=6, cohort_chunk=1)
    tr = _make_trainer(algorithm, n_devices=6, cohort_chunk=1)
    eng = async_rounds.AsyncRoundEngine(tr, lag=0)
    for _ in range(2):
        m_sync = sync.run_round()
        m_async = eng.run_round()
    _server_close(sync, tr, tol=0.0)
    assert m_sync == m_async
    assert _max_abs_diff([sync.cv_global], [tr.cv_global]) == 0.0
    np.testing.assert_array_equal(sync.cv_store.to_array(),
                                  tr.cv_store.to_array())
    assert sync.total_bytes == tr.total_bytes


def test_async_lag1_scaffold_runs_and_stays_finite():
    """Nonzero lag: stale chunks compute dc against the stale broadcast
    they actually trained on (x is the selected version).  The rounds
    must stay finite and move the control variates."""
    tr = _make_trainer("fedhen", n_devices=6, cohort_chunk=1, async_lag=1)
    assert tr.async_engine is not None
    for _ in range(3):
        m = tr.run_round()
        assert np.isfinite(m["loss_simple"]) and np.isfinite(
            m["loss_complex"])
    assert np.isfinite(np.asarray(tr.cv_global)).all()
    assert float(jnp.linalg.norm(tr.cv_global)) > 0.0
    assert np.isfinite(tr.cv_store.to_array()).all()
    assert tr.cv_store.scattered_bytes > 0


# ---------------------------------------------------------------------------
# Wire dtypes + comm billing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comm_dtype", ["bfloat16", "int8"])
def test_scaffold_through_nonidentity_wires(comm_dtype):
    """The cv exchange moves raw f32 alongside any wire: SCAFFOLD must
    compose with the bf16 and quantized paths and stay finite."""
    tr = _make_trainer("fedhen", comm_dtype=comm_dtype)
    for _ in range(2):
        m = tr.run_round()
        assert np.isfinite(m["loss_complex"])
    assert np.isfinite(np.asarray(tr.cv_global)).all()
    assert np.isfinite(tr.cv_store.to_array()).all()


def test_cv_exchange_billing():
    """SCAFFOLD bills the control-variate exchange at raw f32 of the
    trained element counts, both directions, on top of the wire."""
    plain = _make_trainer("fedhen", variance_reduction="none")
    scaf = _make_trainer("fedhen")
    n_m = int(np.sum(np.asarray(scaf.flat_mask)))
    extra_one_way = (scaf.k_simple * 4.0 * n_m
                     + scaf.k_complex * 4.0 * scaf.layout.n_params)
    assert scaf.bytes_per_round - plain.bytes_per_round == pytest.approx(
        2.0 * extra_one_way)
    scaf.run_round()
    assert scaf.total_bytes == pytest.approx(scaf.bytes_per_round)


# ---------------------------------------------------------------------------
# Checkpoint: the cv store rides the sidecar, resume is exact
# ---------------------------------------------------------------------------

def test_checkpoint_resume_reproduces_uninterrupted_run(tmp_path):
    path = str(tmp_path / "ckpt.npz")
    a = _make_trainer("fedhen")
    a.run_round()
    a.run_round()
    save_trainer(path, a)
    a.run_round()

    b = _make_trainer("fedhen")
    restore_trainer(path, b)
    assert b.server.round == 2
    b.run_round()
    _server_close(a, b, tol=0.0)
    np.testing.assert_array_equal(np.asarray(a.cv_global),
                                  np.asarray(b.cv_global))
    np.testing.assert_array_equal(a.cv_store.to_array(),
                                  b.cv_store.to_array())


def test_checkpoint_without_cv_sidecar_rejected(tmp_path):
    """Restoring a plain checkpoint into a SCAFFOLD trainer must fail
    loudly — silently resetting c/c_i would corrupt the correction."""
    path = str(tmp_path / "ckpt.npz")
    plain = _make_trainer("fedhen", variance_reduction="none")
    plain.run_round()
    save_trainer(path, plain)
    scaf = _make_trainer("fedhen")
    with pytest.raises(ValueError, match="no __cv_store__ sidecar"):
        restore_trainer(path, scaf)

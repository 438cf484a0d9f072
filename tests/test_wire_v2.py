"""Compressed wire v2 end-to-end (error feedback + stochastic rounding +
top-k uploads): the dense/deterministic bit-identity pin, a closed-form
residual + server-fold oracle, SCAFFOLD composition, async engine parity,
NaN/pad-slot residual hygiene, and checkpoint resume with the
``__ef_store__`` sidecar."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import restore_trainer, save_trainer
from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core import async_rounds, comm, flatten
from repro.core.federated import (_WIRE_KEY_TAG, FederatedTrainer,
                                  make_client_trainer)
from repro.data.federated import iid_split
from repro.data.synthetic import synthetic_lm

TINY = ModelConfig(n_layers=4, d_model=32, n_heads=2, n_kv_heads=2,
                   d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                   exit_layer=2, compute_dtype="float32")

# the full stack the benchmark gate ships: int8 payload, 1/16 top-k,
# stochastic rounding, error feedback
FULL = dict(comm_dtype="int8", quant_block=64, topk_frac=1 / 16,
            stochastic_rounding=True, error_feedback=True)


def _make_trainer(algorithm="fedhen", *, n_devices=4, participation=1.0,
                  **fed_kw):
    fed = FedConfig(n_devices=n_devices, n_simple=n_devices // 2,
                    participation=participation, rounds=3, local_epochs=1,
                    lr=0.1, batch_size=4, algorithm=algorithm, seed=0,
                    **fed_kw)
    data = synthetic_lm(n_devices * 8, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    from repro.core.adapters import LMAdapter
    return FederatedTrainer(LMAdapter(TINY), fed, shards)


def _max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# The bit-identity pin: every v2 knob at its default keeps the pre-v2
# protocol byte-identical (tol=0)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["fedhen", "decouple"])
def test_default_knobs_bit_identical_to_plain_wire(algorithm):
    """topk_frac=1.0 + deterministic rounding + no EF must trace the
    SAME upload program as before wire v2 existed: two rounds, tol=0."""
    plain = _make_trainer(algorithm)
    pinned = _make_trainer(algorithm, topk_frac=1.0,
                           stochastic_rounding=False, error_feedback=False)
    assert not pinned.wire.uses_deltas
    assert pinned.ef_store is None
    for _ in range(2):
        m_plain = plain.run_round()
        m_pinned = pinned.run_round()
    assert m_plain == m_pinned
    assert _max_abs_diff(plain.server.complex, pinned.server.complex) == 0.0
    assert plain.total_bytes == pinned.total_bytes


def test_near_dense_topk_matches_dense_fold():
    """topk_frac high enough to keep every parameter, on the exact f32
    wire: the delta-space scatter fold must reproduce the dense
    params-space fold up to float summation order."""
    dense = _make_trainer("fedhen")
    sparse = _make_trainer("fedhen", topk_frac=0.9999)
    assert sparse.wire.uses_deltas
    assert sparse.k_top_complex >= sparse.layout.n_params
    for _ in range(2):
        dense.run_round()
        sparse.run_round()
    d = _max_abs_diff(dense.server.complex, sparse.server.complex)
    assert d <= 1e-5, d


# ---------------------------------------------------------------------------
# Closed-form oracle: residual rows and the folded server, one client
# per population (pins packing, key derivation, fold weighting)
# ---------------------------------------------------------------------------

def test_ef_oracle_single_client_populations():
    """One simple + one complex client at full participation under the
    full int8 + top-k + stochastic + EF stack.  The round's residual
    rows must equal the hand-computed ``(d + r) - decode(encode(d + r))``
    and the server must equal the scatter-folded decoded deltas — with
    ``y`` and the encode keys re-derived from scratch, pinning the
    per-client RNG derivation (``fold_in(client_key, _WIRE_KEY_TAG)``)
    and the delta-fold identity."""
    tr = _make_trainer("fedhen", n_devices=2, **FULL)
    fed, layout, wire = tr.fed, tr.layout, tr.wire
    server0 = jax.tree.map(jnp.copy, tr.server.complex)
    plan = tr.sampler.plan(0)
    assert list(plan.simple_ids) == [0] and list(plan.complex_ids) == [1]

    tr.run_round()

    # replicate broadcast + training exactly (same derivation as the
    # SCAFFOLD oracle in tests/test_scaffold.py)
    key = jax.random.PRNGKey(fed.seed * 100003 + 0)
    rs, rc = jax.random.split(key)
    bc = comm.broadcast_roundtrip(wire, layout, server0)
    x_flat = flatten.pack(layout, bc).astype(jnp.float32)
    adapter = tr.adapter
    shard = lambda i: jax.tree.map(lambda v: v[0], tr._gather([i]))

    train_s = make_client_trainer(adapter.loss_simple, fed)
    y_s, _ = train_s(bc, shard(0), jax.random.fold_in(rs, 0))
    train_c = make_client_trainer(adapter.loss_side, fed)
    y_c, _ = train_c(bc, shard(1), jax.random.fold_in(rc, 0))

    d_s = flatten.pack(layout, y_s).astype(jnp.float32) - x_flat
    d_c = flatten.pack(layout, y_c).astype(jnp.float32) - x_flat
    # round 1: residual starts at zero, d_in == d
    enc_s = jax.random.fold_in(jax.random.fold_in(rs, 0), _WIRE_KEY_TAG)
    enc_c = jax.random.fold_in(jax.random.fold_in(rc, 0), _WIRE_KEY_TAG)
    buf_s = comm.sparse_encode(wire, d_s, tr.k_top_simple, key=enc_s)
    buf_c = comm.sparse_encode(wire, d_c, tr.k_top_complex, key=enc_c)
    dhat_s = comm.sparse_decode(wire, buf_s, layout.n_flat)
    dhat_c = comm.sparse_decode(wire, buf_c, layout.n_flat)

    # residual rows: r' = d - scattered decode, exactly
    want_r_s = np.asarray(d_s.at[buf_s.indices].add(
        -comm.sparse_decode_values(wire, buf_s)))
    want_r_c = np.asarray(d_c.at[buf_c.indices].add(
        -comm.sparse_decode_values(wire, buf_c)))
    # the oracle recomputes y outside the round jit, where XLA fuses the
    # local SGD steps differently: d agrees to a few f32 ulps of the
    # parameter magnitude, not bit-exactly.  Where such an ulp moves an
    # entry across a stochastic-rounding threshold or a top-k tie, its
    # residual changes by one quantization step, so a couple of entries
    # may sit outside the ulp bound.  A wrong encode key flips hundreds.
    rows = tr.ef_store.to_array()
    ulps = 4 * float(np.finfo(np.float32).eps) * float(
        np.max(np.abs(np.asarray(x_flat))))
    for row, want in ((rows[0], want_r_s), (rows[1], want_r_c)):
        assert int(np.sum(np.abs(row - want) > ulps)) <= 2
    # ... and the ef_scale column carries their norms
    np.testing.assert_allclose(
        tr.client_state.column("ef_scale")[:2],
        [np.linalg.norm(want_r_s), np.linalg.norm(want_r_c)], rtol=1e-5)

    # server fold: in-M positions average both decoded deltas around x,
    # out-of-M positions take the complex client's alone (d_s is zero
    # outside M, so its top-k never ships signal there)
    mask = np.asarray(tr.flat_mask)
    want_flat = np.where(
        mask, np.asarray(x_flat) + (np.asarray(dhat_s)
                                    + np.asarray(dhat_c)) / 2.0,
        np.asarray(x_flat) + np.asarray(dhat_c))
    got_flat = np.asarray(flatten.pack(layout, tr.server.complex))
    live = np.zeros(layout.n_flat, bool)
    for slot in layout.slots:
        live[slot.offset:slot.offset + slot.size] = True
    # the entries whose quantization step flipped above differ here too;
    # a dropped mask or a wrong fold weight moves thousands
    off = ~np.isclose(got_flat[live], want_flat[live], rtol=1e-5, atol=1e-6)
    assert int(np.sum(off)) <= 2


def test_ef_residual_feeds_the_next_round():
    """Round 2's upload is ``d + r``: zero the store by hand and the
    second round must diverge from the unmodified run."""
    a = _make_trainer("fedhen", **FULL)
    b = _make_trainer("fedhen", **FULL)
    a.run_round()
    b.run_round()
    assert _max_abs_diff(a.server.complex, b.server.complex) == 0.0
    b.ef_store.load(np.zeros_like(b.ef_store.to_array()))
    a.run_round()
    b.run_round()
    assert _max_abs_diff(a.server.complex, b.server.complex) > 0.0


# ---------------------------------------------------------------------------
# SCAFFOLD composition: the cv path is untouched by the compressed wire
# ---------------------------------------------------------------------------

def test_scaffold_composes_with_ef_wire():
    """Control variates are computed client-side from (x, y) — the
    round-1 cv rows under the EF wire must be bit-identical to the
    dense-wire SCAFFOLD run (same broadcast, same training), while the
    server models diverge (compressed uploads)."""
    dense = _make_trainer("fedhen", comm_dtype="int8", quant_block=64,
                          variance_reduction="scaffold")
    ef = _make_trainer("fedhen", variance_reduction="scaffold", **FULL)
    dense.run_round()
    ef.run_round()
    np.testing.assert_array_equal(dense.cv_store.to_array(),
                                  ef.cv_store.to_array())
    np.testing.assert_array_equal(np.asarray(dense.cv_global),
                                  np.asarray(ef.cv_global))
    assert _max_abs_diff(dense.server.complex, ef.server.complex) > 0.0
    # both stores stay finite over further rounds
    ef.run_round()
    assert np.isfinite(ef.cv_store.to_array()).all()
    assert np.isfinite(ef.ef_store.to_array()).all()


# ---------------------------------------------------------------------------
# Async engine: lag=0 bit-parity, lag>0 liveness
# ---------------------------------------------------------------------------

def test_async_lag0_bit_parity_under_full_stack():
    sync = _make_trainer("fedhen", n_devices=6, cohort_chunk=1, **FULL)
    tr = _make_trainer("fedhen", n_devices=6, cohort_chunk=1, **FULL)
    eng = async_rounds.AsyncRoundEngine(tr, lag=0)
    for _ in range(2):
        m_sync = sync.run_round()
        m_async = eng.run_round()
    assert m_sync == m_async
    assert _max_abs_diff(sync.server.complex, tr.server.complex) == 0.0
    np.testing.assert_array_equal(sync.ef_store.to_array(),
                                  tr.ef_store.to_array())
    assert sync.total_bytes == tr.total_bytes


def test_async_lag1_full_stack_stays_finite():
    tr = _make_trainer("fedhen", n_devices=6, cohort_chunk=1,
                       async_lag=1, **FULL)
    assert tr.async_engine is not None
    for _ in range(3):
        m = tr.run_round()
        assert np.isfinite(m["loss_simple"]) and np.isfinite(
            m["loss_complex"])
    assert np.isfinite(tr.ef_store.to_array()).all()
    assert tr.ef_store.scattered_bytes > 0
    assert float(tr.client_state.column("ef_scale").sum()) > 0.0


# ---------------------------------------------------------------------------
# Row hygiene: NaN devices and uniform-sampling pad slots
# ---------------------------------------------------------------------------

class _NanAdapter:
    """Tiny real-training adapter (mirrors tests/test_scaffold.py):
    params drift toward each client's data mean, so a NaN shard produces
    a NaN-trained device whose residual row must be left untouched."""

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


def test_nan_device_keeps_previous_residual_row():
    fed = FedConfig(n_devices=4, n_simple=2, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=4,
                    algorithm="fedhen", seed=0, **FULL)
    rng = np.random.default_rng(0)
    shards = [{"x": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))}
              for _ in range(fed.n_devices)]
    shards[1]["x"] = shards[1]["x"].at[0, 0].set(jnp.nan)
    tr = FederatedTrainer(_NanAdapter(), fed, shards)
    m = tr.run_round()
    assert m["n_valid"] == fed.n_devices - 1
    rows = tr.ef_store.to_array()
    assert np.isfinite(rows).all()
    np.testing.assert_array_equal(rows[1], 0.0)   # kept its (zero) row
    assert np.isfinite(jax.tree.leaves(tr.server.complex)[0]).all()
    assert float(tr.client_state.column("ef_scale")[1]) == 0.0


def test_uniform_pad_slots_never_scatter_residuals():
    tr = _make_trainer("fedhen", n_devices=8, participation=0.25,
                       sample_uniform=True, **FULL)
    for r in range(20):
        plan = tr.sampler.plan(tr.server.round)
        if not plan.all_real:
            break
        tr.run_round()
    else:
        pytest.fail("no uniform round with pad slots in 20 draws")
    before = tr.ef_store.to_array().copy()
    tr.run_round()
    after = tr.ef_store.to_array()
    real = set(int(i) for i in plan.real_ids())
    changed = {i for i in range(tr.fed.n_devices)
               if np.abs(after[i] - before[i]).max() > 0.0}
    assert changed <= real, (changed, real)
    assert changed, "no real row updated"


# ---------------------------------------------------------------------------
# Checkpoint: the residual store rides the __ef_store__ sidecar
# ---------------------------------------------------------------------------

def test_checkpoint_resume_reproduces_uninterrupted_ef_run(tmp_path):
    path = str(tmp_path / "ckpt.npz")
    a = _make_trainer("fedhen", **FULL)
    a.run_round()
    a.run_round()
    save_trainer(path, a)
    a.run_round()

    b = _make_trainer("fedhen", **FULL)
    restore_trainer(path, b)
    assert b.server.round == 2
    b.run_round()
    assert _max_abs_diff(a.server.complex, b.server.complex) == 0.0
    np.testing.assert_array_equal(a.ef_store.to_array(),
                                  b.ef_store.to_array())


def test_checkpoint_without_ef_sidecar_rejected(tmp_path):
    """Restoring a plain checkpoint into an EF trainer must fail loudly
    — silently zeroing the residuals would drop un-uploaded signal."""
    path = str(tmp_path / "ckpt.npz")
    plain = _make_trainer("fedhen")
    plain.run_round()
    save_trainer(path, plain)
    ef = _make_trainer("fedhen", **FULL)
    with pytest.raises(ValueError, match="no __ef_store__ sidecar"):
        restore_trainer(path, ef)

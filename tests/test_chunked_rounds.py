"""Chunked rounds: cohort_chunk streams the cohort through the fold in
chunks and changes only the execution schedule — the server state,
metrics and bytes of a chunked round equal the one-shot round's — plus
the trainer's static flat layout and the auto chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core import masking
from repro.core.adapters import LMAdapter
from repro.core.federated import FederatedTrainer
from repro.data.federated import iid_split
from repro.data.synthetic import synthetic_lm

TINY = ModelConfig(n_layers=4, d_model=32, n_heads=2, n_kv_heads=2,
                   d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                   exit_layer=2, compute_dtype="float32")


# ---------------------------------------------------------------------------
# Streaming cohort engine: chunked rounds == one-shot rounds
# ---------------------------------------------------------------------------

def _make_chunked_trainer(algorithm, chunk, *, n_devices=12, **fed_kw):
    """ks = kc = n_devices/4 active clients per population."""
    fed = FedConfig(n_devices=n_devices, n_simple=n_devices // 2,
                    participation=0.5, rounds=3, local_epochs=1, lr=0.1,
                    clip_norm=10.0, batch_size=4, algorithm=algorithm,
                    seed=0, cohort_chunk=chunk, **fed_kw)
    data = synthetic_lm(n_devices * 4, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    return FederatedTrainer(LMAdapter(TINY), fed, shards)


def _assert_server_allclose(a, b, rtol=3e-5, atol=3e-6):
    for x, y in zip(jax.tree.leaves(a.server.complex),
                    jax.tree.leaves(b.server.complex)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)
    if a.server.simple_host is not None:
        for x, y in zip(jax.tree.leaves(a.server.simple_host),
                        jax.tree.leaves(b.server.simple_host)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=rtol, atol=atol)


@pytest.mark.parametrize("algorithm", ["fedhen", "noside", "decouple"])
@pytest.mark.parametrize("chunk", [1, 3, 0])   # 0 = whole population (k)
def test_chunked_round_matches_one_shot(algorithm, chunk):
    """cohort_chunk only changes the execution schedule, never the round's
    result: server state after a chunked round == the one-shot round."""
    ref = _make_chunked_trainer(algorithm, 0)
    tr = _make_chunked_trainer(algorithm, chunk)
    m_ref = ref.run_round()
    m = tr.run_round()
    _assert_server_allclose(ref, tr)
    assert m["n_valid"] == m_ref["n_valid"]
    assert abs(m["loss_simple"] - m_ref["loss_simple"]) < 1e-4
    assert abs(m["loss_complex"] - m_ref["loss_complex"]) < 1e-4


@pytest.mark.parametrize("algorithm", ["fedhen", "noside", "decouple"])
def test_chunk_not_dividing_k_is_padded(algorithm):
    """ks = kc = 3 with chunk 2: populations are padded with zero-validity
    clients; the padding must not change the aggregate or the metrics."""
    ref = _make_chunked_trainer(algorithm, 0)
    tr = _make_chunked_trainer(algorithm, 2)   # 2 does not divide 3
    m_ref = ref.run_round()
    m = tr.run_round()
    _assert_server_allclose(ref, tr)
    assert m["n_valid"] == m_ref["n_valid"] == tr.k_simple + tr.k_complex
    assert abs(m["loss_simple"] - m_ref["loss_simple"]) < 1e-4


def test_chunked_multi_round_stays_on_trajectory():
    """Chunking composes over rounds (the carry is re-chunked each round)."""
    ref = _make_chunked_trainer("fedhen", 0)
    tr = _make_chunked_trainer("fedhen", 2)
    for _ in range(3):
        ref.run_round()
        tr.run_round()
    _assert_server_allclose(ref, tr, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Flat layout threading and the auto chunk
# ---------------------------------------------------------------------------

def test_auto_cohort_chunk_resolves_from_budget():
    """cohort_chunk="auto": tiny budget floors at 1; huge budget covers the
    whole population; the resolved chunk round still matches one-shot."""
    small = _make_chunked_trainer("fedhen", "auto",
                                  agg_memory_budget_mb=1e-6)
    assert small.cohort_chunk == 1
    big = _make_chunked_trainer("fedhen", "auto",
                                agg_memory_budget_mb=1e9)
    assert big.cohort_chunk == max(big.k_simple, big.k_complex)
    ref = _make_chunked_trainer("fedhen", 0)
    ref.run_round()
    small.run_round()
    _assert_server_allclose(ref, small)


def test_trainer_layout_is_static_and_mask_flat():
    tr = _make_chunked_trainer("fedhen", 2)
    assert tr.layout.n_flat % tr.fed.agg_block_n == 0
    assert tr.flat_mask.shape == (tr.layout.n_flat,)
    assert tr.flat_mask.dtype == jnp.bool_
    n_in_m = masking.mask_size(tr.mask, tr.server.complex)
    assert int(jnp.sum(tr.flat_mask)) == n_in_m == TINY.simple_param_count()


def test_total_bytes_invariant_under_chunking():
    """Chunking is an execution detail: what is *communicated* per round
    (and in total) must not depend on cohort_chunk."""
    ref = _make_chunked_trainer("fedhen", 0)
    tr = _make_chunked_trainer("fedhen", 2)
    assert tr.bytes_per_round == ref.bytes_per_round
    for _ in range(2):
        ref.run_round()
        tr.run_round()
    assert tr.total_bytes == ref.total_bytes > 0

"""The production round-step (fedround_dryrun's payload) is semantically a
FedHeN round: branchless objective select + masked aggregation."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.core import masking


def test_round_step_tiny():
    # import the factory without triggering the module-level XLA_FLAGS
    import importlib.util
    import os
    spec = importlib.util.find_spec("repro.launch.fedround_dryrun")
    # the XLA flag assignment at module top is harmless after jax init
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                      exit_layer=1, compute_dtype="float32")
    from repro.models import transformer as tfm
    from repro.models.common import NO_POLICY

    k_clients, batch, steps, seq = 4, 2, 2, 16
    step = mod.make_round_step(cfg, NO_POLICY, local_steps=steps)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    cohort = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (k_clients,) + x.shape), params)
    data = jax.random.randint(jax.random.PRNGKey(1),
                              (k_clients, batch, steps, seq + 1), 0, 64)
    is_simple = jnp.array([True, True, False, False])

    new_complex, loss = jax.jit(step)(cohort, data, is_simple)
    assert np.isfinite(float(loss))
    for x in jax.tree.leaves(new_complex):
        assert np.isfinite(np.asarray(x, np.float32)).all()
    # simple clients must not have moved the M' (complex-only) slice:
    # aggregation takes M' from complex clients only, so M' != init
    # while the M slice mixes all four — both should differ from init
    changed = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                              b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(new_complex),
                        jax.tree.leaves(params)))
    assert changed


def test_round_step_int8_wire_matches_f32():
    """The launch-side round folds encoded uploads: the int8 wire's
    dequantizing fold lands near the f32 round and stays finite."""
    from repro.core import aggregate, comm
    from repro.launch.steps import make_fed_round_step
    from repro.models import transformer as tfm
    from repro.models.common import NO_POLICY

    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                      exit_layer=1, compute_dtype="float32")
    k_clients, batch, steps, seq = 4, 2, 2, 16
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    cohort = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (k_clients,) + x.shape), params)
    data = jax.random.randint(jax.random.PRNGKey(1),
                              (k_clients, batch, steps, seq + 1), 0, 64)
    is_simple = jnp.array([True, True, False, False])

    ref_step = make_fed_round_step(cfg, NO_POLICY, local_steps=steps,
                                   cohort_chunk=2)
    q_step = make_fed_round_step(
        cfg, NO_POLICY, local_steps=steps, cohort_chunk=2,
        engine=aggregate.EngineSpec(wire=comm.WireSpec("int8", 128)))
    ref_c, ref_loss = jax.jit(ref_step)(cohort, data, is_simple)
    q_c, q_loss = jax.jit(q_step)(cohort, data, is_simple)
    assert np.isfinite(float(q_loss))
    # uploads are quantized but training is identical: same loss metric
    np.testing.assert_allclose(float(q_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(q_c), jax.tree.leaves(ref_c)):
        amax = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= amax / 100.0


def test_round_step_block_n_is_a_tiling_choice():
    """The spec's kernel tile width changes how the flat layout is
    padded, never the round: an ``EngineSpec(block_n=512)`` step lands on
    the default (2048) step's model and loss."""
    from repro.core import aggregate
    from repro.launch.steps import make_fed_round_step
    from repro.models import transformer as tfm
    from repro.models.common import NO_POLICY

    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                      exit_layer=1, compute_dtype="float32")
    k_clients, batch, seq = 4, 2, 16
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    cohort = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (k_clients,) + x.shape), params)
    data = jax.random.randint(jax.random.PRNGKey(2),
                              (k_clients, batch, 1, seq + 1), 0, 64)
    is_simple = jnp.array([True, False, True, False])

    ref_c, ref_loss = jax.jit(make_fed_round_step(
        cfg, NO_POLICY, local_steps=1, cohort_chunk=2))(
            cohort, data, is_simple)
    got_c, got_loss = jax.jit(make_fed_round_step(
        cfg, NO_POLICY, local_steps=1, cohort_chunk=2,
        engine=aggregate.EngineSpec(block_n=512)))(cohort, data, is_simple)
    assert float(got_loss) == float(ref_loss)
    for a, b in zip(jax.tree.leaves(got_c), jax.tree.leaves(ref_c)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

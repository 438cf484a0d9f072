"""Per-architecture smoke tests (deliverable f).

For each of the 10 assigned architectures: instantiate the REDUCED variant
(2-3 layers, d_model <= 256, <= 4 experts) and run one forward and one
FedHeN side-objective train step on CPU, asserting output shapes and the
absence of NaNs.  The FULL configs are exercised only via the dry-run
(ShapeDtypeStruct, no allocation) — here we only sanity-check their
analytical parameter counts against the published sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import INPUT_SHAPES
from repro.core.adapters import LMAdapter
from repro.models import transformer as tfm
from repro.optim.sgd import sgd_update


def _batch(cfg, b=2, s=16, seed=0):
    key = jax.random.PRNGKey(seed)
    batch = {}
    s_tok = s
    if cfg.cross_attention:
        fe = cfg.frontend
        batch["cond"] = jax.random.normal(
            jax.random.fold_in(key, 1), (b, fe.n_tokens, fe.d_in),
            jnp.dtype(cfg.compute_dtype))
        batch["cond_mask"] = jnp.arange(fe.n_tokens)[None, :] < \
            jnp.arange(1, b + 1)[:, None]
    elif cfg.frontend is not None:
        s_tok = s - cfg.frontend.n_tokens
        batch["extra_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1),
            (b, cfg.frontend.n_tokens, cfg.frontend.d_in),
            jnp.dtype(cfg.compute_dtype))
    shape = (b, s_tok + 1)
    if cfg.n_codebooks > 1:
        shape = shape + (cfg.n_codebooks,)
    batch["tokens"] = jax.random.randint(key, shape, 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_reduced_forward_and_fedhen_step(name):
    cfg = configs.get_reduced(name)
    assert cfg.n_layers <= 3 and cfg.d_model <= 256
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    adapter = LMAdapter(cfg)
    params = adapter.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    # forward shapes
    inputs = batch["tokens"][:, :-1]
    exit_h, final_h, _ = tfm.forward(params, cfg, inputs,
                                     extra_embeds=batch.get("extra_embeds"),
                                     cond=batch.get("cond"),
                                     cond_mask=batch.get("cond_mask"))
    s_total = inputs.shape[1] + (cfg.frontend.n_tokens if "extra_embeds"
                                 in batch else 0)
    assert final_h.shape == (2, s_total, cfg.d_model)
    assert exit_h.shape == final_h.shape
    logits = tfm.logits_from_hidden(params, cfg, final_h, "final")
    expected = ((2, s_total, cfg.n_codebooks, cfg.vocab_size)
                if cfg.n_codebooks > 1 else (2, s_total, cfg.vocab_size))
    assert logits.shape == expected
    assert not bool(jnp.isnan(logits).any())

    # one FedHeN side-objective SGD step
    loss, grads = jax.value_and_grad(adapter.loss_side)(params, batch)
    assert np.isfinite(float(loss))
    new_params = sgd_update(params, grads, 0.1, clip_norm=10.0)
    for x in jax.tree.leaves(new_params):
        assert not bool(jnp.isnan(x).any())
    loss2 = adapter.loss_side(new_params, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_reduced_decode_step(name):
    cfg = configs.get_reduced(name)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    b = 2
    cache = tfm.init_cache(cfg, b, 32)
    shape = (b, 1, cfg.n_codebooks) if cfg.n_codebooks > 1 else (b, 1)
    tok = jax.random.randint(jax.random.PRNGKey(1), shape, 0, cfg.vocab_size)
    logits, new_cache = tfm.decode_step(params, cache, cfg, tok, jnp.int32(0))
    assert not bool(jnp.isnan(logits).any())
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


# ---------------------------------------------------------------------------
# Full-config analytical parameter counts vs published sizes
# ---------------------------------------------------------------------------

EXPECTED_PARAMS = {  # (low, high) bounds in billions, generous
    "recurrentgemma-2b": (2.0, 3.6),
    "qwen2-moe-a2.7b": (12.0, 16.5),      # 14.3B total / 2.7B active
    "starcoder2-15b": (13.0, 17.5),
    "gemma2-2b": (2.0, 3.6),
    "xlstm-1.3b": (1.0, 2.0),   # block-diag qkv, pf=2 (see config note)
    "llava-next-34b": (30.0, 40.0),
    "kimi-k2-1t-a32b": (950.0, 1150.0),
    "gemma3-4b": (3.0, 5.0),
    "musicgen-large": (3.0, 3.6),         # 3.3B with cross-attention
    "minitron-8b": (7.0, 10.0),
}


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_full_config_param_counts(name):
    cfg = configs.get_config(name)
    n = cfg.param_count() / 1e9
    lo, hi = EXPECTED_PARAMS[name]
    assert lo <= n <= hi, f"{name}: {n:.2f}B not in [{lo}, {hi}]"
    # FedHeN subnet is a strict, nontrivial sub-network
    s = cfg.simple_param_count()
    assert 0 < s < cfg.param_count()


def test_musicgen_large_counts_the_published_block():
    """48 layers of self-attention, cross-attention (each 4 x 2048^2) and
    a 2 x 2048 x 8192 FFN with three LayerNorms, 4 embeddings of 2049
    rows, 4 untied heads, the 768 -> 2048 conditioning projection with
    its bias, the final and exit LayerNorms: the published 3.3B; the
    simple model holds 24 layers and everything outside the layers but
    the final norm.  The built tree counts the same."""
    cfg = configs.get_config("musicgen-large")
    layer = 2 * 4 * 2048 ** 2 + 2 * 2048 * 8192 + 3 * 2 * 2048
    outside = (4 * 2049 * 2048 + 4 * 2048 * 2048 + 768 * 2048 + 2048
               + 2 * 2048)
    assert cfg.param_count() == 48 * layer + outside + 2 * 2048 \
        == 3_256_961_024
    assert cfg.simple_param_count() == 24 * layer + outside
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == cfg.param_count()


@pytest.mark.parametrize("override", [{"tie_embeddings": True},
                                      {"embed_scale": True}])
def test_parallel_codebooks_refuse_the_generic_heads(override):
    """Parallel codebooks always run MusicGen's delay pattern with untied
    heads over unscaled embeddings; a config asking otherwise is
    refused, not silently given another model."""
    cfg = configs.get_reduced("musicgen-large")
    assert cfg.n_codebooks > 1 and cfg.delay_pattern
    assert not cfg.with_overrides(n_codebooks=1, **override).delay_pattern
    with pytest.raises(ValueError, match="parallel codebooks"):
        cfg.with_overrides(**override)


def test_moe_active_params():
    cfg = configs.get_config("kimi-k2-1t-a32b")
    active = cfg.active_param_count() / 1e9
    assert 25.0 <= active <= 45.0, active   # A32B

    qwen = configs.get_config("qwen2-moe-a2.7b")
    assert 1.8 <= qwen.active_param_count() / 1e9 <= 3.8


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_input_specs_cover_all_shapes(name):
    cfg = configs.get_config(name)
    for shape in INPUT_SHAPES.values():
        specs = configs.input_specs(cfg, shape)
        assert "tokens" in specs
        t = specs["tokens"]
        assert t.shape[0] == shape.global_batch
        if shape.kind == "decode":
            assert t.shape[1] == 1
        # no allocation happened
        assert isinstance(t, jax.ShapeDtypeStruct)


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_exit_layer_on_period_boundary(name):
    for cfg in (configs.get_config(name), configs.get_reduced(name)):
        k = cfg.resolved_exit_layer
        assert k % cfg.period == 0
        assert cfg.period <= k <= cfg.n_layers

"""Server aggregation unit tests against numpy oracles (Alg. 1 ln. 16-22),
one-shot AND streaming paths (the fold's CPU and kernel lowerings).
Referenced by the ``fedhen_server_update`` docstring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregate, flatten, masking


def _random_case(seed, z=9):
    rng = np.random.default_rng(seed)
    cohort = {"a": jnp.asarray(rng.normal(size=(z, 4, 3)).astype(np.float32)),
              "b": jnp.asarray(rng.normal(size=(z, 5)).astype(np.float32))}
    mask = {"a": jnp.asarray(True), "b": jnp.asarray(False)}
    is_simple = jnp.asarray(np.arange(z) < z // 2)
    valid = jnp.ones(z, bool)
    return cohort, mask, is_simple, valid


def _np_group_mean(x, sel):
    sel = np.asarray(sel)
    if not sel.any():
        return np.zeros(x.shape[1:], x.dtype)
    return np.asarray(x)[sel].mean(0)


# ---------------------------------------------------------------------------
# One-shot path vs numpy oracle
# ---------------------------------------------------------------------------

def test_fedhen_m_slice_invariant():
    """The server simple model IS the M slice of the new complex model:
    inside M the update is the all-devices mean, outside the complex-only
    mean — exactly Alg. 1 ln. 18-22."""
    cohort, mask, is_simple, valid = _random_case(0)
    new = aggregate.fedhen_server_update(cohort, is_simple, valid, mask)
    v, s = np.asarray(valid), np.asarray(is_simple)
    np.testing.assert_allclose(  # M slice ("a"): mean over ALL valid
        new["a"], _np_group_mean(cohort["a"], v), rtol=1e-5)
    np.testing.assert_allclose(  # M' ("b"): complex-only mean
        new["b"], _np_group_mean(cohort["b"], v & ~s), rtol=1e-5)


def test_nan_device_exclusion():
    cohort, mask, is_simple, _ = _random_case(1)
    cohort["a"] = cohort["a"].at[2].set(jnp.nan)
    cohort["b"] = cohort["b"].at[7, 0].set(jnp.inf)
    valid = jax.vmap(masking.tree_isfinite)(cohort)
    assert not bool(valid[2]) and not bool(valid[7])
    new = aggregate.fedhen_server_update(cohort, is_simple, valid, mask)
    for leaf in jax.tree.leaves(new):
        assert np.isfinite(np.asarray(leaf)).all()
    v, s = np.asarray(valid), np.asarray(is_simple)
    ok = np.isfinite(np.asarray(cohort["a"])).all(axis=(1, 2))
    np.testing.assert_allclose(
        new["a"], _np_group_mean(cohort["a"], v & ok), rtol=1e-5)


def test_decouple_group_means():
    """Decouple = two independent FedAvg runs: M slice averages simple
    devices only, everything else complex devices only."""
    cohort, mask, is_simple, valid = _random_case(2)
    host, new_complex = aggregate.decouple_server_update(
        cohort, is_simple, valid, mask)
    v, s = np.asarray(valid), np.asarray(is_simple)
    np.testing.assert_allclose(
        host["a"], _np_group_mean(cohort["a"], v & s), rtol=1e-5)
    np.testing.assert_allclose(
        host["b"], _np_group_mean(cohort["b"], v & ~s), rtol=1e-5)
    for key in ("a", "b"):  # complex model: complex-only mean everywhere
        np.testing.assert_allclose(
            new_complex[key], _np_group_mean(cohort[key], v & ~s), rtol=1e-5)


# ---------------------------------------------------------------------------
# Streaming path == one-shot path
# ---------------------------------------------------------------------------

def _stream(cohort, mask, is_simple, valid, algo, chunk,
            stream_dtype=jnp.float32, **fold_kw):
    z = jax.tree.leaves(cohort)[0].shape[0]
    template = jax.tree.map(lambda x: x[0], cohort)
    spec = aggregate.EngineSpec(algorithm=algo, mask=mask,
                                stream_dtype=stream_dtype)
    state = aggregate.streaming_init(template, spec)
    for lo in range(0, z, chunk):
        sl = slice(lo, min(lo + chunk, z))
        state = aggregate.streaming_fold(
            state, jax.tree.map(lambda x: x[sl], cohort),
            is_simple[sl], valid[sl], spec, **fold_kw)
    return aggregate.streaming_finalize(state, spec, template)


def _assert_tree_allclose(got, want, rtol=2e-5, atol=2e-6):
    if want is None:
        assert got is None
        return
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("algo", ["fedhen", "noside", "decouple"])
@pytest.mark.parametrize("chunk", [1, 2, 9])
def test_streaming_matches_one_shot(algo, chunk):
    cohort, mask, is_simple, valid = _random_case(3)
    valid = valid.at[4].set(False)  # one dropped device crosses chunks
    if algo == "decouple":
        want_host, want_c = aggregate.decouple_server_update(
            cohort, is_simple, valid, mask)
    else:
        want_c = aggregate.fedhen_server_update(cohort, is_simple, valid,
                                                mask)
        want_host = None
    got_c, got_host = _stream(cohort, mask, is_simple, valid, algo, chunk)
    for g, w in zip(jax.tree.leaves(got_c), jax.tree.leaves(want_c)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)
    if want_host is None:
        assert got_host is None
    else:
        for g, w in zip(jax.tree.leaves(got_host),
                        jax.tree.leaves(want_host)):
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("algo", ["fedhen", "noside", "decouple"])
@pytest.mark.parametrize("stream_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_streaming_fold_pallas_interpret(algo, stream_dtype):
    """The fold's kernel dispatch (interpret mode: one packed buffer, one
    launch — two for decouple) matches the CPU lowering (per-leaf slices
    of the same flat accumulator), both accumulators, at either stream
    dtype."""
    cohort, mask, is_simple, valid = _random_case(4)
    ref_c, ref_host = _stream(cohort, mask, is_simple, valid, algo, 3,
                              stream_dtype)
    ker_c, ker_host = _stream(cohort, mask, is_simple, valid, algo, 3,
                              stream_dtype, force_pallas_interpret=True)
    _assert_tree_allclose(ker_c, ref_c, rtol=1e-5, atol=1e-6)
    _assert_tree_allclose(ker_host, ref_host, rtol=1e-5, atol=1e-6)


def test_streaming_zero_weight_group_is_zero():
    """An empty group (no valid complex devices) yields zeros, like the
    one-shot ``_norm_weights`` guard — never NaN from 0/0."""
    cohort, mask, is_simple, _ = _random_case(5)
    valid = jnp.asarray(np.asarray(is_simple))  # only simple devices valid
    got_c, _ = _stream(cohort, mask, is_simple, valid, "fedhen", 2)
    np.testing.assert_allclose(got_c["b"], np.zeros_like(got_c["b"]))
    v = np.asarray(valid)
    np.testing.assert_allclose(got_c["a"], _np_group_mean(cohort["a"], v),
                               rtol=1e-5)


def test_streaming_rejects_unknown_algorithm():
    """The spec is the one place an algorithm name is checked: at
    construction and again when a copy is bound."""
    with pytest.raises(ValueError):
        aggregate.EngineSpec(algorithm="fedavg")
    with pytest.raises(ValueError):
        aggregate.EngineSpec().bind(algorithm="fedavg")


@pytest.mark.parametrize("payload", ["cv_chunk", "sparse_chunk"])
def test_streaming_fold_rejects_misrouted_payloads(payload):
    """A control-variate chunk needs the SCAFFOLD accumulator, and a
    delta-mode chunk needs a delta wire: each is refused with its own
    message instead of being folded into the wrong sums."""
    from repro.core import comm
    cohort, mask, is_simple, valid = _random_case(15)
    template = jax.tree.map(lambda x: x[0], cohort)
    layout = flatten.layout_of(template, total_multiple=512)
    spec = aggregate.EngineSpec(mask=mask, layout=layout, block_n=512,
                                wire=comm.WireSpec("int8", 128))
    state = aggregate.streaming_init(template, spec)
    z, n_flat = is_simple.shape[0], layout.n_flat
    if payload == "cv_chunk":
        kw, match = {"cv_chunk": jnp.zeros((z, n_flat))}, "cv accumulator"
        chunk = cohort
    else:
        kw = {"sparse_chunk": aggregate.SparseChunk(
            jnp.zeros((n_flat,)), jnp.zeros((z, n_flat)), None, None)}
        match, chunk = "delta-mode wire", None
    with pytest.raises(ValueError, match=match):
        aggregate.streaming_fold(state, chunk, is_simple, valid, spec, **kw)


# ---------------------------------------------------------------------------
# Streaming fold == one-shot oracle on the hard cases
# ---------------------------------------------------------------------------

def _hard_case(seed, z=9):
    """NaN device + zero-weight padding device crossing chunk boundaries."""
    cohort, mask, is_simple, valid = _random_case(seed, z)
    cohort["a"] = cohort["a"].at[3].set(jnp.nan)   # NaN device
    valid = valid.at[3].set(False)
    valid = valid.at[z - 1].set(False)             # zero-weight padding
    return cohort, mask, is_simple, valid


@pytest.mark.parametrize("algo", ["fedhen", "noside", "decouple"])
@pytest.mark.parametrize("chunk", [2, 4])
def test_streaming_fold_vs_oracle(algo, chunk):
    """The fold agrees with the one-shot oracle with a NaN device and a
    zero-weight padding device in the cohort (both must be invisible)."""
    cohort, mask, is_simple, valid = _hard_case(7)
    if algo == "decouple":
        want_host, want_c = aggregate.decouple_server_update(
            cohort, is_simple, valid, mask)
    else:
        want_c = aggregate.fedhen_server_update(cohort, is_simple, valid,
                                                mask)
        want_host = None
    got_c, got_host = _stream(cohort, mask, is_simple, valid, algo, chunk)
    _assert_tree_allclose(got_c, want_c)
    _assert_tree_allclose(got_host, want_host)
    for leaf in jax.tree.leaves(got_c):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.parametrize("algo", ["fedhen", "decouple"])
def test_bf16_stream_f32_accumulation(algo):
    """bf16 chunk streaming: inputs are rounded to bf16 but the running
    sums stay f32 — the result matches the f32 path at bf16 tolerance and
    beats accumulating in bf16 outright."""
    cohort, mask, is_simple, valid = _random_case(8)
    template = jax.tree.map(lambda x: x[0], cohort)
    spec = aggregate.EngineSpec(algorithm=algo, mask=mask,
                                stream_dtype=jnp.bfloat16)
    state = aggregate.streaming_init(template, spec)
    for lo in range(0, 9, 3):
        sl = slice(lo, lo + 3)
        state = aggregate.streaming_fold(
            state, jax.tree.map(lambda x: x[sl], cohort),
            is_simple[sl], valid[sl], spec)
    assert state.acc.dtype == jnp.float32
    got_c, got_host = aggregate.streaming_finalize(state, spec, template)
    want_c, want_host = _stream(cohort, mask, is_simple, valid, algo, 3)
    _assert_tree_allclose(got_c, want_c, rtol=2e-2, atol=2e-2)
    if algo == "decouple":
        _assert_tree_allclose(got_host, want_host, rtol=2e-2, atol=2e-2)


def _count_pallas_calls(fn, *args, **kw):
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
    return sum(1 for eqn in jaxpr.jaxpr.eqns
               if eqn.primitive.name == "pallas_call")


@pytest.mark.parametrize("algo,n_launches", [("fedhen", 1), ("noside", 1),
                                             ("decouple", 2)])
def test_flat_fold_is_one_kernel_launch(algo, n_launches):
    """ONE masked-agg launch per fold for the whole model (two for
    decouple's extra accumulator), however many leaves the model has."""
    cohort, mask, is_simple, valid = _random_case(9)
    template = jax.tree.map(lambda x: x[0], cohort)
    spec = aggregate.EngineSpec(algorithm=algo, mask=mask)
    state = aggregate.streaming_init(template, spec)
    n_flat = _count_pallas_calls(
        aggregate.streaming_fold, state, cohort, is_simple, valid,
        spec=spec, force_pallas_interpret=True)
    assert n_flat == n_launches


# ---------------------------------------------------------------------------
# Float validity weights (the async engine's staleness path)
# ---------------------------------------------------------------------------

def _np_weighted_mean(x, w):
    w = np.asarray(w, np.float64)
    x = np.where((w > 0).reshape((-1,) + (1,) * (np.asarray(x).ndim - 1)),
                 np.asarray(x, np.float64), 0.0)
    tot = w.sum()
    if tot <= 0:
        return np.zeros(x.shape[1:])
    return (x * w.reshape((-1,) + (1,) * (x.ndim - 1))).sum(0) / tot


@pytest.mark.parametrize("algo", ["fedhen", "noside", "decouple"])
@pytest.mark.parametrize("lowering", ["cpu", "interpret"])
def test_float_staleness_weights_match_oracle(algo, lowering):
    """``valid`` as f32 per-client weights (validity x staleness decay):
    the fold implements the weighted mean on both of its lowerings (the
    CPU per-leaf path and the Pallas kernel in interpret mode), with a
    NaN device and a zero-weight device gated out — the async engine's
    whole fold contract in one case."""
    cohort, mask, is_simple, _ = _random_case(11)
    cohort["a"] = cohort["a"].at[2].set(jnp.nan)     # NaN device
    # fractional staleness weights; device 2 (NaN) and 5 at weight 0
    w = jnp.asarray([1.0, 0.5, 0.0, 0.25, 1.0, 0.0, 0.5, 1.0, 0.25],
                    jnp.float32)
    got_c, got_host = _stream(
        cohort, mask, is_simple, w, algo, 3,
        force_pallas_interpret=lowering == "interpret")
    s = np.asarray(is_simple)
    w_np = np.asarray(w)
    w_in = w_np * s if algo == "decouple" else w_np
    w_out = w_np * ~s
    if algo == "decouple":
        # new complex model: complex-group weighted mean everywhere
        want_a = _np_weighted_mean(cohort["a"], w_out)
        want_b = _np_weighted_mean(cohort["b"], w_out)
    else:
        want_a = _np_weighted_mean(cohort["a"], w_in)    # inside M
        want_b = _np_weighted_mean(cohort["b"], w_out)   # outside M
    np.testing.assert_allclose(np.asarray(got_c["a"]), want_a,
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_c["b"]), want_b,
                               rtol=2e-5, atol=2e-6)
    for leaf in jax.tree.leaves(got_c):
        assert np.isfinite(np.asarray(leaf)).all()
    if algo == "decouple":
        # the simple host: simple-group mean in M, complex-group outside
        np.testing.assert_allclose(
            np.asarray(got_host["a"]),
            _np_weighted_mean(cohort["a"], w_in), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(
            np.asarray(got_host["b"]),
            _np_weighted_mean(cohort["b"], w_out), rtol=2e-5, atol=2e-6)


def test_all_one_float_weights_bit_match_bool_valid():
    """The lag=0 parity primitive: f32 all-ones weights are bit-identical
    to bool validity through the fold."""
    cohort, mask, is_simple, valid = _random_case(12)
    got_b, _ = _stream(cohort, mask, is_simple, valid, "fedhen", 3)
    got_f, _ = _stream(cohort, mask, is_simple,
                       valid.astype(jnp.float32) * 1.0, "fedhen", 3)
    for a, b in zip(jax.tree.leaves(got_b), jax.tree.leaves(got_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flat_fold_uses_prebuilt_layout_and_mask():
    """The trainer path: one static layout + precomputed flat bitvector
    give the same result as the self-deriving defaults."""
    cohort, mask, is_simple, valid = _random_case(10)
    template = jax.tree.map(lambda x: x[0], cohort)
    layout = flatten.layout_of(template, total_multiple=512)
    spec = aggregate.EngineSpec(mask=mask, layout=layout,
                                flat_mask=flatten.pack_mask(layout, mask),
                                block_n=512)
    init, fold, finalize = aggregate.make_engine(spec)
    state = fold(init(template), cohort, is_simple, valid)
    got_c, _ = finalize(state, template=template)
    want_c, _ = _stream(cohort, mask, is_simple, valid, "fedhen", 9)
    _assert_tree_allclose(got_c, want_c, rtol=1e-6, atol=1e-7)


def test_engine_attrs_records_the_full_spec():
    from repro.core import comm
    cohort, mask, _, _ = _random_case(14)
    template = jax.tree.map(lambda x: x[0], cohort)
    layout = flatten.layout_of(template, total_multiple=512)
    spec = aggregate.EngineSpec(mask=mask, layout=layout, block_n=512,
                                wire=comm.WireSpec("int8", 128),
                                variance_reduction="scaffold")
    attrs = aggregate.engine_attrs(spec)
    assert attrs == {
        "algorithm": "fedhen", "agg_block_n": 512,
        "agg_stream_dtype": "float32", "variance_reduction": "scaffold",
        "wire_dtype": "int8", "wire_quantized": True,
        "wire_quant_block": 128, "wire_topk_frac": 1.0,
        "wire_stochastic": False, "wire_error_feedback": False,
    }


# ---------------------------------------------------------------------------
# Index set M of the decoder zoo: heads the exit head uses are inside M
# ---------------------------------------------------------------------------

def test_untied_heads_fold_over_simple_clients_too():
    """MusicGen's untied codebook heads serve the exit head, so they lie
    in M: one round of one simple and one complex client through
    FederatedTrainer folds the heads (and the conditioning projection,
    and the first layer's cross-attention) as the mean over BOTH
    clients, and everything outside M as the complex client's alone —
    the one-shot masked mean of the clients the round trained."""
    from repro import configs
    from repro.configs.base import FedConfig
    from repro.core.adapters import LMAdapter
    from repro.core.federated import FederatedTrainer, make_client_trainer
    from repro.data.synthetic import synthetic_conditioning, synthetic_lm

    cfg = configs.get_reduced("musicgen-large").with_overrides(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab_size=16)
    adapter = LMAdapter(cfg)
    fe = cfg.frontend
    data = synthetic_lm(4, 8, cfg.vocab_size, seed=1,
                        n_codebooks=cfg.n_codebooks)
    data.update(synthetic_conditioning(4, fe.n_tokens, fe.d_in, seed=1))
    shards = [{k: jnp.asarray(v[2 * i:2 * i + 2]) for k, v in data.items()
               if k != "labels"} for i in range(2)]
    fed = FedConfig(n_devices=2, n_simple=1, participation=1.0,
                    local_epochs=1, lr=0.5, batch_size=2, seed=4,
                    algorithm="fedhen")
    tr = FederatedTrainer(adapter, fed, shards)
    w0 = tr.server.complex
    mask = adapter.subnet_mask(w0)
    assert all(bool(m) for m in jax.tree.leaves(mask["unembed"])) and \
        all(bool(m) for m in jax.tree.leaves(mask["frontend_proj"]))
    cross = mask["periods"][0]["cross"]["wq"]
    assert cross.ravel().tolist() == [True, False]

    rs, rc = jax.random.split(jax.random.PRNGKey(fed.seed * 100003))
    simple = make_client_trainer(adapter.loss_simple, fed)(
        w0, shards[0], jax.random.fold_in(rs, 0))[0]
    complex_ = make_client_trainer(adapter.loss_side, fed)(
        w0, shards[1], jax.random.fold_in(rc, 0))[0]
    moved = np.abs(np.asarray(simple["unembed"]["w"])
                   - np.asarray(w0["unembed"]["w"])).max()
    assert moved > 1e-4                    # the simple client trains heads
    tr.run_round()
    cohort = jax.tree.map(lambda a, b: jnp.stack([a, b]), simple, complex_)
    want = aggregate.fedhen_server_update(
        cohort, jnp.asarray([True, False]), jnp.ones(2, bool), mask)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-6),
        tr.server.complex, want)


@pytest.mark.parametrize("name", [
    n for n in __import__("repro.configs", fromlist=["ARCH_NAMES"]).ARCH_NAMES
    if n != "musicgen-large"])
def test_registry_masks_unchanged(name):
    """Every other registry config ties its output to the embedding, so M
    is what it always was: the embedding, the frontend projector, the
    first K periods of layers and the exit norm; never the tail layers
    or the final norm."""
    from repro import configs
    from repro.core.adapters import LMAdapter
    cfg = configs.get_reduced(name)
    assert cfg.tie_embeddings and not cfg.cross_attention
    params = jax.eval_shape(LMAdapter(cfg).init, jax.random.PRNGKey(0))
    mask = masking.transformer_subnet_mask(params, cfg)
    whole = {k for k, v in mask.items()
             if k not in ("periods", "rem")
             and all(bool(m) for m in jax.tree.leaves(v))}
    assert whole == {"embed", "exit_norm"} | (
        {"frontend_proj"} if cfg.frontend is not None else set())
    prefix = np.arange(cfg.n_periods) < cfg.exit_period
    for m in jax.tree.leaves(mask["periods"]):
        assert m.ravel().tolist() == prefix.tolist()
    assert not any(bool(m) for m in jax.tree.leaves(
        (mask["rem"], mask["final_norm"])))

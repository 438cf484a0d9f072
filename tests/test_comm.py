"""Quantized flat-buffer communication (core/comm.py): wire roundtrips,
per-slot error bounds, the dequantizing fold's parity with the f32 upload
path, and measured-vs-analytic byte accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core import aggregate, comm, flatten
from repro.core.adapters import LMAdapter
from repro.core.federated import FederatedTrainer
from repro.data.federated import iid_split
from repro.data.synthetic import synthetic_lm


def _tree(seed=0, scale_b=100.0):
    """Leaves at very different magnitudes: per-slot scales must keep the
    error of each leaf proportional to ITS OWN magnitude."""
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(3, 5)).astype(np.float32)),
            "b": jnp.asarray((scale_b * rng.normal(size=(200,)))
                             .astype(np.float32)),
            "c": jnp.asarray(rng.normal(size=(2, 2)).astype(np.float32))}


# ---------------------------------------------------------------------------
# WireSpec validation
# ---------------------------------------------------------------------------

def test_wire_spec_validation():
    assert comm.WireSpec("float32").is_identity
    assert comm.WireSpec("int8").is_quantized
    assert not comm.WireSpec("bfloat16").is_identity
    with pytest.raises(ValueError):
        comm.WireSpec("float16")
    with pytest.raises(ValueError):
        comm.WireSpec("int8", quant_block=0)
    with pytest.raises(ValueError):
        comm.WireSpec("int8", quant_block=96)   # does not divide 128
    with pytest.raises(ValueError):
        comm.WireSpec("int8", quant_block=256)  # exceeds the alignment


def test_fedconfig_wire_validation():
    with pytest.raises(ValueError):
        FedConfig(comm_dtype="float16")
    with pytest.raises(ValueError):
        FedConfig(quant_block=96)
    FedConfig(comm_dtype="int8")
    FedConfig(comm_dtype="bfloat16")


# ---------------------------------------------------------------------------
# Quantize / dequantize
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound_per_group():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 512)).astype(np.float32))
    q, scales = comm.quantize(x, 128)
    assert q.dtype == jnp.int8 and scales.shape == (4, 4)
    back = np.asarray(comm.dequantize(q, scales, 128))
    # error of each element <= half a quantization step of ITS group
    err = np.abs(back - np.asarray(x)).reshape(4, 4, 128)
    step = np.asarray(scales)[..., None]
    assert (err <= 0.5 * step + 1e-7).all()


def test_quantize_zero_group_is_exact_zero():
    x = jnp.zeros((256,))
    q, scales = comm.quantize(x, 128)
    np.testing.assert_array_equal(np.asarray(scales), 0.0)
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(comm.dequantize(q, scales,
                                                             128)), 0.0)


def test_encode_decode_roundtrip_per_slot_bounds():
    """Int8 wire error of every slot is bounded by that slot's own group
    maxima — a 100x louder neighbouring leaf must not leak error in."""
    tree = _tree()
    layout = flatten.build_layout(tree, total_multiple=256)
    flat = flatten.pack(layout, tree)
    spec = comm.WireSpec("int8", 128)
    back = comm.decode(spec, comm.encode(spec, flat))
    flat_np, back_np = np.asarray(flat), np.asarray(back)
    for slot in layout.slots:
        seg = slice(slot.offset, slot.offset + slot.size)
        amax = np.abs(flat_np[seg]).max()
        err = np.abs(back_np[seg] - flat_np[seg]).max()
        assert err <= amax / 127.0 * 0.5 + 1e-7, (slot, err, amax)
    # alignment padding decodes to exactly zero
    live = np.zeros(layout.n_flat, bool)
    for slot in layout.slots:
        live[slot.offset:slot.offset + slot.size] = True
    np.testing.assert_array_equal(back_np[~live], 0.0)


@pytest.mark.parametrize("dtype,rtol", [("float32", 0.0),
                                        ("bfloat16", 1e-2)])
def test_encode_decode_float_wires(dtype, rtol):
    flat = flatten.pack(flatten.build_layout(_tree(), total_multiple=256),
                        _tree())
    spec = comm.WireSpec(dtype)
    back = comm.decode(spec, comm.encode(spec, flat))
    assert back.dtype == jnp.float32
    if rtol == 0.0:
        np.testing.assert_array_equal(np.asarray(back), np.asarray(flat))
    else:
        np.testing.assert_allclose(np.asarray(back), np.asarray(flat),
                                   rtol=rtol, atol=rtol)


def test_encode_handles_non_group_multiple_length():
    spec = comm.WireSpec("int8", 128)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(300,))
                    .astype(np.float32))
    buf = comm.encode(spec, x)
    assert buf.payload.shape == (300,) and buf.scales.shape == (3,)
    back = comm.decode(spec, buf)
    assert back.shape == (300,)
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(back - x))) <= amax / 127.0 * 0.5 + 1e-7


# ---------------------------------------------------------------------------
# Measured byte accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("n", [128, 300, 4096])
def test_wire_bytes_measured_matches_analytic(dtype, n):
    spec = comm.WireSpec(dtype, 128)
    measured = comm.wire_bytes(spec, n)
    assert measured == comm.analytic_wire_bytes(spec, n)
    # and both match a concretely encoded buffer
    buf = comm.encode(spec, jnp.ones((n,)))
    assert comm.buffer_nbytes(buf) == measured


def test_int8_wire_bytes_beat_f32_by_3x():
    """The acceptance ratio at the accounting level: payload/4 + sidecar
    still >= 3x smaller (3.88x at quant_block=128)."""
    spec8 = comm.WireSpec("int8", 128)
    spec32 = comm.WireSpec("float32")
    for n in (2048, 165888):
        assert comm.wire_bytes(spec32, n) / comm.wire_bytes(spec8, n) >= 3.0


# ---------------------------------------------------------------------------
# Upload fold parity: wire vs f32 (all algorithms, NaN/zero-weight devices)
# ---------------------------------------------------------------------------

def _random_cohort(seed, z=8):
    rng = np.random.default_rng(seed)
    cohort = {"a": jnp.asarray(rng.normal(size=(z, 4, 3))
                               .astype(np.float32)),
              "b": jnp.asarray((50.0 * rng.normal(size=(z, 5)))
                               .astype(np.float32))}
    mask = {"a": jnp.asarray(True), "b": jnp.asarray(False)}
    is_simple = jnp.asarray(np.arange(z) < z // 2)
    valid = jnp.ones(z, bool)
    # a NaN device and a zero-weight padding device (both must be gated)
    cohort["a"] = cohort["a"].at[2].set(jnp.nan)
    valid = valid.at[2].set(False)
    valid = valid.at[z - 1].set(False)
    return cohort, mask, is_simple, valid


def _stream_wire(cohort, mask, is_simple, valid, algo, chunk, wire,
                 stream_dtype=jnp.float32, **fold_kw):
    z = jax.tree.leaves(cohort)[0].shape[0]
    template = jax.tree.map(lambda x: x[0], cohort)
    spec = aggregate.EngineSpec(algorithm=algo, mask=mask, wire=wire,
                                stream_dtype=stream_dtype)
    state = aggregate.streaming_init(template, spec)
    for lo in range(0, z, chunk):
        sl = slice(lo, min(lo + chunk, z))
        state = aggregate.streaming_fold(
            state, jax.tree.map(lambda x: x[sl], cohort),
            is_simple[sl], valid[sl], spec, **fold_kw)
    return aggregate.streaming_finalize(state, spec, template)


def _assert_tree_allclose(got, want, rtol, atol):
    if want is None:
        assert got is None
        return
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("algo", ["fedhen", "noside", "decouple"])
@pytest.mark.parametrize("chunk", [3, 8])
def test_int8_upload_fold_matches_f32_fold(algo, chunk):
    cohort, mask, is_simple, valid = _random_cohort(3)
    wire = comm.WireSpec("int8", 128)
    f32_c, f32_host = _stream_wire(cohort, mask, is_simple, valid, algo,
                                   chunk, None)
    q_c, q_host = _stream_wire(cohort, mask, is_simple, valid, algo,
                               chunk, wire)
    # int8 tolerance: |err| <= amax/254 per group; leaves here are O(50)
    _assert_tree_allclose(q_c, f32_c, rtol=2e-2, atol=0.3)
    _assert_tree_allclose(q_host, f32_host, rtol=2e-2, atol=0.3)
    for leaf in jax.tree.leaves(q_c):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.parametrize("algo", ["fedhen", "decouple"])
def test_int8_fold_kernel_path_matches_cpu_path(algo):
    """The dequantizing kernel (interpret mode) and the per-leaf CPU ref
    produce the same accumulators: identical quantization grouping."""
    cohort, mask, is_simple, valid = _random_cohort(4)
    wire = comm.WireSpec("int8", 128)
    cpu_c, cpu_host = _stream_wire(cohort, mask, is_simple, valid, algo,
                                   3, wire)
    ker_c, ker_host = _stream_wire(cohort, mask, is_simple, valid, algo,
                                   3, wire, force_pallas_interpret=True)
    _assert_tree_allclose(ker_c, cpu_c, rtol=1e-5, atol=1e-6)
    _assert_tree_allclose(ker_host, cpu_host, rtol=1e-5, atol=1e-6)


def test_bf16_wire_fold_rides_stream_dtype():
    cohort, mask, is_simple, valid = _random_cohort(5)
    wire = comm.WireSpec("bfloat16")
    got_c, _ = _stream_wire(cohort, mask, is_simple, valid, "fedhen", 4,
                            wire)
    want_c, _ = _stream_wire(cohort, mask, is_simple, valid, "fedhen", 4,
                             None, stream_dtype=jnp.bfloat16)
    _assert_tree_allclose(got_c, want_c, rtol=1e-6, atol=1e-7)


_DELTA_WIRES = {
    "topk-int8": comm.WireSpec("int8", 64, topk_frac=0.25),
    "dense-int8": comm.WireSpec("int8", 64, stochastic=True),
    "dense-bf16": comm.WireSpec("bfloat16", stochastic=True),
}


def _sparse_chunk(wire, base, deltas, k):
    """Encode (Z, n_flat) deltas for ``wire`` as the fold's SparseChunk:
    index+value rows under top-k, dense payload rows otherwise."""
    if wire.is_sparse:
        buf = jax.vmap(lambda v: comm.sparse_encode(wire, v, k))(deltas)
        return aggregate.SparseChunk(base, buf.payload, buf.scales,
                                     buf.indices)
    buf = comm.encode(wire, deltas)
    return aggregate.SparseChunk(base, buf.payload, buf.scales, None)


@pytest.mark.parametrize("algo", ["fedhen", "decouple"])
@pytest.mark.parametrize("payload", list(_DELTA_WIRES))
def test_delta_fold_kernel_path_matches_cpu_path(algo, payload):
    """Delta-mode uploads fold the same on the kernel path (interpret
    mode) as on the CPU path, through each payload branch of the delta
    fold: top-k int8 index+value rows (scatter fold), dense int8 rows
    (dequantizing accumulate) and dense bf16 rows (plain accumulate) —
    plus decouple's second accumulator — with a NaN device and a
    zero-weight device in the cohort."""
    cohort, mask, is_simple, valid = _random_cohort(6)
    wire = _DELTA_WIRES[payload]
    template = jax.tree.map(lambda x: x[0], cohort)
    layout = flatten.layout_of(template, total_multiple=512)
    spec = aggregate.EngineSpec(
        algorithm=algo, mask=mask, layout=layout, block_n=512, wire=wire,
        flat_mask=flatten.pack_mask(layout, mask))
    base = flatten.pack(layout, template)  # client 0: a finite broadcast
    deltas = flatten.pack_stacked(layout, cohort) - base[None]
    sp = _sparse_chunk(wire, base, deltas,
                       comm.topk_count(wire, layout.n_flat))

    def fold(interpret):
        state = aggregate.streaming_init(template, spec)
        for lo in range(0, is_simple.shape[0], 3):
            sl = slice(lo, lo + 3)
            rows = aggregate.SparseChunk(
                base, sp.values[sl],
                None if sp.scales is None else sp.scales[sl],
                None if sp.indices is None else sp.indices[sl])
            state = aggregate.streaming_fold(
                state, None, is_simple[sl], valid[sl], spec,
                sparse_chunk=rows, force_pallas_interpret=interpret)
        return aggregate.streaming_finalize(state, spec, template)

    cpu_c, cpu_host = fold(False)
    ker_c, ker_host = fold(True)
    _assert_tree_allclose(ker_c, cpu_c, rtol=1e-5, atol=1e-5)
    _assert_tree_allclose(ker_host, cpu_host, rtol=1e-5, atol=1e-5)
    for leaf in jax.tree.leaves(ker_c):
        assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# Broadcast roundtrip
# ---------------------------------------------------------------------------

def test_decode_tree_rejects_mismatched_template():
    tree = _tree()
    layout = flatten.build_layout(tree, total_multiple=256)
    spec = comm.WireSpec("float32")
    buf = comm.encode_tree(spec, layout, tree)
    out = comm.decode_tree(spec, layout, buf, template=tree)
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError):
        comm.decode_tree(spec, layout, buf, template={"x": tree["a"]})


def test_broadcast_roundtrip_identity_for_f32():
    tree = _tree()
    layout = flatten.build_layout(tree, total_multiple=256)
    out = comm.broadcast_roundtrip(comm.WireSpec("float32"), layout, tree)
    assert out is tree        # no ops traced at all


def test_broadcast_roundtrip_int8_bounds():
    tree = _tree()
    layout = flatten.build_layout(tree, total_multiple=256)
    out = comm.broadcast_roundtrip(comm.WireSpec("int8", 128), layout, tree)
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert got.dtype == want.dtype
        amax = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= amax / 127.0


# ---------------------------------------------------------------------------
# Trainer integration: wire rounds + measured accounting
# ---------------------------------------------------------------------------

TINY = ModelConfig(n_layers=4, d_model=32, n_heads=2, n_kv_heads=2,
                   d_ff=64, vocab_size=64, pattern=(LayerSpec("attn"),),
                   exit_layer=2, compute_dtype="float32")


def _make_trainer(algorithm="fedhen", **fed_kw):
    fed_kw.setdefault("cohort_chunk", 2)
    fed = FedConfig(n_devices=8, n_simple=4, participation=0.5, rounds=3,
                    local_epochs=1, lr=0.1, batch_size=4,
                    algorithm=algorithm, seed=0, **fed_kw)
    data = synthetic_lm(32, 16, TINY.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    return FederatedTrainer(LMAdapter(TINY), fed, shards)


def test_trainer_measured_equals_analytic_for_f32_wire():
    """The f32 wire bills exactly the paper's analytic accounting (true
    element counts x 4 bytes, down+up) — padding is never billed."""
    tr = _make_trainer()
    assert tr.bytes_per_round == tr.analytic_bytes_per_round()
    assert tr.bytes_down_per_round == tr.bytes_up_per_round
    assert tr.bytes_per_round == (tr.bytes_down_per_round
                                  + tr.bytes_up_per_round)


def test_trainer_measured_bytes_monotone_and_gated():
    f32 = _make_trainer()
    bf16 = _make_trainer(comm_dtype="bfloat16")
    int8 = _make_trainer(comm_dtype="int8")
    assert int8.bytes_per_round < bf16.bytes_per_round < f32.bytes_per_round
    assert bf16.bytes_per_round == f32.bytes_per_round / 2
    assert f32.bytes_per_round / int8.bytes_per_round >= 3.0


@pytest.mark.parametrize("algorithm", ["fedhen", "decouple"])
def test_int8_wire_round_stays_near_f32_round(algorithm):
    """One full round through the quantized broadcast + dequantizing
    upload fold lands close to the f32 round and stays finite."""
    ref = _make_trainer(algorithm)
    tr = _make_trainer(algorithm, comm_dtype="int8")
    m_ref = ref.run_round()
    m = tr.run_round()
    assert np.isfinite(m["loss_complex"])
    assert m["n_valid"] == m_ref["n_valid"]
    for a, b in zip(jax.tree.leaves(tr.server.complex),
                    jax.tree.leaves(ref.server.complex)):
        delta = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
        assert delta < 0.05, delta


def test_total_bytes_accumulate_per_direction():
    tr = _make_trainer(comm_dtype="int8")
    tr.run_round()
    tr.run_round()
    assert tr.total_bytes_down == 2 * tr.bytes_down_per_round
    assert tr.total_bytes_up == 2 * tr.bytes_up_per_round
    assert tr.total_bytes == tr.total_bytes_down + tr.total_bytes_up
    test = {"tokens": jnp.asarray(synthetic_lm(8, 16, TINY.vocab_size,
                                               seed=9)["tokens"])}
    ev = tr.evaluate(test)
    assert ev["mbytes"] == pytest.approx(ev["mbytes_down"]
                                         + ev["mbytes_up"])


# ---------------------------------------------------------------------------
# Wire v2: validation, stochastic rounding, top-k codec, upload accounting
# ---------------------------------------------------------------------------

def test_wire_spec_v2_validation():
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="topk_frac must be in"):
            comm.WireSpec("float32", topk_frac=bad)
    with pytest.raises(ValueError, match="stochastic rounding requires"):
        comm.WireSpec("float32", stochastic=True)
    with pytest.raises(ValueError, match="error_feedback requires"):
        comm.WireSpec("float32", error_feedback=True)
    # lossy paths make all three legal
    comm.WireSpec("int8", stochastic=True, error_feedback=True)
    comm.WireSpec("bfloat16", stochastic=True)
    comm.WireSpec("float32", topk_frac=0.5, error_feedback=True)


def test_uses_deltas_gate():
    """uses_deltas is THE switch that moves uploads off the pre-existing
    traced program — every pre-v2 config must keep it False."""
    for dtype in ("float32", "bfloat16", "int8"):
        assert not comm.WireSpec(dtype).uses_deltas
    assert comm.WireSpec("float32", topk_frac=0.25).uses_deltas
    assert comm.WireSpec("int8", stochastic=True).uses_deltas
    assert comm.WireSpec("int8", error_feedback=True).uses_deltas


def test_stochastic_encode_is_seeded_and_reproducible():
    spec = comm.WireSpec("int8", 128, stochastic=True)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256,))
                    .astype(np.float32))
    k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    a = comm.encode(spec, x, key=k1)
    b = comm.encode(spec, x, key=k1)
    c = comm.encode(spec, x, key=k2)
    np.testing.assert_array_equal(np.asarray(a.payload),
                                  np.asarray(b.payload))
    assert not np.array_equal(np.asarray(a.payload), np.asarray(c.payload))


def test_encode_ignores_key_on_deterministic_spec():
    """The broadcast path may thread a key by accident — a non-stochastic
    spec must stay bit-identical to the keyless encode."""
    spec = comm.WireSpec("int8", 128)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(256,))
                    .astype(np.float32))
    a = comm.encode(spec, x)
    b = comm.encode(spec, x, key=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(a.payload),
                                  np.asarray(b.payload))


def test_topk_count_lane_aligned():
    spec = comm.WireSpec("int8", 128, topk_frac=0.1)
    for n in (128, 1000, 4096, 165888):
        k = comm.topk_count(spec, n)
        assert k % 128 == 0 and k >= n * 0.1
    assert comm.topk_count(comm.WireSpec("int8"), 300) == 300  # dense
    # tiny populations still ship at least one lane
    assert comm.topk_count(spec, 64) == 128


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_sparse_encode_keeps_the_k_largest(dtype):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1024,)).astype(np.float32))
    spec = comm.WireSpec(dtype, 128, topk_frac=0.25)
    k = comm.topk_count(spec, 1024)
    buf = comm.sparse_encode(spec, x, k)
    assert buf.indices.dtype == jnp.int32
    idx = np.asarray(buf.indices)
    assert (np.diff(idx) > 0).all()          # sorted, distinct
    want = set(np.argsort(-np.abs(np.asarray(x)))[:k].tolist())
    assert set(idx.tolist()) == want
    # decoded values sit within the dense wire's error of the kept entries
    vals = np.asarray(comm.sparse_decode_values(spec, buf))
    kept = np.asarray(x)[idx]
    tol = {"float32": 0.0, "bfloat16": 0.05,
           "int8": np.abs(kept).max() / 127.0}[dtype]
    assert np.abs(vals - kept).max() <= tol + 1e-7


def test_sparse_decode_scatters_only_kept_positions():
    x = jnp.asarray(np.random.default_rng(8).normal(size=(512,))
                    .astype(np.float32))
    spec = comm.WireSpec("float32", topk_frac=0.25)
    k = comm.topk_count(spec, 512)
    buf = comm.sparse_encode(spec, x, k)
    dense = np.asarray(comm.sparse_decode(spec, buf, 512))
    idx = np.asarray(buf.indices)
    np.testing.assert_array_equal(dense[idx], np.asarray(x)[idx])
    dropped = np.setdiff1d(np.arange(512), idx)
    np.testing.assert_array_equal(dense[dropped], 0.0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("frac", [1.0, 0.5, 1 / 16])
def test_wire_bytes_up_measured_matches_analytic(dtype, frac):
    spec = comm.WireSpec(dtype, 128, topk_frac=frac)
    for n in (2048, 165888):
        measured = comm.wire_bytes_up(spec, n)
        assert measured == comm.analytic_wire_bytes_up(spec, n)
        if frac == 1.0:
            assert measured == comm.wire_bytes(spec, n)
        else:
            # and both match a concretely encoded sparse buffer
            k = comm.topk_count(spec, n)
            buf = comm.sparse_encode(spec, jnp.ones((n,)), k)
            assert comm.sparse_buffer_nbytes(buf) == measured


def test_int8_topk_upload_beats_f32_by_10x():
    """The tentpole's upload-direction acceptance ratio at the accounting
    level: int8 payload + scales + int32 indices at topk_frac=1/16."""
    spec = comm.WireSpec("int8", 128, topk_frac=1 / 16,
                         stochastic=True, error_feedback=True)
    f32 = comm.WireSpec("float32")
    for n in (16384, 165888):
        ratio = comm.wire_bytes_up(f32, n) / comm.wire_bytes_up(spec, n)
        assert ratio >= 10.0, ratio


# ---------------------------------------------------------------------------
# Scatter-fold kernel: interpret-mode parity with the CPU reference
# ---------------------------------------------------------------------------

def _scatter_case(seed, n=512, z=4, k=128, quant_block=64):
    rng = np.random.default_rng(seed)
    acc = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    idx = np.stack([np.sort(rng.choice(n, size=k, replace=False))
                    for _ in range(z)]).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(z, k)).astype(np.float32))
    scales = jnp.asarray(rng.uniform(0.5, 2.0, size=(z, k // quant_block))
                         .astype(np.float32))
    mask = jnp.asarray(rng.random(n) < 0.5)
    w_m = jnp.asarray(rng.uniform(0, 1, size=(z,)).astype(np.float32))
    w_r = jnp.asarray(rng.uniform(0, 1, size=(z,)).astype(np.float32))
    return acc, vals, scales, jnp.asarray(idx), mask, w_m, w_r


@pytest.mark.parametrize("with_scales", [True, False])
def test_scatter_fold_kernel_matches_ref(with_scales):
    from repro.kernels.masked_agg import ops as agg_ops
    acc, vals, scales, idx, mask, w_m, w_r = _scatter_case(11)
    sc = scales if with_scales else None
    ref = agg_ops.masked_scatter_acc_ref(acc, vals, sc, idx, mask,
                                         w_m, w_r, quant_block=64)
    ker = agg_ops.masked_scatter_acc_pallas(acc, vals, sc, idx, mask,
                                            w_m, w_r, quant_block=64,
                                            block_n=256, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_scatter_bf16_split_is_exact():
    """The scatter kernel contracts each f32 term as three bf16 parts;
    they must sum back to the term bit for bit, at any sign and at any
    magnitude whose parts stay normal (subnormals flush to zero), or the
    one-hot selection rounds the folded values."""
    from repro.kernels.masked_agg import kernel as K
    rng = np.random.default_rng(5)
    x = np.concatenate([rng.normal(size=4096) * 10.0 ** rng.integers(
        -20, 20, size=4096), [0.0, -0.0, 1.0 + 2 ** -23, -3.4e38]])
    x = jnp.asarray(x.astype(np.float32))[None, :]
    parts = K._bf16_split3(x)
    assert parts.dtype == jnp.bfloat16 and parts.shape == (3, x.shape[1])
    p = np.asarray(parts.astype(jnp.float32), np.float64)
    np.testing.assert_array_equal(p[0] + p[1] + p[2],
                                  np.asarray(x[0], np.float64))


def test_scatter_fold_kernel_tiles_k_and_n():
    """Several k tiles (Z*k = 1920 entries padded to 4 x 512), positions
    colliding across clients, and an N that is no block multiple: the
    grid's k axis must add every tile into the resident block once."""
    from repro.kernels.masked_agg import ops as agg_ops
    acc, vals, scales, idx, mask, w_m, w_r = _scatter_case(
        14, n=1000, z=5, k=384, quant_block=128)
    w_r = w_r.at[2].set(0.0)            # a simple client: M only
    ref = agg_ops.masked_scatter_acc_ref(acc, vals, scales, idx, mask,
                                         w_m, w_r, quant_block=128)
    ker = agg_ops.masked_scatter_acc_pallas(acc, vals, scales, idx, mask,
                                            w_m, w_r, quant_block=128,
                                            block_n=256, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_scatter_fold_gates_nan_rows():
    """A NaN row at weight 0 (both masks) must leave the accumulator
    untouched — the kernel gates BEFORE the multiply."""
    from repro.kernels.masked_agg import ops as agg_ops
    acc, vals, scales, idx, mask, w_m, w_r = _scatter_case(12)
    vals = vals.at[1].set(jnp.nan)
    w_m = w_m.at[1].set(0.0)
    w_r = w_r.at[1].set(0.0)
    for fn, kw in ((agg_ops.masked_scatter_acc_ref, {}),
                   (agg_ops.masked_scatter_acc_pallas,
                    {"block_n": 256, "interpret": True})):
        out = fn(acc, vals, scales, idx, mask, w_m, w_r,
                 quant_block=64, **kw)
        assert np.isfinite(np.asarray(out)).all()


def test_scatter_fold_dequantizes_like_reference():
    """scales fold as a per-group multiply of the values — pin against
    an explicit dense dequantize + scatter + weighted sum."""
    from repro.kernels.masked_agg import ops as agg_ops
    acc, vals, scales, idx, mask, w_m, w_r = _scatter_case(13)
    got = agg_ops.masked_scatter_acc_ref(acc, vals, scales, idx, mask,
                                         w_m, w_r, quant_block=64)
    want = np.asarray(acc).copy()
    for z in range(vals.shape[0]):
        deq = np.asarray(vals[z]).reshape(-1, 64) \
            * np.asarray(scales[z])[:, None]
        deq = deq.reshape(-1)
        w_at = np.where(np.asarray(mask)[np.asarray(idx[z])],
                        float(w_m[z]), float(w_r[z]))
        np.add.at(want, np.asarray(idx[z]), deq * w_at)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Trainer integration: upload-direction accounting under the v2 wire
# ---------------------------------------------------------------------------

def test_trainer_bills_sparse_uploads_separately():
    dense = _make_trainer(comm_dtype="int8")
    sparse = _make_trainer(comm_dtype="int8", topk_frac=1 / 16,
                           stochastic_rounding=True, error_feedback=True)
    assert sparse.bytes_down_per_round == dense.bytes_down_per_round
    assert sparse.bytes_up_per_round < dense.bytes_up_per_round
    f32 = _make_trainer()
    assert f32.bytes_up_per_round / sparse.bytes_up_per_round >= 10.0


def test_auto_chunk_budgets_int8_sidecar():
    """cohort_chunk="auto" under the int8 wire must budget the scale
    sidecar: the int8 stream copy is cheaper than f32, so the resolved
    chunk can only grow — and stream_bytes includes the sidecar."""
    layout = flatten.build_layout(LMAdapter(TINY).init(
        jax.random.PRNGKey(0)), total_multiple=2048)
    b8 = layout.stream_bytes(jnp.int8, quant_block=128)
    assert b8 == layout.n_flat + layout.n_flat // 128 * 4
    f32 = _make_trainer(cohort_chunk="auto", agg_memory_budget_mb=1.0)
    int8 = _make_trainer(cohort_chunk="auto", agg_memory_budget_mb=1.0,
                         comm_dtype="int8")
    assert int8.cohort_chunk >= f32.cohort_chunk

"""Property-based tests (hypothesis) for the system's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import aggregate, comm, flatten, masking
from repro.models import common

jax.config.update("jax_platform_name", "cpu")

_settings = settings(max_examples=25, deadline=None,
                     derandomize=True)


# ---------------------------------------------------------------------------
# Server aggregation (Alg. 1) invariants
# ---------------------------------------------------------------------------

@_settings
@given(z=st.integers(2, 12), n=st.integers(1, 40), seed=st.integers(0, 999))
def test_fedhen_update_is_convex_combination(z, n, seed):
    """Every output coordinate lies in the convex hull of the valid
    cohort's coordinates (means can't extrapolate)."""
    rng = np.random.default_rng(seed)
    cohort = {"w": jnp.asarray(rng.normal(size=(z, n)).astype(np.float32))}
    mask = {"w": jnp.asarray(rng.random(n) < 0.5)}
    is_simple = jnp.asarray(rng.random(z) < 0.5)
    valid = jnp.asarray(np.ones(z, bool))
    if not bool(jnp.any(~is_simple)):
        is_simple = is_simple.at[0].set(False)
    out = aggregate.fedhen_server_update(cohort, is_simple, valid, mask)
    lo = jnp.min(cohort["w"], axis=0) - 1e-5
    hi = jnp.max(cohort["w"], axis=0) + 1e-5
    assert bool(jnp.all((out["w"] >= lo) & (out["w"] <= hi)))


@_settings
@given(z=st.integers(2, 10), seed=st.integers(0, 999))
def test_consensus_is_fixed_point(z, seed):
    """If every client returns the same model, the server keeps it
    (for every algorithm)."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(7,)).astype(np.float32)
    cohort = {"w": jnp.asarray(np.tile(w, (z, 1)))}
    mask = {"w": jnp.asarray(np.array([1, 1, 1, 0, 0, 0, 0], bool))}
    is_simple = jnp.asarray(rng.random(z) < 0.5)
    if not bool(jnp.any(~is_simple)):
        is_simple = is_simple.at[0].set(False)
    valid = jnp.ones(z, bool)
    out = aggregate.fedhen_server_update(cohort, is_simple, valid, mask)
    np.testing.assert_allclose(out["w"], w, rtol=1e-6)


@_settings
@given(z=st.integers(3, 10), bad=st.integers(0, 2), seed=st.integers(0, 99))
def test_invalid_devices_never_contribute(z, bad, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(z, 5)).astype(np.float32)
    x[:bad] = np.inf
    cohort = {"w": jnp.asarray(x)}
    mask = {"w": jnp.asarray(np.ones(5, bool))}
    is_simple = jnp.zeros(z, bool)
    valid = jax.vmap(masking.tree_isfinite)(cohort)
    out = aggregate.fedhen_server_update(cohort, is_simple, valid, mask)
    assert np.isfinite(np.asarray(out["w"])).all()
    np.testing.assert_allclose(out["w"], x[bad:].mean(0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Flat packing layout invariants
# ---------------------------------------------------------------------------

_shapes = st.lists(
    st.lists(st.integers(1, 6), min_size=0, max_size=3).map(tuple),
    min_size=1, max_size=6)


@_settings
@given(shapes=_shapes, seed=st.integers(0, 999),
       block=st.sampled_from([128, 256, 1024]))
def test_pack_unpack_roundtrip(shapes, seed, block):
    """unpack(pack(tree)) == tree exactly (f32), for any tree shape mix
    and any kernel block size — the flat layout loses nothing."""
    rng = np.random.default_rng(seed)
    tree = {f"l{i}": jnp.asarray(rng.normal(size=s).astype(np.float32))
            for i, s in enumerate(shapes)}
    layout = flatten.build_layout(tree, total_multiple=block)
    assert layout.n_flat % block == 0
    flat = flatten.pack(layout, tree)
    back = flatten.unpack(layout, flat)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@_settings
@given(shapes=_shapes, z=st.integers(1, 5), seed=st.integers(0, 999),
       algorithm=st.sampled_from(["fedhen", "decouple"]))
def test_flat_fold_matches_one_shot(shapes, z, seed, algorithm):
    """One flat fold == the one-shot server update for random trees,
    masks and validity (the packed buffer and bitvector preserve the
    masked-mean semantics per element)."""
    rng = np.random.default_rng(seed)
    cohort = {f"l{i}": jnp.asarray(
        rng.normal(size=(z,) + s).astype(np.float32))
        for i, s in enumerate(shapes)}
    mask = {f"l{i}": jnp.asarray(bool(rng.integers(2)))
            for i in range(len(shapes))}
    is_simple = jnp.asarray(rng.integers(2, size=z).astype(bool))
    valid = jnp.asarray(rng.integers(2, size=z).astype(bool))
    template = jax.tree.map(lambda x: x[0], cohort)
    spec = aggregate.EngineSpec(algorithm=algorithm, mask=mask)
    state = aggregate.streaming_fold(
        aggregate.streaming_init(template, spec), cohort, is_simple,
        valid, spec)
    got = aggregate.streaming_finalize(state, spec, template)
    if algorithm == "decouple":
        host, new = aggregate.decouple_server_update(cohort, is_simple,
                                                     valid, mask)
        want = (new, host)
    else:
        want = (aggregate.fedhen_server_update(cohort, is_simple, valid,
                                               mask), None)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# Wire v2 invariants (encode/decode, stochastic rounding, top-k, EF)
# ---------------------------------------------------------------------------

_qblocks = st.sampled_from([16, 32, 64, 128])   # divisors of the lane width


def _flat(seed, n, scale=10.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray((scale * rng.normal(size=(n,))).astype(np.float32))


@_settings
@given(dtype=st.sampled_from(["float32", "bfloat16", "int8"]),
       qb=_qblocks, groups=st.integers(1, 6), seed=st.integers(0, 999))
def test_wire_roundtrip_error_bound(dtype, qb, groups, seed):
    """decode(encode(x)) stays within the wire's per-group error bound:
    exact for f32, half a mantissa step for bf16 (relative), half a
    quantization step of the element's OWN group for int8."""
    x = _flat(seed, groups * qb)
    spec = comm.WireSpec(dtype, qb)
    back = np.asarray(comm.decode(spec, comm.encode(spec, x)))
    x_np = np.asarray(x)
    if dtype == "float32":
        np.testing.assert_array_equal(back, x_np)
    elif dtype == "bfloat16":
        np.testing.assert_allclose(back, x_np, rtol=2 ** -8, atol=1e-30)
    else:
        err = np.abs(back - x_np).reshape(groups, qb)
        step = np.abs(x_np).reshape(groups, qb).max(axis=1) / 127.0
        assert (err <= 0.5 * step[:, None] + 1e-7).all()


@_settings
@given(dtype=st.sampled_from(["bfloat16", "int8"]), qb=_qblocks,
       seed=st.integers(0, 99))
def test_stochastic_rounding_is_unbiased(dtype, qb, seed):
    """The mean of decode(encode(x, key)) over many seeds converges on x
    (round-to-nearest would sit a deterministic half-step away).  The
    int8 bound: averaging 96 uniform [0, 1) draws has std
    step/sqrt(12*96) ~ 0.03 step — 0.25 step is ~8.5 sigma, far outside
    chance but far inside round-to-nearest's worst case (0.5 step);
    bf16's relative step drives its bound the same way."""
    x = _flat(seed, 2 * qb)
    spec = comm.WireSpec(dtype, qb, stochastic=True)
    n_keys = 96
    keys = jax.vmap(jax.random.PRNGKey)(
        jnp.arange(n_keys) + seed * n_keys)
    dec = jax.vmap(
        lambda k: comm.decode(spec, comm.encode(spec, x, key=k)))(keys)
    mean = np.asarray(jnp.mean(dec, axis=0))
    x_np = np.asarray(x)
    if dtype == "int8":
        step = np.abs(x_np).reshape(2, qb).max(axis=1) / 127.0
        tol = 0.25 * np.repeat(step, qb) + 1e-7
    else:
        tol = 0.25 * np.abs(x_np) * 2 ** -8 * 256 + 1e-6
    assert (np.abs(mean - x_np) <= tol).all()


@_settings
@given(seed=st.integers(0, 999), n_lanes=st.integers(2, 8),
       frac_kept=st.integers(1, 7))
def test_topk_payload_is_exactly_the_k_largest(seed, n_lanes, frac_kept):
    """On the f32 wire the sparse payload reproduces the k largest-|x|
    entries bit for bit, and nothing else ships."""
    n = n_lanes * 128
    x = _flat(seed, n)
    spec = comm.WireSpec("float32", topk_frac=frac_kept / 8.0)
    k = comm.topk_count(spec, n)
    buf = comm.sparse_encode(spec, x, k)
    idx = np.asarray(buf.indices)
    x_np = np.asarray(x)
    want = np.sort(np.abs(x_np))[::-1][:k]
    np.testing.assert_array_equal(
        np.sort(np.abs(np.asarray(buf.payload)))[::-1], want)
    np.testing.assert_array_equal(np.asarray(buf.payload), x_np[idx])
    dense = np.asarray(comm.sparse_decode(spec, buf, n))
    np.testing.assert_array_equal(dense[idx], x_np[idx])
    assert np.count_nonzero(dense) <= k


@_settings
@given(dtype=st.sampled_from(["float32", "bfloat16", "int8"]),
       frac_kept=st.integers(1, 8), seed=st.integers(0, 999),
       stochastic=st.booleans())
def test_error_feedback_conserves_the_delta(dtype, frac_kept, seed,
                                            stochastic):
    """The EF update's conservation law: whatever the wire drops stays in
    the residual — ``residual' + decode(payload) == delta + residual``
    for every dtype, sparsity and rounding mode.  This is the invariant
    that makes compressed SGD converge (Karimireddy et al. 2019)."""
    if stochastic and dtype == "float32":
        stochastic = False               # invalid combination
    n = 512
    d = _flat(seed, n)
    r = _flat(seed + 10_000, n, scale=3.0)
    spec = comm.WireSpec(dtype, 64, topk_frac=frac_kept / 8.0,
                         stochastic=stochastic, error_feedback=True)
    d_in = d + r
    key = jax.random.PRNGKey(seed)
    if spec.is_sparse:
        k = comm.topk_count(spec, n)
        buf = comm.sparse_encode(spec, d_in, k, key=key)
        vals = comm.sparse_decode_values(spec, buf)
        r_new = d_in.at[buf.indices].add(-vals)
        decoded = comm.sparse_decode(spec, buf, n)
    else:
        buf = comm.encode(spec, d_in, key=key)
        decoded = comm.decode(spec, buf)
        r_new = d_in - decoded
    got = np.asarray(r_new + decoded)
    want = np.asarray(d_in)
    # float cancellation only: (a - v) + v vs a, ~1 ulp of the magnitudes
    tol = 1e-5 * max(1.0, np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)


# ---------------------------------------------------------------------------
# Cross-entropy invariants
# ---------------------------------------------------------------------------

@_settings
@given(b=st.integers(1, 4), s=st.integers(1, 8), v=st.integers(2, 33),
       seed=st.integers(0, 999))
def test_ce_matches_naive(b, s, v, seed):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(b, s, v)).astype(np.float32) * 3)
    labels = jnp.asarray(rng.integers(0, v, size=(b, s)))
    got = common.softmax_cross_entropy(logits, labels)
    lp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))
    # fp32: one-hot-contraction vs take_along_axis differ by a few ulp
    np.testing.assert_allclose(float(got), float(want), rtol=5e-5,
                               atol=1e-6)


@_settings
@given(shift=st.floats(-50, 50), seed=st.integers(0, 99))
def test_ce_shift_invariance(shift, seed):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(2, 3, 17)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 17, size=(2, 3)))
    a = common.softmax_cross_entropy(logits, labels)
    b = common.softmax_cross_entropy(logits + shift, labels)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Sharding policy invariants
# ---------------------------------------------------------------------------

@_settings
@given(dims=st.lists(st.sampled_from([1, 3, 8, 16, 64, 256]),
                     min_size=2, max_size=4),
       mode=st.sampled_from(["auto", "replicate", "seq2d", "dp2d",
                             "head_dim"]))
def test_policy_specs_always_valid(dims, mode):
    """Resolved specs never reuse a mesh axis and always divide the dim."""
    import os
    if jax.device_count() < 4:
        # policy math is device-independent; build a fake mesh via
        # make_mesh on available devices if possible
        return
    from repro.configs.base import ModelConfig
    from repro.launch.sharding import MeshPolicy, _axis_size
    mesh = jax.make_mesh(
        (2, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = ModelConfig(attn_shard=mode, n_heads=4, n_kv_heads=4)
    pol = MeshPolicy(mesh, cfg)
    names = ["batch", "seq", "heads", "ffn"][:len(dims)]
    spec = pol.spec(tuple(dims), tuple(names))
    used = []
    for dim, ax in zip(dims, tuple(spec)):
        if ax is None:
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        assert dim % _axis_size(mesh, axes) == 0
        for a in axes:
            assert a not in used
            used.append(a)

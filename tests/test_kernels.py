"""Pallas kernel validation: interpret=True vs pure-jnp oracles, swept over
shapes and dtypes (deliverable c)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.masked_agg.kernel import (masked_agg_acc_deq_pallas,
                                             masked_agg_acc_pallas)
from repro.kernels.masked_agg.ref import (masked_agg_acc_deq_ref,
                                          masked_agg_acc_ref,
                                          masked_agg_ref)
from repro.kernels.rglru_scan.kernel import lru_scan_pallas
from repro.kernels.rglru_scan.ref import lru_scan_ref


# ---------------------------------------------------------------------------
# masked_agg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("z,n", [(4, 256), (10, 2048), (7, 5000), (32, 999)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("acc_init", ["zeros", "random"])
def test_masked_agg_acc_sweep(z, n, dtype, acc_init):
    """The fold's kernel: out = acc + masked sum, f32 accumulation
    regardless of the streaming dtype, against the one-shot masked sum
    (``masked_agg_ref``) and the row-streamed accumulating reference."""
    key = jax.random.PRNGKey(z * 7 + n)
    ks = jax.random.split(key, 5)
    acc = (jnp.zeros((n,), jnp.float32) if acc_init == "zeros"
           else jax.random.normal(ks[0], (n,), jnp.float32))
    x = jax.random.normal(ks[1], (z, n), dtype)
    mask = jax.random.bernoulli(ks[2], 0.5, (n,))
    w_m = jax.nn.softmax(jax.random.normal(ks[3], (z,)))
    w_rest = jax.nn.softmax(jax.random.normal(ks[4], (z,)))
    got = masked_agg_acc_pallas(acc, x, mask, w_m, w_rest, block_n=1024,
                                interpret=True)
    assert got.dtype == jnp.float32
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    want = acc + masked_agg_ref(x.astype(jnp.float32), mask, w_m, w_rest)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    want = masked_agg_acc_ref(acc, x, mask, w_m, w_rest)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_masked_agg_acc_folds_match_one_shot():
    """Chained accumulating folds over chunks == one masked_agg over the
    whole cohort plus the starting accumulator."""
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (8, 512))
    mask = jax.random.bernoulli(ks[1], 0.5, (512,))
    w_m = jnp.arange(1.0, 9.0) / 8
    w_rest = jnp.ones((8,)) / 8
    acc = jnp.zeros((512,), jnp.float32)
    for lo in range(0, 8, 2):
        acc = masked_agg_acc_pallas(acc, x[lo:lo + 2], mask,
                                    w_m[lo:lo + 2], w_rest[lo:lo + 2],
                                    block_n=256, interpret=True)
    want = masked_agg_ref(x, mask, w_m, w_rest)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("acc_init", ["zeros", "nonzero"])
def test_masked_agg_acc_nan_gating(dtype, acc_init):
    """A NaN client at weight 0 is gated before the multiply, whatever
    the stream dtype and whatever the accumulator already holds."""
    acc = (jnp.zeros((2,), jnp.float32) if acc_init == "zeros"
           else jnp.array([1.0, 2.0], jnp.float32))
    x = jnp.array([[jnp.nan, 1.0], [2.0, 3.0]], dtype)
    mask = jnp.array([True, False])
    got = masked_agg_acc_pallas(acc, x, mask, jnp.array([0.0, 1.0]),
                                jnp.array([0.0, 1.0]), interpret=True)
    np.testing.assert_allclose(got, np.asarray(acc) + [2.0, 3.0])


def test_masked_agg_acc_rejects_non_f32_accumulator():
    with pytest.raises(ValueError):
        masked_agg_acc_pallas(jnp.zeros((4,), jnp.bfloat16),
                              jnp.zeros((2, 4)), jnp.zeros((4,), bool),
                              jnp.ones((2,)), jnp.ones((2,)),
                              interpret=True)


def test_masked_agg_acc_aliases_accumulator():
    """The jitted accumulating kernel declares the acc->out alias: with
    donation, XLA reuses the accumulator buffer (in-place update)."""
    n = 512
    fn = jax.jit(
        lambda acc, x, m, wm, wr: masked_agg_acc_pallas(
            acc, x, m, wm, wr, block_n=256, interpret=True),
        donate_argnums=(0,))
    acc = jnp.ones((n,), jnp.float32)
    x = jnp.ones((3, n))
    out = fn(acc, x, jnp.ones((n,), bool), jnp.ones((3,)) / 3,
             jnp.ones((3,)) / 3)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    if jax.default_backend() != "cpu":   # CPU ignores donation
        assert acc.is_deleted()  # the donated input buffer was consumed


@pytest.mark.parametrize("z,n,quant_block", [(4, 512, 128), (7, 2048, 64),
                                             (3, 1024, 32)])
def test_masked_agg_acc_deq_sweep(z, n, quant_block):
    """Dequantizing accumulate (the quantized-upload fold's kernel):
    interpret mode == the XLA ref, for int8 payload + per-group scales."""
    from repro.core import comm
    key = jax.random.PRNGKey(z * 13 + n)
    ks = jax.random.split(key, 5)
    acc = jax.random.normal(ks[0], (n,), jnp.float32)
    x = jax.random.normal(ks[1], (z, n)) * 10.0
    q, scales = comm.quantize(x, quant_block)
    mask = jax.random.bernoulli(ks[2], 0.5, (n,))
    w_m = jax.nn.softmax(jax.random.normal(ks[3], (z,)))
    w_rest = jax.nn.softmax(jax.random.normal(ks[4], (z,)))
    got = masked_agg_acc_deq_pallas(acc, q, scales, mask, w_m, w_rest,
                                    quant_block=quant_block, block_n=512,
                                    interpret=True)
    want = masked_agg_acc_deq_ref(acc, q, scales, mask, w_m, w_rest,
                                  quant_block=quant_block)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_masked_agg_acc_deq_matches_dequant_then_fold():
    """Fusing the dequant into the accumulate changes nothing numerically:
    deq-fold == dequantize (f32 materialize) then plain acc fold."""
    from repro.core import comm
    key = jax.random.PRNGKey(21)
    ks = jax.random.split(key, 4)
    acc = jax.random.normal(ks[0], (512,), jnp.float32)
    x = jax.random.normal(ks[1], (5, 512)) * 3.0
    q, scales = comm.quantize(x, 128)
    mask = jax.random.bernoulli(ks[2], 0.5, (512,))
    w_m = jax.nn.softmax(jax.random.normal(ks[3], (5,)))
    got = masked_agg_acc_deq_ref(acc, q, scales, mask, w_m, w_m,
                                 quant_block=128)
    want = masked_agg_acc_ref(acc, comm.dequantize(q, scales, 128), mask,
                              w_m, w_m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_masked_agg_acc_deq_nan_scale_gating():
    """A NaN device's scales are NaN (quantize of NaN rows): weight-0
    gating must kill the row before the multiply on both paths."""
    acc = jnp.array([1.0, 2.0] * 64)
    q = jnp.ones((2, 128), jnp.int8)
    scales = jnp.array([[jnp.nan], [2.0]])
    mask = jnp.ones((128,), bool)
    w = jnp.array([0.0, 1.0])
    for fn in (lambda: masked_agg_acc_deq_ref(
                   acc, q, scales, mask, w, w, quant_block=128),
               lambda: masked_agg_acc_deq_pallas(
                   acc, q, scales, mask, w, w, quant_block=128,
                   block_n=128, interpret=True)):
        got = np.asarray(fn())
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, np.asarray(acc) + 2.0)


def test_masked_agg_acc_deq_validates_inputs():
    acc = jnp.zeros((256,), jnp.float32)
    q = jnp.zeros((2, 256), jnp.int8)
    scales = jnp.zeros((2, 2))
    mask = jnp.zeros((256,), bool)
    w = jnp.ones((2,))
    with pytest.raises(ValueError):   # non-f32 accumulator
        masked_agg_acc_deq_pallas(acc.astype(jnp.bfloat16), q, scales,
                                  mask, w, w, quant_block=128,
                                  interpret=True)
    with pytest.raises(ValueError):   # non-int8 payload
        masked_agg_acc_deq_pallas(acc, q.astype(jnp.float32), scales,
                                  mask, w, w, quant_block=128,
                                  interpret=True)
    with pytest.raises(ValueError):   # block_n not a group multiple
        masked_agg_acc_deq_pallas(acc, q, scales, mask, w, w,
                                  quant_block=96, block_n=128,
                                  interpret=True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _rel(got, want):
    """Relative Frobenius distance, in f32."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _qkv(seed, b, h, kh, s, dh, dtype=jnp.float32):
    """Heads-first q (B, H, S, Dh), k/v (B, Kh, S, Dh)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, s, dh), dtype),
            jax.random.normal(ks[1], (b, kh, s, dh), dtype),
            jax.random.normal(ks[2], (b, kh, s, dh), dtype))


# The kernel rounds each product's operands to bf16 (the default matmul
# precision of an f32 program on a TPU), so it sits ~3e-3 (relative) from
# an f32 oracle in either dtype; a dropped or doubled block is O(1).
FLASH_TOL = 3e-2
FLASH_REL = 1e-2


@pytest.mark.parametrize("s,h,kh,dh", [
    (256, 4, 4, 64),
    (384, 4, 2, 64),
    (256, 8, 2, 32),
    (256, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, h, kh, dh, dtype):
    """The forward kernel at 128-blocks (blocks above the diagonal
    skipped, GQA groups inside a tile) against the independent oracle."""
    q, k, v = _qkv(s + h, 2, h, kh, s, dh, dtype)
    got = flash_attention(q, k, v, block=128, interpret=True)
    want = flash_attention_ref(q, k, v)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=FLASH_TOL, atol=FLASH_TOL)
    assert _rel(got, want) < FLASH_REL


@pytest.mark.parametrize("s,h,kh,block", [
    (256, 1, 1, 256),      # one block: the diagonal alone
    (256, 2, 2, 128),      # 3 live blocks of 4
    (512, 2, 1, 256),      # grouped queries, 3 live of 4
    (512, 1, 1, 128),      # 10 live of 16
])
def test_flash_matches_model_attention(s, h, kh, block):
    """Kernel forward and gradients (dq, dk, dv) == the model's XLA
    chunked path, the path it replaces, at the default precision."""
    from repro.models.attention import chunked_causal_attention
    q, k, v = _qkv(s + 10 * h + kh, 2, h, kh, s, 64)
    ct = jax.random.normal(jax.random.PRNGKey(s), q.shape)
    t = lambda x: x.transpose(0, 2, 1, 3)

    def xla(q, k, v):
        return t(chunked_causal_attention(t(q), t(k), t(v), q_chunk=128))

    def kernel(q, k, v):
        return flash_attention(q, k, v, block=block, interpret=True)

    got, got_vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(xla, q, k, v)
    assert _rel(got, want) < FLASH_REL
    for name, g, w in zip("qkv", got_vjp(ct), want_vjp(ct)):
        assert g.shape == w.shape and g.dtype == jnp.float32
        assert _rel(g, w) < FLASH_REL, name


def test_flash_block_for():
    """The block is the largest of 512/256/128 that divides S, else 0."""
    assert [flash_ops.block_for(s) for s in (1536, 768, 384, 1024, 200,
                                             64)] == [512, 256, 128, 512,
                                                      0, 0]


def _attn_cfg(**kw):
    from repro.configs.base import ModelConfig
    base = dict(d_model=128, n_heads=2, n_kv_heads=1, head_dim=64,
                compute_dtype="float32", param_dtype="float32",
                use_qk_norm=True, positions="rope")
    return ModelConfig(**{**base, **kw})


class _MeshLike:
    """A policy that places activations on a mesh (only ``mesh`` is read
    by the dispatch)."""
    mesh = object()

    def constrain(self, x, axes):
        return x


@pytest.mark.parametrize("case,takes", [
    ("s512", True),
    ("s256_prefill", True),
    ("s200", False),          # no block divides S
    ("window", False),
    ("softcap", False),
    ("mesh", False),
    ("cpu", False),           # use_pallas() false: not a TPU
])
def test_flash_dispatch(monkeypatch, case, takes):
    """apply_attention takes the kernel exactly where it can (a TPU, no
    window, no softcap, no mesh, a block that divides S), and every other
    call keeps the XLA chunked path."""
    from repro.models import attention
    monkeypatch.setattr(flash_ops, "use_pallas", lambda: case != "cpu")
    cfg = _attn_cfg(attn_logit_softcap=30.0 if case == "softcap" else 0.0)
    s = 200 if case == "s200" else 256 if case == "s256_prefill" else 512
    p = attention.init_attention(jax.random.PRNGKey(0), cfg)
    h = jnp.zeros((2, s, cfg.d_model), jnp.float32)
    kw = dict(window=64 if case == "window" else 0,
              return_kv=case == "s256_prefill")
    if case == "mesh":
        kw["policy"] = _MeshLike()
    jaxpr = str(jax.make_jaxpr(
        lambda p, h: attention.apply_attention(p, h, cfg, **kw))(p, h))
    assert ("flash_attn_fwd" in jaxpr) == takes
    assert ("pallas_call" in jaxpr) == takes


def test_flash_path_matches_xla_layer(monkeypatch):
    """The whole layer on the kernel's path (q/k/v projected heads first,
    RoPE and qk-norm there, the output projection reading O heads first,
    K/V handed to a decode cache in the usual layout) == the XLA path,
    values and parameter gradients."""
    from repro.models import attention
    cfg = _attn_cfg()
    p = attention.init_attention(jax.random.PRNGKey(1), cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 256, cfg.d_model))

    def layer(p, h):
        out, k, v = attention.apply_attention(p, h, cfg, return_kv=True)
        return out, k, v

    def loss(p, h):
        return jnp.sum(layer(p, h)[0] ** 2)

    want, want_g = layer(p, h), jax.grad(loss)(p, h)
    monkeypatch.setattr(flash_ops, "use_pallas", lambda: True)
    monkeypatch.setattr(flash_ops, "flash_attention", functools.partial(
        flash_ops.flash_attention, interpret=True))
    got, got_g = layer(p, h), jax.grad(loss)(p, h)
    assert "flash_attn_fwd" in str(jax.make_jaxpr(layer)(p, h))
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert _rel(g, w) < FLASH_REL
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        assert _rel(g, w) < FLASH_REL, path


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,d,block_s,block_d", [
    (2, 64, 256, 16, 128),
    (1, 128, 512, 32, 512),
    (3, 32, 384, 8, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lru_scan_sweep(b, s, d, block_s, block_d, dtype):
    key = jax.random.PRNGKey(b * 100 + s)
    ka, kb = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(ka, (b, s, d))).astype(dtype)
    bb = (jax.random.normal(kb, (b, s, d)) * 0.1).astype(dtype)
    got = lru_scan_pallas(a, bb, block_d=block_d, block_s=block_s,
                          interpret=True)
    want = lru_scan_ref(a.astype(jnp.float32), bb.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)


def test_lru_scan_matches_model_path():
    """Kernel == the associative-scan path used by models/rglru.py."""
    from repro.kernels.rglru_scan.ops import lru_scan
    key = jax.random.PRNGKey(3)
    ka, kb = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(ka, (2, 64, 256)))
    b = jax.random.normal(kb, (2, 64, 256)) * 0.2
    got = lru_scan_pallas(a, b, block_d=128, block_s=16, interpret=True)
    want = lru_scan(a, b)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

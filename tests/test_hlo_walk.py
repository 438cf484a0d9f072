"""Validate the loop-aware HLO cost walker against known graphs."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import hlo_walk, hw


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_single_dot_flops():
    a = jnp.zeros((128, 256), jnp.float32)
    b = jnp.zeros((256, 64), jnp.float32)
    txt = _compile_text(lambda x, y: x @ y, a, b)
    got = hlo_walk.analyze(txt)["flops"]
    assert got == 2 * 128 * 256 * 64, got


def test_scan_multiplies_by_trip_count():
    a = jnp.zeros((128, 128), jnp.float32)
    w = jnp.zeros((10, 128, 128), jnp.float32)

    def f(a, w):
        def body(h, wi):
            return h @ wi, None
        h, _ = jax.lax.scan(body, a, w)
        return h

    txt = _compile_text(f, a, w)
    got = hlo_walk.analyze(txt)["flops"]
    expect = 10 * 2 * 128 ** 3
    # allow small over/under from loop bookkeeping fusions
    assert abs(got - expect) / expect < 0.05, (got, expect)
    # sanity: XLA's own cost analysis misses the trip count (the reason
    # this walker exists)
    ca = hlo_walk.xla_cost_analysis(jax.jit(f).lower(a, w).compile())
    assert ca["flops"] < 0.3 * expect


def test_nested_scan():
    a = jnp.zeros((64, 64), jnp.float32)
    w = jnp.zeros((4, 3, 64, 64), jnp.float32)

    def f(a, w):
        def outer(h, wo):
            def inner(h2, wi):
                return h2 @ wi, None
            h, _ = jax.lax.scan(inner, h, wo)
            return h, None
        h, _ = jax.lax.scan(outer, a, w)
        return h

    txt = _compile_text(f, a, w)
    got = hlo_walk.analyze(txt)["flops"]
    expect = 12 * 2 * 64 ** 3
    assert abs(got - expect) / expect < 0.05, (got, expect)


def test_grad_flops_roughly_triple():
    a = jnp.zeros((64, 512), jnp.float32)
    w = jnp.zeros((512, 512), jnp.float32)

    def loss(w, a):
        return jnp.sum((a @ w) ** 2)

    fwd = hlo_walk.analyze(_compile_text(loss, w, a))["flops"]
    bwd = hlo_walk.analyze(
        _compile_text(jax.grad(loss, argnums=(0, 1)), w, a))["flops"]
    assert 2.4 < bwd / fwd < 3.6, (fwd, bwd)


def test_collectives_counted_with_trips():
    devs = jax.local_device_count()
    if devs < 2:
        pytest.skip("needs >= 2 host devices")


def test_hbm_bytes_scale_with_tensor_size():
    a = jnp.zeros((1024, 1024), jnp.float32)
    txt = _compile_text(lambda x: x * 2.0 + 1.0, a)
    got = hlo_walk.analyze(txt)["hbm_bytes"]
    # one read + one write of 4MB, give or take bookkeeping
    assert 0.5 * 8e6 < got < 4 * 8e6, got


def test_peaks_are_keyed_by_device_kind():
    """v5e reports itself as "TPU v5 lite"; its published peaks are in the
    table, and a kind with no entry raises instead of borrowing them."""
    v5e = hw.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    assert hw.peaks(hw.TARGET_KIND) is v5e
    with pytest.raises(KeyError, match="no published peaks"):
        hw.peaks("cpu")

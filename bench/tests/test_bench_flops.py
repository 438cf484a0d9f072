"""``bench/flops.py`` against XLA's own count of a compiled client step,
and the fold byte counts against the kernels' argument shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, flops


def _xla_flops(fn, *args) -> float:
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"])


def test_resnet_step_flops_match_xla():
    from bench.run import load_cell
    cfg = dict(load_cell("preact18-gn.paper-f32")["cfg"], image_size=16)
    fam = families.family(cfg)
    adapter = fam.adapter(cfg)
    params = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    b = 2
    batch = {"images": jax.ShapeDtypeStruct((b, 16, 16, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((b,), jnp.int32)}
    for loss, simple in ((adapter.loss_side, False),
                         (adapter.loss_simple, True)):
        got = b * fam.client_flops(cfg, simple)
        want = _xla_flops(jax.grad(loss), params, batch)
        assert got == pytest.approx(want, rel=0.05)


@pytest.mark.parametrize("z,dtype", [(5, jnp.float32), (2, jnp.bfloat16)])
def test_fold_bytes_match_kernel_arguments(z, dtype):
    """The bytes counted for one fold call equal the bytes of the kernel's
    operands and result as ``core/aggregate.py`` passes them."""
    n = 11_175_936
    operands = [jax.ShapeDtypeStruct((n,), jnp.float32),      # accumulator
                jax.ShapeDtypeStruct((z, n), dtype),          # client rows
                jax.ShapeDtypeStruct((n,), jnp.bool_)]        # mask M
    result = jax.ShapeDtypeStruct((n,), jnp.float32)
    moved = sum(int(np.prod(o.shape)) * o.dtype.itemsize
                for o in operands + [result])
    assert flops.fold_bytes(z, n, jnp.dtype(dtype).itemsize) == moved

"""Pallas TPU kernels: causal flash attention, forward and backward.

:func:`flash_attention` is causal self-attention with a ``jax.custom_vjp``
whose three kernels keep every score tile in VMEM, so nothing of size
S x S reaches HBM, forward or backward:

* ``flash_attn_fwd`` — grid (batch x kv-head, q-block, k-block).  The
  online-softmax state (m, l, acc) lives in VMEM scratch, carried across
  the k-block grid dimension, which a TPU runs sequentially.  It writes O
  and the per-row logsumexp, the backward pass's only extra residual.
* ``flash_attn_dkv`` — grid (batch x kv-head, k-block, q-block): dK and dV
  of one k-block, accumulated over the q-blocks that see it.  It works on
  the transposed scores (keys on sublanes), so the per-query statistics
  broadcast as lane-dense rows and every product is a plain one.
* ``flash_attn_dq`` — grid (batch x kv-head, q-block, k-block): dQ of one
  q-block, accumulated over the k-blocks it sees.

``delta = rowsum(dO * O)`` is computed in XLA between them (FA2's split).
Each backward kernel recomputes P from Q, K and the logsumexp.

Causality: a block strictly above the diagonal is dead.  Its block index
is clamped to the last live one, so the pipeline issues no DMA for it,
and ``pl.when`` skips its compute; only the diagonal block is masked.
With ``block`` = S / n there are n (n + 1) / 2 live blocks of n^2.

Grouped-query attention: the G query heads that share a kv head come in
one q tile (G, block, Dh), so the kv tile is read once per group; the
forward kernel stacks them into one ``(G * block, Dh)`` score tile, the
backward ones take them in turn.

Numerics are the default matmul precision of an f32 program on a TPU:
q, k, v, O, dO and the gradients stay in their dtype in HBM, each operand
is cast to bf16 in VMEM right before its MXU product (QK^T, PV, dO V^T,
dS K, dS^T Q, P^T dO), every product accumulates in f32, and the softmax
statistics and dS are f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30   # large-but-finite: exp() of a masked score is 0
_MXU = jnp.bfloat16
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _dot(a, b, dims=_NN):
    """One MXU product: bf16 operands, f32 accumulation."""
    return jax.lax.dot_general(a.astype(_MXU), b.astype(_MXU), dims,
                               preferred_element_type=jnp.float32)


def _visible(shape, transposed: bool):
    """Causal mask of a diagonal block (q-block == k-block): rows are
    queries (group-major, ``G * block`` of them), or keys when
    ``transposed``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if transposed:
        return cols >= rows
    block = shape[1]
    if shape[0] > block:
        rows = rows % block
    return rows >= cols


def _live_steps(i, j, step):
    """Run ``step(masked)`` for the live block (q-block i, k-block j):
    unmasked below the diagonal, masked on it, not at all above it."""
    pl.when(j < i)(lambda: step(False))
    pl.when(j == i)(lambda: step(True))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float):
    i, j = pl.program_id(1), pl.program_id(2)
    _, g, bq, dh = q_ref.shape

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        q = q_ref[0].reshape(g * bq, dh) * scale
        s = _dot(q, k_ref[0], _NT)                        # (G*bq, bk)
        if masked:
            s = jnp.where(_visible(s.shape, False), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + _dot(p, v_ref[0])
        m_scr[...] = m_new

    _live_steps(i, j, step)

    @pl.when(j == i)          # the diagonal is the row's last live block
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).reshape(g, bq, dh).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)                     # (G*bq, 1)
        for h in range(g):
            lse_ref[0, h:h + 1, :] = lse[h * bq:(h + 1) * bq].T


def _fwd(q, k, v, block: int, interpret: bool):
    """q (N, G, S, Dh), k/v (N, S, Dh) -> O like q, logsumexp (N, G, S)."""
    n, g, s, dh = q.shape
    nb = s // block
    q_spec = pl.BlockSpec((1, g, block, dh), lambda b, i, j: (b, 0, i, 0))
    kv_spec = pl.BlockSpec((1, block, dh),
                           lambda b, i, j: (b, jnp.minimum(j, i), 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=dh ** -0.5),
        grid=(n, nb, nb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, g, block), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, g, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g * block, 1), jnp.float32),
                        pltpu.VMEM((g * block, 1), jnp.float32),
                        pltpu.VMEM((g * block, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attn_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale: float):
    j, i = pl.program_id(1), pl.program_id(2)
    g = q_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        k, v = k_ref[0], v_ref[0]
        for h in range(g):
            q = q_ref[0, h] * scale                       # (bq, Dh)
            do = do_ref[0, h]
            s_t = _dot(k, q, _NT)                         # (bk, bq)
            if masked:
                s_t = jnp.where(_visible(s_t.shape, True), s_t, NEG_INF)
            p_t = jnp.exp(s_t - lse_ref[0, h:h + 1, :])
            dv_scr[...] += _dot(p_t, do)
            ds_t = p_t * (_dot(v, do, _NT) - delta_ref[0, h:h + 1, :])
            dk_scr[...] += _dot(ds_t, q)

    _live_steps(i, j, step)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale: float):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked: bool):
        k, v = k_ref[0], v_ref[0]
        for h in range(q_ref.shape[1]):
            s = _dot(q_ref[0, h] * scale, k, _NT)         # (bq, bk)
            if masked:
                s = jnp.where(_visible(s.shape, False), s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, h:h + 1, :].T)
            dp = _dot(do_ref[0, h], v, _NT)
            ds = p * (dp - delta_ref[0, h:h + 1, :].T)
            dq_scr[h] += _dot(ds, k)

    _live_steps(i, j, step)

    @pl.when(j == i)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, block: int, interpret: bool):
    n, g, s, dh = q.shape
    nb = s // block
    scale = dh ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    # dK, dV: k-block j outer, q-blocks i >= j inner
    q_spec = pl.BlockSpec((1, g, block, dh),
                          lambda b, j, i: (b, 0, jnp.maximum(i, j), 0))
    row_spec = pl.BlockSpec((1, g, block),
                            lambda b, j, i: (b, 0, jnp.maximum(i, j)))
    kv_spec = pl.BlockSpec((1, block, dh), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(n, nb, nb),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, dh), jnp.float32),
                        pltpu.VMEM((block, dh), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attn_dkv",
    )(q, k, v, do, lse, delta)

    # dQ: q-block i outer, k-blocks j <= i inner
    q_spec = pl.BlockSpec((1, g, block, dh), lambda b, i, j: (b, 0, i, 0))
    row_spec = pl.BlockSpec((1, g, block), lambda b, i, j: (b, 0, i))
    kv_spec = pl.BlockSpec((1, block, dh),
                           lambda b, i, j: (b, jnp.minimum(j, i), 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(n, nb, nb),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((g, block, dh), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attn_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, block, interpret):
    return _fwd(q, k, v, block, interpret)[0]


def _flash_fwd(q, k, v, block, interpret):
    o, lse = _fwd(q, k, v, block, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(block, interpret, res, do):
    return _bwd(*res, do, block, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    block: int, interpret: bool = False) -> jax.Array:
    """Causal attention, heads first: q (B, H, S, Dh), k/v (B, Kh, S, Dh)
    -> (B, H, S, Dh).  Query head ``h`` reads kv head ``h // (H / Kh)``.
    ``block`` (the q- and k-block) divides S and is a multiple of 128."""
    b, h, s, dh = q.shape
    kh = k.shape[1]
    if s % block or block % 128:
        raise ValueError(f"block {block} must divide S={s} and be a "
                         f"multiple of 128")
    out = _flash(q.reshape(b * kh, h // kh, s, dh), k.reshape(b * kh, s, dh),
                 v.reshape(b * kh, s, dh), block, interpret)
    return out.reshape(b, h, s, dh)

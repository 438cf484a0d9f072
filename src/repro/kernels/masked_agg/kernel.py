"""Pallas TPU kernel: masked cohort aggregation (FedHeN server hot path).

The server step reduces a stacked cohort (Z client models) into one model
with different weights inside/outside the index set M.  The op is purely
memory-bound (read Z x N, write N), so the kernel's job is to stream the
cohort through VMEM exactly once with lane-aligned tiles:

* grid over N in ``block_n`` tiles (lane-dim multiple of 128),
* the whole cohort axis Z (<= ~32 active devices) rides along inside the
  tile: block (Z, block_n) -> VMEM,
* weights are selected per element from (w_m, w_rest) by the mask tile and
  reduced over Z in one fused multiply-add in f32, added to the f32
  running sum.

Three variants:

* ``masked_agg_acc_pallas`` — the streaming fold's accumulating form:
  ``out = acc + masked sum`` with ``input_output_aliases`` so the running
  f32 accumulator is updated **in place** — the fold writes N floats
  instead of reading+writing two accumulator copies, halving accumulator
  HBM traffic.  Inputs may be bf16; accumulation is always f32.
* ``masked_agg_acc_deq_pallas`` — the quantized-upload fold: the cohort
  tile arrives as int8 payload + per-group f32 scales (the wire format of
  ``core/comm.py``) and is dequantized *inside* the accumulate, so the
  server never materializes an f32 copy of the uploads — int8 tiles also
  cut the fold's HBM read traffic 4x vs f32.  ``quant_block`` must divide
  ``block_n`` so scale groups tile with the grid; the dequant reshape
  keeps the 128-lane axis intact ((Z, block_n) -> (Z, groups, 128-mult)).
* ``masked_scatter_acc_pallas`` — the top-k sparse-upload fold (wire v2):
  each client ships ``k`` compacted values (+ scale sidecar over the
  compacted payload) and their int32 flat positions.  TPU has no dynamic
  lane scatter, so the scatter is a one-hot contraction on a 2-D grid of
  (N blocks, k tiles): each step compares one ``k_tile`` slice of the
  indices against its block's positions (``broadcasted_iota``) and
  contracts the weighted values with the resulting one-hot — the
  (block_n, k_tile) one-hot lives only in VMEM, and the dense
  ``(Z, n_flat)`` f32 cohort copy never materializes anywhere.

No wrapper is ``jax.jit``-ed: each always runs inside the already jitted
round (or a jitted test harness), where an extra jit would only add
eager-dispatch overhead and a second compilation cache.

VMEM budget: Z=32, block_n=2048, bf16 -> 128 KiB per input tile plus the
mask/acc/out tiles; well under the 16 MiB of VMEM a kernel may use by
default on v5e (Mosaic's scoped limit, Pallas TPU docs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _agg_acc_kernel(acc_ref, x_ref, mask_ref, wm_ref, wr_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)              # (Z, block_n)
    w = jnp.where(mask_ref[...],
                  wm_ref[...].astype(jnp.float32),
                  wr_ref[...].astype(jnp.float32))  # (Z, block_n)
    x = jnp.where(w > 0, x, 0.0)                    # NaN-device gating
    out_ref[...] = acc_ref[...] + jnp.sum(x * w, axis=0, keepdims=True)


def masked_agg_acc_pallas(acc: jax.Array, x: jax.Array, mask: jax.Array,
                          w_m: jax.Array, w_rest: jax.Array, *,
                          block_n: int = 2048,
                          interpret: bool = False) -> jax.Array:
    """Accumulating fold: acc (N,) f32 + masked sum of x (Z, N) -> (N,) f32.

    ``acc`` is aliased to the output (in-place update).  x may be any
    float dtype (bf16 streaming); the accumulation is f32.  N should be a
    multiple of ``block_n`` (the flat layout guarantees it); other sizes
    are padded, which costs the alias a copy.
    """
    if acc.dtype != jnp.float32:
        raise ValueError(f"accumulator must be f32, got {acc.dtype}")
    z, n = x.shape
    pad = (-n) % block_n
    if pad:
        acc = jnp.pad(acc, (0, pad))
        x = jnp.pad(x, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, (0, pad))
    np_ = x.shape[1]
    grid = (np_ // block_n,)

    out = pl.pallas_call(
        _agg_acc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((z, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((z, 1), lambda i: (0, 0)),
            pl.BlockSpec((z, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        input_output_aliases={0: 0},
        name="masked_agg_acc",
        interpret=interpret,
    )(acc[None, :], x, mask[None, :], w_m[:, None], w_rest[:, None])
    return out[0, :n]


def _make_agg_acc_deq_kernel(quant_block: int):
    def kernel(acc_ref, q_ref, scale_ref, mask_ref, wm_ref, wr_ref, out_ref):
        z, bn = q_ref.shape
        g = q_ref[...].astype(jnp.float32).reshape(z, bn // quant_block,
                                                   quant_block)
        x = (g * scale_ref[...][..., None]).reshape(z, bn)  # fused dequant
        w = jnp.where(mask_ref[...],
                      wm_ref[...].astype(jnp.float32),
                      wr_ref[...].astype(jnp.float32))      # (Z, block_n)
        x = jnp.where(w > 0, x, 0.0)                        # NaN-device gating
        out_ref[...] = acc_ref[...] + jnp.sum(x * w, axis=0, keepdims=True)
    return kernel


def masked_agg_acc_deq_pallas(acc: jax.Array, q: jax.Array,
                              scales: jax.Array, mask: jax.Array,
                              w_m: jax.Array, w_rest: jax.Array, *,
                              quant_block: int, block_n: int = 2048,
                              interpret: bool = False) -> jax.Array:
    """Dequantizing accumulating fold: acc (N,) f32 + masked sum of the
    int8 payload q (Z, N) x per-group scales (Z, N/quant_block) -> (N,) f32.

    ``acc`` is aliased to the output (in-place update); the payload is
    dequantized tile-locally in VMEM, never materialized in f32.  N must be
    a multiple of ``quant_block`` (the flat layout guarantees it: the wire
    contract requires quant_block | 128 | n_flat) and ``block_n`` must be a
    group multiple so scale groups tile with the grid.
    """
    if acc.dtype != jnp.float32:
        raise ValueError(f"accumulator must be f32, got {acc.dtype}")
    if q.dtype != jnp.int8:
        raise ValueError(f"payload must be int8, got {q.dtype}")
    if block_n % quant_block:
        raise ValueError(f"block_n={block_n} not a multiple of "
                         f"quant_block={quant_block}")
    z, n = q.shape
    if n % quant_block:
        raise ValueError(f"N={n} not a multiple of quant_block={quant_block}")
    pad = (-n) % block_n
    if pad:
        acc = jnp.pad(acc, (0, pad))
        q = jnp.pad(q, ((0, 0), (0, pad)))
        scales = jnp.pad(scales, ((0, 0), (0, pad // quant_block)))
        mask = jnp.pad(mask, (0, pad))
    np_ = q.shape[1]
    grid = (np_ // block_n,)
    block_g = block_n // quant_block
    # one (Z, block_g) scale tile per grid step as the two minor dims of a
    # (blocks, Z, block_g) array: both equal the full dims, which Mosaic's
    # (8, 128) block rule accepts for any block_g (a (Z, block_g) window of
    # the (Z, groups) array would need block_g % 128 == 0)
    scales = scales.reshape(z, grid[0], block_g).transpose(1, 0, 2)

    out = pl.pallas_call(
        _make_agg_acc_deq_kernel(quant_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((z, block_n), lambda i: (0, i)),
            pl.BlockSpec((None, z, block_g), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((z, 1), lambda i: (0, 0)),
            pl.BlockSpec((z, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        input_output_aliases={0: 0},
        name="masked_agg_acc_deq",
        interpret=interpret,
    )(acc[None, :], q, scales, mask[None, :], w_m[:, None], w_rest[:, None])
    return out[0, :n]


# compacted-entry tile of the scatter fold's grid: bounds the per-step
# (block_n, k_tile) one-hot to 2048 x 512 bf16 = 2 MiB of VMEM
_SCATTER_K_TILE = 512


def _bf16_top(x: jax.Array) -> jax.Array:
    """f32 x with the low 16 bits cleared: its sign, exponent and leading
    8 significand bits, a value bf16 holds exactly."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _bf16_split3(x: jax.Array) -> jax.Array:
    """f32 (R, K) -> bf16 (3R, K) rows [hi; mid; lo] with hi + mid + lo ==
    x exactly (3 x 8 significand bits cover f32's 24): the one-hot
    contraction then runs as one bf16 MXU pass and still selects every
    value bit for bit.

    The parts are cut by bit mask, not by an f32 -> bf16 -> f32 round
    trip: the TPU compiler may keep such a round trip in f32 (excess
    precision), which leaves hi inexact and mid and lo zero."""
    hi = _bf16_top(x)
    r = x - hi
    mid = _bf16_top(r)
    return jnp.concatenate([hi, mid, r - mid], axis=0).astype(jnp.bfloat16)


def _scatter_acc_kernel(acc_ref, lhs_ref, idx_ref, mask_ref, out_ref):
    i, t = pl.program_id(0), pl.program_id(1)
    block_n = out_ref.shape[1]

    @pl.when(t == 0)
    def _():
        out_ref[...] = acc_ref[...]

    rel = idx_ref[...] - i * block_n                      # (1, k_tile)
    pos = jax.lax.broadcasted_iota(jnp.int32, (block_n, rel.shape[1]), 0)
    onehot = (rel == pos).astype(jnp.bfloat16)            # (block_n, k_tile)
    part = jax.lax.dot_general(                           # (8, block_n)
        lhs_ref[...], onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    in_m = (part[0:1] + part[1:2]) + part[2:3]            # w_m-weighted
    out_m = (part[3:4] + part[4:5]) + part[5:6]           # w_rest-weighted
    out_ref[...] += jnp.where(mask_ref[...], in_m, out_m)


def masked_scatter_acc_pallas(acc: jax.Array, values: jax.Array,
                              scales, indices: jax.Array,
                              mask: jax.Array, w_m: jax.Array,
                              w_rest: jax.Array, *, quant_block: int,
                              block_n: int = 2048,
                              interpret: bool = False) -> jax.Array:
    """Sparse scatter-fold: acc (N,) f32 += masked scatter of each
    client's compacted payload values (Z, k) x per-group scales
    (Z, k/quant_block) at flat positions indices (Z, k) int32.

    ``acc`` is aliased to the output (in-place update).  ``values`` may
    be int8/bf16/f32; ``scales=None`` means no sidecar.  ``k`` must be a
    ``quant_block`` multiple (``comm.topk_count`` rounds up to a lane
    multiple, which any valid ``quant_block`` divides).  Per-row indices
    must be distinct (``top_k`` guarantees it) and inside ``[0, N)``;
    the weight at each target position is selected by the mask there
    (w_m inside M, w_rest outside), and zero weights gate the value
    before any multiply (NaN/padding devices).

    The compacted payload is dequantized and weighted once, outside the
    grid, into two f32 streams (w_m- and w_rest-weighted, each gated by
    its own weight) over all ``Z * k`` entries, split into exact bf16
    parts.  The grid runs over (N blocks, k tiles): each step contracts
    one ``k_tile`` slice of the streams with the one-hot of its indices
    against the block's positions and adds the mask-selected sum into the
    resident accumulator block.  Work is O(Z * k * N).
    """
    if acc.dtype != jnp.float32:
        raise ValueError(f"accumulator must be f32, got {acc.dtype}")
    z, k = values.shape
    if k % quant_block:
        raise ValueError(f"k={k} not a multiple of "
                         f"quant_block={quant_block}")
    if indices.shape != (z, k):
        raise ValueError(f"indices shape {indices.shape} != {(z, k)}")
    v = values.astype(jnp.float32)
    if scales is not None:
        v = v * jnp.repeat(scales, quant_block, axis=1,
                           total_repeat_length=k)
    lhs = jnp.concatenate([                 # (8, Z*k): w_m, w_rest, 0 rows
        _bf16_split3((jnp.where(w[:, None] > 0, v, 0.0)
                      * w[:, None]).reshape(1, z * k))
        for w in (w_m.astype(jnp.float32), w_rest.astype(jnp.float32))]
        + [jnp.zeros((2, z * k), jnp.bfloat16)])
    idx = indices.astype(jnp.int32).reshape(1, z * k)
    k_tile = min(_SCATTER_K_TILE, -(-z * k // 128) * 128)
    k_pad = (-z * k) % k_tile
    if k_pad:                           # index -1 matches no position
        lhs = jnp.pad(lhs, ((0, 0), (0, k_pad)))
        idx = jnp.pad(idx, ((0, 0), (0, k_pad)), constant_values=-1)
    n = acc.shape[0]
    pad = (-n) % block_n
    if pad:
        acc = jnp.pad(acc, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    np_ = acc.shape[0]
    grid = (np_ // block_n, idx.shape[1] // k_tile)

    out = pl.pallas_call(
        _scatter_acc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, t: (0, i)),
            pl.BlockSpec((8, k_tile), lambda i, t: (0, t)),
            pl.BlockSpec((1, k_tile), lambda i, t: (0, t)),
            pl.BlockSpec((1, block_n), lambda i, t: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i, t: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        input_output_aliases={0: 0},
        name="masked_scatter_acc",
        interpret=interpret,
    )(acc[None, :], lhs, idx, mask[None, :])
    return out[0, :n]

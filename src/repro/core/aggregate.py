"""Server aggregation — FedHeN Alg. 1 ln. 16-22, plus NoSide and Decouple.

All three operate on a *stacked cohort*: client models share the complex
treedef with a leading cohort axis ``Z``.  Simple clients' complex-only
slices are carried untouched (they are weighted out by the masks), so one
stacked representation serves every algorithm.

Two entry points:

* One-shot (``fedhen_server_update`` / ``decouple_server_update``): the
  whole cohort is stacked and reduced at once.  Reference semantics — the
  parity oracle the streaming fold is tested against.
* Streaming (``streaming_init`` / ``streaming_fold`` /
  ``streaming_finalize``, bound together by ``make_engine``) — THE
  production fold.  ``StreamState`` carries one flat f32 accumulator
  vector (plus one more for decouple): each trained chunk is packed into
  a single contiguous ``(Z, n_flat)`` buffer by the trainer's static
  ``core.flatten.FlatLayout`` and folded with ONE accumulating
  ``masked_agg_acc`` launch (``input_output_aliases`` updates the running
  sum in place on TPU), against one precomputed flat mask bitvector.
  Chunks may stream in bf16; accumulation is always f32.  Under a wire
  format (``core/comm.py``) the fold consumes the *encoded uploads* —
  int8 payloads fold through the dequantizing accumulate variant, never
  materializing an f32 copy of the chunk.  Unpacking back to the
  parameter tree happens once, at finalize.

  **Flat layout contract**: the layout's offsets are static per (treedef,
  leaf shapes, align, block_n) — built once per trainer and valid for
  every round.

The fold accumulates chunks into running *unnormalized* masked sums plus
two scalar weight totals; the division happens once at finalize, so
server memory is O(chunk) and the result matches the one-shot path up to
float summation order.

**Weight contract.**  ``valid`` is a per-client coefficient, not just a
bool: a bool marks plain validity (NaN exclusion, padding), while a float
carries validity *times* any per-client coefficient — the asynchronous
engine (``core/async_rounds.py``) multiplies its staleness decay
``1/(1+s)^a`` into it, so staleness weighting rides the exact same masked
weight path as NaN/padding exclusion and needs no second code path.  A
weight of 0 gates the client's values before the multiply on every path
(a NaN device at weight 0 can never poison the sums), and all-1 float
weights are bit-identical to bool validity.

The hot path — a weighted masked sum over the cohort axis — is exactly the
``masked_agg`` kernels' contract; the fold dispatches to them on TPU via
``kernels/masked_agg/ops.py``, with the XLA references as the CPU
fallback (what the dry-run lowers, since Pallas cannot lower on the CPU
backend).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import comm, flatten, masking
from repro.kernels.masked_agg import ops as agg_ops

Tree = Any

ALGORITHMS = ("fedhen", "noside", "decouple")


# ---------------------------------------------------------------------------
# EngineSpec: the one object the fold is configured by
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class EngineSpec:
    """Everything the fold needs, in one frozen value.

    Built ONCE (``from_config`` next to the ``FedConfig`` that owns the
    knobs) and handed whole to every seam; trace-time values the config
    cannot know (the mask tree, the trainer's layout, a ``flat_mask``
    that is a round *argument*) are attached with :meth:`bind`.

    ``eq=False``: ``mask``/``flat_mask`` may hold (traced) arrays, so
    identity comparison is the only safe equality — specs are plumbing,
    never dict keys.
    """

    algorithm: str = "fedhen"
    mask: Tree = None
    layout: Optional[flatten.FlatLayout] = None
    flat_mask: Optional[jax.Array] = None
    block_n: int = 2048
    stream_dtype: Any = jnp.float32
    wire: Optional[comm.WireSpec] = None
    variance_reduction: str = "none"

    def __post_init__(self):
        if self.algorithm not in ALGORITHMS:
            raise ValueError(self.algorithm)

    @classmethod
    def from_config(cls, fed, *, mask: Tree = None,
                    layout: Optional[flatten.FlatLayout] = None,
                    wire: Optional[comm.WireSpec] = None) -> "EngineSpec":
        """Build the spec from a ``FedConfig`` (the knobs' one source)."""
        return cls(algorithm=fed.algorithm, mask=mask, layout=layout,
                   block_n=fed.agg_block_n,
                   stream_dtype=jnp.dtype(fed.agg_stream_dtype),
                   wire=wire, variance_reduction=fed.variance_reduction)

    def bind(self, **kw) -> "EngineSpec":
        """A copy with trace-time values attached (mask, layout,
        flat_mask, ...)."""
        return dataclasses.replace(self, **kw)


def _gated_wsum_leaf(x: jax.Array, weights: jax.Array) -> jax.Array:
    """f32 weighted sum of one stacked leaf over the cohort axis.

    Gates before multiplying: a NaN device with weight 0 must not poison
    the sum (paper's NaN-device exclusion)."""
    w = weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(jnp.float32)
    xf = jnp.where(w > 0, x.astype(jnp.float32), 0.0)
    return jnp.sum(xf * w, axis=0)


def _wmean(stacked: Tree, weights: jax.Array) -> Tree:
    """Weighted mean over leading cohort axis.  weights: (Z,) already
    normalized (sums to 1 over the intended group)."""
    return jax.tree.map(
        lambda x: _gated_wsum_leaf(x, weights).astype(x.dtype), stacked)


def _norm_weights(raw: jax.Array) -> jax.Array:
    total = jnp.sum(raw)
    return jnp.where(total > 0, raw / jnp.maximum(total, 1e-12),
                     jnp.zeros_like(raw))


def fedhen_server_update(cohort: Tree, is_simple: jax.Array,
                         valid: jax.Array, mask: Tree) -> Tree:
    """FedHeN / NoSide server step (they share it — paper Appendix A).

    cohort: stacked client models (Z, ...) in complex structure.
    is_simple: (Z,) bool; valid: (Z,) bool (NaN-device exclusion).
    mask: index-set-M mask tree.

    Returns the new complex server model; the simple server model is its
    M-slice by construction (invariant tested in tests/test_aggregate.py).
    """
    valid_f = valid.astype(jnp.float32)
    w_all = _norm_weights(valid_f)                          # ln. 18: 1/|Z|
    w_complex = _norm_weights(valid_f * (~is_simple))       # ln. 22: 1/|Z_c|
    mean_all = _wmean(cohort, w_all)
    mean_complex = _wmean(cohort, w_complex)
    # ln. 18-20: M slice <- mean over ALL devices; ln. 22: M' <- complex mean
    return masking.where_mask(mask, mean_all, mean_complex)


def decouple_server_update(cohort: Tree, is_simple: jax.Array,
                           valid: jax.Array, mask: Tree) -> Tree:
    """Decouple (Alg. 3): two independent FedAvg runs in one stacked tree.

    M slice <- mean over simple devices only; M' <- mean over complex only.
    (The simple server model lives in the M slice; the complex server model's
    M slice is tracked separately by the caller — see ``ServerState``.)
    """
    valid_f = valid.astype(jnp.float32)
    w_simple = _norm_weights(valid_f * is_simple)
    w_complex = _norm_weights(valid_f * (~is_simple))
    mean_simple = _wmean(cohort, w_simple)
    mean_complex = _wmean(cohort, w_complex)
    return masking.where_mask(mask, mean_simple, mean_complex), mean_complex


# ---------------------------------------------------------------------------
# Streaming helpers
# ---------------------------------------------------------------------------

def _chunk_weights(is_simple: jax.Array, valid: jax.Array,
                   algorithm: str) -> Tuple[jax.Array, jax.Array]:
    """Raw (unnormalized) per-client weights of one chunk.

    ``valid`` may be bool (plain validity) or float (validity x any
    per-client coefficient, e.g. the async engine's staleness decay) —
    see the module's weight contract.  ``w_in`` weights the inside-M
    accumulator: every valid device for fedhen/noside (Alg. 1 ln. 18),
    simple devices only for decouple.  ``w_out`` weights outside M:
    complex devices only (ln. 22), for all three algorithms.
    """
    valid_f = valid.astype(jnp.float32)
    w_in = valid_f * is_simple if algorithm == "decouple" else valid_f
    w_out = valid_f * (~is_simple)
    return w_in, w_out


def _safe_inv(tot: jax.Array) -> jax.Array:
    """1/tot with the zero-weight-group guard (0 -> 0, never NaN)."""
    return jnp.where(tot > 0, 1.0 / jnp.maximum(tot, 1e-12), 0.0)


# ---------------------------------------------------------------------------
# Streaming aggregation (the production fold)
# ---------------------------------------------------------------------------

class StreamState(NamedTuple):
    """Running sums of a chunked server aggregation (a jit/scan carry).

    ``acc`` is ONE flat f32 vector of *unnormalized* masked sums over the
    trainer's ``FlatLayout``: inside M each element accumulates
    ``sum_z w_in[z] * x[z]``, outside M ``sum_z w_out[z] * x[z]`` — exactly
    one accumulating ``masked_agg`` kernel pass per chunk, updated in place.
    ``acc_out`` (decouple only, else ``None``) additionally carries the
    *whole-vector* ``w_out`` sums, because decouple's new complex model is
    the complex-group mean everywhere, including inside M.  ``tot_in`` /
    ``tot_out`` are the scalar weight totals the finalize divides by.
    ``cv_acc`` (SCAFFOLD only, else ``None``) is the second flat
    accumulator: the raw sum of per-client control-variate deltas folded
    through the exact same masked launch as the params; the round divides
    it by N_devices itself (``finalize`` never touches it).
    """
    acc: jax.Array
    acc_out: Optional[jax.Array]
    tot_in: jax.Array
    tot_out: jax.Array
    cv_acc: Optional[jax.Array] = None


class SparseChunk(NamedTuple):
    """One chunk's delta-mode uploads (wire v2, ``core/comm.py``).

    When the wire ``uses_deltas`` (top-k / stochastic / error-feedback),
    clients upload the encoded *difference* vs the flat decoded broadcast
    they trained on — ``base`` here, one ``(n_flat,)`` f32 vector shared
    by the chunk.  Each client's true upload is
    ``base + decode(row z of values)``, so the fold adds
    ``(sum_z w[z]) * base`` densely (ONE Z=1 masked accumulate with the
    summed weights) plus every encoded delta row at its own weight —
    the same total as folding the dense uploads, without materializing
    them.

    ``indices=None`` means the delta payload is dense (EF/stochastic
    without top-k): ``values`` is ``(Z, n_flat)`` and folds through the
    plain (bf16/f32) or dequantizing (int8 — ``scales`` present)
    accumulate.  With top-k, ``values``/``indices`` are the compacted
    ``(Z, k)`` payloads (``scales`` grouped over the compacted axis)
    scattered by the ``masked_scatter_acc`` kernel variant — no dense
    f32 cohort copy on either path."""
    base: jax.Array
    values: jax.Array
    scales: Optional[jax.Array]
    indices: Optional[jax.Array]


def _layout_for(tree: Tree, layout, block_n: int, *, stacked: bool = False):
    if layout is not None:
        return layout
    return flatten.layout_of(tree, total_multiple=block_n, stacked=stacked)


def streaming_init(params_like: Tree, spec: EngineSpec) -> StreamState:
    """Zero flat accumulators for one round of streaming aggregation.

    Args:
      params_like: ONE (unstacked) complex model tree — only shapes are
        read, to size the flat accumulator.
      spec: the :class:`EngineSpec` (decouple allocates the second
        accumulator, SCAFFOLD the control-variate one).

    Returns: a :class:`StreamState` of f32 zeros (``(n_flat,)`` acc(s) +
    two scalar weight totals)."""
    layout = _layout_for(params_like, spec.layout, spec.block_n)
    zeros = jnp.zeros((layout.n_flat,), jnp.float32)
    acc_out = zeros if spec.algorithm == "decouple" else None
    cv_acc = (jnp.zeros((layout.n_flat,), jnp.float32)
              if spec.variance_reduction == "scaffold" else None)
    return StreamState(zeros, acc_out, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32), cv_acc)


def streaming_fold(state: StreamState, chunk: Tree, is_simple: jax.Array,
                   valid: jax.Array, spec: EngineSpec, *,
                   cv_chunk: Optional[jax.Array] = None,
                   sparse_chunk: Optional[SparseChunk] = None,
                   force_pallas_interpret: bool = False) -> StreamState:
    """Fold one stacked chunk of client models into the flat sums.

    Args:
      state: the running :class:`StreamState` (from ``streaming_init`` or
        a previous fold).
      chunk: stacked client models, leaves ``(Z, *shape)``.
      is_simple: ``(Z,)`` bool — population membership per client.
      valid: ``(Z,)`` bool validity, or f32 per-client weights (validity x
        staleness coefficient — the async engine's path; see the module
        weight contract).
      spec: the :class:`EngineSpec` (algorithm, mask, layout, flat mask,
        block, stream dtype, wire).
      cv_chunk: optional ``(Z, n_flat)`` control-variate deltas (SCAFFOLD)
        folded into ``state.cv_acc`` with the same per-client weights and
        flat mask as the params — one extra accumulating launch, nothing
        else changes.
      sparse_chunk: delta-mode uploads (:class:`SparseChunk`; wire v2)
        REPLACING the dense ``chunk`` fold — ``chunk`` may then be
        ``None`` (the spec's layout sizes everything).  Requires a wire
        whose ``uses_deltas`` is true; the fold adds the shared base
        densely at the summed weights plus each encoded delta row
        (scatter-fold when ``indices`` is present, dequantizing/plain
        accumulate otherwise).
      force_pallas_interpret: run the kernel path in interpret mode
        (tests on CPU).

    Returns: the updated state (same shapes; ``acc`` stays f32).

    On the kernel path (TPU, or interpret mode in tests) the chunk is
    packed into one ``(Z, n_flat)`` buffer (``stream_dtype``; bf16 halves
    fold HBM traffic, accumulation stays f32) and reduced with ONE
    ``masked_agg`` launch — two for decouple, whose second accumulator uses
    ``w_out`` on both mask branches.  The CPU fallback keeps the same flat
    f32 accumulator but folds leaf by leaf into its slices (static slot
    offsets), row-streaming the cohort axis — no packed ``(Z, n_flat)``
    scratch buffer and no reduce op materializes, matching the kernel's
    summation order exactly.  Invalid (NaN / padding) devices carry weight
    0 and are gated before the multiply on both paths, so they can never
    poison the accumulators.

    ``spec.wire`` switches the fold to the communication path
    (core/comm.py): the uploads are what the fold consumes.  A bf16 wire
    overrides ``stream_dtype``; an int8 wire quantizes the packed chunk
    (symmetric per-group, ``wire.quant_block`` elements per f32 scale —
    the kernel path packs the chunk to f32 first, the client-side encode,
    so the fold's peak temp matches the unquantized path) and folds it
    with the
    *dequantizing* accumulate — ``masked_agg_acc_deq`` on the kernel path,
    its XLA ref per leaf slice on CPU — so the *server side* never
    materializes a dequantized f32 copy of the uploads.  Quantization
    grouping is identical on both paths (groups never cross slots because
    ``quant_block`` divides the lane alignment).
    """
    mask, layout, flat_mask = spec.mask, spec.layout, spec.flat_mask
    block_n, stream_dtype, wire = spec.block_n, spec.stream_dtype, spec.wire
    w_in, w_out = _chunk_weights(is_simple, valid, spec.algorithm)
    layout = _layout_for(chunk, layout, block_n, stacked=True)
    quantized = wire is not None and wire.is_quantized
    if wire is not None and not wire.is_identity and not quantized:
        stream_dtype = wire.payload_dtype      # bf16 wire == bf16 stream
    if sparse_chunk is not None:
        if wire is None or not wire.uses_deltas:
            raise ValueError("sparse_chunk requires a delta-mode wire "
                             "(topk_frac < 1, stochastic or error_feedback)")
        if flat_mask is None:
            flat_mask = flatten.pack_mask(layout, mask)
        acc = _fold_sparse(state.acc, sparse_chunk, flat_mask, w_in, w_out,
                           quant_block=wire.quant_block, block_n=block_n,
                           force_pallas_interpret=force_pallas_interpret)
        acc_out = state.acc_out
        if acc_out is not None:                # decouple reuses the upload
            acc_out = _fold_sparse(
                acc_out, sparse_chunk, flat_mask, w_out, w_out,
                quant_block=wire.quant_block, block_n=block_n,
                force_pallas_interpret=force_pallas_interpret)
    elif force_pallas_interpret or agg_ops.use_pallas():
        if flat_mask is None:
            flat_mask = flatten.pack_mask(layout, mask)
        if quantized:
            xz = flatten.pack_stacked(layout, chunk, dtype=jnp.float32)
            q, scales = comm.quantize(xz, wire.quant_block)
            deq = functools.partial(
                agg_ops.masked_agg_acc_deq_pallas, q=q, scales=scales,
                mask=flat_mask, quant_block=wire.quant_block,
                block_n=block_n, interpret=force_pallas_interpret)
            acc = deq(state.acc, w_m=w_in, w_rest=w_out)
            acc_out = state.acc_out
            if acc_out is not None:            # decouple reuses the upload
                acc_out = deq(acc_out, w_m=w_out, w_rest=w_out)
        else:
            xz = flatten.pack_stacked(layout, chunk, dtype=stream_dtype)
            acc = agg_ops.masked_agg_acc_pallas(
                state.acc, xz, flat_mask, w_in, w_out, block_n=block_n,
                interpret=force_pallas_interpret)
            acc_out = state.acc_out
            if acc_out is not None:
                acc_out = agg_ops.masked_agg_acc_pallas(
                    acc_out, xz, flat_mask, w_out, w_out, block_n=block_n,
                    interpret=force_pallas_interpret)
    elif quantized:
        acc = _fold_leaves_into_flat_deq(state.acc, chunk, mask, layout,
                                         w_in, w_out, wire.quant_block)
        acc_out = state.acc_out
        if acc_out is not None:
            acc_out = _fold_leaves_into_flat_deq(
                acc_out, chunk, mask, layout, w_out, w_out,
                wire.quant_block)
    else:
        acc = _fold_leaves_into_flat(state.acc, chunk, mask, layout,
                                     w_in, w_out, stream_dtype)
        acc_out = state.acc_out
        if acc_out is not None:
            acc_out = _fold_leaves_into_flat(acc_out, chunk, mask, layout,
                                             w_out, w_out, stream_dtype)
    cv_acc = state.cv_acc
    if cv_chunk is not None:
        if cv_acc is None:
            raise ValueError("cv_chunk passed but the stream state has no "
                             "cv accumulator (init with a SCAFFOLD spec)")
        if flat_mask is None:                  # CPU path never packed one
            flat_mask = flatten.pack_mask(layout, mask)
        cv_acc = _fold_cv(cv_acc, cv_chunk, flat_mask, w_in, w_out,
                          block_n=block_n,
                          force_pallas_interpret=force_pallas_interpret)
    return StreamState(acc, acc_out, state.tot_in + jnp.sum(w_in),
                       state.tot_out + jnp.sum(w_out), cv_acc)


def _fold_sparse(acc: jax.Array, sp: SparseChunk, flat_mask: jax.Array,
                 w_in: jax.Array, w_out: jax.Array, *, quant_block: int,
                 block_n: int,
                 force_pallas_interpret: bool = False) -> jax.Array:
    """Fold one delta-mode chunk: ``sum_z w[z] * (base + d_hat[z])``
    rewritten as ``(sum_z w[z]) * base + sum_z w[z] * d_hat[z]``.

    The base term is ONE Z=1 masked accumulate at the summed weights
    (base is the server broadcast — always finite, so summed weights
    need no per-client NaN gating; an all-invalid chunk sums to weight
    0 and contributes nothing).  The delta term dispatches on payload
    shape: compacted index+value rows go through the scatter-fold
    kernel/ref, dense rows through the dequantizing (int8) or plain
    (bf16/f32) accumulate — same NaN/pad weight gating as every fold."""
    kernel = force_pallas_interpret or agg_ops.use_pallas()
    base = sp.base.astype(jnp.float32)[None, :]
    sw_in, sw_out = jnp.sum(w_in)[None], jnp.sum(w_out)[None]
    if kernel:
        acc = agg_ops.masked_agg_acc_pallas(
            acc, base, flat_mask, sw_in, sw_out, block_n=block_n,
            interpret=force_pallas_interpret)
    else:
        acc = agg_ops.masked_agg_acc_ref(acc, base, flat_mask, sw_in, sw_out)
    if sp.indices is not None:
        if kernel:
            return agg_ops.masked_scatter_acc_pallas(
                acc, sp.values, sp.scales, sp.indices, flat_mask, w_in,
                w_out, quant_block=quant_block, block_n=block_n,
                interpret=force_pallas_interpret)
        return agg_ops.masked_scatter_acc_ref(
            acc, sp.values, sp.scales, sp.indices, flat_mask, w_in, w_out,
            quant_block=quant_block)
    if sp.scales is not None:                  # dense int8 delta payload
        if kernel:
            return agg_ops.masked_agg_acc_deq_pallas(
                acc, sp.values, sp.scales, flat_mask, w_in, w_out,
                quant_block=quant_block, block_n=block_n,
                interpret=force_pallas_interpret)
        return agg_ops.masked_agg_acc_deq_ref(
            acc, sp.values, sp.scales, flat_mask, w_in, w_out,
            quant_block=quant_block)
    vals = sp.values.astype(jnp.float32)       # dense bf16/f32 delta payload
    if kernel:
        return agg_ops.masked_agg_acc_pallas(
            acc, vals, flat_mask, w_in, w_out, block_n=block_n,
            interpret=force_pallas_interpret)
    return agg_ops.masked_agg_acc_ref(acc, vals, flat_mask, w_in, w_out)


def _fold_cv(cv_acc: jax.Array, cv_chunk: jax.Array, flat_mask: jax.Array,
             w_in: jax.Array, w_out: jax.Array, *, block_n: int,
             force_pallas_interpret: bool = False) -> jax.Array:
    """Fold a ``(Z, n_flat)`` control-variate delta chunk into the running
    cv sum — the identical masked accumulate launch the params take, so
    SCAFFOLD rides the kernel (and its weight-0 NaN gating) for free.
    Control variates are born flat (they ARE FlatLayout vectors)."""
    cv32 = cv_chunk.astype(jnp.float32)
    if force_pallas_interpret or agg_ops.use_pallas():
        return agg_ops.masked_agg_acc_pallas(
            cv_acc, cv32, flat_mask, w_in, w_out, block_n=block_n,
            interpret=force_pallas_interpret)
    return agg_ops.masked_agg_acc_ref(cv_acc, cv32, flat_mask, w_in, w_out)


def _fold_leaves_into_flat(acc: jax.Array, chunk: Tree, mask: Tree,
                           layout: flatten.FlatLayout, w_m: jax.Array,
                           w_rest: jax.Array, stream_dtype) -> jax.Array:
    """CPU lowering of the flat fold: per-leaf gated sums accumulated into
    the flat accumulator's static slices (in-place dynamic-update-slices),
    without materializing the packed ``(Z, n_flat)`` buffer."""
    for x, m, slot in zip(jax.tree.leaves(chunk), jax.tree.leaves(mask),
                          layout.slots):
        z = x.shape[0]
        body = x.reshape(z, -1).astype(stream_dtype)
        m_flat = jnp.broadcast_to(jnp.asarray(m), x.shape[1:]).reshape(-1)
        seg = jax.lax.dynamic_slice_in_dim(acc, slot.offset, slot.size)
        seg = agg_ops.masked_agg_acc_ref(seg, body, m_flat, w_m, w_rest)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, seg, slot.offset, 0)
    return acc


def _fold_leaves_into_flat_deq(acc: jax.Array, chunk: Tree, mask: Tree,
                               layout: flatten.FlatLayout, w_m: jax.Array,
                               w_rest: jax.Array, quant_block: int
                               ) -> jax.Array:
    """CPU lowering of the quantized fold: each leaf slice is quantized to
    the wire format (padded to the slot's aligned extent so scale groups
    match the packed-buffer path element for element) and folded with the
    dequantizing ref — XLA fuses quantize -> dequant -> FMA per leaf, so
    no f32 copy of the whole chunk materializes."""
    for x, m, slot in zip(jax.tree.leaves(chunk), jax.tree.leaves(mask),
                          layout.slots):
        z = x.shape[0]
        body = x.reshape(z, -1).astype(jnp.float32)
        m_flat = jnp.broadcast_to(jnp.asarray(m), x.shape[1:]).reshape(-1)
        if slot.padded != slot.size:
            body = jnp.pad(body, ((0, 0), (0, slot.padded - slot.size)))
            m_flat = jnp.pad(m_flat, (0, slot.padded - slot.size))
        q, scales = comm.quantize(body, quant_block)
        seg = jax.lax.dynamic_slice_in_dim(acc, slot.offset, slot.padded)
        seg = agg_ops.masked_agg_acc_deq_ref(seg, q, scales, m_flat,
                                             w_m, w_rest,
                                             quant_block=quant_block)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, seg, slot.offset, 0)
    return acc


def streaming_finalize(state: StreamState, spec: EngineSpec,
                       template: Tree) -> Tuple[Tree, Optional[Tree]]:
    """Normalize the flat sums, unpack to trees, cast to ``template`` dtypes.

    Args:
      state: the fully folded :class:`StreamState`.
      spec: the :class:`EngineSpec` the state was folded with.
      template: tree providing the output leaf dtypes (shapes come from
        the layout; ``ShapeDtypeStruct`` leaves are fine).

    Returns: ``(new_complex, new_simple_host)``; the host is ``None`` except
    for decouple (matching ``ServerState``).  A group with zero total weight
    yields zeros, like ``_norm_weights`` in the one-shot path.
    ``state.cv_acc`` is deliberately NOT normalized here — SCAFFOLD's
    server update divides the raw delta sum by N_devices, not by the
    cohort weight totals (the round owns that step).
    """
    mask, layout, flat_mask = spec.mask, spec.layout, spec.flat_mask
    layout = _layout_for(template, layout, spec.block_n)
    if flat_mask is None:
        flat_mask = flatten.pack_mask(layout, mask)
    inv_in, inv_out = _safe_inv(state.tot_in), _safe_inv(state.tot_out)
    cast = lambda tree: jax.tree.map(
        lambda a, t: a.astype(t.dtype), tree, template)
    combined_flat = state.acc * jnp.where(flat_mask, inv_in, inv_out)
    combined = cast(flatten.unpack(layout, combined_flat, cast=False))
    if spec.algorithm == "decouple":
        new_complex = cast(flatten.unpack(layout, state.acc_out * inv_out,
                                          cast=False))
        return new_complex, combined
    return combined, None


def make_engine(spec: EngineSpec) -> Tuple[Callable, Callable, Callable]:
    """The ``(init, fold, finalize)`` triple for a configured fold.

    The single binding point every consumer (the trainer's round, the
    launch-side round step, benchmarks) takes its fold through:

    * ``init(params_like) -> state``
    * ``fold(state, chunk, is_simple, valid[, cv_chunk=...]) -> state``
    * ``finalize(state, template=...) -> (new_complex, simple_host)``

    The spec's ``wire`` routes the fold through the communication path
    (the uploads are what the server folds): bf16 wires ride the stream
    dtype, int8 wires use the dequantizing accumulate.
    """
    init = functools.partial(streaming_init, spec=spec)
    fold = functools.partial(streaming_fold, spec=spec)
    finalize = functools.partial(streaming_finalize, spec=spec)
    return init, fold, finalize


def engine_attrs(spec: EngineSpec) -> dict:
    """Static description of a configured fold, as plain scalars.

    What the telemetry ``run_config`` ledger records about the
    aggregation path — read off the same spec :func:`make_engine` binds,
    so the recorded configuration cannot drift from the one that runs.
    """
    attrs = {
        "algorithm": spec.algorithm,
        "agg_block_n": int(spec.block_n),
        "agg_stream_dtype": str(jnp.dtype(spec.stream_dtype)),
        "variance_reduction": spec.variance_reduction,
    }
    if spec.wire is not None:
        attrs.update({
            "wire_dtype": str(spec.wire.payload_dtype),
            "wire_quantized": bool(spec.wire.is_quantized),
            "wire_quant_block": int(spec.wire.quant_block)
            if spec.wire.is_quantized else 0,
            "wire_topk_frac": float(spec.wire.topk_frac),
            "wire_stochastic": bool(spec.wire.stochastic),
            "wire_error_feedback": bool(spec.wire.error_feedback),
        })
    return attrs

"""Quantized flat-buffer communication over ``core.flatten.FlatLayout``.

FedHeN's headline claim is *communication savings*; related systems (FedHe,
HeteroFL) make the savings concrete with reduced-payload exchange.  This
module is the wire layer of that claim: the PR 3 ``(Z, n_flat)`` packed
representation — one contiguous lane-aligned buffer per client — becomes
the unit of both directions of the protocol:

* **broadcast** (server -> client): the server's flat vector is encoded to
  the wire dtype and the client trains on the decoded copy, so the round
  sees the real quantization error;
* **upload** (client -> server): each trained chunk is encoded to the same
  wire format and the fold *dequantizes inside the accumulate* — the
  ``masked_agg`` kernel's ``masked_agg_acc_deq`` variant consumes int8
  payloads + per-group f32 scales directly, so no separate materialized
  f32 copy of the cohort ever exists on the server.

Wire formats (``WireSpec.dtype``):

* ``float32`` — the identity wire (paper accounting; no transform);
* ``bfloat16`` — 2-byte payload, no sidecar;
* ``int8`` — symmetric per-group quantization ``q = round(x / s)``,
  ``s = max|x| / 127`` per contiguous group of ``quant_block`` elements,
  plus an f32 scale sidecar (``ceil(n / quant_block)`` scales).

``quant_block`` must divide the layout's lane alignment (128), so a scale
group never crosses a ``LeafSlot`` boundary: quantization error is bounded
*per slot* by that slot's own magnitudes, alignment-padding groups are
all-zero (scale 0 -> payload 0 -> decode 0), and the CPU fallback can fold
leaf by leaf without changing group boundaries.

Byte accounting is **measured, not estimated**: ``wire_bytes`` runs the
real encoder under ``jax.eval_shape`` and sums the output buffer sizes, so
the trainer's per-round numbers are the encoder's actual output — payload
*and* sidecar — for the true (compact) element counts.  Alignment padding
is a local layout artifact the sender strips (offsets are static on both
ends), so it is never billed to the wire.

**Wire v2 — the compressed upload path.**  Three composable mechanisms
ride the *upload* direction only (the broadcast stays dense and
deterministic); any of them switches uploads from parameters to deltas
``d = y - x`` against the broadcast the client trained on
(``WireSpec.uses_deltas``), leaving every pre-existing configuration's
traced program untouched:

* **top-k sparsification** (``topk_frac < 1``): each client ships only
  the ``k`` largest-|d| entries as an index+value payload
  (:func:`sparse_encode`); ``k`` is the true element count times
  ``topk_frac``, rounded up to a lane multiple so int8 scale groups tile
  the compacted payload exactly.  The server folds the sparse payload
  through a scatter-fold ``masked_agg`` variant — no dense f32 cohort
  copy materializes.
* **stochastic rounding** (``stochastic=True``): the int8/bf16 encode
  rounds with per-client seeded random bits instead of
  round-to-nearest, making the quantizer unbiased so rounding noise
  averages out across the cohort.  The XLA implementation here is the
  bit-reproducible CPU reference for ``pltpu.stochastic_round``: int8
  takes ``floor(v + u)`` with ``u = bits * 2**-32``; bf16 adds the low
  16 random bits to the f32 bit pattern and truncates the mantissa.
* **error feedback** (``error_feedback=True``): each client keeps a
  residual row ``r`` in a second ``FlatStateStore``; it uploads
  ``encode(d + r)`` and keeps ``r' = (d + r) - decode(encode(d + r))``,
  so compression error is carried into the next round instead of lost.
  EF requires a lossy upload (a quantized/bf16 wire or ``topk_frac <
  1``) — on a lossless wire the residual is identically zero.

Upload billing under wire v2 is still measured: ``wire_bytes_up`` runs
the real sparse encoder under ``jax.eval_shape`` (values + scale sidecar
+ int32 indices) and degenerates to ``wire_bytes`` when ``topk_frac ==
1``.

Under the asynchronous round engine (``core/async_rounds.py``) broadcasts
are **version-tagged**: a chunk that trains on a stale version its clients
already hold does not re-download it.  The ``version_tag`` column of
``core.client_state`` keeps that accounting truthful — one download is
billed per (client, version), so the measured per-round download shrinks
exactly when a cached stale broadcast is reused, and degenerates to the
synchronous numbers at ``async_lag=0`` (every round publishes a fresh
version, so every client re-downloads).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import flatten

Tree = Any

WIRE_DTYPES = ("float32", "bfloat16", "int8")

# int8 symmetric range: +-127 (−128 unused, keeps the code symmetric)
_QMAX = 127.0


@dataclasses.dataclass(frozen=True)
class WireSpec:
    """Static description of the wire format for one federated link.

    ``dtype`` is the payload dtype; ``quant_block`` is the elements-per-
    scale group (int8 only; must divide the lane alignment so groups stay
    inside slots — see module docstring).  The wire-v2 upload knobs:
    ``topk_frac`` keeps that fraction of each upload's entries (top-k by
    magnitude, 1.0 = dense), ``stochastic`` switches the lossy encode to
    seeded stochastic rounding, ``error_feedback`` carries per-client
    compression-error residuals across rounds.  Any of the three moves
    uploads to delta space (``uses_deltas``); none touches the broadcast.
    """
    dtype: str = "float32"
    quant_block: int = 128
    topk_frac: float = 1.0
    stochastic: bool = False
    error_feedback: bool = False

    def __post_init__(self):
        if self.dtype not in WIRE_DTYPES:
            raise ValueError(f"wire dtype must be one of {WIRE_DTYPES}, "
                             f"got {self.dtype!r}")
        if self.quant_block <= 0 or flatten.LANES % self.quant_block:
            raise ValueError(f"quant_block must divide the lane alignment "
                             f"({flatten.LANES}), got {self.quant_block}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], got "
                             f"{self.topk_frac}")
        if self.stochastic and self.dtype == "float32":
            raise ValueError("stochastic rounding requires a lossy wire "
                             "dtype (bfloat16 or int8), not float32")
        if self.error_feedback and self.dtype == "float32" \
                and self.topk_frac == 1.0:
            raise ValueError(
                "error_feedback requires a lossy upload path (bfloat16/"
                "int8 wire or topk_frac < 1); on the dense float32 wire "
                "the residual is identically zero")

    @property
    def is_identity(self) -> bool:
        return self.dtype == "float32"

    @property
    def is_quantized(self) -> bool:
        return self.dtype == "int8"

    @property
    def is_sparse(self) -> bool:
        """True when uploads ship top-k index+value payloads."""
        return self.topk_frac < 1.0

    @property
    def uses_deltas(self) -> bool:
        """True when uploads are deltas against the broadcast (the wire-v2
        path).  False keeps the pre-existing params-space upload traced
        program byte-identical."""
        return self.is_sparse or self.stochastic or self.error_feedback

    @property
    def payload_dtype(self):
        return jnp.dtype(self.dtype)


class WireBuffer(NamedTuple):
    """One encoded flat buffer: payload in the wire dtype (+ the f32 scale
    sidecar for quantized wires, else ``None``)."""
    payload: jax.Array
    scales: Optional[jax.Array]


class SparseWireBuffer(NamedTuple):
    """One top-k encoded flat buffer: the ``k`` kept values in the wire
    dtype (+ the f32 scale sidecar over the *compacted* payload for
    quantized wires), and their int32 flat positions."""
    payload: jax.Array
    scales: Optional[jax.Array]
    indices: jax.Array


def buffer_nbytes(buf: WireBuffer) -> int:
    """Measured wire size of one encoded buffer (payload + sidecar).
    Works on concrete arrays and ``ShapeDtypeStruct``s alike."""
    n = buf.payload.size * jnp.dtype(buf.payload.dtype).itemsize
    if buf.scales is not None:
        n += buf.scales.size * jnp.dtype(buf.scales.dtype).itemsize
    return int(n)


def sparse_buffer_nbytes(buf: SparseWireBuffer) -> int:
    """Measured wire size of one sparse upload: values + scale sidecar +
    int32 index payload (the indices are real traffic — billing them is
    what makes the top-k ratio honest)."""
    n = buffer_nbytes(WireBuffer(buf.payload, buf.scales))
    return n + int(buf.indices.size
                   * jnp.dtype(buf.indices.dtype).itemsize)


# ---------------------------------------------------------------------------
# Stochastic rounding (bit-reproducible CPU reference for
# pltpu.stochastic_round: uint32 bits drive both shapes)
# ---------------------------------------------------------------------------

def random_round_bits(key: jax.Array, shape) -> jax.Array:
    """Uniform uint32 rounding bits — the CPU-side stand-in for
    ``pltpu.prng_random_bits`` (one 32-bit word per element)."""
    return jax.random.bits(key, shape, jnp.uint32)


def stochastic_round_int(v: jax.Array, bits: jax.Array) -> jax.Array:
    """Stochastically round pre-scaled values to integers:
    ``floor(v + u)`` with ``u = bits * 2**-32`` uniform in [0, 1), so
    ``E[result] = v`` exactly.  Clipped to the symmetric int8 range
    (f32 addition can round ``127 + u`` up to 128.0)."""
    u = bits.astype(jnp.float32) * jnp.float32(2.0 ** -32)
    return jnp.clip(jnp.floor(v + u), -_QMAX, _QMAX)


def stochastic_round_bf16(x: jax.Array, bits: jax.Array) -> jax.Array:
    """Stochastic f32 -> bf16: add the low 16 random bits to the f32 bit
    pattern and truncate the mantissa — the carry into the kept bits
    fires with probability equal to the dropped fraction, so the
    rounding is unbiased in magnitude (and, by sign symmetry of the
    payload format, in value).  This is the mantissa-truncation shape
    ``pltpu.stochastic_round`` implements in hardware."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + (bits & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Quantize / dequantize (symmetric per-group int8)
# ---------------------------------------------------------------------------

def quantize(x: jax.Array, quant_block: int, *,
             key: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-group int8 quantization.

    Args:
      x: ``(..., n)`` values (cast to f32 internally); ``n`` must be a
        multiple of ``quant_block``.  Leading axes (cohort ``Z``, version
        stack ``V``) are batched through unchanged.
      quant_block: elements per scale group, ``s = max|group| / 127``.
      key: optional PRNG key — when given, round with
        :func:`stochastic_round_int` (unbiased) instead of
        round-to-nearest.  ``None`` keeps the deterministic encode
        bit-identical to the pre-v2 wire.

    Returns: ``(q, scales)`` with ``q`` int8 of ``x``'s shape and
    ``scales`` f32 of shape ``(..., n / quant_block)``.

    All-zero groups get scale 0 and payload 0 (decode is exactly 0, so
    alignment padding stays invisible to any sum).  Non-finite inputs
    produce a non-finite scale; the fold's weight gating zeroes those
    devices before the multiply, mirroring the f32 NaN-device contract.
    """
    n = x.shape[-1]
    if n % quant_block:
        raise ValueError(f"length {n} not a multiple of "
                         f"quant_block={quant_block}")
    g = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, quant_block))
    scales = jnp.max(jnp.abs(g), axis=-1) / _QMAX
    v = g / jnp.maximum(scales[..., None], 1e-30)
    if key is None:
        q = jnp.round(v)
    else:
        q = stochastic_round_int(v, random_round_bits(key, v.shape))
    q = jnp.where(scales[..., None] > 0, q, 0.0)
    q = jnp.clip(q, -_QMAX, _QMAX).astype(jnp.int8)
    return q.reshape(x.shape), scales


def dequantize(q: jax.Array, scales: jax.Array,
               quant_block: int) -> jax.Array:
    """Inverse of :func:`quantize`.

    Args:
      q: int8 payload ``(..., n)`` (``n`` a multiple of ``quant_block``).
      scales: f32 ``(..., n / quant_block)`` per-group scales.
      quant_block: the grouping both were produced with.

    Returns: f32 ``(..., n)`` — ``q * scale`` per group.  The server-side
    fold never calls this on uploads; the dequantizing ``masked_agg``
    accumulate fuses it into the FMA instead."""
    g = q.astype(jnp.float32).reshape(q.shape[:-1] + (-1, quant_block))
    return (g * scales[..., None]).reshape(q.shape)


# ---------------------------------------------------------------------------
# Encode / decode (one flat vector or a stacked (Z, n) chunk)
# ---------------------------------------------------------------------------

def encode(spec: WireSpec, flat: jax.Array, *,
           key: Optional[jax.Array] = None) -> WireBuffer:
    """Encode a flat vector for the wire.

    Args:
      spec: the wire format.
      flat: ``(..., n)`` f32 values — one packed model per trailing
        vector; leading axes (version stack, cohort) batch through.
      key: optional PRNG key — with ``spec.stochastic`` the lossy encode
        (int8 quantize / bf16 cast) rounds stochastically.  Callers on
        the broadcast path never pass one, so the downlink stays
        deterministic; only the per-client upload encode seeds it.

    Returns: a :class:`WireBuffer` — payload in ``spec.payload_dtype`` of
    ``flat``'s shape, plus the f32 scale sidecar for int8 wires.  Lengths
    that are not a group multiple are zero-padded into the last group (the
    sidecar covers ``ceil(n / quant_block)`` groups); payload keeps the
    caller's length."""
    key = key if spec.stochastic else None
    if spec.is_quantized:
        n = flat.shape[-1]
        pad = (-n) % spec.quant_block
        body = jnp.pad(flat.astype(jnp.float32),
                       [(0, 0)] * (flat.ndim - 1) + [(0, pad)]) \
            if pad else flat
        q, scales = quantize(body, spec.quant_block, key=key)
        return WireBuffer(q[..., :n], scales)
    if spec.dtype == "bfloat16" and key is not None:
        bits = random_round_bits(key, flat.shape)
        return WireBuffer(stochastic_round_bf16(flat, bits), None)
    return WireBuffer(flat.astype(spec.payload_dtype), None)


def decode(spec: WireSpec, buf: WireBuffer) -> jax.Array:
    """Decode a wire buffer back to values.

    Args:
      spec: the wire format the buffer was encoded with.
      buf: payload ``(..., n)`` (+ scales for int8).

    Returns: f32 ``(..., n)`` of the payload's length — what a client
    actually trains on (the broadcast's real quantization error included).
    """
    if spec.is_quantized:
        n = buf.payload.shape[-1]
        pad = (-n) % spec.quant_block
        q = jnp.pad(buf.payload, [(0, 0)] * (buf.payload.ndim - 1)
                    + [(0, pad)]) if pad else buf.payload
        return dequantize(q, buf.scales, spec.quant_block)[..., :n]
    return buf.payload.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Top-k sparse encode / decode (wire v2 uploads)
# ---------------------------------------------------------------------------

def topk_count(spec: WireSpec, n_elements: int) -> int:
    """Entries a sparse upload of ``n_elements`` true elements keeps:
    ``ceil(n * topk_frac)`` rounded up to a lane multiple (128), so int8
    scale groups tile the compacted payload exactly and the payload stays
    lane-aligned.  Dense specs keep everything."""
    if not spec.is_sparse:
        return int(n_elements)
    k = max(1, math.ceil(n_elements * spec.topk_frac))
    return -(-k // flatten.LANES) * flatten.LANES


def sparse_encode(spec: WireSpec, flat: jax.Array, k: int, *,
                  key: Optional[jax.Array] = None) -> SparseWireBuffer:
    """Top-k encode one flat vector: keep the ``k`` largest-|x| entries,
    encode the compacted values through the dense wire encoder (int8
    scale groups cover the compacted payload), and ship their sorted
    int32 flat positions alongside.

    Args:
      spec: the wire format; ``k`` must be a ``quant_block`` multiple
        (``topk_count`` guarantees a lane multiple) and ``<= n``.
      flat: ``(n,)`` f32 values (one client's delta).
      key: optional PRNG key for stochastic rounding of the values.

    Returns: a :class:`SparseWireBuffer`.  Indices are sorted ascending —
    deterministic, and scale groups over the compacted payload then
    cover position-contiguous runs of the flat vector."""
    flat = flat.astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = jnp.sort(idx).astype(jnp.int32)
    dense = encode(spec, jnp.take(flat, idx), key=key)
    return SparseWireBuffer(dense.payload, dense.scales, idx)


def sparse_decode_values(spec: WireSpec, buf: SparseWireBuffer
                         ) -> jax.Array:
    """Decode only the compacted ``(..., k)`` values of a sparse buffer
    (what the scatter-fold consumes together with ``buf.indices``)."""
    return decode(spec, WireBuffer(buf.payload, buf.scales))


def sparse_decode(spec: WireSpec, buf: SparseWireBuffer,
                  n: int) -> jax.Array:
    """Reference dense decode of one sparse upload: the decoded values
    scattered into an ``(n,)`` f32 zero vector.  The server fold never
    calls this — the scatter-fold ``masked_agg`` variant accumulates the
    compacted payload directly — but tests and the EF residual math pin
    their semantics against it."""
    vals = sparse_decode_values(spec, buf)
    return jnp.zeros((n,), jnp.float32).at[buf.indices].add(vals)


# ---------------------------------------------------------------------------
# Measured byte accounting
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def wire_bytes(spec: WireSpec, n_elements: int) -> int:
    """Measured wire size of an ``n_elements`` exchange: the real encoder's
    output buffers under ``jax.eval_shape`` (no compute), payload + scale
    sidecar.  This is what the trainer bills per client per direction."""
    buf = jax.eval_shape(functools.partial(encode, spec),
                         jax.ShapeDtypeStruct((n_elements,), jnp.float32))
    return buffer_nbytes(buf)


def analytic_wire_bytes(spec: WireSpec, n_elements: int) -> int:
    """Closed-form size the measured number must match (consistency test):
    ``n * itemsize`` plus ``ceil(n / quant_block) * 4`` for int8."""
    n = n_elements * spec.payload_dtype.itemsize
    if spec.is_quantized:
        n += (-(-n_elements // spec.quant_block)) * 4
    return n


@functools.lru_cache(maxsize=None)
def wire_bytes_up(spec: WireSpec, n_elements: int) -> int:
    """Measured size of one *upload* of ``n_elements`` true elements.

    Dense wires bill exactly :func:`wire_bytes` (the upload payload has
    the broadcast's shape, delta-space or not).  Sparse wires run the
    real top-k encoder under ``jax.eval_shape``: values payload + scale
    sidecar + int32 indices for ``topk_count(spec, n_elements)`` kept
    entries — the same ``k`` the runtime encode uses, so this is the
    byte-exact size of the buffers a client actually ships."""
    if not spec.is_sparse:
        return wire_bytes(spec, n_elements)
    k = topk_count(spec, n_elements)
    # eval_shape only needs a vector long enough for top_k's k
    n_vec = max(-(-n_elements // flatten.LANES) * flatten.LANES, k)
    buf = jax.eval_shape(
        functools.partial(sparse_encode, spec, k=k),
        jax.ShapeDtypeStruct((n_vec,), jnp.float32))
    return sparse_buffer_nbytes(buf)


def analytic_wire_bytes_up(spec: WireSpec, n_elements: int) -> int:
    """Closed-form upload size the measured number must match:
    ``k * itemsize`` values + ``k/quant_block * 4`` scales (int8) +
    ``k * 4`` int32 indices, with ``k = topk_count``."""
    if not spec.is_sparse:
        return analytic_wire_bytes(spec, n_elements)
    k = topk_count(spec, n_elements)
    n = k * spec.payload_dtype.itemsize + k * 4
    if spec.is_quantized:
        n += (k // spec.quant_block) * 4
    return n


# ---------------------------------------------------------------------------
# Tree-level paths (broadcast + checkpoint reuse the same encoder)
# ---------------------------------------------------------------------------

def encode_tree(spec: WireSpec, layout: flatten.FlatLayout,
                tree: Tree) -> WireBuffer:
    """Pack a parameter tree through ``layout`` and encode the flat vector
    — the broadcast/checkpoint unit (one contiguous buffer per model)."""
    return encode(spec, flatten.pack(layout, tree))


def decode_tree(spec: WireSpec, layout: flatten.FlatLayout,
                buf: WireBuffer, template: Optional[Tree] = None) -> Tree:
    """Decode a wire buffer and unpack to the layout's tree (leaf dtypes
    from the layout).  When ``template`` is given its treedef must equal
    the layout's — a mismatch means the buffer would unpack into the
    wrong structure."""
    if template is not None and \
            jax.tree.structure(template) != layout.treedef:
        raise ValueError("template treedef does not match the layout's")
    return flatten.unpack(layout, decode(spec, buf))


def broadcast_roundtrip(spec: WireSpec, layout: flatten.FlatLayout,
                        tree: Tree) -> Tree:
    """What a client receives: the server tree after one encode/decode trip
    through the wire.  Identity (no ops traced) for the f32 wire."""
    if spec.is_identity:
        return tree
    return decode_tree(spec, layout, encode_tree(spec, layout, tree))

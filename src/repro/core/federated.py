"""Federated runtime: local client training, server state, round functions.

Implements the paper's three algorithms over any adapter:

* ``fedhen``   — Alg. 1 + Alg. 2 (side objective on complex devices)
* ``noside``   — Alg. 4 (HeteroFL-style: same server step, no side objective)
* ``decouple`` — Alg. 3 (two independent FedAvg runs)

Local training (Alg. 2): E epochs of minibatch SGD, eta, global-norm clip 10,
per-device NaN exclusion (Appendix A).

**Streaming contract.**  A round is one jit (inputs donated): each
population (simple, then complex) is split into chunks of
``FedConfig.cohort_chunk`` clients, and ``lax.scan`` trains the clients
chunk by chunk, folding each trained chunk into running masked
aggregation sums (``aggregate.streaming_fold``, the ``masked_agg`` kernel's
contract) that are normalized once at the end of the round
(``aggregate.streaming_finalize``).  Within a chunk the clients train one
after another (``lax.map``), each from the same broadcast.  Device memory
is therefore O(chunk), not O(k), and the training activations O(one
client), so cohorts of hundreds of clients stream through a fixed-size
working set.  ``cohort_chunk=0`` trains each population in a single
chunk; ``cohort_chunk="auto"`` derives the chunk from the flat layout's
per-client byte footprint against ``FedConfig.agg_memory_budget_mb``
(``flatten.auto_cohort_chunk`` — the resolved value is
``FederatedTrainer.cohort_chunk``).  Populations the chunk size does not
divide are padded with zero-validity clients (wrapped data, weight 0), so
padding can never change the aggregate; per-client RNG keys are derived
by ``fold_in(population_key, client_index)``, so the round's result is
invariant to the chunking up to float summation order.
On the production mesh the chunk axis is sharded over ``data``/``pod``
(see launch/), making the per-chunk fold an all-reduce: the communication
the paper saves.

**Flat layout contract.**  The trainer builds ONE static
``core.flatten.FlatLayout`` for the complex treedef at ``__init__``
(offsets are a pure function of treedef + leaf shapes + ``agg_block_n``,
so the layout is valid for every round and checkpoint restore) and
precomputes the index-set-M mask as one flat bitvector.  Each trained
chunk is packed into a single contiguous ``(Z, n_flat)`` buffer — in
``agg_stream_dtype`` (bf16 halves fold read traffic; accumulation is
always f32) — and the whole fold is ONE accumulating ``masked_agg_acc``
launch updating the flat running sum in place.

**Wire contract.**  ``FedConfig.comm_dtype`` selects the round's wire
format (``core/comm.py``): the server broadcast is encoded/decoded through
it before clients train (so the round sees the real quantization error),
and client uploads are folded through it — the int8 wire via the
dequantizing ``masked_agg`` accumulate, so the server never materializes
an f32 copy of the uploads.  Per-round byte accounting is *measured* from
the encoder's real output sizes (payload + scale sidecar, download and
upload separately), replacing the old analytic estimate (kept as
``analytic_bytes_per_round`` — the consistency oracle).

**Wire v2 (compressed uploads).**  When the wire ``uses_deltas``
(``topk_frac < 1``, ``stochastic_rounding`` or ``error_feedback``),
clients upload the encoded DELTA vs the decoded broadcast they trained
on instead of full params: each chunk packs ``x`` (its broadcast) and
``y`` (its trained result), encodes ``d = y - x`` — plus the client's
gathered error-feedback residual row when EF is on, whose update
``r' = (d + r) - decode(encode(d + r))`` keeps what the lossy encode
dropped for the next participation — and the server folds
``(sum_z w_z) * x`` densely plus every encoded delta row
(``aggregate.SparseChunk``; top-k payloads through the scatter-fold
kernel).  Residual rows live in a second ``FlatStateStore``
(``FederatedTrainer.ef_store``, gathered/scattered per round exactly
like SCAFFOLD's control variates; row norms feed the scalar matrix's
``ef_scale`` column).  With every v2 knob at its default the upload
path is the pre-existing program, bit for bit (test-pinned).

**Async contract.**  With ``FedConfig.async_lag > 0`` the trainer
delegates ``run_round`` to ``core/async_rounds.AsyncRoundEngine``: chunk
``t`` of a round trains on the version-tagged server params published at
fold ``t - async_lag`` of the global fold stream (the first ``async_lag``
chunks overlap the previous round's fold and carry a stale broadcast),
and stale uploads fold with the polynomial staleness decay
``1/(1+s)^async_decay`` multiplied into the same validity-weight path the
NaN/padding exclusion uses.  ``async_lag=0`` IS this module's synchronous
engine, bit-for-bit (test-enforced).  Download accounting becomes
version-aware under async (the ``version_tag`` column of
``core.client_state``): reused stale broadcasts are not re-billed, so
``total_bytes_down`` is measured per round instead of a static per-round
constant.

Cohort composition is stratified (k_s simple + k_c complex per round, the
expectation of the paper's uniform 10% sampling) so shapes stay static;
``sample_uniform=True`` recovers uniform sampling via validity-weight
padding.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core import aggregate, client_state, comm, flatten, masking
from repro.core import sampling, state_store
from repro.obs import telemetry as obslib
from repro.obs.scopes import stage
from repro.optim.sgd import sgd_update

Tree = Any
Batch = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Local client optimization (Alg. 2)
# ---------------------------------------------------------------------------

def make_client_trainer(loss_fn: Callable[[Tree, Batch], jax.Array],
                        fed: FedConfig, *,
                        cv_layout: Optional[flatten.FlatLayout] = None):
    """Returns train(params, data, rng[, corr_flat]) -> (params', mean_loss).

    data: dict of arrays with leading dim N_i (the client's local dataset).
    Runs E epochs of shuffled minibatch SGD with global-norm clipping.

    ``cv_layout`` (SCAFFOLD): when set, ``train`` takes a fourth argument
    — the client's packed gradient correction ``corr = c - c_i`` (already
    masked to the population's trainable slice by the caller) — unpacked
    through this layout once and ADDED to every minibatch gradient before
    the clipped SGD update (Karimireddy et al. 2020 option II: the clip,
    like the step, acts on the corrected gradient).
    """

    def train(params: Tree, data: Batch, rng: jax.Array,
              corr_flat: Optional[jax.Array] = None):
        n = jax.tree.leaves(data)[0].shape[0]
        steps = max(n // fed.batch_size, 1)
        server_params = params  # the received server model (FedProx anchor)
        corr = (flatten.unpack(cv_layout, corr_flat, cast=False)
                if cv_layout is not None else None)

        def full_loss(p, batch):
            loss = loss_fn(p, batch)
            if fed.prox_mu:
                sq = sum(jnp.sum(jnp.square(a.astype(jnp.float32) -
                                            b.astype(jnp.float32)))
                         for a, b in zip(jax.tree.leaves(p),
                                         jax.tree.leaves(server_params)))
                loss = loss + 0.5 * fed.prox_mu * sq
            return loss

        def epoch(params, key):
            perm = jax.random.permutation(key, n)
            idxs = perm[:steps * fed.batch_size].reshape(steps,
                                                         fed.batch_size)

            def step(params, idx):
                batch = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), data)
                loss, grads = jax.value_and_grad(full_loss)(params, batch)
                if corr is not None:
                    grads = jax.tree.map(
                        lambda g, c: g + c.astype(g.dtype), grads, corr)
                return sgd_update(params, grads, fed.lr, fed.clip_norm), loss

            return jax.lax.scan(step, params, idxs)

        keys = jax.random.split(rng, fed.local_epochs)
        params, losses = jax.lax.scan(epoch, params, keys)
        return params, jnp.mean(losses)

    return train


def local_step_count(data: Batch, fed: FedConfig) -> int:
    """Static SGD step count K one client runs on ``data`` — the divisor
    of SCAFFOLD's option-II delta ``(x - y) / (K * lr)``.  ``data`` is
    the STACKED population batch ``(k, N_i, ...)``; mirrors
    ``make_client_trainer``'s ``steps * local_epochs`` exactly."""
    n = jax.tree.leaves(data)[0].shape[1]
    return max(n // fed.batch_size, 1) * fed.local_epochs


class ScaffoldCtx(NamedTuple):
    """Per-population SCAFFOLD context threaded through one chunk stream.

    ``rows``: the cohort's gathered ``(k, n_flat)`` control variates
    ``c_i`` (``FlatStateStore.gather``).  ``c_global``: the server's
    ``(n_flat,)`` control variate ``c``.  ``pop_mask``: flat bool mask of
    the slice this population trains (simple clients own only M — their
    correction and delta live on M alone); ``None`` = whole vector.
    ``layout``: the trainer's FlatLayout (packs ``x`` and ``y``).
    ``inv_k_lr``: the static scalar ``1 / (K * lr)``.
    """
    rows: jax.Array
    c_global: jax.Array
    pop_mask: Optional[jax.Array]
    layout: Any
    inv_k_lr: float


# fold_in tag deriving a client's wire-encode key from its training key:
# the stochastic-rounding bit stream must be independent of the SGD
# stream, and deriving from the same per-client base key keeps the
# encode invariant to chunk placement (like the training RNG)
_WIRE_KEY_TAG = 0x57495245          # "WIRE"


class WireUploadCtx(NamedTuple):
    """Per-population wire-v2 upload context threaded through one chunk
    stream (delta-mode encode; active iff ``WireSpec.uses_deltas``).

    ``spec``: the round's wire.  ``layout``: the trainer's FlatLayout
    (packs the broadcast ``x`` and trained result ``y``; the upload is
    the encoded delta ``y - x``).  ``k_top``: static top-k payload
    length for this population — ``comm.topk_count`` of its TRUE
    element count (simple clients' deltas are identically zero outside
    M, so their budget is |M|).  ``ef_rows``: the cohort's gathered
    ``(k, n_flat)`` error-feedback residuals
    (``FlatStateStore.gather``); ``None`` when ``error_feedback`` is
    off."""
    spec: comm.WireSpec
    layout: Any
    k_top: int
    ef_rows: Optional[jax.Array]


# ---------------------------------------------------------------------------
# The chunk-stream scan (shared by the sync round and the async engine)
# ---------------------------------------------------------------------------

def chunk_geometry(k: int, cohort_chunk: int) -> Tuple[int, int]:
    """(chunk, n_chunks) of one population's scan: ``chunk <= k``, the
    population padded up to a chunk multiple with zero-validity clients."""
    chunk = k if cohort_chunk <= 0 else min(cohort_chunk, k)
    return chunk, -(-k // chunk)


def stream_population(state, get_src, train_fn, data, key, agg_fold, *,
                      k: int, chunk: int, n_chunks: int,
                      is_simple_flag: bool, skip_nan: bool,
                      version_idx=None, staleness_w=None,
                      real_mask=None, scaffold: Optional[ScaffoldCtx] = None,
                      upload: Optional[WireUploadCtx] = None):
    """Scan over one population's chunks: train + fold into running sums.

    The ONE chunk-stream implementation — the synchronous round and the
    asynchronous engine (``core/async_rounds.py``) both call it, so the
    two engines cannot drift (the async lag=0 bit-parity gate covers
    exactly the extras below).

    Args:
      state: the running aggregation state (``agg_fold``'s carry).
      get_src: ``get_src(version_idx_or_None) -> params tree`` — the
        broadcast one chunk trains on.  The sync round ignores the
        argument (one fresh broadcast); the async engine dynamic-indexes
        its version stack with it.
      train_fn / data / key / agg_fold: the population's client trainer,
        stacked client datasets (leading dim ``k``), population RNG key
        (per-client keys are ``fold_in(key, i)``), and the engine's fold.
      k / chunk / n_chunks: the population's static chunk geometry
        (:func:`chunk_geometry`).  ``k`` is padded up to
        ``n_chunks * chunk`` with zero-validity clients (wrapped data) so
        shapes stay static; padding never reaches the aggregate or the
        loss metric.
      is_simple_flag / skip_nan: population membership constant and the
        NaN-device exclusion toggle.
      version_idx / staleness_w: the async extras — per-chunk
        ``(n_chunks,)`` broadcast version index (handed to ``get_src``)
        and staleness coefficient (multiplied into validity as f32, the
        shared masked-weight path).  ``None``/``None`` keeps validity
        bool: the synchronous engine's exact program.
      real_mask: optional ``(k,)`` bool — which of the ``k`` slots hold a
        distinct sampled client (uniform super-cohort mode,
        ``core/sampling.py``: unfilled slots wrap drawn ids and must fold
        at weight 0).  ``None`` (stratified mode) keeps every slot real —
        the exact pre-existing program, traced with no mask input.  The
        mean loss normalizes by the realized client count.
      scaffold: optional :class:`ScaffoldCtx`.  When set, each chunk (a)
        corrects every client's local gradients by ``c - c_i`` (unpacked
        inside the client trainer), (b) computes the option-II delta
        ``dc = (x - y)/(K*lr) - c`` from the packed broadcast/result
        vectors, (c) folds ``dc`` into the engine's second flat
        accumulator with the SAME per-client weights as the params, and
        (d) stacks the updated rows ``c_i + dc`` (invalid clients keep
        their old row) as scan outputs.  ``None`` traces the literal
        pre-existing program — ``variance_reduction="none"`` stays
        bit-identical.
      upload: optional :class:`WireUploadCtx` (wire v2).  When set, each
        chunk uploads the encoded DELTA ``d = y - x`` vs the broadcast it
        trained on instead of dense params: (a) the client's gathered EF
        residual (if any) is added before the encode, (b) the encode is
        top-k and/or stochastic per the spec (per-client encode keys are
        ``fold_in(client_key, _WIRE_KEY_TAG)``), (c) the fold consumes an
        :class:`aggregate.SparseChunk` — base ``x`` densely at the summed
        weights plus each encoded delta row, so the dense uploads never
        materialize — and (d) the new residuals
        ``r' = (d + r) - decode(encoded)`` ride out as scan outputs
        (invalid/NaN clients keep their old row).  ``None`` traces the
        literal pre-existing upload path.

    Returns: ``(state, mean_loss, n_valid, cv_rows, ef_rows)`` —
    ``cv_rows`` is the ``(k, n_flat)`` updated control variates (``None``
    without ``scaffold``), ``ef_rows`` the updated error-feedback
    residuals (``None`` without EF).  Pad rows are sliced off both, but
    the HOST still must scatter only real slots — pad slots wrap real
    clients' ids.
    """
    k_pad = n_chunks * chunk
    wrap = jnp.arange(k_pad) % k
    if k_pad != k:
        data = jax.tree.map(lambda x: jnp.take(x, wrap, axis=0), data)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(k_pad))
    real = jnp.arange(k_pad) < k
    denom = jnp.asarray(k, jnp.float32)
    if real_mask is not None:
        real = real & jnp.pad(jnp.asarray(real_mask, bool),
                              (0, k_pad - k))
        denom = jnp.maximum(jnp.sum(real.astype(jnp.float32)), 1.0)

    to_chunks = lambda x: x.reshape((n_chunks, chunk) + x.shape[1:])
    is_async = version_idx is not None
    xs = (jax.tree.map(to_chunks, data), to_chunks(keys), to_chunks(real))
    if is_async:
        xs = xs + (version_idx, staleness_w)
    if scaffold is not None:
        rows = scaffold.rows
        if k_pad != k:
            rows = jnp.take(rows, wrap, axis=0)
        xs = xs + (to_chunks(rows),)
    cv_pos = len(xs) - 1
    ef_on = upload is not None and upload.ef_rows is not None
    if ef_on:
        ef = upload.ef_rows
        if k_pad != k:
            ef = jnp.take(ef, wrap, axis=0)
        xs = xs + (to_chunks(ef),)
    ef_pos = len(xs) - 1
    is_simple = jnp.full((chunk,), is_simple_flag)

    def train_chunk(src, *per_client):
        """The chunk's clients trained one after another from ``src``;
        ``per_client`` holds the data, keys and (SCAFFOLD) corrections,
        stacked ``(chunk, ...)``, and so do the trained trees and losses.
        Vmapped over per-client weights, a convolution would become a
        grouped convolution over the client axis, which the TPU lays out
        apart from the ops around it and copies every activation between
        the two layouts; a dense client's matmuls gain nothing from the
        batch either, and in sequence one client's activations are live
        at a time."""
        return jax.lax.map(lambda xs: train_fn(src, *xs), per_client)

    def _mask_pop(v):
        """Zero a (Z, n_flat) cv vector outside the population's slice."""
        if scaffold.pop_mask is None:
            return v
        return jnp.where(scaffold.pop_mask[None], v, 0.0)

    def _encode_upload(x_flat, y_flat, ef_i, keys_i, valid):
        """Wire v2: encode the chunk's deltas ``y - x`` (plus each
        client's EF residual ``ef_i``) -> the fold's
        :class:`aggregate.SparseChunk` and the new residual rows."""
        spec_w = upload.spec
        d = (y_flat.astype(jnp.float32)
             - x_flat.astype(jnp.float32)[None])
        d_in = d + ef_i if ef_on else d
        enc_keys = jax.vmap(
            lambda kk: jax.random.fold_in(kk, _WIRE_KEY_TAG))(keys_i)
        if spec_w.is_sparse:
            buf = jax.vmap(lambda v, kk: comm.sparse_encode(
                spec_w, v, upload.k_top, key=kk))(d_in, enc_keys)
            sp = aggregate.SparseChunk(x_flat.astype(jnp.float32),
                                       buf.payload, buf.scales,
                                       buf.indices)
            if ef_on:
                dec = jax.vmap(lambda b: comm.sparse_decode_values(
                    spec_w, b))(buf)
                r_new = jax.vmap(
                    lambda v, ix, dv: v.at[ix].add(-dv))(
                        d_in, buf.indices, dec)
        else:
            buf = jax.vmap(lambda v, kk: comm.encode(
                spec_w, v, key=kk))(d_in, enc_keys)
            sp = aggregate.SparseChunk(x_flat.astype(jnp.float32),
                                       buf.payload, buf.scales, None)
            if ef_on:
                r_new = d_in - jax.vmap(
                    lambda b: comm.decode(spec_w, b))(buf)
        ef_out = None
        if ef_on:
            # r' = (d + r) - decode(encode(d + r)); NaN clients keep
            # their residual row, like cv rows
            ef_out = jnp.where(valid[:, None], r_new, ef_i)
        return sp, ef_out

    def fold_chunk(carry, xs):
        state, loss_sum, valid_sum = carry
        if is_async:
            data_i, keys_i, real_i, idx_i, w_i = xs[:5]
        else:
            data_i, keys_i, real_i = xs[:3]
            idx_i = None
        src = get_src(idx_i)
        with stage("local_sgd"):
            if scaffold is None:
                trained, losses = train_chunk(src, data_i, keys_i)
            else:
                cv_i = xs[cv_pos]
                corr = _mask_pop(scaffold.c_global[None] - cv_i)
                trained, losses = train_chunk(src, data_i, keys_i, corr)
            valid = real_i
            if skip_nan:
                valid = valid & jax.vmap(masking.tree_isfinite)(trained)
        with stage("fold"):
            fold_valid = (valid.astype(jnp.float32) * w_i if is_async
                          else valid)
        # x is the decoded broadcast this chunk trained on (async: its
        # selected stale version), y the trained result — shared by the
        # SCAFFOLD delta and the wire-v2 delta encode, so the packing
        # belongs to the wire when there is an upload to encode
        x_flat = y_flat = None
        if scaffold is not None or upload is not None:
            pack_layout = (scaffold.layout if scaffold is not None
                           else upload.layout)
            with stage("fold" if upload is None else "wire"):
                x_flat = flatten.pack(pack_layout, src)
                y_flat = flatten.pack_stacked(pack_layout, trained)
        rows_out = ef_out = None
        fold_kw = {}
        if scaffold is not None:
            with stage("fold"):
                # option II: dc = (x - y)/(K*lr) - c on the trained slice
                dc = _mask_pop((x_flat[None] - y_flat) * scaffold.inv_k_lr
                               - scaffold.c_global[None])
                fold_kw["cv_chunk"] = dc
                # NaN clients fold at weight 0 (dc gated in the kernel)
                # AND keep their previous row — a NaN row must never
                # persist
                rows_out = jnp.where(valid[:, None], cv_i + dc, cv_i)
        if upload is None:
            with stage("fold"):
                state = agg_fold(state, trained, is_simple, fold_valid,
                                 **fold_kw)
        else:
            ef_i = xs[ef_pos] if ef_on else None
            with stage("wire"):
                sp, ef_out = _encode_upload(x_flat, y_flat, ef_i, keys_i,
                                            valid)
            with stage("fold"):
                state = agg_fold(state, None, is_simple, fold_valid,
                                 sparse_chunk=sp, **fold_kw)
        loss_sum = loss_sum + jnp.sum(jnp.where(real_i, losses, 0.0))
        valid_sum = valid_sum + jnp.sum(valid)
        return (state, loss_sum, valid_sum), (rows_out, ef_out)

    zero = jnp.zeros((), jnp.float32)
    (state, loss_sum, valid_sum), ys = jax.lax.scan(
        fold_chunk, (state, zero, zero), xs)
    rows_ys, ef_ys = ys
    cv_rows = ef_rows = None
    if scaffold is not None:
        cv_rows = rows_ys.reshape(k_pad, -1)[:k]
    if ef_on:
        ef_rows = ef_ys.reshape(k_pad, -1)[:k]
    return state, loss_sum / denom, valid_sum, cv_rows, ef_rows


# ---------------------------------------------------------------------------
# Server state
# ---------------------------------------------------------------------------

@dataclass
class ServerState:
    """``complex`` is the server complex model; for fedhen/noside the server
    simple model IS its M slice (Alg. 1 ln. 20 invariant).  Decouple keeps an
    independent ``simple_host`` (complex-structured; only its M slice is
    meaningful)."""
    complex: Tree
    simple_host: Optional[Tree] = None
    round: int = 0


# ---------------------------------------------------------------------------
# Telemetry plumbing (shared by the sync trainer and the async engine)
# ---------------------------------------------------------------------------

class RoundDispatch:
    """Calls a round jit under telemetry spans.

    With telemetry disabled this is a transparent passthrough to the jit
    wrapper — the seed code path, zero extra work.  Enabled, the first
    call is split into explicit ``trace_lower`` and ``compile`` spans via
    AOT (``jit.lower(...).compile()``), the compiled program's roofline
    ledger (``roofline/hlo_walk.py`` over the lowered HLO, plus XLA's own
    cost analysis) is emitted once, and the cached executable serves
    every subsequent round under a blocking ``execute`` span.  The AOT path compiles the SAME lowering the jit
    wrapper would, so round results are bit-identical either way
    (test-enforced by the no-op-sink parity test).
    """

    def __init__(self, obs: obslib.Telemetry, jit_fn):
        self.obs = obs
        self.jit_fn = jit_fn
        self.compiled = None

    def _emit_roofline(self):
        from repro.roofline import hlo_walk
        counters = hlo_walk.analyze(self.compiled.as_text())
        values = {"flops": counters["flops"],
                  "hbm_bytes": counters["hbm_bytes"],
                  "collective_bytes": counters["total_collective_bytes"]}
        ca = hlo_walk.xla_cost_analysis(self.compiled)
        if "flops" in ca:
            values["xla_flops"] = float(ca["flops"])
        self.obs.ledger("roofline", values)

    def __call__(self, *args):
        obs = self.obs
        if not obs.enabled:
            return self.jit_fn(*args)
        if self.compiled is None:
            with obs.span("trace_lower"):
                lowered = self.jit_fn.lower(*args)
            with obs.span("compile"):
                self.compiled = lowered.compile()
            self._emit_roofline()
        with obs.span("execute"):
            return jax.block_until_ready(self.compiled(*args))


# ---------------------------------------------------------------------------
# Round functions
# ---------------------------------------------------------------------------

@jax.jit
def _stack_clients(*datasets: Batch) -> Batch:
    """The clients' datasets stacked on a leading axis, in one device
    call: eager ``jnp.stack`` first copies each client's arrays to add
    the axis, so the cohort's data would sit on the device twice."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *datasets)


class FederatedTrainer:
    """Drives T rounds of any of the three algorithms (paper protocol)."""

    def __init__(self, adapter, fed: FedConfig,
                 client_data: List[Batch], *,
                 rng: Optional[jax.Array] = None,
                 telemetry: Optional[obslib.Telemetry] = None):
        fed.validate()   # every config-rejection rule, one entry point
        self.adapter = adapter
        self.fed = fed
        # observability (repro/obs): None -> the disabled NOOP singleton,
        # whose every emit short-circuits — the default, un-instrumented
        # path (overhead CI-gated by benchmarks/obs_overhead.py)
        self.obs = obslib.coalesce(telemetry)
        self.client_data = client_data
        # cohort sampler (core/sampling.py): pure in (seed, round) — no
        # sequential host RNG stream to checkpoint, so resume re-creates
        # the uninterrupted run's cohort sequence exactly
        self.sampler = sampling.CohortSampler(
            n_devices=fed.n_devices, n_simple=fed.n_simple,
            participation=fed.participation, seed=fed.seed,
            uniform=fed.sample_uniform)
        # sharded per-client state (core/client_state.py): participation,
        # last round, version tags — ONE flat host matrix, O(cohort)/round
        self.client_state = client_state.ClientStateMatrix(fed.n_devices)
        key = rng if rng is not None else jax.random.PRNGKey(fed.seed)
        self.server = ServerState(complex=adapter.init(key))
        if fed.algorithm == "decouple":
            self.server.simple_host = jax.tree.map(jnp.copy,
                                                   self.server.complex)
        self.mask = adapter.subnet_mask(self.server.complex)
        # static per-population slot capacities (jit shapes): stratified
        # keeps the old max(round(p * pop), 1); uniform splits the
        # super-cohort into min(k_super, pop)-slot blocks
        self.k_simple = self.sampler.cap_simple
        self.k_complex = self.sampler.cap_complex
        # flat aggregation layout: built ONCE — offsets are static per
        # (treedef, leaf shapes, agg_block_n), valid for every round
        self.layout = flatten.build_layout(self.server.complex,
                                           total_multiple=fed.agg_block_n)
        self.flat_mask = flatten.pack_mask(self.layout, self.mask)
        # communication wire format (core/comm.py): the broadcast is
        # decoded from it on clients, uploads are folded through it, and
        # the byte accounting below measures its real encoded sizes
        self.wire = comm.WireSpec(fed.comm_dtype, fed.quant_block,
                                  topk_frac=fed.topk_frac,
                                  stochastic=fed.stochastic_rounding,
                                  error_feedback=fed.error_feedback)
        # THE engine configuration: one frozen spec built from the config,
        # bound with the trace-time flat_mask inside the round fn
        self.engine_spec = aggregate.EngineSpec.from_config(
            fed, mask=self.mask, layout=self.layout, wire=self.wire)
        # SCAFFOLD state (tentpole consumer of core/state_store.py):
        # per-client control variates c_i as one (N, n_flat) store row
        # each, plus the server's c — both zero-initialized (round 1 is
        # then bit-identical to variance_reduction="none", test-enforced)
        self.cv_store: Optional[state_store.FlatStateStore] = None
        self.cv_global: Optional[jax.Array] = None
        if fed.variance_reduction == "scaffold":
            self.cv_store = state_store.FlatStateStore(
                fed.n_devices, self.layout.n_flat,
                backend=fed.state_store_backend)
            self.cv_global = jnp.zeros((self.layout.n_flat,), jnp.float32)
        # wire-v2 error-feedback residuals: the second FlatStateStore
        # consumer — one packed row per client accumulating what the
        # lossy upload encode dropped, re-uploaded next participation
        self.ef_store: Optional[state_store.FlatStateStore] = None
        if fed.error_feedback:
            self.ef_store = state_store.FlatStateStore(
                fed.n_devices, self.layout.n_flat,
                backend=fed.state_store_backend)
        # static top-k payload lengths, per population (simple clients'
        # deltas are identically zero outside M, so their k budgets |M|)
        self.k_top_simple = self.k_top_complex = 0
        if self.wire.uses_deltas:
            n_m = int(np.sum(np.asarray(self.flat_mask)))
            self.k_top_simple = comm.topk_count(self.wire, n_m)
            self.k_top_complex = comm.topk_count(self.wire,
                                                 self.layout.n_params)
        self.cohort_chunk = self._resolve_cohort_chunk()
        (self.bytes_down_per_round,
         self.bytes_up_per_round) = self._measured_comm_bytes()
        self.bytes_per_round = (self.bytes_down_per_round
                                + self.bytes_up_per_round)
        self.total_bytes = 0.0
        self.total_bytes_down = 0.0
        self.total_bytes_up = 0.0
        # donate the server state buffers into the round (they are replaced
        # wholesale each round); CPU has no donation support, skip the noise
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        self._round_fn = jax.jit(self._make_round_fn(),
                                 donate_argnums=donate)
        self._dispatch = RoundDispatch(self.obs, self._round_fn)
        # bounded-lag async engine (core/async_rounds.py): owns the
        # version stack + staleness schedule; run_round delegates to it.
        # Imported lazily — async_rounds imports this module at top level.
        self.async_engine = None
        if fed.async_lag > 0:
            from repro.core import async_rounds
            self.async_engine = async_rounds.AsyncRoundEngine(self)
        if self.obs.enabled:
            self._emit_run_config()

    # -- chunk-size autotuning (ROADMAP item) --------------------------------

    def _resolve_cohort_chunk(self) -> int:
        """``cohort_chunk="auto"`` -> largest chunk whose per-client packed
        footprint fits ``agg_memory_budget_mb`` (else the configured int)."""
        fed = self.fed
        if fed.cohort_chunk == "auto":
            stream_dtype, qb = self._effective_stream()
            return flatten.auto_cohort_chunk(
                self.layout,
                budget_bytes=fed.agg_memory_budget_mb * 2**20,
                k=max(self.k_simple, self.k_complex),
                stream_dtype=stream_dtype, quant_block=qb)
        return int(fed.cohort_chunk)

    def _effective_stream(self):
        """(dtype, quant_block) the fold's stream buffer actually uses:
        the wire payload when a wire is configured, else the plain
        streaming dtype."""
        if self.wire.is_quantized:
            return jnp.dtype(jnp.int8), self.wire.quant_block
        if not self.wire.is_identity:
            return self.wire.payload_dtype, 0
        return jnp.dtype(self.fed.agg_stream_dtype), 0

    def stream_bytes_per_client(self) -> int:
        """One client's packed stream-buffer footprint at the effective
        wire/stream dtype (incl. the int8 scale sidecar) — what
        ``cohort_chunk="auto"`` budgets per client."""
        stream_dtype, qb = self._effective_stream()
        return self.layout.stream_bytes(stream_dtype, quant_block=qb)

    # -- communication accounting ------------------------------------------

    def _measured_comm_bytes(self) -> Tuple[float, float]:
        """(download, upload) bytes per round, MEASURED from the wire
        encoder's real output buffers (payload + scale sidecar) for the
        true element counts: complex devices exchange the whole model,
        simple devices only the index set M.  Alignment padding is a local
        layout artifact (static offsets on both ends) and is never billed.

        Also pins ``per_simple_bytes`` / ``per_complex_bytes`` — ONE
        client's one-way wire cost per population — the single source the
        async engine's version-aware billing reuses, so the two
        accountings cannot desynchronize.

        SCAFFOLD adds a control-variate exchange each way (``c`` down,
        ``dc`` up) of the client's trained element count, billed at f32
        (``per_simple_cv_bytes`` / ``per_complex_cv_bytes``): the cv
        vectors move raw, not through the wire encoder — honest
        accounting, and the measured cost of turning the knob on.
        """
        n_m = int(np.sum(np.asarray(self.flat_mask)))   # |M| true elements
        self.per_complex_bytes = comm.wire_bytes(self.wire,
                                                 self.layout.n_params)
        self.per_simple_bytes = comm.wire_bytes(self.wire, n_m)
        # the upload direction carries the wire-v2 delta payload: under
        # top-k it is the compacted index+value buffer, measured from the
        # encoder's real output shapes like the dense path (identical to
        # the download numbers when no v2 knob is on)
        self.per_complex_bytes_up = comm.wire_bytes_up(self.wire,
                                                       self.layout.n_params)
        self.per_simple_bytes_up = comm.wire_bytes_up(self.wire, n_m)
        cv = self.cv_store is not None
        self.per_simple_cv_bytes = 4.0 * n_m if cv else 0.0
        self.per_complex_cv_bytes = 4.0 * self.layout.n_params if cv else 0.0
        down = float(
            self.k_simple * (self.per_simple_bytes
                             + self.per_simple_cv_bytes)
            + self.k_complex * (self.per_complex_bytes
                                + self.per_complex_cv_bytes))
        up = float(
            self.k_simple * (self.per_simple_bytes_up
                             + self.per_simple_cv_bytes)
            + self.k_complex * (self.per_complex_bytes_up
                                + self.per_complex_cv_bytes))
        return down, up

    def _round_bytes(self, plan: sampling.CohortPlan) -> Tuple[float, float]:
        """(download, upload) bytes of ONE round under ``plan``.  With
        every slot real (stratified mode, and full uniform rounds) this is
        the static per-round constant; uniform rounds with pad slots bill
        only the realized clients — a pad slot moves no bytes."""
        if plan.all_real:
            return self.bytes_down_per_round, self.bytes_up_per_round
        down = float(
            plan.n_real_simple * (self.per_simple_bytes
                                  + self.per_simple_cv_bytes)
            + plan.n_real_complex * (self.per_complex_bytes
                                     + self.per_complex_cv_bytes))
        up = float(
            plan.n_real_simple * (self.per_simple_bytes_up
                                  + self.per_simple_cv_bytes)
            + plan.n_real_complex * (self.per_complex_bytes_up
                                     + self.per_complex_cv_bytes))
        return down, up

    def analytic_bytes_per_round(self) -> float:
        """The pre-wire estimate (param counts x param itemsize, down+up)
        — kept as the consistency oracle for the measured numbers."""
        params = self.server.complex
        total = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(params))
        simple = 0
        for m, x in zip(jax.tree.leaves(self.mask),
                        jax.tree.leaves(params)):
            simple += int(np.sum(np.broadcast_to(np.asarray(m), x.shape))
                          ) * x.dtype.itemsize
        # down + up for each active device
        return 2.0 * (self.k_simple * simple + self.k_complex * total)

    # -- telemetry (repro/obs) ----------------------------------------------

    def _geometry(self) -> Tuple[Tuple[int, int], Tuple[int, int]]:
        """((chunk_s, n_chunks_s), (chunk_c, n_chunks_c)) — the static
        per-population chunk geometry of one round."""
        return (chunk_geometry(self.k_simple, self.cohort_chunk),
                chunk_geometry(self.k_complex, self.cohort_chunk))

    def _emit_run_config(self) -> None:
        """One ``run_config`` ledger at construction: the static facts a
        run report leads with (cohort geometry, engine, wire, per-round
        wire cost, and the client model's shape where the adapter gives
        one)."""
        fed = self.fed
        (chunk_s, n_s), (chunk_c, n_c) = self._geometry()
        values = {
            "engine": "async" if self.async_engine is not None else "sync",
            "n_devices": fed.n_devices, "n_simple": fed.n_simple,
            "k_simple": self.k_simple, "k_complex": self.k_complex,
            "participation": fed.participation,
            "sample_uniform": fed.sample_uniform,
            "client_state_bytes": self.client_state.nbytes,
            "cohort_chunk": self.cohort_chunk,
            "n_chunks_simple": n_s, "n_chunks_complex": n_c,
            "comm_dtype": fed.comm_dtype,
            "async_lag": fed.async_lag,
            "n_params": self.layout.n_params,
            "bytes_down_per_round": self.bytes_down_per_round,
            "bytes_up_per_round": self.bytes_up_per_round,
        }
        if self.cv_store is not None:
            values.update({
                "state_store_backend": self.cv_store.backend,
                "state_store_bytes": self.cv_store.nbytes,
            })
        if self.ef_store is not None:
            values.update({
                "ef_store_backend": self.ef_store.backend,
                "ef_store_bytes": self.ef_store.nbytes,
            })
        values.update(aggregate.engine_attrs(self.engine_spec))
        geometry = getattr(self.adapter, "geometry", None)
        if geometry is not None:
            values.update(geometry(self.client_data[0], fed.batch_size))
        self.obs.ledger("run_config", values)

    def _emit_round_health(self, metrics: Dict[str, float], *,
                           down: Optional[float] = None,
                           up: Optional[float] = None,
                           k_real: Optional[int] = None) -> None:
        """Per-round client-health counters + the comm/client-state ledgers.

        The counters surface what the validity-weight path folds away
        silently: devices excluded for NaNs this round and the weight-0
        padding slots — both the chunk geometry's and (uniform mode) the
        super-cohort's unfilled arch slots.  The comm ledger repeats the
        trainer's OWN accounting fields (cumulative totals included) so a
        run log is exactly reconcilable against ``total_bytes*`` — the
        async engine passes its version-aware ``down``/``up`` here, the
        synchronous round uses ``_round_bytes``.  ``k_real`` is the
        realized (non-pad) client count; ``None`` means every slot real.
        """
        (chunk_s, n_s), (chunk_c, n_c) = self._geometry()
        k = self.k_simple + self.k_complex
        if k_real is None:
            k_real = k
        obs = self.obs
        obs.counter("nan_excluded_devices", k_real - int(metrics["n_valid"]))
        obs.counter("padding_weight0_clients",
                    (n_s * chunk_s - self.k_simple)
                    + (n_c * chunk_c - self.k_complex)
                    + (k - k_real))
        obs.ledger("comm_bytes", {
            "down": self.bytes_down_per_round if down is None else down,
            "up": self.bytes_up_per_round if up is None else up,
            "cum_down": self.total_bytes_down,
            "cum_up": self.total_bytes_up,
            "cum_total": self.total_bytes,
        })
        obs.ledger("client_state", {
            "state_bytes": self.client_state.nbytes,
            "tracked_clients": self.client_state.tracked_clients(),
        })
        if self.cv_store is not None:
            obs.ledger("state_store", {
                "store_bytes": self.cv_store.nbytes,
                "cum_gathered_bytes": self.cv_store.gathered_bytes,
                "cum_scattered_bytes": self.cv_store.scattered_bytes,
            })
        if self.ef_store is not None:
            obs.ledger("ef_store", {
                "store_bytes": self.ef_store.nbytes,
                "cum_gathered_bytes": self.ef_store.gathered_bytes,
                "cum_scattered_bytes": self.ef_store.scattered_bytes,
            })
        obs.ledger("participation_hist",
                   self.client_state.participation_histogram())

    # -- the jitted round (streaming cohort engine) --------------------------

    def _make_round_fn(self):
        adapter, fed, mask = self.adapter, self.fed, self.mask
        algo = fed.algorithm
        scaffold_on = fed.variance_reduction == "scaffold"
        cv_layout = self.layout if scaffold_on else None
        train_simple = make_client_trainer(adapter.loss_simple, fed,
                                           cv_layout=cv_layout)
        complex_loss = (adapter.loss_side if algo == "fedhen"
                        else adapter.loss_complex)
        train_complex = make_client_trainer(complex_loss, fed,
                                            cv_layout=cv_layout)

        layout = self.layout
        wire = self.wire
        spec = self.engine_spec

        def make_agg(flat_mask):
            """Bind the fold.  ``flat_mask`` is a round *argument* (not a
            closed-over constant) so the precomputed bitvector lives in
            argument memory, shared across rounds, instead of being baked
            into the executable's temp allocation."""
            return aggregate.make_engine(spec.bind(flat_mask=flat_mask))

        chunk_s, n_chunks_s = chunk_geometry(self.k_simple,
                                             self.cohort_chunk)
        chunk_c, n_chunks_c = chunk_geometry(self.k_complex,
                                             self.cohort_chunk)

        delta_mode = wire.uses_deltas
        ef_on = fed.error_feedback
        k_top_s, k_top_c = self.k_top_simple, self.k_top_complex

        def round_fn(complex_params: Tree, simple_host: Optional[Tree],
                     data_s: Batch, data_c: Batch, rng: jax.Array,
                     flat_mask: jax.Array,
                     real_s: Optional[jax.Array] = None,
                     real_c: Optional[jax.Array] = None,
                     cv_global: Optional[jax.Array] = None,
                     cv_s: Optional[jax.Array] = None,
                     cv_c: Optional[jax.Array] = None,
                     ef_s: Optional[jax.Array] = None,
                     ef_c: Optional[jax.Array] = None):
            # real_s / real_c: per-slot reality masks (uniform
            # super-cohort mode only — stratified rounds never pass them,
            # keeping the traced program literally the pre-existing one).
            # cv_global / cv_s / cv_c: SCAFFOLD's server control variate
            # and the cohort's gathered store rows (scaffold only — the
            # "none" trace takes none of them and stays bit-identical).
            # ef_s / ef_c: the cohort's gathered error-feedback residual
            # rows (wire v2 with error_feedback only — same discipline).
            agg_init, agg_fold, agg_finalize = make_agg(flat_mask)
            rs, rc = jax.random.split(rng)
            # the server -> client broadcast crosses the wire: clients
            # train on the DECODED copy, so the round carries the real
            # quantization error (identity for the f32 wire)
            with stage("wire"):
                bc_complex = comm.broadcast_roundtrip(wire, layout,
                                                      complex_params)
                src_simple = (comm.broadcast_roundtrip(wire, layout,
                                                       simple_host)
                              if algo == "decouple" else bc_complex)
            sc_s = sc_c = None
            if scaffold_on:
                # simple clients train (and correct) only the M slice:
                # their c_i lives on M alone
                sc_s = ScaffoldCtx(
                    rows=cv_s, c_global=cv_global, pop_mask=flat_mask,
                    layout=layout,
                    inv_k_lr=1.0 / (local_step_count(data_s, fed)
                                    * fed.lr))
                sc_c = ScaffoldCtx(
                    rows=cv_c, c_global=cv_global, pop_mask=None,
                    layout=layout,
                    inv_k_lr=1.0 / (local_step_count(data_c, fed)
                                    * fed.lr))
            up_s = up_c = None
            if delta_mode:
                up_s = WireUploadCtx(wire, layout, k_top_s, ef_s)
                up_c = WireUploadCtx(wire, layout, k_top_c, ef_c)
            with stage("fold"):
                state = agg_init(complex_params)
            state, loss_s, valid_s, rows_s, efrows_s = stream_population(
                state, lambda _: src_simple, train_simple, data_s, rs,
                agg_fold, k=self.k_simple, chunk=chunk_s,
                n_chunks=n_chunks_s, is_simple_flag=True,
                skip_nan=fed.skip_nan_devices, real_mask=real_s,
                scaffold=sc_s, upload=up_s)
            state, loss_c, valid_c, rows_c, efrows_c = stream_population(
                state, lambda _: bc_complex, train_complex, data_c, rc,
                agg_fold, k=self.k_complex, chunk=chunk_c,
                n_chunks=n_chunks_c, is_simple_flag=False,
                skip_nan=fed.skip_nan_devices, real_mask=real_c,
                scaffold=sc_c, upload=up_c)
            cv_out = None
            with stage("finalize"):
                if scaffold_on:
                    # server control variate: c += (1/N) * sum_i dc_i —
                    # the RAW second accumulator (group weighting already
                    # rode w_in/w_out through the fold), over ALL N
                    # devices (non-participants contribute 0), per
                    # Karimireddy eq. 5
                    new_cv_global = (cv_global
                                     + state.cv_acc / float(fed.n_devices))
                    cv_out = (new_cv_global, rows_s, rows_c)
                new_complex, new_simple_host = agg_finalize(
                    state, template=complex_params)
            ef_out = (efrows_s, efrows_c) if ef_on else None
            metrics = {"loss_simple": loss_s,
                       "loss_complex": loss_c,
                       "n_valid": valid_s + valid_c}
            return new_complex, new_simple_host, metrics, cv_out, ef_out

        return round_fn

    # -- sampling + gather (host side; this is the "data loading" tier) -----

    def _sample_plan(self) -> sampling.CohortPlan:
        """This round's cohort — pure in ``(fed.seed, server.round)``, so
        a checkpoint restore that recovers the round counter recovers the
        cohort sequence (no sampler RNG state exists to lose)."""
        return self.sampler.plan(self.server.round)

    def _sample_cohort(self):
        """(simple_ids, complex_ids) of this round's plan — the slot-block
        view (pad slots included in uniform mode)."""
        plan = self._sample_plan()
        return plan.simple_ids, plan.complex_ids

    def _gather(self, ids) -> Batch:
        return _stack_clients(*[self.client_data[i] for i in ids])

    # -- public API ----------------------------------------------------------

    def _cv_args(self, plan: sampling.CohortPlan) -> tuple:
        """The SCAFFOLD round arguments: ``(c_global, rows_s, rows_c)``
        gathered O(cohort) from the state store — empty when off (the
        traced round then literally has no cv inputs)."""
        if self.cv_store is None:
            return ()
        return (self.cv_global,
                self.cv_store.gather(plan.simple_ids),
                self.cv_store.gather(plan.complex_ids))

    def _ef_args(self, plan: sampling.CohortPlan) -> tuple:
        """The error-feedback round arguments: ``(rows_s, rows_c)``
        residuals gathered O(cohort) from the EF store — empty when off
        (the traced round then literally has no ef inputs)."""
        if self.ef_store is None:
            return ()
        return (self.ef_store.gather(plan.simple_ids),
                self.ef_store.gather(plan.complex_ids))

    def _round_args(self, plan: sampling.CohortPlan, data_s: Batch,
                    data_c: Batch, key: jax.Array) -> tuple:
        args = (self.server.complex, self.server.simple_host, data_s,
                data_c, key, self.flat_mask)
        cv = self._cv_args(plan)
        ef = self._ef_args(plan)
        if self.fed.sample_uniform:
            args += (jnp.asarray(plan.simple_real),
                     jnp.asarray(plan.complex_real))
        elif cv or ef:
            args += (None, None)     # skip the real-mask slots positionally
        if ef and not cv:
            cv = (None, None, None)  # skip the cv slots positionally
        return args + cv + ef

    def _apply_cv_update(self, plan: sampling.CohortPlan, cv_out) -> None:
        """Commit one round's SCAFFOLD outputs: the new server control
        variate, and the updated rows scattered back for REAL slots only
        (pad slots wrap real clients' ids — writing them would clobber
        rows the wrapped client just updated at full weight).  Also tracks
        each updated row's norm in the scalar matrix's ``cv_scale``
        column (telemetry: control-variate drift over rounds)."""
        new_cv_global, rows_s, rows_c = cv_out
        self.cv_global = new_cv_global
        for ids, real, rows in (
                (plan.simple_ids, plan.simple_real, rows_s),
                (plan.complex_ids, plan.complex_real, rows_c)):
            real = np.asarray(real, bool)
            if not real.any():
                continue
            ids = np.asarray(ids, np.int64)[real]
            rows = np.asarray(rows)[real]
            self.cv_store.scatter(ids, rows)
            self.client_state.set_cv_scale(
                ids, np.linalg.norm(rows.astype(np.float64), axis=1))

    def _apply_ef_update(self, plan: sampling.CohortPlan, ef_out) -> None:
        """Commit one round's error-feedback residuals: updated rows
        scattered back for REAL slots only (the same pad-slot rule as
        ``_apply_cv_update`` — pad slots wrap real clients' ids), row
        norms tracked in the scalar matrix's ``ef_scale`` column
        (telemetry: how much compression error each client carries)."""
        rows_s, rows_c = ef_out
        for ids, real, rows in (
                (plan.simple_ids, plan.simple_real, rows_s),
                (plan.complex_ids, plan.complex_real, rows_c)):
            real = np.asarray(real, bool)
            if not real.any():
                continue
            ids = np.asarray(ids, np.int64)[real]
            rows = np.asarray(rows)[real]
            self.ef_store.scatter(ids, rows)
            self.client_state.set_ef_scale(
                ids, np.linalg.norm(rows.astype(np.float64), axis=1))

    def lower_round(self):
        """AOT-lower the jitted round with this trainer's shapes.

        Used by benchmarks/tests to inspect the compiled round (peak memory,
        HLO) without running it.  Consumes one cohort sample from the
        host-side sampler.
        """
        if self.async_engine is not None:
            return self.async_engine.lower_round()
        plan = self._sample_plan()
        key = jax.random.PRNGKey(self.fed.seed * 100003 + self.server.round)
        args = self._round_args(plan, self._gather(plan.simple_ids),
                                self._gather(plan.complex_ids), key)
        return self._round_fn.lower(*args)

    def run_round(self) -> Dict[str, float]:
        if self.async_engine is not None:
            return self.async_engine.run_round()
        obs = self.obs
        obs.set_round(self.server.round)
        with obs.span("round", engine="sync"):
            with obs.span("sample_gather"):
                plan = self._sample_plan()
                data_s = self._gather(plan.simple_ids)
                data_c = self._gather(plan.complex_ids)
            key = jax.random.PRNGKey(
                self.fed.seed * 100003 + self.server.round)
            args = self._round_args(plan, data_s, data_c, key)
            (new_complex, new_simple_host, metrics,
             cv_out, ef_out) = self._dispatch(*args)
            if cv_out is not None:
                self._apply_cv_update(plan, cv_out)
            if ef_out is not None:
                self._apply_ef_update(plan, ef_out)
            self.client_state.record_round(plan.real_ids(),
                                           plan.round_index)
            self.server = ServerState(complex=new_complex,
                                      simple_host=new_simple_host,
                                      round=self.server.round + 1)
            down, up = self._round_bytes(plan)
            self.total_bytes += down + up
            self.total_bytes_down += down
            self.total_bytes_up += up
            metrics = {k: float(v) for k, v in metrics.items()}
            if obs.enabled:
                self._emit_round_health(
                    metrics, down=down, up=up,
                    k_real=plan.n_real_simple + plan.n_real_complex)
        return metrics

    def evaluate(self, test_batch: Batch) -> Dict[str, float]:
        """Server-model metrics.  For decouple, the simple accuracy comes
        from the simple host; otherwise from the complex model's M slice
        (which IS the server simple model)."""
        m = {k: float(v) for k, v in
             self.adapter.evaluate(self.server.complex, test_batch).items()}
        if self.fed.algorithm == "decouple":
            ms = self.adapter.evaluate(self.server.simple_host, test_batch)
            m["acc_simple"] = float(ms["acc_simple"])
        m["mbytes"] = self.total_bytes / 1e6
        m["mbytes_down"] = self.total_bytes_down / 1e6
        m["mbytes_up"] = self.total_bytes_up / 1e6
        return m

    def run(self, rounds: int, *, eval_every: int = 0,
            test_batch: Optional[Batch] = None,
            log: Optional[Callable[[str], None]] = None) -> List[Dict]:
        history = []
        obs = self.obs
        for r in range(rounds):
            metrics = self.run_round()
            if eval_every and test_batch is not None and \
                    (r + 1) % eval_every == 0:
                ev = self.evaluate(test_batch)
                metrics.update(ev)
                # eval ledger is stamped with the COMPLETED round count
                # (the log line's "round N") — rounds-to-target reads it
                obs.set_round(self.server.round)
                obs.ledger("eval", ev)
            metrics["round"] = self.server.round
            history.append(metrics)
            if (log or obs.enabled) and \
                    (eval_every and (r + 1) % eval_every == 0):
                line = f"round {self.server.round}: " + ", ".join(
                    f"{k}={v:.4f}" for k, v in metrics.items()
                    if k != "round")
                # the legacy line, routed through the event stream: a
                # StdoutSink prints exactly this string, so the printed
                # format is bit-identical to the pre-telemetry driver
                obs.log(line)
                if log is not None:
                    log(line)
        return history


def rounds_to_target(history: List[Dict], key: str, target: float) -> int:
    """Paper's evaluation metric: first round reaching the target.

    Direction is inferred from the metric name (``obs.report``'s rule —
    the one inference, shared): accuracy-like metrics are reached
    at-or-above the target, loss-like metrics at-or-below."""
    from repro.obs.report import higher_is_better
    maximize = higher_is_better(key)
    for h in history:
        if key in h and (h[key] >= target if maximize
                         else h[key] <= target):
            return h["round"]
    return -1

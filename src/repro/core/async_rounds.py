"""Asynchronous round engine: bounded-lag chunk streaming with
staleness-weighted folds (FedAsync/FedBuff semantics over the flat-buffer
stack).

FedHeN trains devices of different complexities jointly, which makes
stragglers structural: the big-architecture cohort members gate the round
clock for everyone.  This module removes that gate.  The synchronous
engine (``core/federated.py``) broadcasts the round's server params, scans
the cohort chunk by chunk, and only publishes a new model once *every*
chunk has folded — so the slowest chunk sets the round period.  The async
engine lets chunk training **overlap the server fold across rounds**:

**Bounded-lag contract.**  Let ``F`` be the number of chunk folds per
round (simple chunks first, then complex — the same stream order as the
synchronous scan) and ``t`` a chunk's position in that stream.  With
``FedConfig.async_lag = L``, chunk ``t`` of round ``r`` trains on the
server params published at global fold ``r*F + t - L`` — the newest
*round* model available at that fold time.  Concretely the chunk's
broadcast is ``staleness = ceil((L - t) / F)`` rounds old (clamped to
``[0, r]``): the first ``L`` chunks of every round started training
before the previous round's fold finished, so they carry a one-round-
(or more-)stale, version-tagged broadcast.  ``L = 0`` makes every chunk
train on the fresh round broadcast — **bit-for-bit the synchronous
engine** (the parity oracle, test- and CI-enforced).

**Version-tagged broadcasts.**  The engine keeps the last
``ceil(L / F) + 1`` published server models as one stacked ``(V, n_flat)``
flat buffer (``core.flatten.pack``), rolled once per round.  Inside the
round jit the whole stack crosses the wire once
(``comm.encode``/``decode`` batched over ``V`` — identical bits to the
synchronous ``broadcast_roundtrip`` per version) and each chunk selects
its version with one ``lax.dynamic_index_in_dim``.  Download accounting
is version-aware: each client's last-fetched version tag lives in the
trainer's per-client state matrix (``core.client_state``, the
``version_tag`` column) and one vectorized tag-compare per round bills
only the clients whose chunk trains on a version they do not hold —
billing-identical to a per-client dict of last-fetched tags (the oracle
in ``tests/billing_oracle.py``), but O(cohort) with no O(N_clients) host
dict.  So
measured bytes stay truthful under stale-broadcast reuse at any
population size.

**Staleness-weighted folds.**  A stale upload moved away from a model the
server has since replaced; folding it at full weight drags the average
backwards.  Uploads are folded with the FedAsync polynomial decay
``w = 1 / (1 + s)^a`` (``s`` = staleness in rounds,
``a = FedConfig.async_decay``; ``FedConfig.async_staleness = "none"``
disables it).  The coefficient multiplies the client's validity weight
and enters ``aggregate.streaming_fold`` through the exact same masked
weight path as NaN-device/padding exclusion — no second aggregation code
path, and weight-0 devices stay gated before the multiply on every
backend.  Fresh chunks (``s = 0``) fold at weight exactly 1.0, which is
why the ``L = 0`` parity is bit-exact rather than merely close.

The engine SHARES the synchronous machinery rather than mirroring it:
the same ``make_client_trainer``, the same ``aggregate.make_engine`` fold
triple (any wire — int8 uploads still fold through the
dequantizing ``masked_agg`` accumulate), and the ONE chunk-stream scan
``federated.stream_population`` (the async extras — per-chunk version
index and staleness coefficient — are optional arguments of that shared
scan, so the two engines cannot drift).  Chunk padding with weight-0
clients and per-client RNG derivation are therefore identical by
construction: a round's result at a given schedule is invariant to
chunking up to float summation order, exactly like the synchronous
engine.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregate, comm, federated, flatten
from repro.obs.scopes import stage

STALENESS_SCHEMES = ("poly", "none")


def staleness_weight(staleness, *, scheme: str = "poly",
                     decay: float = 0.5) -> jax.Array:
    """Fold coefficient for an upload that trained on a stale broadcast.

    Args:
      staleness: scalar or array of staleness values ``s`` (broadcast
        versions behind the current one, in rounds; 0 = fresh).
      scheme: ``"poly"`` — the FedAsync polynomial decay
        ``1 / (1 + s)^decay``; ``"none"`` — constant 1 (staleness
        ignored).
      decay: the polynomial exponent ``a`` (>= 0).

    Returns: f32 weights of ``staleness``'s shape, exactly 1.0 at
    ``s = 0`` for every scheme (the bit-for-bit lag=0 parity relies on
    this).
    """
    s = jnp.asarray(staleness, jnp.float32)
    if scheme == "none":
        return jnp.ones_like(s)
    if scheme == "poly":
        return (1.0 + s) ** jnp.float32(-decay)
    raise ValueError(f"unknown staleness scheme {scheme!r} "
                     f"(one of {STALENESS_SCHEMES})")


def fold_schedule(n_folds: int, lag: int, round_index: int) -> np.ndarray:
    """Per-chunk broadcast staleness of one round's fold stream.

    Args:
      n_folds: chunk folds per round ``F`` (simple + complex populations).
      lag: ``FedConfig.async_lag`` — folds of bounded staleness ``L``.
      round_index: the round ``r`` being scheduled (clamps staleness so no
        chunk can train on a pre-initialization model).

    Returns: int array of shape ``(n_folds,)``: position ``t`` trains on
    the round broadcast published ``ceil((L - t) / F)`` rounds ago,
    clamped to ``[0, round_index]``.  All zeros when ``lag = 0``.
    """
    t = np.arange(n_folds)
    d = -((t - lag) // n_folds)          # ceil((lag - t) / n_folds)
    return np.minimum(np.maximum(d, 0), round_index)


class AsyncRoundEngine:
    """Drives asynchronous rounds for a :class:`~repro.core.federated.
    FederatedTrainer` (which delegates ``run_round`` here when
    ``FedConfig.async_lag > 0``).

    The engine owns the version stack, the staleness schedule, the async
    round jit, and the version-aware byte accounting; server state still
    lives on the trainer, so checkpointing and evaluation are unchanged.
    Construct directly with an explicit ``lag`` to run the async code
    path at a lag the trainer's config would not choose — the lag=0
    parity tests and the CI benchmark gate do exactly that.
    """

    def __init__(self, trainer, *, lag: Optional[int] = None,
                 scheme: Optional[str] = None,
                 decay: Optional[float] = None):
        fed = trainer.fed
        self.trainer = trainer
        self.lag = fed.async_lag if lag is None else lag
        self.scheme = fed.async_staleness if scheme is None else scheme
        self.decay = fed.async_decay if decay is None else decay
        if self.lag < 0:
            raise ValueError(f"lag must be >= 0, got {self.lag}")
        if self.scheme not in STALENESS_SCHEMES:
            raise ValueError(f"unknown staleness scheme {self.scheme!r}")
        self.algo = fed.algorithm
        self.layout = trainer.layout
        self.wire = trainer.wire
        # static chunk geometry — the synchronous scan's exact rule
        self.chunk_s, self.n_chunks_s = federated.chunk_geometry(
            trainer.k_simple, trainer.cohort_chunk)
        self.chunk_c, self.n_chunks_c = federated.chunk_geometry(
            trainer.k_complex, trainer.cohort_chunk)
        self.folds_per_round = self.n_chunks_s + self.n_chunks_c
        # version stack depth: deepest offset any chunk can reach, plus
        # the fresh slot — static, so the round jit never retraces
        self.n_versions = -(-self.lag // self.folds_per_round) + 1
        self._reset_versions()
        # per-client one-way wire cost: the trainer's numbers, not a
        # recomputation — sync and async billing share one source (the
        # upload direction carries the wire-v2 delta payload sizes)
        self._per_simple = trainer.per_simple_bytes
        self._per_complex = trainer.per_complex_bytes
        self._per_simple_up = trainer.per_simple_bytes_up
        self._per_complex_up = trainer.per_complex_bytes_up
        self.last_bytes_down = 0.0
        self.last_bytes_up = 0.0
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        self._round_fn = jax.jit(self._make_round_fn(),
                                 donate_argnums=donate)
        # telemetry rides the trainer's registry (one event stream per
        # run); the dispatch adds the compile/execute split when enabled
        self._dispatch = federated.RoundDispatch(trainer.obs,
                                                 self._round_fn)

    # -- version stack -------------------------------------------------------

    def _reset_versions(self):
        """(Re)seed the version stack and download ledger from the
        trainer's CURRENT server state.

        Called at construction and whenever ``trainer.server`` is
        replaced from outside the engine (checkpoint restore in
        ``launch/train.py --resume``): the replaced state's history is
        unknown, so every slot becomes the current model — the same
        pre-history semantics a fresh engine starts with — and the
        clients' cached version tags are wiped (they referred to the
        discarded history)."""
        tr = self.trainer
        flat = flatten.pack(self.layout, tr.server.complex)
        self.versions = jnp.tile(flat[None], (self.n_versions, 1))
        self.versions_host = None
        if self.algo == "decouple":
            host = flatten.pack(self.layout, tr.server.simple_host)
            self.versions_host = jnp.tile(host[None], (self.n_versions, 1))
        tr.client_state.reset_version_tags()
        # cumulative billing tallies (hits = reused stale broadcasts,
        # misses = downloads); telemetry emits per-round deltas, so
        # also remember where the last round left off
        self.cache_hits = 0
        self.cache_misses = 0
        self._seen_cache_counts = (0, 0)
        self._published_server = tr.server

    # -- schedule ------------------------------------------------------------

    def schedule(self, round_index: int) -> Tuple[np.ndarray, np.ndarray]:
        """(staleness_simple, staleness_complex) for one round — the fold
        stream split back into the two population scans."""
        s_all = fold_schedule(self.folds_per_round, self.lag, round_index)
        return s_all[:self.n_chunks_s], s_all[self.n_chunks_s:]

    # -- the jitted async round ----------------------------------------------

    def _make_round_fn(self):
        tr = self.trainer
        adapter, fed = tr.adapter, tr.fed
        algo = self.algo
        scaffold_on = fed.variance_reduction == "scaffold"
        cv_layout = self.layout if scaffold_on else None
        train_simple = federated.make_client_trainer(adapter.loss_simple,
                                                     fed, cv_layout=cv_layout)
        complex_loss = (adapter.loss_side if algo == "fedhen"
                        else adapter.loss_complex)
        train_complex = federated.make_client_trainer(complex_loss, fed,
                                                      cv_layout=cv_layout)
        layout, wire = self.layout, self.wire
        k_simple, k_complex = tr.k_simple, tr.k_complex
        # finalize only reads dtypes from the template — static structs
        # keep the server tree out of the round's argument list
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            tr.server.complex)
        spec = tr.engine_spec

        def make_agg(flat_mask):
            return aggregate.make_engine(spec.bind(flat_mask=flat_mask))

        def decode_versions(versions):
            """(V, n_flat) packed stack -> stacked broadcast trees, each
            version through the same wire trip a synchronous broadcast
            takes (identity wires skip the encode, like the sync path)."""
            if not wire.is_identity:
                versions = comm.decode(wire, comm.encode(wire, versions))
            return flatten.unpack_stacked(layout, versions)

        def version_select(bcasts):
            """``get_src`` for the shared chunk scan: one dynamic index
            into the stacked broadcast trees per chunk."""
            return lambda idx: jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, idx, 0, keepdims=False), bcasts)

        delta_mode = wire.uses_deltas
        ef_on = fed.error_feedback
        k_top_s, k_top_c = tr.k_top_simple, tr.k_top_complex

        def round_fn(versions, versions_host, data_s, data_c,
                     rng, flat_mask, idx_s, w_s, idx_c, w_c,
                     real_s=None, real_c=None,
                     cv_global=None, cv_s=None, cv_c=None,
                     ef_s=None, ef_c=None):
            # real_s / real_c: super-cohort slot reality masks (uniform
            # sampling mode only — absent, the traced program is exactly
            # the pre-existing async round).  cv_global / cv_s / cv_c:
            # SCAFFOLD's server control variate and the cohort's gathered
            # store rows — the "none" trace takes none of them.
            # ef_s / ef_c: gathered error-feedback residual rows (wire v2
            # with error_feedback only).  Under lag > 0 the wire-v2 delta
            # is measured vs the chunk's SELECTED STALE broadcast — the
            # model the client really trained from.
            agg_init, agg_fold, agg_finalize = make_agg(flat_mask)
            rs, rc = jax.random.split(rng)
            with stage("wire"):
                bcasts_c = decode_versions(versions)
                bcasts_s = (decode_versions(versions_host)
                            if algo == "decouple" else bcasts_c)
            sc_s = sc_c = None
            if scaffold_on:
                # the option-II delta's x is whatever broadcast the chunk
                # trained on — under lag > 0 that is the chunk's SELECTED
                # STALE version (packed from get_src's result inside the
                # shared scan), so dc measures the drift actually taken
                sc_s = federated.ScaffoldCtx(
                    rows=cv_s, c_global=cv_global, pop_mask=flat_mask,
                    layout=layout,
                    inv_k_lr=1.0 / (federated.local_step_count(data_s, fed)
                                    * fed.lr))
                sc_c = federated.ScaffoldCtx(
                    rows=cv_c, c_global=cv_global, pop_mask=None,
                    layout=layout,
                    inv_k_lr=1.0 / (federated.local_step_count(data_c, fed)
                                    * fed.lr))
            up_s = up_c = None
            if delta_mode:
                up_s = federated.WireUploadCtx(wire, layout, k_top_s, ef_s)
                up_c = federated.WireUploadCtx(wire, layout, k_top_c, ef_c)
            with stage("fold"):
                state = agg_init(template)
            (state, loss_s, valid_s, rows_s,
             efrows_s) = federated.stream_population(
                state, version_select(bcasts_s), train_simple, data_s, rs,
                agg_fold, k=k_simple, chunk=self.chunk_s,
                n_chunks=self.n_chunks_s, is_simple_flag=True,
                skip_nan=fed.skip_nan_devices,
                version_idx=idx_s, staleness_w=w_s, real_mask=real_s,
                scaffold=sc_s, upload=up_s)
            (state, loss_c, valid_c, rows_c,
             efrows_c) = federated.stream_population(
                state, version_select(bcasts_c), train_complex, data_c, rc,
                agg_fold, k=k_complex, chunk=self.chunk_c,
                n_chunks=self.n_chunks_c, is_simple_flag=False,
                skip_nan=fed.skip_nan_devices,
                version_idx=idx_c, staleness_w=w_c, real_mask=real_c,
                scaffold=sc_c, upload=up_c)
            cv_out = None
            new_versions_host = None
            with stage("finalize"):
                if scaffold_on:
                    cv_out = (cv_global
                              + state.cv_acc / float(fed.n_devices),
                              rows_s, rows_c)
                new_complex, new_host = agg_finalize(state,
                                                     template=template)
                # publish: roll the new round model into the version stack
                new_versions = jnp.concatenate(
                    [flatten.pack(layout, new_complex)[None],
                     versions[:-1]], axis=0)
                if algo == "decouple":
                    new_versions_host = jnp.concatenate(
                        [flatten.pack(layout, new_host)[None],
                         versions_host[:-1]], axis=0)
            ef_out = (efrows_s, efrows_c) if ef_on else None
            metrics = {"loss_simple": loss_s, "loss_complex": loss_c,
                       "n_valid": valid_s + valid_c}
            return (new_complex, new_host, new_versions,
                    new_versions_host, metrics, cv_out, ef_out)

        return round_fn

    # -- byte accounting (version-aware) -------------------------------------

    def _bill_download(self, plan, s_s, s_c, round_index: int) -> float:
        """Measured download of one round: each real client fetches the
        version its chunk trains on — billed once per (client, version)
        by the vectorized tag-compare on the trainer's client-state
        matrix (``ClientStateMatrix.bill_downloads``), so cached stale
        broadcasts cost 0.  Pad slots (super-cohort routing) wrap real
        clients that already fetched this round, so padding is never
        billed (same contract as the synchronous accounting)."""
        down = 0.0
        for ids, real, staleness, chunk, nbytes in (
                (plan.simple_ids, plan.simple_real, s_s,
                 self.chunk_s, self._per_simple),
                (plan.complex_ids, plan.complex_real, s_c,
                 self.chunk_c, self._per_complex)):
            pos = np.arange(ids.size)
            tags = round_index - np.asarray(staleness)[pos // chunk]
            billed, hits, misses = self.trainer.client_state.bill_downloads(
                ids[real], tags[real], nbytes)
            down += billed
            self.cache_hits += hits
            self.cache_misses += misses
        return float(down)

    # -- public API ----------------------------------------------------------

    def _round_args(self):
        """One round's concrete argument tuple (shared by run/lower)."""
        tr = self.trainer
        if tr.server is not self._published_server:
            # the server state was replaced from outside (checkpoint
            # restore): the version stack must follow it, or every chunk
            # would keep training on the discarded pre-restore broadcast
            self._reset_versions()
        r = tr.server.round
        s_s, s_c = self.schedule(r)
        w_s = staleness_weight(s_s, scheme=self.scheme, decay=self.decay)
        w_c = staleness_weight(s_c, scheme=self.scheme, decay=self.decay)
        plan = tr._sample_plan()
        key = jax.random.PRNGKey(tr.fed.seed * 100003 + r)
        args = (self.versions, self.versions_host,
                tr._gather(plan.simple_ids), tr._gather(plan.complex_ids),
                key, tr.flat_mask, jnp.asarray(s_s, jnp.int32), w_s,
                jnp.asarray(s_c, jnp.int32), w_c)
        cv = tr._cv_args(plan)
        ef = tr._ef_args(plan)
        if tr.fed.sample_uniform:
            args += (jnp.asarray(plan.simple_real),
                     jnp.asarray(plan.complex_real))
        elif cv or ef:
            args += (None, None)     # skip the real-mask slots positionally
        if ef and not cv:
            cv = (None, None, None)  # skip the cv slots positionally
        return args + cv + ef, (plan, s_s, s_c, r)

    def lower_round(self):
        """AOT-lower the async round jit with this trainer's shapes (the
        async mirror of ``FederatedTrainer.lower_round``; consumes one
        cohort sample)."""
        args, _ = self._round_args()
        return self._round_fn.lower(*args)

    def _emit_async_health(self, s_s, s_c) -> None:
        """Async-specific client health: the round's per-chunk staleness
        histogram (``{staleness: chunk count}`` over the fold stream) and
        the version-cache hit/miss deltas (a hit is a stale broadcast the
        client already held — the reuse the byte accounting credits)."""
        obs = self.trainer.obs
        hist: dict = {}
        for s in list(s_s) + list(s_c):
            hist[int(s)] = hist.get(int(s), 0) + 1
        obs.ledger("staleness_hist",
                   {str(k): v for k, v in sorted(hist.items())})
        seen_h, seen_m = self._seen_cache_counts
        obs.counter("version_cache_hit", self.cache_hits - seen_h)
        obs.counter("version_cache_miss", self.cache_misses - seen_m)
        self._seen_cache_counts = (self.cache_hits, self.cache_misses)

    def run_round(self):
        """One async round: schedule staleness, train + fold the chunk
        stream, publish the new version, update the trainer's server
        state and measured byte totals."""
        tr = self.trainer
        obs = tr.obs
        obs.set_round(tr.server.round)
        with obs.span("round", engine="async", lag=self.lag):
            with obs.span("sample_gather"):
                args, (plan, s_s, s_c, r) = self._round_args()
            (new_complex, new_host, self.versions, self.versions_host,
             metrics, cv_out, ef_out) = self._dispatch(*args)
            if cv_out is not None:
                tr._apply_cv_update(plan, cv_out)
            if ef_out is not None:
                tr._apply_ef_update(plan, ef_out)
            tr.client_state.record_round(plan.real_ids(), r)
            tr.server = federated.ServerState(
                complex=new_complex, simple_host=new_host, round=r + 1)
            self._published_server = tr.server
            down = self._bill_download(plan, s_s, s_c, r)
            # cv exchange: c_global is republished every round (no version
            # to cache), c_i deltas ride the upload — both billed raw f32,
            # the trainer's honest-accounting numbers (0 when off)
            down += float(plan.n_real_simple * tr.per_simple_cv_bytes
                          + plan.n_real_complex * tr.per_complex_cv_bytes)
            up = float(plan.n_real_simple * (self._per_simple_up
                                             + tr.per_simple_cv_bytes)
                       + plan.n_real_complex * (self._per_complex_up
                                                + tr.per_complex_cv_bytes))
            self.last_bytes_down, self.last_bytes_up = down, up
            tr.total_bytes_down += down
            tr.total_bytes_up += up
            tr.total_bytes += down + up
            metrics = {k: float(v) for k, v in metrics.items()}
            if obs.enabled:
                self._emit_async_health(s_s, s_c)
                tr._emit_round_health(
                    metrics, down=down, up=up,
                    k_real=plan.n_real_simple + plan.n_real_complex)
        return metrics

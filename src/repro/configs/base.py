"""Configuration dataclasses for the FedHeN framework.

Every model in the zoo is described by a :class:`ModelConfig`.  The layer
stack is expressed as a repeating *pattern period* (e.g. gemma-2's
``[local_attn, global_attn]`` alternation or recurrentgemma's
``[rglru, rglru, local_attn]``), which lets the runtime compile the stack as
``lax.scan`` over full periods with the remainder layers unrolled — faithful
interleaving with compact HLO.

FedHeN (the paper's technique) is configured via ``exit_layer``: the simple
architecture is the depth-prefix ``blocks[:exit_layer]`` plus an early-exit
head (own final norm, shared unembedding).  ``exit_layer`` must sit on a
period boundary so the prefix is expressible as a scan over whole periods.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple, Union

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Layer kinds
# ---------------------------------------------------------------------------

ATTN_GLOBAL = "attn"          # full causal attention
ATTN_LOCAL = "local_attn"     # sliding-window causal attention
RGLRU = "rglru"               # Griffin/RecurrentGemma real-gated LRU block
MLSTM = "mlstm"               # xLSTM matrix-memory block (chunked parallel)
SLSTM = "slstm"               # xLSTM scalar-memory block (sequential scan)

MIXER_KINDS = (ATTN_GLOBAL, ATTN_LOCAL, RGLRU, MLSTM, SLSTM)

MLP_DENSE = "dense"
MLP_MOE = "moe"
MLP_NONE = "none"             # block has no separate MLP (xLSTM style)


@dataclass(frozen=True)
class LayerSpec:
    """One position in the repeating layer pattern."""

    mixer: str = ATTN_GLOBAL
    mlp: str = MLP_DENSE

    def __post_init__(self):
        if self.mixer not in MIXER_KINDS:
            raise ValueError(f"unknown mixer kind {self.mixer!r}")
        if self.mlp not in (MLP_DENSE, MLP_MOE, MLP_NONE):
            raise ValueError(f"unknown mlp kind {self.mlp!r}")


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int            # routed experts
    top_k: int
    n_shared: int = 0         # always-on shared experts
    d_expert: int = 0         # per-expert FFN hidden dim
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2
    # pad the expert axis to this size (0 = off): dead experts never get
    # routed tokens, but make E divisible by the model axis so the combine
    # stays local + one small all-reduce (EXPERIMENTS.md §Perf H4)
    pad_to: int = 0


@dataclass(frozen=True)
class StubFrontend:
    """Modality frontend stub (the sanctioned carve-out).

    The dry-run's ``input_specs`` provides precomputed embeddings of shape
    ``(batch, n_tokens, d_in)``; the backbone owns only the projector.
    """

    kind: str                 # "vision" | "audio_conditioning"
    n_tokens: int             # tokens the frontend contributes to the sequence
    d_in: int                 # embedding dim produced by the (stubbed) encoder


@dataclass(frozen=True)
class ModelConfig:
    # -- identity ----------------------------------------------------------
    name: str = "model"
    arch_type: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""          # citation for the config numbers

    # -- dimensions --------------------------------------------------------
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0         # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab_size: int = 1024

    # -- layer pattern -----------------------------------------------------
    pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    window: int = 4096        # sliding window for local attention layers
    # positions: "rope" rotates q/k in every attention layer; "sin" adds
    # Audiocraft's sinusoidal absolute embedding (cos then sin, period
    # ``rope_theta``) to the summed token embeddings
    positions: str = "rope"
    rope_theta: float = 10000.0
    attn_logit_softcap: float = 0.0   # gemma-2 style; 0 disables
    final_logit_softcap: float = 0.0
    norm_eps: float = 1e-6
    norm: str = "rms"         # "rms" (gemma-style scale) | "layer" (+ bias)
    embed_scale: bool = True  # multiply token embeddings by sqrt(d_model)
    tie_embeddings: bool = True
    use_qk_norm: bool = False
    d_rnn: int = 0            # RG-LRU width (0 -> d_model)
    lru_temporal_width: int = 4

    # -- MoE / modality ----------------------------------------------------
    moe: Optional[MoEConfig] = None
    mlp_glu: bool = True      # gated (3-matrix) vs plain (2-matrix) MLP
    gelu_exact: bool = False  # erf GELU (torch's default) vs the tanh form
    # musicgen: parallel EnCodec codebooks.  More than one puts them in
    # the delay pattern: codebook k runs k steps late, the gaps hold a
    # special token (id ``vocab_size``, one more embedding row per
    # codebook), the loss leaves out every label that is that token, and
    # each codebook has its own untied head over unscaled summed embeddings
    n_codebooks: int = 1
    frontend: Optional[StubFrontend] = None
    # the frontend's embeddings, projected (with bias) to d_model, are the
    # source of a cross-attention sub-block in every layer instead of
    # tokens prepended to the sequence
    cross_attention: bool = False

    # -- xLSTM -------------------------------------------------------------
    mlstm_proj_factor: float = 2.0
    slstm_ff_factor: float = 4.0 / 3.0
    mlstm_chunk: int = 64

    # -- FedHeN ------------------------------------------------------------
    exit_layer: int = 0       # K: simple subnet = blocks[:K]; 0 -> n_layers//2
                              # (rounded down to a period boundary)

    # -- numerics ----------------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    # -- sharding hints (resolved by launch/sharding.py) --------------------
    attn_shard: str = "auto"    # auto | heads | uneven_heads | replicate
    shard_experts_2d: bool = False  # also shard expert d_ff over data (ZeRO-ish)

    # -- long-context variant ------------------------------------------------
    longctx_window: int = 8192  # window used when forcing the sliding-window
                                # variant for long_500k on full-attention archs

    # ------------------------------------------------------------------

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads != 0:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.positions not in ("rope", "sin"):
            raise ValueError(f"unknown positions {self.positions!r}")
        if self.cross_attention and self.frontend is None:
            raise ValueError("cross_attention needs a frontend (its source)")
        if self.n_codebooks > 1 and (self.tie_embeddings or self.embed_scale):
            raise ValueError("parallel codebooks take untied heads and "
                             "unscaled embeddings (tie_embeddings=False, "
                             "embed_scale=False)")

    # Derived quantities -------------------------------------------------

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def resolved_d_rnn(self) -> int:
        return self.d_rnn if self.d_rnn else self.d_model

    @property
    def delay_pattern(self) -> bool:
        """Parallel codebooks run in MusicGen's delay pattern."""
        return self.n_codebooks > 1

    @property
    def embed_rows(self) -> int:
        """Rows of each embedding table: the vocabulary, plus the delay
        pattern's special token."""
        return self.vocab_size + int(self.delay_pattern)

    @property
    def norm_params(self) -> int:
        return self.d_model * (2 if self.norm == "layer" else 1)

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def n_remainder(self) -> int:
        return self.n_layers % self.period

    @property
    def resolved_exit_layer(self) -> int:
        """FedHeN K, rounded down to a period boundary (>= one period)."""
        k = self.exit_layer if self.exit_layer else self.n_layers // 2
        k = (k // self.period) * self.period
        return max(k, self.period)

    @property
    def exit_period(self) -> int:
        return self.resolved_exit_layer // self.period

    def layer_spec(self, idx: int) -> LayerSpec:
        return self.pattern[idx % self.period]

    def jnp_param_dtype(self):
        return jnp.dtype(self.param_dtype)

    def jnp_compute_dtype(self):
        return jnp.dtype(self.compute_dtype)

    # Parameter counting (used by comm accounting + roofline) -------------

    def param_count(self) -> int:
        """Analytical parameter count of the complex model."""
        total = self._shared_params()
        for i in range(self.n_layers):
            total += self._layer_params(self.layer_spec(i))
        total += self.norm_params                  # final norm
        return total

    def _shared_params(self) -> int:
        """Embeddings, heads, frontend projector and the exit norm: what
        the simple and the complex model both hold outside the layers."""
        d, v = self.d_model, self.vocab_size
        total = self.embed_rows * d * self.n_codebooks   # embeddings
        if not self.tie_embeddings:
            total += v * d * self.n_codebooks     # untied heads
        if self.frontend is not None:
            total += self.frontend.d_in * d       # projector
            if self.cross_attention:
                total += d                        # its bias
        total += self.norm_params                  # exit norm (FedHeN head)
        return total

    def _layer_params(self, spec: LayerSpec) -> int:
        d = self.d_model
        hd = self.resolved_head_dim
        n = 0
        attn = (2 * d * self.n_heads * hd          # Wq, Wo
                + 2 * d * self.n_kv_heads * hd)    # Wk, Wv
        if self.cross_attention:
            n += attn + self.norm_params           # cross-attention + norm
        if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
            n += attn
        elif spec.mixer == RGLRU:
            dr = self.resolved_d_rnn
            n += 2 * d * dr + dr * d               # in/gate/out proj
            n += dr * self.lru_temporal_width      # temporal conv
            n += 3 * dr                            # a, input-gate, rec-gate diag
        elif spec.mixer == MLSTM:
            di = int(self.d_model * self.mlstm_proj_factor)
            n += 2 * d * di                        # up + gate proj
            n += 3 * di * (di // self.n_heads)     # block-diag q, k, v
            n += di * 2 * self.n_heads             # i, f gate projections
            n += di * d                            # down proj
        elif spec.mixer == SLSTM:
            nh, dh = self.n_heads, d // self.n_heads
            n += 4 * d * d                         # i, f, z, o input projections
            n += 4 * nh * dh * dh                  # recurrent (block-diag)
            dff = int(d * self.slstm_ff_factor)
            n += 2 * d * dff                       # post FFN
        n += 2 * self.norm_params                  # pre norms (mixer + mlp)
        mats = 3 if self.mlp_glu else 2            # (gate,) up, down
        if spec.mlp == MLP_DENSE:
            n += mats * d * self.d_ff
        elif spec.mlp == MLP_MOE:
            m = self.moe
            de = m.d_expert or self.d_ff
            n += d * m.n_experts                   # router
            n += mats * d * de * (m.n_experts + m.n_shared)
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        de = m.d_expert or self.d_ff
        total = self.param_count()
        n_moe_layers = sum(
            1 for i in range(self.n_layers)
            if self.layer_spec(i).mlp == MLP_MOE
        )
        mats = 3 if self.mlp_glu else 2
        inactive = n_moe_layers * mats * self.d_model * de * (m.n_experts -
                                                              m.top_k)
        return total - inactive

    def simple_param_count(self) -> int:
        """Analytical parameter count of the FedHeN simple subnet."""
        total = self._shared_params()
        for i in range(self.resolved_exit_layer):
            total += self._layer_params(self.layer_spec(i))
        return total

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # "train" | "prefill" | "decode"


TRAIN_4K = InputShape("train_4k", 4_096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32_768, 128, "decode")
LONG_500K = InputShape("long_500k", 524_288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


# ---------------------------------------------------------------------------
# Federated experiment config (paper §3 + Appendix A)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FedConfig:
    """Hyper-parameters of the FedHeN experimental protocol."""

    n_devices: int = 100           # total federated clients
    n_simple: int = 50             # first 50 simple, rest complex (paper)
    participation: float = 0.10    # 10% active per round
    # Cohort sampling mode (core/sampling.py).  False (default): stratified
    # per-population draws of max(round(participation * pop), 1) clients —
    # the expectation of the paper's protocol, with every slot real (the
    # pre-existing behavior, bit-parity-tested).  True: the paper's EXACT
    # uniform sampling — one draw of ceil(participation * n_devices)
    # clients over the whole population, routed into static per-arch slot
    # blocks whose unfilled slots fold at weight 0 through the validity
    # path (shapes stay static; loss/bytes use realized counts).
    sample_uniform: bool = False
    rounds: int = 1000             # T
    local_epochs: int = 5          # E
    lr: float = 0.1                # eta
    clip_norm: float = 10.0        # gradient clipping (Appendix A)
    batch_size: int = 50
    dirichlet_alpha: float = 0.3   # non-IID split concentration
    iid: bool = True
    algorithm: str = "fedhen"      # fedhen | noside | decouple
    seed: int = 0
    skip_nan_devices: bool = True  # Appendix A: drop NaN devices for the round
    # beyond-paper: FedProx-style proximal term mu/2 ||w - w_server||^2 on
    # client objectives (Li et al. 2020, the paper's related-work family);
    # composes with any of the three algorithms.  0 = off (paper setting).
    prox_mu: float = 0.0
    # Streaming cohort engine: train the round's cohort in chunks of this
    # many clients (per population), folding each chunk into running masked
    # aggregation sums — device memory becomes O(cohort_chunk) instead of
    # O(k).  0 = whole population in one chunk.  "auto" derives the chunk
    # from the flat layout's per-client byte footprint vs
    # ``agg_memory_budget_mb`` (core/flatten.auto_cohort_chunk).  Populations
    # whose size the chunk does not divide are padded with zero-validity
    # clients, so the aggregate is unchanged (see core/federated.py).
    cohort_chunk: Union[int, str] = 0
    # masked_agg kernel lane-tile width (multiple of 128) — the ROADMAP
    # block-size sweep knob; the flat layout's total length is rounded up
    # to it so the fold needs no call-time padding.
    agg_block_n: int = 2048
    # dtype trained chunks stream through the fold in ("bfloat16" halves
    # the fold's HBM read traffic; accumulation is always f32).
    agg_stream_dtype: str = "float32"
    # memory budget targeted by cohort_chunk="auto" (per-client packed
    # footprint x multiplier x chunk <= this).
    agg_memory_budget_mb: float = 512.0
    # Wire dtype of the communication path (core/comm.py): the server
    # broadcast is decoded from this format on clients, and client uploads
    # are folded through it ("int8" via the dequantizing masked_agg
    # variant — ~3.9x smaller payloads than f32 incl. the scale sidecar).
    # "float32" is the identity wire (paper accounting, no transform).
    comm_dtype: str = "float32"
    # int8 wire scale-group size: one f32 scale per this many elements.
    # Must divide the flat layout's lane alignment (128) so scale groups
    # never cross a LeafSlot boundary.
    quant_block: int = 128
    # Wire v2 upload-path knobs (core/comm.py).  topk_frac < 1 uploads
    # only the k = ceil(frac * n) largest-|delta| entries as index+value
    # payloads (k rounded up to the 128-lane multiple); stochastic
    # rounding makes the lossy encode unbiased (seeded per client+round);
    # error_feedback keeps a per-client residual row
    # (core/state_store.py) accumulating the compression error so it is
    # re-uploaded next participation.  Any of the three switches the
    # upload from full params to deltas vs the trained-on broadcast; all
    # defaults leave the pre-existing wire bit-identical.
    topk_frac: float = 1.0
    stochastic_rounding: bool = False
    error_feedback: bool = False
    # Asynchronous round engine (core/async_rounds.py): bounded staleness
    # lag measured in chunk folds.  Chunk ``i`` of a round trains on the
    # server params published at fold ``i - async_lag`` of the global fold
    # stream — the first ``async_lag`` chunks of every round overlap the
    # previous round's server fold and therefore train on a stale,
    # version-tagged broadcast.  0 = fully synchronous (today's engine,
    # bit-for-bit).
    async_lag: int = 0
    # Staleness weighting scheme for stale uploads: "poly" applies the
    # FedAsync polynomial decay 1/(1+s)^async_decay (s = staleness in
    # rounds) to the client's validity weight before the masked fold;
    # "none" folds stale uploads at full weight.
    async_staleness: str = "poly"
    # Exponent a of the polynomial staleness decay 1/(1+s)^a.
    async_decay: float = 0.5
    # Variance reduction over the per-client flat state store
    # (core/state_store.py): "scaffold" maintains a global control variate
    # c and per-client c_i (Karimireddy et al. 2020, option II) packed
    # through the same FlatLayout as params, corrected into every local
    # SGD step and folded as a second flat accumulator through the masked
    # aggregation launch.  "none" = paper protocol, bit-identical rounds.
    variance_reduction: str = "none"
    # Backing store for the (N_clients, n_flat) per-client vectors:
    # "device" (jnp array), "host" (numpy), "mmap" (np.memmap tempfile for
    # population-scale N), or "auto" (pick by footprint).
    state_store_backend: str = "auto"

    def __post_init__(self):
        self.validate()

    def validate(self) -> None:
        """Single entry point for every config-rejection rule.

        Called from ``__post_init__`` (construction-time failure),
        ``FederatedTrainer.__init__`` and ``launch/train.py`` — so a
        config built by ``dataclasses.replace`` or deserialization hits
        the same wall as one built by the CLI.  Raises ``ValueError``
        with a distinct message per rule (one test each in
        tests/test_config.py).
        """
        # call-time import: the config leaf module must not pull repro.core
        # (aggregate/comm) at import — both import jax-heavy machinery and
        # comm itself imports this module
        from repro.core.aggregate import ALGORITHMS
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {self.algorithm!r} "
                             f"(expected one of {ALGORITHMS})")
        if self.agg_block_n <= 0 or self.agg_block_n % 128:
            raise ValueError("agg_block_n must be a positive multiple of 128")
        if self.agg_stream_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"agg_stream_dtype must be float32 or "
                             f"bfloat16, got {self.agg_stream_dtype!r}")
        if isinstance(self.cohort_chunk, str) and self.cohort_chunk != "auto":
            raise ValueError(f"cohort_chunk must be an int or 'auto', got "
                             f"{self.cohort_chunk!r}")
        # wire validation lives with the wire (one source of truth for the
        # dtype set, quant_block | lane-alignment rule and the v2 knob
        # rules: topk_frac range, stochastic-on-f32, EF-on-lossless)
        from repro.core.comm import WireSpec
        WireSpec(self.comm_dtype, self.quant_block,
                 topk_frac=self.topk_frac,
                 stochastic=self.stochastic_rounding,
                 error_feedback=self.error_feedback)
        if self.async_lag < 0:
            raise ValueError("async_lag must be >= 0 (folds of broadcast "
                             f"staleness), got {self.async_lag}")
        if self.async_staleness not in ("poly", "none"):
            raise ValueError(f"async_staleness must be 'poly' or 'none', "
                             f"got {self.async_staleness!r}")
        if self.async_decay < 0:
            raise ValueError(f"async_decay must be >= 0, "
                             f"got {self.async_decay}")
        if self.variance_reduction not in ("none", "scaffold"):
            raise ValueError(f"variance_reduction must be 'none' or "
                             f"'scaffold', got {self.variance_reduction!r}")
        if self.state_store_backend not in ("auto", "device", "host", "mmap"):
            raise ValueError(f"state_store_backend must be one of "
                             f"auto/device/host/mmap, "
                             f"got {self.state_store_backend!r}")
        if self.variance_reduction == "scaffold" and self.lr <= 0:
            raise ValueError("variance_reduction='scaffold' requires lr > 0 "
                             "(control-variate deltas divide by K*lr)")

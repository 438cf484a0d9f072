"""FedConfig validation (configs/base.py) and the CLI contract: every
rejection rule in ``FedConfig.validate()`` has exactly one test here,
and every FedConfig field must be reachable from the launch/train.py
command line (or be explicitly exempted below) so the config and the
driver cannot drift apart silently."""

import dataclasses
from pathlib import Path

import pytest

from repro.configs.base import FedConfig
from repro.launch.train import build_parser


def _cfg(**kw) -> FedConfig:
    return FedConfig(n_devices=4, n_simple=2, rounds=1, **kw)


# ---------------------------------------------------------------------------
# validate(): one test per rejection message
# ---------------------------------------------------------------------------

def test_valid_config_passes():
    fed = _cfg()
    fed.validate()  # explicit call is idempotent with __post_init__


def test_rejects_unknown_algorithm():
    with pytest.raises(ValueError, match="unknown algorithm 'fedavg'"):
        _cfg(algorithm="fedavg")


@pytest.mark.parametrize("bad", [0, -128, 100])
def test_rejects_bad_agg_block_n(bad):
    with pytest.raises(ValueError,
                       match="agg_block_n must be a positive multiple of 128"):
        _cfg(agg_block_n=bad)


def test_rejects_bad_agg_stream_dtype():
    with pytest.raises(ValueError,
                       match="agg_stream_dtype must be float32 or"):
        _cfg(agg_stream_dtype="float16")


def test_rejects_bad_cohort_chunk_string():
    with pytest.raises(ValueError,
                       match="cohort_chunk must be an int or 'auto'"):
        _cfg(cohort_chunk="all")


def test_rejects_unknown_comm_dtype():
    # delegated to WireSpec — one source of truth for the wire dtype set
    with pytest.raises(ValueError, match="wire dtype must be one of"):
        _cfg(comm_dtype="float16")


def test_rejects_bad_quant_block():
    # delegated to WireSpec: one f32 scale group must never cross the
    # flat layout's 128-lane alignment
    with pytest.raises(ValueError,
                       match="quant_block must divide the lane alignment"):
        _cfg(comm_dtype="int8", quant_block=96)


@pytest.mark.parametrize("bad", [0.0, -0.25, 1.5])
def test_rejects_bad_topk_frac(bad):
    # delegated to WireSpec — one source of truth for the sparsity knob
    with pytest.raises(ValueError, match="topk_frac must be in"):
        _cfg(topk_frac=bad)


def test_rejects_stochastic_rounding_on_f32_wire():
    with pytest.raises(ValueError,
                       match="stochastic rounding requires a lossy wire"):
        _cfg(stochastic_rounding=True)


def test_rejects_error_feedback_on_lossless_wire():
    # f32 + dense: the residual would be identically zero
    with pytest.raises(ValueError,
                       match="error_feedback requires a lossy upload"):
        _cfg(error_feedback=True)
    _cfg(error_feedback=True, comm_dtype="int8")        # lossy: fine
    _cfg(error_feedback=True, topk_frac=0.5)            # sparse: fine


def test_rejects_negative_async_lag():
    with pytest.raises(ValueError, match="async_lag must be >= 0"):
        _cfg(async_lag=-1)


def test_rejects_unknown_async_staleness():
    with pytest.raises(ValueError,
                       match="async_staleness must be 'poly' or 'none'"):
        _cfg(async_staleness="linear")


def test_rejects_negative_async_decay():
    with pytest.raises(ValueError, match="async_decay must be >= 0"):
        _cfg(async_decay=-0.5)


def test_rejects_unknown_variance_reduction():
    with pytest.raises(ValueError,
                       match="variance_reduction must be 'none' or"):
        _cfg(variance_reduction="svrg")


def test_rejects_unknown_state_store_backend():
    with pytest.raises(ValueError,
                       match="state_store_backend must be one of"):
        _cfg(state_store_backend="gpu")


def test_rejects_scaffold_with_nonpositive_lr():
    with pytest.raises(ValueError,
                       match="variance_reduction='scaffold' requires lr > 0"):
        _cfg(variance_reduction="scaffold", lr=0.0)


def test_replace_reruns_validation():
    """dataclasses.replace re-triggers __post_init__ -> validate(), so a
    config mutated after construction hits the same wall as the CLI."""
    fed = _cfg()
    with pytest.raises(ValueError, match="unknown algorithm"):
        dataclasses.replace(fed, algorithm="fedavg")


# ---------------------------------------------------------------------------
# CLI drift: every FedConfig field has a launch/train.py flag (or is
# explicitly exempted here, with the reason)
# ---------------------------------------------------------------------------

# field -> flag, where the flag name is not the mechanical --kebab-case
ALIASES = {
    "n_devices": "--clients",
    "iid": "--non-iid",                 # inverted boolean
    "dirichlet_alpha": "--alpha",
    "async_staleness": "--staleness",
    "async_decay": "--staleness-decay",
}

# fields deliberately NOT exposed as flags (keep this list honest: a new
# field lands here only with a reason, otherwise add the flag)
EXEMPT = {
    "n_simple": "derived as clients // 2 (the paper's 50/50 split)",
    "clip_norm": "Appendix A constant (10.0) — not an experiment knob",
    "skip_nan_devices": "Appendix A protocol constant, always on",
    "prox_mu": "beyond-paper FedProx term, library-only for now",
}


def test_every_fed_config_field_has_a_cli_flag():
    flags = set()
    for action in build_parser()._actions:
        flags.update(action.option_strings)

    missing = []
    for field in dataclasses.fields(FedConfig):
        if field.name in EXEMPT:
            assert field.name not in ALIASES
            continue
        flag = ALIASES.get(field.name,
                           "--" + field.name.replace("_", "-"))
        if flag not in flags:
            missing.append(f"{field.name} (expected {flag})")
    assert not missing, (
        "FedConfig fields without a launch/train.py flag (add the flag "
        f"or an EXEMPT entry with a reason): {missing}")


def test_exempt_list_matches_reality():
    """Exempted fields must still exist on the dataclass (catches a
    rename leaving a stale exemption behind)."""
    names = {f.name for f in dataclasses.fields(FedConfig)}
    stale = set(EXEMPT) - names
    assert not stale, f"EXEMPT names no longer on FedConfig: {stale}"


def test_cli_flags_construct_a_valid_config():
    """The parser's defaults round-trip into a FedConfig that passes
    validate() via build_trainer's construction path."""
    args = build_parser().parse_args([])
    fed = FedConfig(
        n_devices=args.clients, n_simple=args.clients // 2,
        participation=args.participation, rounds=args.rounds,
        local_epochs=args.local_epochs, lr=args.lr,
        batch_size=args.batch_size, iid=not args.non_iid,
        dirichlet_alpha=args.alpha, algorithm=args.algorithm,
        seed=args.seed, cohort_chunk=args.cohort_chunk,
        sample_uniform=args.sample_uniform,
        agg_block_n=args.agg_block_n,
        agg_stream_dtype=args.agg_stream_dtype,
        agg_memory_budget_mb=args.agg_memory_budget_mb,
        comm_dtype=args.comm_dtype, quant_block=args.quant_block,
        topk_frac=args.topk_frac,
        stochastic_rounding=args.stochastic_rounding,
        error_feedback=args.error_feedback,
        async_lag=args.async_lag, async_staleness=args.staleness,
        async_decay=args.staleness_decay,
        variance_reduction=args.variance_reduction,
        state_store_backend=args.state_store_backend)
    fed.validate()


# ---------------------------------------------------------------------------
# compile cache placement (launch/train.py use_compile_cache)
# ---------------------------------------------------------------------------

def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache goes to a fixed,
    gitignored directory at the root of the checkout."""
    import jax
    from repro.launch import train
    repo = Path(__file__).resolve().parents[1]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = train.use_compile_cache()
        assert path == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()


def test_compile_cache_left_to_the_environment(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and the
    helper sets nothing."""
    import jax
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert train.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before

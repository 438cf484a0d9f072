"""The readers of the round's stages and of the host's share of a round:
each on a hand-made trace and span list, and the stage tag they spell
against the one the program puts on its ops."""

import importlib.util
from types import SimpleNamespace

import pytest

from conftest import ROOT

STAGE_METRICS = {"local_sgd_ms": "local_sgd", "fold_ms": "fold",
                 "finalize_ms": "finalize"}


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _op(name, stage=None):
    attrs = f', frontend_attributes={{fedhen_scope="{stage}"}}' \
        if stage else ""
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop{attrs}"


OPS = {
    _op("fusion.1", "local_sgd"): 0.004,
    _op("fusion.2", "local_sgd"): 0.002,
    _op("fusion.3", "fold"): 0.0005,
    "%masked_agg_acc.1 = f32[1,2048]{1,0} custom-call(%a), "
    'custom_call_target="tpu_custom_call", '
    'frontend_attributes={fedhen_scope="fold",kernel_metadata={}}': 0.0015,
    _op("fusion.4", "finalize"): 0.0002,
    _op("fusion.5"): 0.001,                   # untagged: loop machinery
    _op("fusion.6", "local_sgd_extra"): 0.5,  # another stage's name
}


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_stage_reader_sums_its_tagged_ops(name):
    want = {"local_sgd_ms": 0.006, "fold_ms": 0.002,
            "finalize_ms": 0.0002}[name]
    ctx = SimpleNamespace(trace={"ops": OPS}, rounds=2)
    assert _reader(name)(ctx) == pytest.approx(1e3 * want / 2)


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_stage_reader_reads_nothing_without_its_tag(name):
    ops = {_op("fusion.5"): 0.001,
           "%_lambda_.1 = f32[1,2048]{1,0} custom-call(%a), "
           'custom_call_target="tpu_custom_call", '
           "frontend_attributes={kernel_metadata={}}": 0.002}
    assert _reader(name)(SimpleNamespace(trace={"ops": ops},
                                         rounds=1)) is None


def _span(name, rnd, dur):
    return {"kind": "span", "name": name, "round": rnd, "dur_s": dur}


def test_host_round_reads_round_less_execute():
    spans = [_span("sample_gather", 3, 0.010), _span("execute", 3, 2.75),
             _span("round", 3, 2.765),
             _span("sample_gather", 4, 0.011), _span("execute", 4, 2.76),
             _span("round", 4, 2.777)]
    got = _reader("host_round_ms")(SimpleNamespace(spans=spans))
    assert got == pytest.approx(1e3 * (0.015 + 0.017) / 2)


def test_host_round_reads_nothing_without_execute_spans():
    spans = [_span("round", 3, 2.765), _span("sample_gather", 3, 0.01)]
    assert _reader("host_round_ms")(SimpleNamespace(spans=spans)) is None
    assert _reader("host_round_ms")(SimpleNamespace(spans=[])) is None


def test_stage_tags_match_what_the_program_emits():
    """The text the readers look for is the text the program's stages
    put on a lowered op (a rename on either side fails here)."""
    import jax
    import jax.numpy as jnp
    from repro.obs import scopes
    from bench.metrics import _scopes

    assert set(STAGE_METRICS.values()) < set(scopes.STAGES)

    def f(x):
        out = []
        for stage in scopes.STAGES:
            with scopes.stage(stage):
                x = jnp.sin(x) * 2.0
            out.append(x)
        return out

    text = jax.jit(f).lower(jnp.ones((4,))).as_text(dialect="hlo")
    for stage in STAGE_METRICS.values():
        lines = [ln for ln in text.splitlines()
                 if _scopes.tagged(stage)(ln)]
        assert any(" sine(" in ln for ln in lines), stage

"""The readers of the client model's parts: each on a hand-made trace,
and the part tag they spell against the one the program puts on its
ops, forward and backward."""

import importlib.util
from types import SimpleNamespace

import pytest

from conftest import ROOT

PART_METRICS = {"self_attn_ms": "self_attn", "cross_attn_ms": "cross_attn",
                "codebook_heads_ms": "heads"}


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _op(name, part=None, stage="local_sgd"):
    attrs = [f'fedhen_part="{part}"'] if part else []
    attrs.append(f'fedhen_scope="{stage}"')
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, "
            f"frontend_attributes={{{','.join(attrs)}}}")


OPS = {
    _op("fusion.1", "self_attn"): 0.004,
    _op("convolution.2", "self_attn"): 0.002,
    _op("fusion.3", "cross_attn"): 0.0005,
    _op("fusion.4", "heads"): 0.003,
    _op("fusion.5", "ffn"): 0.006,
    _op("fusion.6"): 0.001,                      # untagged: loop machinery
    _op("fusion.7", "heads_extra"): 0.5,         # another part's name
    _op("fusion.8", stage="fold"): 0.0015,
}


@pytest.mark.parametrize("name", sorted(PART_METRICS))
def test_part_reader_sums_its_tagged_ops(name):
    want = {"self_attn_ms": 0.006, "cross_attn_ms": 0.0005,
            "codebook_heads_ms": 0.003}[name]
    ctx = SimpleNamespace(trace={"ops": OPS}, rounds=2)
    assert _reader(name)(ctx) == pytest.approx(1e3 * want / 2)


@pytest.mark.parametrize("name", sorted(PART_METRICS))
def test_part_reader_reads_nothing_without_its_tag(name):
    ops = {_op("fusion.6"): 0.001, _op("fusion.5", "ffn"): 0.002,
           _op("fusion.8", stage="fold"): 0.003}
    assert _reader(name)(SimpleNamespace(trace={"ops": ops},
                                         rounds=1)) is None


def test_part_tags_match_what_the_program_emits():
    """The text the readers look for is the text the program's parts put
    on the ops of a client's gradient step, forward and backward (a
    rename on either side fails here)."""
    import jax
    from repro.obs import scopes
    from bench.metrics import _parts
    from test_bench_musicgen import _batch, small_cfg
    from bench import families

    assert set(PART_METRICS.values()) < set(scopes.PARTS)
    cfg = small_cfg()
    adapter = families.family(cfg).adapter(cfg)
    params = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: _batch(cfg))
    text = jax.jit(jax.grad(adapter.loss_side)).lower(
        params, batch).as_text(dialect="hlo")
    for part in PART_METRICS.values():
        lines = [ln for ln in text.splitlines() if _parts.tagged(part)(ln)]
        assert any(" dot(" in ln for ln in lines), part

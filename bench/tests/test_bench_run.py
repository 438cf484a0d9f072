"""A whole run of the tiny ResNet cell on the CPU: the program's set-up
rounds against the plain reference, and the result line's shape."""

from types import SimpleNamespace

import pytest
from conftest import tiny_cell

from bench import run


def test_tiny_run_matches_reference():
    """The program's set-up rounds agree with the reference to float32
    rounding, so ``correct`` is true; the line carries the end-to-end
    metrics, the set-up's parts, and the compared numbers last."""
    result = run.run_cell(tiny_cell(), 3_000_000_019, 0.2, False)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"round_s", "peak_hbm_gb",
                                      "wire_mb_per_round", "setup_s"}
    assert list(result["setup_phases"]) == ["jax", "weights", "data",
                                            "trainer", "round1", "round2"]
    assert sum(result["setup_phases"].values()) <= \
        result["metrics"]["setup_s"]["value"]


def test_a_declared_metric_that_reads_nothing_fails_the_run():
    """A fold metric on a trace with no fold kernel raises, naming the
    custom calls the trace holds, instead of leaving the metric out."""
    op = ('%fusion.1 = f32[8]{0} custom-call(f32[8]{0} %p), '
          'custom_call_target="Sharding"')
    ctx = SimpleNamespace(trace={"ops": {op: 1e-3}}, rounds=2)
    with pytest.raises(RuntimeError, match="fold_kernel_ms.*fusion.1"):
        run._read_metric("fold_kernel_ms", ctx)

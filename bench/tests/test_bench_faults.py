"""A run with the timed path broken comes out not correct."""

import pytest

from bench import calibrate, families, run


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_planted_fault_fails_the_check(tiny, fault, monkeypatch):
    build = families.build_trainer
    monkeypatch.setattr(
        families, "build_trainer",
        lambda *a, **k: calibrate.plant(build(*a, **k), fault))
    result = run.run_cell(tiny, 41, 0.2, False)
    assert not result["correct"], result["checks"]

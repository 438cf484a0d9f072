"""Every cell of BENCHMARK.json loads, and the harness refuses a CPU."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import families, run
from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load(name):
    cell = run.load_cell(name)
    cfg, traffic = cell["cfg"], cell["traffic_data"]
    assert cfg["name"] == cell["config"]
    assert traffic["name"] == cell["traffic"]
    assert cell["limits"] and set(cell["limits"]) <= {
        "loss_gap", "update_gap", "change_gap"}
    adapter = families.family(cfg).adapter(cfg)
    shapes = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == cfg["n_params"]
    assert {m["name"] for m in cell["end_to_end"]} >= {"round_s", "setup_s"}
    for m in cell["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs 1 TPU" in proc.stderr
    assert not proc.stdout.strip()

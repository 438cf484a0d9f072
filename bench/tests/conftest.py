"""Shared pieces of the benchmark's CPU tests: the ``preact18-gn`` cell at
its published widths on 8x8 images, cut to a federation of four clients
that train one step each, so that the whole run path (program, reference,
comparison) runs in seconds."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_TRAFFIC = {
    "clients": 4, "simple_clients": 2, "participation": 0.5,
    "local_epochs": 1, "batch_size": 2, "points_per_client": 2,
    "dirichlet_alpha": 0.3, "lr": 0.1, "clip_norm": 10.0,
    "algorithm": "fedhen", "comm_dtype": "float32", "cohort_chunk": 0,
    "async_lag": 0, "check_rounds": 2}

# f32 on the CPU: program and reference differ by summation order only
TINY_LIMITS = {"loss_gap": 1e-4, "update_gap": 1e-4, "change_gap": 1e-4}


def tiny_cell(**traffic) -> dict:
    from bench import run
    cell = run.load_cell("preact18-gn.paper-f32")
    return {"name": "tiny", "cfg": dict(cell["cfg"], image_size=8),
            "traffic_data": dict(TINY_TRAFFIC, **traffic),
            "limits": TINY_LIMITS, "per_layer": [],
            "end_to_end": cell["end_to_end"]}


@pytest.fixture
def tiny():
    return tiny_cell()

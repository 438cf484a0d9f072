"""The trace reduction: busy union, idle share, kernel sums, gap labels."""

import pytest

from bench import trace


def test_reduce_events_on_a_hand_made_timeline():
    ms = 1_000_000
    device = {"/device:TPU:0": [
        ("fusion.1", 0 * ms, 4 * ms),
        ("kernel.3", 3 * ms, 6 * ms),               # overlaps the first
        ("while.2", 8 * ms, 9 * ms),
        ("fusion.4", 8 * ms, 8.5 * ms),             # nested in the while
        ("fusion.1", 12 * ms, 20 * ms),             # runs past the window
    ]}
    host = [("bench_window", 1 * ms, 15 * ms, 0),
            ("run_round", 1 * ms, 15 * ms, 1),
            ("stack", 6 * ms, 8 * ms, 2),
            ("sample", 9 * ms, 12 * ms, 2)]
    red = trace.reduce_events(device, host, (1 * ms, 15 * ms))
    assert red["window_s"] == pytest.approx(0.014)
    # busy: [1, 6] + [8, 9] + [12, 15] = 9 ms of 14
    assert red["busy_s"] == pytest.approx(0.009)
    assert red["ops"]["fusion.1"] == pytest.approx(0.006)
    assert red["ops"]["while.2"] == pytest.approx(0.0005)     # self time
    assert trace.kernel_seconds(red["ops"],
                                lambda op: op.startswith("kernel")) == \
        pytest.approx(0.003)
    assert red["gaps"] == [("sample", pytest.approx(0.003)),
                           ("stack", pytest.approx(0.002))]


def test_reduce_a_trace_recorded_on_the_chip():
    """A v5e trace (committed fixture) of a window holding two calls of the
    accumulating fold kernel and a matmul, with a 5 ms host sleep after
    each fold: the window and the kernels are found, the device is idle
    nearly all of the window, and the longest gaps carry the host's sleep."""
    import importlib.util
    from types import SimpleNamespace
    from conftest import ROOT
    red = trace.load(str(ROOT / "bench" / "tests" / "fixtures"
                         / "fold_window.xplane.pb"))
    assert red["window_s"] == pytest.approx(0.015813725)
    assert 0 < red["busy_s"] < 1e-4
    path = ROOT / "bench" / "metrics" / "fold_kernel_ms.py"
    spec = importlib.util.spec_from_file_location("fold_kernel_ms", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    from bench.metrics._folds import is_fold
    folds = [op for op in red["ops"] if is_fold(op)]
    assert [trace.short_name(op) for op in folds] == ["_lambda_.1"]
    assert reader.read(SimpleNamespace(trace=red, rounds=2)) == \
        pytest.approx(1e3 * 2.362e-06 / 2)
    assert [label for label, _ in red["gaps"][:2]] == ["$time sleep"] * 2

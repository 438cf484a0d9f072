"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``) and
a traffic mix (``bench/traffic/``).  One run:

1. set-up: makes the weights and every client's data on the device from
   ``--seed``, builds the program's ``FederatedTrainer`` on them, and drives
   its first ``check_rounds`` rounds through ``run_round``, the window's
   own call (the first of them compiles or loads from the compile cache);
2. window: runs rounds back to back until ``--seconds`` have passed, each
   ended by the host's read of its metrics and the last by
   ``block_until_ready`` on the server model;
3. check: frees the program's state and runs the plain reference
   (``bench/reference/``) over the set-up rounds from the same seed, and
   compares what the program produced with it (``bench/check.py``).

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the window is traced and the line carries the per-layer
metrics, each read by ``bench/metrics/<name>.py``.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration and
    traffic files and its limits read in."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in spec["configs"]}
    cell["cfg"] = json.loads((root / configs[cell["config"]]["file"])
                             .read_text())
    cell["traffic_data"] = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["limits"] = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    cell["end_to_end"] = [m for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def _setup_jax() -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_chips(n: int):
    """The first device, which must be a TPU, with ``n`` devices present."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(f"needs {n} TPU chip(s); JAX found {len(devs)} "
                         f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs[0]


def _fold_calls(info: dict) -> list:
    """(rows, stream itemsize) of every fold call of a round."""
    calls = []
    for k in (info["k_simple"], info["k_complex"]):
        chunk = k if info["cohort_chunk"] <= 0 else min(info["cohort_chunk"],
                                                        k)
        calls += [(chunk, info["itemsize"])] * (-(-k // chunk))
    return calls


def _read_metric(name: str, ctx):
    """The reading of ``bench/metrics/<name>.py``.  Every per-layer metric
    that reaches here is declared for this cell, so one that finds nothing
    to read is a fault of the reader or of the run, not an omission."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(ctx)
    if value is None:
        from bench import trace as tracelib
        kernels = sorted({tracelib.short_name(op) for op in ctx.trace["ops"]
                          if "custom-call" in op})
        raise RuntimeError(f"per-layer metric {name} is declared for this "
                           f"cell but found nothing to read; custom calls "
                           f"in the trace: {kernels}")
    return value


def drive_setup(trainer, rounds: int, marks=None) -> dict:
    """Run the trainer's first ``rounds`` rounds through ``run_round``;
    keep each round's mean client losses and a host copy of the server
    model after each."""
    import jax
    import numpy as np
    program = {"losses": [], "models": {}}
    for r in range(rounds):
        m = trainer.run_round()
        program["losses"].append((m["loss_simple"], m["loss_complex"]))
        program["models"][r + 1] = jax.tree.map(np.asarray,
                                                trainer.server.complex)
        if marks is not None:
            marks[f"round{r + 1}"] = time.perf_counter()
    return program


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t0: float = T0) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    import numpy as np
    from bench import check, families, flops, peaks
    from bench import trace as tracelib
    from repro.obs import telemetry as obslib

    cfg, traffic = cell["cfg"], cell["traffic_data"]
    rounds = traffic["check_rounds"]
    dev = jax.devices()[0]
    marks = {"start": t0, "jax": time.perf_counter()}
    sink = obslib.MemorySink() if trace else None
    tel = obslib.Telemetry([sink]) if trace else None
    trainer = families.build_trainer(cfg, traffic, seed, telemetry=tel,
                                     marks=marks)
    cohort = trainer.k_simple + trainer.k_complex
    info = {"k_simple": trainer.k_simple, "k_complex": trainer.k_complex,
            "cohort_chunk": trainer.cohort_chunk,
            "n_flat": trainer.layout.n_flat,
            "itemsize": np.dtype(traffic["comm_dtype"]).itemsize}

    program = drive_setup(trainer, rounds, marks)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    first_round = trainer.server.round
    bytes0 = trainer.total_bytes
    n, failed = 0, 0
    t_start = time.perf_counter()
    setup_s = t_start - t0
    with jax.profiler.TraceAnnotation(tracelib.WINDOW):
        while True:
            m = trainer.run_round()
            n += 1
            failed += int(m["n_valid"] < cohort)
            if time.perf_counter() - t_start >= seconds:
                break
        jax.block_until_ready(trainer.server.complex)
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    round_s = (t_end - t_start) / n
    wire_mb = (trainer.total_bytes - bytes0) / n / 1e6
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    spans = ([e for e in sink.events if e["kind"] == "span"
              and e["round"] is not None
              and first_round <= e["round"] < first_round + n]
             if trace else [])
    del trainer, m
    gc.collect()

    checks = check.check(cell, seed, program, rounds)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": check.correct(checks), "attempted": n,
              "failed": failed}
    if not trace:
        values = {"round_s": round_s, "peak_hbm_gb": peak / 1e9,
                  "wire_mb_per_round": wire_mb, "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}
    else:
        red = tracelib.load(tracelib.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = SimpleNamespace(
            trace=red, rounds=n, round_s=round_s, spans=spans,
            flops=flops.round_flops(cfg, traffic, info["k_simple"],
                                    info["k_complex"]),
            fold_bytes=[flops.fold_bytes(z, info["n_flat"], isz)
                        for z, isz in _fold_calls(info)],
            peaks=peaks.peaks(dev.device_kind))
        result["metrics"] = {
            m["name"]: {"value": _read_metric(m["name"], ctx),
                        "unit": m["unit"]}
            for m in cell["per_layer"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {
            "device_ops": tracelib.top(
                (tracelib.short_name(k), v) for k, v in red["ops"].items()),
            "idle_gaps": [[k, v] for k, v in red["gaps"][:10]]}
    result["device"] = device
    result["setup_phases"] = _phases(marks)
    result["checks"] = checks
    return result


def _phases(marks: dict) -> dict:
    """Seconds of each part of set-up, from the host clock's marks."""
    names = list(marks)
    return {b: marks[b] - marks[a] for a, b in zip(names, names[1:])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    _setup_jax()
    require_chips(cell["chips"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print("setup phases (s): " + " ".join(
        f"{k} {v:.3f}" for k, v in result["setup_phases"].items()),
        file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

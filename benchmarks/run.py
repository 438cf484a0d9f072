"""Benchmark harness — one benchmark per paper table/figure + system
benches.  Prints ``name,us_per_call,derived`` CSV rows.

  table1  — Table 1: rounds-to-target, IID split (FedHeN/NoSide/Decouple)
  table2  — Table 2: rounds-to-target, non-IID (Dirichlet) split
  comm    — communication-savings accounting (bytes to target)
  sidecost— 'side objective adds minimal cost' (paper §2): step-time +
            FLOPs ratio of ClientTrainingSideObj vs ClientTraining
  aggsrv  — server masked-aggregation throughput (kernel contract, XLA path)
  streamscale — streaming cohort engine: cohort x chunk sweep of round
            latency + compiled peak temp memory (O(chunk) memory claim)
  serve   — early-exit serving throughput (reduced arch, CPU)
  roofline— aggregates results/dryrun/*.json (see EXPERIMENTS.md §Roofline)

Env: BENCH_FAST=1 shrinks rounds; BENCH_ONLY=name,name selects a subset.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _row(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------

def bench_tables(which: str):
    from benchmarks.fed_common import table_rows
    iid = which == "table1"
    rounds = 16 if os.environ.get("BENCH_FAST") else 40
    t0 = time.time()
    rows = table_rows(iid=iid, rounds=rounds)
    wall = (time.time() - t0) * 1e6
    meta = rows.pop()["_meta"]
    for r in rows:
        name = f"{which}_{r['model']}_tgt{r['target']}"
        derived = (f"fedhen={r['fedhen']};noside={r['noside']};"
                   f"decouple={r['decouple']};gain={r['gain']:.2f}x")
        _row(name, meta["fedhen"]["us_per_round"], derived)
    _row(which + "_total", wall, f"rounds={rounds}")
    return rows, meta


def bench_comm():
    from benchmarks.fed_common import run_protocol, TARGETS
    from repro.core.federated import rounds_to_target
    rounds = 16 if os.environ.get("BENCH_FAST") else 40
    out = {}
    for a in ("fedhen", "noside", "decouple"):
        res = run_protocol(a, iid=True, rounds=rounds)
        r = rounds_to_target(res["history"], "acc_simple", TARGETS[0])
        bytes_to_tgt = res["bytes_per_round"] * r if r > 0 else float("nan")
        out[a] = bytes_to_tgt
        _row(f"comm_bytes_to_target_{a}", res["wall_per_round_us"],
             f"rounds={r};MB={bytes_to_tgt / 1e6:.1f}")
    if out["fedhen"] == out["fedhen"]:  # not nan
        rest = [v for k, v in out.items() if k != "fedhen" and v == v]
        if rest:
            _row("comm_savings", 0.0,
                 f"fedhen_vs_best_baseline="
                 f"{min(rest) / out['fedhen']:.2f}x")


def bench_sidecost():
    """Paper §2 claim: the side objective is cheap (one extra head)."""
    from repro.configs.base import LayerSpec, ModelConfig
    from repro.core.adapters import LMAdapter
    from repro.optim.sgd import sgd_update
    cfg = ModelConfig(n_layers=6, d_model=128, n_heads=4, n_kv_heads=4,
                      d_ff=256, vocab_size=512,
                      pattern=(LayerSpec("attn"),), exit_layer=3,
                      compute_dtype="float32")
    ad = LMAdapter(cfg)
    params = ad.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 65),
                                          0, cfg.vocab_size)}
    times, flops = {}, {}
    from repro.roofline import hlo_walk
    for name, loss in (("plain", ad.loss_complex), ("side", ad.loss_side)):
        step = jax.jit(lambda p, b, f=loss: sgd_update(
            p, jax.grad(f)(p, b), 0.1, 10.0))
        out = step(params, batch)  # compile
        jax.block_until_ready(out)
        t0 = time.time()
        n = 10
        for _ in range(n):
            out = step(params, batch)
        jax.block_until_ready(out)
        times[name] = (time.time() - t0) / n * 1e6
        txt = jax.jit(lambda p, b, f=loss: jax.grad(f)(p, b)).lower(
            params, batch).compile().as_text()
        flops[name] = hlo_walk.analyze(txt)["flops"]

    _row("side_objective_cost", times["side"],
         f"time_ratio={times['side'] / times['plain']:.3f};"
         f"flops_ratio={flops['side'] / flops['plain']:.3f}"
         f";paper_claim=minimal")


def bench_aggsrv():
    """Server aggregation throughput (the masked_agg kernel contract)."""
    from repro.kernels.masked_agg.ref import masked_agg_ref
    z, n = 10, 4_000_000
    x = jax.random.normal(jax.random.PRNGKey(0), (z, n), jnp.float32)
    mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (n,))
    w = jnp.full((z,), 1.0 / z)
    fn = jax.jit(lambda x: masked_agg_ref(x, mask, w, w))
    jax.block_until_ready(fn(x))
    t0 = time.time()
    reps = 5
    for _ in range(reps):
        out = fn(x)
    jax.block_until_ready(out)
    us = (time.time() - t0) / reps * 1e6
    gbps = (z * n * 4) / (us / 1e6) / 1e9
    _row("server_masked_agg", us, f"GBps={gbps:.2f};leaf=10x4M")


def bench_streamscale():
    """Cohort x chunk sweep: the streaming engine's memory/latency
    story."""
    from benchmarks.streaming_cohort import sweep
    rounds = 1 if os.environ.get("BENCH_FAST") else 3
    for r in sweep(timed_rounds=rounds):
        derived = (f"k={r['k']};chunk={r['chunk']};"
                   f"temp_mib={r['temp_bytes'] / 2**20:.2f};"
                   f"fold_kib={r['fold_temp_bytes'] / 2**10:.0f};"
                   f"reduces={r['hlo_reduce_ops']}")
        if "fits_under_seed_peak" in r:
            derived += f";fits_under_seed_peak={r['fits_under_seed_peak']}"
        _row(f"streamscale_{r['label']}", r["us_per_round"], derived)


def bench_serve():
    from repro import configs
    from repro.launch.serve import generate
    from repro.models import transformer as tfm
    cfg = configs.get_reduced("gemma2-2b")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    _, stats = generate(params, cfg, prompts, 16, adaptive_threshold=0.5)
    us = (time.time() - t0) * 1e6
    _row("serve_early_exit", us / (8 * 16),
         f"exit_confident={stats['exit_confident_frac']:.2f};"
         f"agreement={stats['exit_agreement']:.2f}")


def bench_roofline():
    path = "results/dryrun"
    if not os.path.isdir(path):
        _row("roofline", 0.0, "no results/dryrun; run repro.launch.dryrun")
        return
    n, worst = 0, None
    for f in sorted(os.listdir(path)):
        if not f.endswith(".json"):
            continue
        with open(os.path.join(path, f)) as fh:
            d = json.load(fh)
        n += 1
        frac = d.get("useful_flops_ratio", 0)
        if d["mesh"] == "16x16" and (worst is None or frac < worst[1]):
            worst = (f"{d['arch']}x{d['shape']}", frac)
    _row("roofline_records", 0.0,
         f"n={n};worst_useful_flops={worst[0]}:{worst[1]:.3f}"
         if worst else f"n={n}")


BENCHES = {
    "table1": lambda: bench_tables("table1"),
    "table2": lambda: bench_tables("table2"),
    "comm": bench_comm,
    "sidecost": bench_sidecost,
    "aggsrv": bench_aggsrv,
    "streamscale": bench_streamscale,
    "serve": bench_serve,
    "roofline": bench_roofline,
}


def main() -> None:
    only = os.environ.get("BENCH_ONLY")
    names = only.split(",") if only else list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        try:
            BENCHES[name]()
        except Exception as e:  # noqa: BLE001
            _row(name + "_ERROR", 0.0, repr(e)[:150])


if __name__ == "__main__":
    main()

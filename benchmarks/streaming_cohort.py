"""Streaming cohort engine: cohort size x chunk size sweep.

Measures, for each (cohort k, cohort_chunk) point, the compiled round's
peak temp memory (``memory_analysis().temp_size_in_bytes`` of the AOT
round — XLA's scheduled scratch high-water mark, the quantity the
streaming engine bounds), the HLO op / reduce counts, the same for ONE
fold lowered alone (``fold_temp_bytes`` / ``fold_reduce_ops`` — what the
fold owns, isolated from training scratch), and the wall-clock round
latency.

Headline row: ``k40_chunk5`` — a cohort 4x the seed default (k=40 vs
k=10) streamed with ``cohort_chunk=5`` must fit under the one-shot k=10
round's peak temp memory.

Run as a script to emit ``BENCH_streaming.json`` and exit nonzero on a
regression (the CI smoke): ``python benchmarks/streaming_cohort.py --fast``.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core.adapters import LMAdapter
from repro.core.federated import FederatedTrainer
from repro.data.federated import iid_split
from repro.data.synthetic import synthetic_lm

STREAM_CFG = ModelConfig(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                         d_ff=128, vocab_size=256,
                         pattern=(LayerSpec("attn"),), exit_layer=2,
                         compute_dtype="float32")

# (label, total clients, cohort_chunk); participation 0.5 -> k =
# clients/2.  k=10 matches the seed FedConfig default cohort (100 devices
# x 10%).
SWEEP: Tuple[Tuple[str, int, int], ...] = (
    ("k10_chunk0", 20, 0),      # seed-default cohort, one-shot
    ("k10_chunk5", 20, 5),
    ("k40_chunk0", 80, 0),      # 4x cohort, one-shot: memory blow-up
    ("k40_chunk10", 80, 10),
    ("k40_chunk5", 80, 5),      # 4x cohort streamed: acceptance row
)


def build_trainer(n_devices: int, chunk: int, *,
                  timed_rounds: int) -> FederatedTrainer:
    fed = FedConfig(n_devices=n_devices, n_simple=n_devices // 2,
                    participation=0.5, rounds=timed_rounds, local_epochs=1,
                    lr=0.1, batch_size=8, algorithm="fedhen", seed=0,
                    cohort_chunk=chunk)
    data = synthetic_lm(n_devices * 16, 32, STREAM_CFG.vocab_size, seed=1)
    shards = iid_split(data, fed.n_devices, seed=2)
    shards = [{"tokens": jnp.asarray(s["tokens"])} for s in shards]
    return FederatedTrainer(LMAdapter(STREAM_CFG), fed, shards)


def measure_fold(trainer, z: int) -> Dict:
    """Lower ONE aggregation fold (z stacked clients) by itself: the temp
    bytes and op counts the engine owns, isolated from training scratch."""
    from repro.core import aggregate
    template = trainer.server.complex
    chunk = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (z,) + x.shape), template)
    is_simple = jnp.zeros(z, bool)
    valid = jnp.ones(z, bool)
    spec = aggregate.EngineSpec(algorithm="fedhen", mask=trainer.mask,
                                layout=trainer.layout,
                                block_n=trainer.fed.agg_block_n)

    def fold(state, chunk, is_simple, valid, flat_mask):
        """flat_mask enters as a traced argument (mirroring the round jit)"""
        return aggregate.streaming_fold(state, chunk, is_simple, valid,
                                        spec.bind(flat_mask=flat_mask))

    state = aggregate.streaming_init(template, spec)
    compiled = jax.jit(fold).lower(state, chunk, is_simple, valid,
                                   trainer.flat_mask).compile()
    hlo = compiled.as_text()
    return {"fold_temp_bytes":
            int(compiled.memory_analysis().temp_size_in_bytes),
            "fold_reduce_ops": hlo.count(" reduce(")}


def measure(n_devices: int, chunk: int, *, timed_rounds: int = 3) -> Dict:
    trainer = build_trainer(n_devices, chunk, timed_rounds=timed_rounds)
    compiled = trainer.lower_round().compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    trainer.run_round()                      # compile + warm the jit cache
    t0 = time.time()
    for _ in range(timed_rounds):
        trainer.run_round()
    us = (time.time() - t0) / timed_rounds * 1e6
    row = {"k": trainer.k_simple + trainer.k_complex, "chunk": chunk,
           "us_per_round": us,
           "temp_bytes": int(mem.temp_size_in_bytes),
           "arg_bytes": int(mem.argument_size_in_bytes),
           "hlo_ops": hlo.count(" = "),
           "hlo_reduce_ops": hlo.count(" reduce(")}
    row.update(measure_fold(
        trainer, chunk if chunk > 0 else max(trainer.k_simple,
                                             trainer.k_complex)))
    return row


def sweep(timed_rounds: int = 3) -> List[Dict]:
    rows = []
    for label, n_devices, chunk in SWEEP:
        r = measure(n_devices, chunk, timed_rounds=timed_rounds)
        r["label"] = label
        rows.append(r)
    by = {r["label"]: r for r in rows}
    # the PR 2 acceptance comparison: 4x cohort streamed vs seed one-shot
    flat = by["k40_chunk5"]
    flat["fits_under_seed_peak"] = (
        flat["temp_bytes"] <= by["k10_chunk0"]["temp_bytes"])
    # CI-gated variant with headroom: a broken chunking path blows round
    # temp up ~4x (see k40_chunk0), while allocator-level jitter across
    # jax/XLA releases moves it by fractions of a percent — 1.5x separates
    # the two without making CI track XLA's buffer assignment exactly
    flat["stream_memory_ok"] = (
        flat["temp_bytes"] <= 1.5 * by["k10_chunk0"]["temp_bytes"])
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="1 timed round per point (CI smoke)")
    ap.add_argument("--out", default="BENCH_streaming.json")
    args = ap.parse_args(argv)

    rows = sweep(timed_rounds=1 if args.fast else 3)
    from repro.core import flatten
    params_abs = jax.eval_shape(LMAdapter(STREAM_CFG).init,
                                jax.random.PRNGKey(0))
    payload = {
        "bench": "streaming_cohort",
        "backend": jax.default_backend(),
        "model": STREAM_CFG.name,
        "n_flat": flatten.build_layout(params_abs, total_multiple=2048).n_flat,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    for r in rows:
        print(f"{r['label']:>16}: {r['us_per_round']:.0f} us/round, "
              f"round temp {r['temp_bytes'] / 2**20:.2f} MiB, "
              f"fold temp {r['fold_temp_bytes'] / 2**10:.0f} KiB, "
              f"{r['hlo_reduce_ops']} reduce ops")

    flat = next(r for r in rows if r["label"] == "k40_chunk5")
    if not flat["stream_memory_ok"]:
        print(f"REGRESSION: ['stream_memory_ok'] (see {args.out})")
        return 1
    print(f"ok — wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

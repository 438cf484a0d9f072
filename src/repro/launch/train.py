"""Federated training driver (FedHeN / NoSide / Decouple).

Runs the paper's protocol end-to-end on any zoo architecture (or the
paper's own ResNet/CIFAR setting), with round-resumable checkpointing and
communication accounting.

Examples:
    # paper setting, reduced scale (synthetic CIFAR-shaped data)
    PYTHONPATH=src python -m repro.launch.train --model resnet \
        --algorithm fedhen --rounds 50 --eval-every 10

    # federated LM fine-tuning on a reduced zoo architecture
    PYTHONPATH=src python -m repro.launch.train --model lm \
        --arch gemma2-2b --reduced --algorithm fedhen --rounds 20
"""

from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.checkpoint import restore_trainer, save_trainer
from repro.configs.base import FedConfig
from repro.core.adapters import LMAdapter, ResNetAdapter
from repro.core.federated import FederatedTrainer, rounds_to_target
from repro.data import federated as fed_data
from repro.data.synthetic import (synthetic_cifar, synthetic_conditioning,
                                  synthetic_lm)
from repro.obs import telemetry as obslib


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of this checkout: a fixed path, so every later run from the
    same checkout finds what earlier runs compiled.  Call it before the
    first compile; importing this module sets nothing.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_trainer(args, telemetry=None) -> tuple:
    fed = FedConfig(
        n_devices=args.clients, n_simple=args.clients // 2,
        participation=args.participation, rounds=args.rounds,
        local_epochs=args.local_epochs, lr=args.lr,
        batch_size=args.batch_size, iid=not args.non_iid,
        dirichlet_alpha=args.alpha, algorithm=args.algorithm,
        seed=args.seed, cohort_chunk=args.cohort_chunk,
        sample_uniform=args.sample_uniform,
        agg_block_n=args.agg_block_n,
        agg_stream_dtype=args.agg_stream_dtype,
        agg_memory_budget_mb=args.agg_memory_budget_mb,
        comm_dtype=args.comm_dtype, quant_block=args.quant_block,
        topk_frac=args.topk_frac,
        stochastic_rounding=args.stochastic_rounding,
        error_feedback=args.error_feedback,
        async_lag=args.async_lag, async_staleness=args.staleness,
        async_decay=args.staleness_decay,
        variance_reduction=args.variance_reduction,
        state_store_backend=args.state_store_backend)
    fed.validate()

    if args.model == "resnet":
        data = synthetic_cifar(args.data_points, 10, seed=args.seed)
        test = synthetic_cifar(512, 10, seed=args.seed + 999)
        test_batch = {"images": jnp.asarray(test["images"]),
                      "labels": jnp.asarray(test["labels"])}
        adapter = ResNetAdapter(10)
    else:
        cfg = (configs.get_reduced(args.arch) if args.reduced
               else configs.get_config(args.arch))
        data = synthetic_lm(args.data_points, args.seq_len, cfg.vocab_size,
                            seed=args.seed, n_codebooks=cfg.n_codebooks)
        test = synthetic_lm(64, args.seq_len, cfg.vocab_size,
                            seed=args.seed + 999,
                            n_codebooks=cfg.n_codebooks)
        if cfg.cross_attention:
            fe = cfg.frontend
            data.update(synthetic_conditioning(
                args.data_points, fe.n_tokens, fe.d_in, seed=args.seed))
            test.update(synthetic_conditioning(
                64, fe.n_tokens, fe.d_in, seed=args.seed + 999))
        test_batch = {k: jnp.asarray(v) for k, v in test.items()
                      if k != "labels"}
        adapter = LMAdapter(cfg)

    split = (fed_data.iid_split if fed.iid else
             lambda d, n, seed: fed_data.dirichlet_split(
                 d, n, fed.dirichlet_alpha, seed))
    shards = split(data, fed.n_devices, args.seed + 1)
    shards = [{k: jnp.asarray(v) for k, v in s.items() if k != "labels"
               or args.model == "resnet"} for s in shards]
    trainer = FederatedTrainer(adapter, fed, shards, telemetry=telemetry)
    return trainer, test_batch


def _chunk_arg(v: str):
    return v if v == "auto" else int(v)


def build_parser() -> argparse.ArgumentParser:
    """The driver's full CLI.  Factored out of :func:`main` so tests can
    assert the FedConfig <-> flag mapping stays complete (every config
    field reachable from the command line or explicitly exempted)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("resnet", "lm"), default="resnet")
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced variant of --arch (CPU-friendly)")
    ap.add_argument("--algorithm", default="fedhen",
                    choices=("fedhen", "noside", "decouple"))
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--participation", type=float, default=0.1)
    ap.add_argument("--sample-uniform", action="store_true",
                    help="the paper's exact uniform cohort sampling: one "
                         "draw of ceil(participation*clients) over the "
                         "whole population, routed into static per-arch "
                         "slots (unfilled slots fold at weight 0); "
                         "default is the stratified per-arch "
                         "approximation")
    ap.add_argument("--cohort-chunk", type=_chunk_arg, default=0,
                    help="stream the cohort in chunks of this many clients "
                         "(0 = whole cohort at once; 'auto' = derive from "
                         "--agg-memory-budget-mb and the flat layout's "
                         "per-client footprint); memory is O(chunk)")
    ap.add_argument("--agg-block-n", type=int, default=2048,
                    help="masked_agg kernel tile width (multiple of 128)")
    ap.add_argument("--agg-stream-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="dtype trained chunks stream through the fold in "
                         "(accumulation is always f32)")
    ap.add_argument("--agg-memory-budget-mb", type=float, default=512.0,
                    help="memory budget targeted by --cohort-chunk auto")
    ap.add_argument("--comm-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8"),
                    help="wire format of the communication path: clients "
                         "train on the decoded broadcast and uploads are "
                         "folded through it (int8 = symmetric per-group "
                         "quantization with f32 scales, dequantized inside "
                         "the masked_agg accumulate)")
    ap.add_argument("--quant-block", type=int, default=128,
                    help="int8 wire scale-group size (elements per f32 "
                         "scale; must divide 128)")
    ap.add_argument("--topk-frac", type=float, default=1.0,
                    help="upload sparsification: each client uploads only "
                         "the top-k largest-|x| entries of its DELTA "
                         "against the broadcast it trained on (k = frac * "
                         "population size, rounded up to a lane multiple), "
                         "as index+value payloads; 1.0 = dense uploads "
                         "(the pre-existing wire, bit-identical)")
    ap.add_argument("--stochastic-rounding", action="store_true",
                    help="unbiased stochastic rounding on lossy upload "
                         "encodes (int8/bf16): E[decode(encode(x))] = x, "
                         "seeded per client per round (bit-reproducible); "
                         "broadcasts stay round-to-nearest")
    ap.add_argument("--error-feedback", action="store_true",
                    help="per-client error-feedback residuals: the wire "
                         "compression error of each upload is remembered "
                         "in a flat state-store row and added to the next "
                         "upload's delta, so compression error accumulates "
                         "into the average instead of being lost; requires "
                         "a lossy upload path (bf16/int8 wire or "
                         "--topk-frac < 1)")
    ap.add_argument("--async-lag", type=int, default=0,
                    help="bounded broadcast staleness in chunk folds: "
                         "chunk i of a round trains on the server version "
                         "published at fold i-lag (the first lag chunks "
                         "overlap the previous round's fold); 0 = fully "
                         "synchronous")
    ap.add_argument("--staleness", default="poly", choices=("poly", "none"),
                    help="staleness weighting of stale uploads: 'poly' = "
                         "FedAsync 1/(1+s)^a decay, 'none' = full weight")
    ap.add_argument("--staleness-decay", type=float, default=0.5,
                    help="exponent a of the polynomial staleness decay "
                         "1/(1+s)^a")
    ap.add_argument("--variance-reduction", default="none",
                    choices=("none", "scaffold"),
                    help="client-drift correction: 'scaffold' keeps a "
                         "per-client control variate in the flat state "
                         "store and corrects local gradients by c - c_i "
                         "(Karimireddy et al. 2020, option II); cv "
                         "exchange is billed raw f32 on top of the wire")
    ap.add_argument("--state-store-backend", default="auto",
                    choices=("auto", "device", "host", "mmap"),
                    help="where the (N_clients, n_flat) per-client state "
                         "rows live: device array, host numpy, or an "
                         "mmap-backed file; 'auto' picks by footprint")
    ap.add_argument("--local-epochs", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--batch-size", type=int, default=50)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--alpha", type=float, default=0.3)
    ap.add_argument("--data-points", type=int, default=4000)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--checkpoint-format", default="tree",
                    choices=("tree", "flat"),
                    help="'flat' saves ONE packed flat buffer per model "
                         "through the comm wire encoder (int8 wires make "
                         "it lossy — same error the broadcast carries)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--target-simple", type=float, default=0.0)
    ap.add_argument("--history-out", default="")
    ap.add_argument("--telemetry", action="store_true",
                    help="instrument the run with the repro/obs telemetry "
                         "layer (round-phase spans, client-health "
                         "counters, comm/roofline ledgers); off by "
                         "default — the trainer runs the no-op path")
    ap.add_argument("--telemetry-out", default="",
                    help="write the telemetry event stream as JSONL to "
                         "this path (implies --telemetry; render it with "
                         "tools/obs_report.py)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    use_compile_cache()

    # the driver's prints always route through a telemetry stdout sink
    # (line formats are bit-identical — the sink prints log events
    # verbatim); the TRAINER is only instrumented when asked, so the
    # library default stays the no-op path
    instrument = args.telemetry or bool(args.telemetry_out)
    tel = obslib.Telemetry([obslib.StdoutSink()])
    if args.telemetry_out:
        tel.add_sink(obslib.JsonlSink(args.telemetry_out))
    say = tel.log

    trainer, test_batch = build_trainer(
        args, telemetry=tel if instrument else None)
    if args.cohort_chunk == "auto":
        per_mb = trainer.stream_bytes_per_client() / 2**20
        say(f"cohort_chunk=auto -> {trainer.cohort_chunk} "
            f"(per-client packed {per_mb:.2f} MiB at wire/stream dtype, "
            f"budget {args.agg_memory_budget_mb:.0f} MiB)")
    if args.async_lag:
        eng = trainer.async_engine
        steady = eng.schedule(10**9)
        say(f"async rounds: lag={eng.lag} folds/round="
            f"{eng.folds_per_round} versions={eng.n_versions} "
            f"staleness/chunk={list(map(int, steady[0]))} + "
            f"{list(map(int, steady[1]))} "
            f"(weights {args.staleness}, a={args.staleness_decay})")
    if args.comm_dtype != "float32" or trainer.wire.uses_deltas:
        say(f"comm wire {args.comm_dtype}: "
            f"{trainer.bytes_per_round / 1e6:.3f} MB/round measured "
            f"(down {trainer.bytes_down_per_round / 1e6:.3f} + up "
            f"{trainer.bytes_up_per_round / 1e6:.3f}; f32 analytic "
            f"{trainer.analytic_bytes_per_round() / 1e6:.3f})")
    if args.resume and args.checkpoint and os.path.exists(args.checkpoint):
        # trainer-level restore: server state + sampler validation +
        # client-state matrix.  The sampler is pure in (seed, round), so
        # restoring the round counter resumes the exact cohort sequence
        # an uninterrupted run would have drawn (test-enforced).
        restore_trainer(args.checkpoint, trainer,
                        fmt=args.checkpoint_format)
        say(f"resumed from round {trainer.server.round}")

    t0 = time.time()
    history = []
    for r in range(trainer.server.round, args.rounds):
        m = trainer.run_round()
        if args.eval_every and (r + 1) % args.eval_every == 0:
            ev = trainer.evaluate(test_batch)
            m.update(ev)
            if instrument:
                tel.set_round(r + 1)
                tel.ledger("eval", ev)
            say(f"[round {r + 1:4d}] " + "  ".join(
                f"{k}={v:.4f}" for k, v in sorted(m.items())))
        m["round"] = r + 1
        history.append(m)
        if args.checkpoint and args.checkpoint_every and \
                (r + 1) % args.checkpoint_every == 0:
            save_trainer(args.checkpoint, trainer,
                         fmt=args.checkpoint_format)

    dt = time.time() - t0
    say(f"\n{args.algorithm}: {args.rounds} rounds in {dt:.1f}s "
        f"({trainer.total_bytes / 1e6:.1f} MB communicated)")
    if args.target_simple:
        r = rounds_to_target(history, "acc_simple", args.target_simple)
        say(f"rounds to simple acc {args.target_simple}: {r}")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)
    tel.close()
    if args.telemetry_out:
        print(f"telemetry run log: {args.telemetry_out} "
              f"(render: python tools/obs_report.py {args.telemetry_out})")
    return history


if __name__ == "__main__":
    main()

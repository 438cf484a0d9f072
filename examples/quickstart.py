"""Quickstart: FedHeN vs NoSide vs Decouple on a tiny federated LM.

Reproduces the paper's qualitative result in ~2 minutes on CPU: with the
side objective (FedHeN), the *simple* server model reaches a target
accuracy in fewer communication rounds than either baseline, because it
trains on complex devices' data too (Eq. 2).

Run:  PYTHONPATH=src python examples/quickstart.py

Add ``--telemetry --telemetry-out run.jsonl`` to record the structured
event stream (round-phase spans, client-health counters, byte ledgers)
and render it with ``python tools/obs_report.py run.jsonl``.
"""

import argparse

import jax.numpy as jnp

from repro.configs.base import FedConfig, LayerSpec, ModelConfig
from repro.core.adapters import LMAdapter
from repro.core.federated import FederatedTrainer, rounds_to_target
from repro.data.federated import iid_split
from repro.data.synthetic import synthetic_lm
from repro.obs import telemetry as obslib

CFG = ModelConfig(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                  vocab_size=256, pattern=(LayerSpec("attn"),), exit_layer=2,
                  compute_dtype="float32")
ROUNDS = 36
TARGET = 0.15   # held-out token accuracy (chain optimum ~0.75)

# Current engine surface (docs/ARCHITECTURE.md maps every knob): stream
# the cohort in chunks of 2 clients through the flat-buffer fold, over
# the paper-accounting f32 wire, fully synchronous rounds.  Try
# comm_dtype="int8" for ~3.9x smaller payloads, or async_lag=1 to let
# the first chunk overlap the previous round's server fold.
ENGINE = dict(cohort_chunk=2, comm_dtype="float32", async_lag=0)


def run(algorithm: str, rounds: int = ROUNDS, telemetry=None):
    fed = FedConfig(n_devices=20, n_simple=10, participation=0.2,
                    rounds=rounds, local_epochs=1, lr=0.1, batch_size=8,
                    algorithm=algorithm, seed=0, **ENGINE)
    data = synthetic_lm(400, 32, CFG.vocab_size, seed=1)
    shards = [
        {"tokens": jnp.asarray(s["tokens"])}
        for s in iid_split(data, fed.n_devices, seed=2)]
    test = {"tokens": jnp.asarray(
        synthetic_lm(64, 32, CFG.vocab_size, seed=99)["tokens"])}
    trainer = FederatedTrainer(LMAdapter(CFG), fed, shards,
                               telemetry=telemetry)
    history = trainer.run(rounds, eval_every=2, test_batch=test)
    r = rounds_to_target(history, "acc_simple", TARGET)
    final = [h for h in history if "acc_simple" in h][-1]
    return {"algorithm": algorithm, "rounds_to_target": r,
            "final_acc_simple": final["acc_simple"],
            "final_acc_complex": final["acc_complex"],
            "mbytes": trainer.total_bytes / 1e6}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--telemetry", action="store_true",
                    help="instrument the fedhen run with the repro/obs "
                         "telemetry layer")
    ap.add_argument("--telemetry-out", default="",
                    help="write the fedhen run's event stream as JSONL "
                         "here (implies --telemetry; render with "
                         "tools/obs_report.py)")
    args = ap.parse_args(argv)
    tel = None
    if args.telemetry or args.telemetry_out:
        sinks = ([obslib.JsonlSink(args.telemetry_out)]
                 if args.telemetry_out else [])
        tel = obslib.Telemetry(sinks)

    print(f"target: simple-model accuracy >= {TARGET} "
          f"(rounds to target, lower is better)\n")
    # one event stream per run: only the fedhen leg is instrumented, so
    # the JSONL log stays reconcilable against one trainer's accounting
    results = [run(a, rounds=args.rounds,
                   telemetry=tel if a == "fedhen" else None)
               for a in ("fedhen", "noside", "decouple")]
    if tel is not None:
        tel.close()
    hdr = f"{'algorithm':10s} {'rounds->tgt':>11s} {'simple':>8s} " \
          f"{'complex':>8s} {'comm MB':>9s}"
    print(hdr)
    print("-" * len(hdr))
    for r in results:
        rt = r["rounds_to_target"]
        print(f"{r['algorithm']:10s} "
              f"{rt if rt > 0 else '>'+str(args.rounds):>11} "
              f"{r['final_acc_simple']:8.3f} {r['final_acc_complex']:8.3f} "
              f"{r['mbytes']:9.1f}")
    best_baseline = min(
        (r["rounds_to_target"] for r in results[1:]
         if r["rounds_to_target"] > 0), default=-1)
    fh = results[0]["rounds_to_target"]
    if fh > 0 and best_baseline > 0:
        print(f"\nFedHeN communication gain vs best baseline: "
              f"{best_baseline / fh:.2f}x  (paper reports 1.1-3.3x)")


if __name__ == "__main__":
    main()

"""Smoke run of the federated round on one TPU chip.

Drives the training driver's own entry points (``repro.launch.train``
``build_parser`` + ``build_trainer``, then ``run_round`` / ``evaluate``)
at the paper's model width, PreActResNet18 with GroupNorm, in one
process:

  A. the paper protocol on the f32 wire (100 clients, 10% participation,
     E=5, batch 50, 500 non-IID CIFAR-shaped images per client);
  B. the README's headline command: A plus ``--cohort-chunk auto
     --comm-dtype int8 --async-lag 1`` (dequantizing fold, donation
     through the async version stack);
  C. the top-k scatter fold kernel at the model's flat width, against
     its XLA reference on the same chip.

Every phase checks its results and any failure exits non-zero.  Without
a TPU it exits non-zero before any work: it never falls back to the CPU.
The times it prints come from one run each and are information, not
benchmark numbers.  The last line of stdout is one JSON object naming the
device.

    python chip_smoke.py
"""

from __future__ import annotations

import functools
import json
import statistics
import sys
import time
from pathlib import Path

PROTOCOL = ["--model", "resnet", "--algorithm", "fedhen",
            "--clients", "100", "--participation", "0.1",
            "--local-epochs", "5", "--batch-size", "50",
            "--data-points", "50000", "--non-iid",
            "--rounds", "3", "--eval-every", "3"]
HEADLINE = PROTOCOL + ["--cohort-chunk", "auto", "--comm-dtype", "int8",
                       "--async-lag", "1"]

# phase C: PreActResNet18's flat width, one chunk of 5 clients, and the
# top-k payload of --topk-frac 1/14 at that width
SCATTER_N, SCATTER_Z, SCATTER_K, QUANT_BLOCK = 11_175_936, 5, 798_208, 128


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _check(cond: bool, msg: str) -> None:
    if not cond:
        _fail(msg)


def _import_repo() -> None:
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        _fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, str(src))


def require_tpu():
    """The first device, which must be a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
    return dev


def run_protocol(name: str, argv: list) -> dict:
    """Build the driver's trainer from ``argv`` and run its rounds.

    Checks that every round and eval metric is finite, that every cohort
    client trained to finite parameters, and that the server model moved
    and stayed finite.  Returns the per-round wall times (each ended by
    ``block_until_ready``) and the compiled round's HLO text."""
    import jax
    import numpy as np
    from repro.launch import train

    args = train.build_parser().parse_args(argv)
    trainer, test_batch = train.build_trainer(args)
    # host copy: the first round donates the server buffers on the chip
    start = [np.asarray(x) for x in jax.tree.leaves(trainer.server.complex)]
    cohort = trainer.k_simple + trainer.k_complex
    walls = []
    for r in range(args.rounds):
        t0 = time.perf_counter()
        m = trainer.run_round()
        jax.block_until_ready(trainer.server.complex)
        walls.append(time.perf_counter() - t0)
        if args.eval_every and (r + 1) % args.eval_every == 0:
            m.update(trainer.evaluate(test_batch))
        print(f"[{name}] round {r + 1}: " + "  ".join(
            f"{k}={v!r}" for k, v in sorted(m.items())), flush=True)
        _check(all(np.isfinite(v) for v in m.values()),
               f"{name}: non-finite metric in round {r + 1}: {m}")
        _check(m["n_valid"] == cohort,
               f"{name}: {m['n_valid']} of {cohort} clients valid in "
               f"round {r + 1}")
    end = [np.asarray(x) for x in jax.tree.leaves(trainer.server.complex)]
    _check(all(np.isfinite(x).all() for x in end),
           f"{name}: non-finite server parameters")
    moved = max(float(np.max(np.abs(a - b))) for a, b in zip(start, end))
    _check(moved > 0.0, f"{name}: server parameters did not move")
    hlo = trainer.lower_round().compile().as_text()
    return {"walls": walls, "hlo": hlo, "moved": moved}


def report_protocol(name: str, res: dict) -> None:
    import jax
    _check("tpu_custom_call" in res["hlo"],
           f"{name}: the compiled round holds no Pallas kernel")
    walls = res["walls"]
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{name}] first round (compile + run) {walls[0]!r} s; later "
          f"rounds median {statistics.median(walls[1:])!r} s; "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')!r}; "
          f"max |server change| {res['moved']!r}; tpu_custom_call "
          f"present", flush=True)


def scatter_fold_check(n: int, z: int, k: int, quant_block: int) -> float:
    """Run ``masked_scatter_acc_pallas`` on int8 values + f32 scales at
    random distinct positions, compare it with ``masked_scatter_acc_ref``
    on the same device, and return the kernel's wall time in seconds."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.masked_agg import ops as agg_ops

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    acc = jax.random.normal(ks[0], (n,), jnp.float32)
    values = jax.random.randint(ks[1], (z, k), -127, 128, jnp.int32
                                ).astype(jnp.int8)
    scales = jax.random.uniform(ks[2], (z, k // quant_block), jnp.float32,
                                1e-3, 1e-2)
    indices = jax.vmap(lambda kk: jax.random.permutation(kk, n)[:k])(
        jax.random.split(ks[3], z)).astype(jnp.int32)
    mask = jax.random.bernoulli(ks[4], 0.5, (n,))
    w_m = jax.random.uniform(ks[5], (z,), jnp.float32, 0.5, 1.5)
    w_rest = w_m.at[0].set(0.0)        # row 0: a simple client, M only
    args = (acc, values, scales, indices, mask, w_m, w_rest)

    kernel = jax.jit(functools.partial(agg_ops.masked_scatter_acc_pallas,
                                       quant_block=quant_block))
    compiled = kernel.lower(*args).compile()
    _check("tpu_custom_call" in compiled.as_text(),
           "C: the scatter fold compiled without its Pallas kernel")
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(*args))
    seconds = time.perf_counter() - t0

    ref = jax.jit(functools.partial(agg_ops.masked_scatter_acc_ref,
                                    quant_block=quant_block))
    want = ref(*args)
    # the same fold over magnitudes: |acc| + sum of |weighted terms|
    mag = ref(jnp.abs(acc), jnp.abs(values), scales, indices, mask, w_m,
              w_rest)
    # Tolerance: the kernel's bf16 three-way split of each f32 term is
    # exact, so the two differ only in the order in which a position's
    # terms (its accumulator and at most Z client values) are added.
    # That order moves the sum by a few f32 ulps of the summed magnitudes.
    tol = 8 * float(jnp.finfo(jnp.float32).eps) * mag
    err = jnp.abs(got - want)
    hit = int(jnp.sum(got != acc))
    print(f"[C] masked_scatter_acc_pallas N={n} Z={z} k={k} int8: "
          f"{seconds!r} s (one call); max |kernel - ref| "
          f"{float(jnp.max(err))!r} (bound {float(jnp.max(tol))!r}); "
          f"{hit} positions updated", flush=True)
    _check(got.shape == (n,) and bool(jnp.isfinite(got).all()),
           "C: kernel output has the wrong shape or non-finite values")
    _check(bool(jnp.all(err <= tol)),
           "C: kernel differs from the reference beyond the bound")
    _check(hit > 0, "C: the kernel left the accumulator unchanged")
    return seconds


def main() -> None:
    _import_repo()
    dev = require_tpu()
    import jax
    from repro.launch import train

    print(f"compile cache: {train.use_compile_cache()}", flush=True)
    print(f"device: {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}", flush=True)
    report_protocol("A", run_protocol("A", PROTOCOL))
    print("[A] passed", flush=True)
    report_protocol("B", run_protocol("B", HEADLINE))
    print("[B] passed", flush=True)
    scatter_fold_check(SCATTER_N, SCATTER_Z, SCATTER_K, QUANT_BLOCK)
    print("[C] passed", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

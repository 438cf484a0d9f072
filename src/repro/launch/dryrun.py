import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) combination:
``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` on the production
mesh — 16x16 (single pod, 256 chips) and 2x16x16 (2 pods, 512 chips) —
then record ``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs /
bytes for the roofline) and the collective schedule parsed from the
compiled HLO.

The two XLA_FLAGS lines above MUST stay the first statements in this file:
jax locks the device count at first init.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh single,multi --out results/dryrun
"""

import argparse
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.core.adapters import LMAdapter
from repro.launch import sharding, steps
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.roofline import analysis, hw


def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0))


def _abstract_cache(cfg: ModelConfig, batch: int, seq_len: int,
                    window_override):
    return jax.eval_shape(
        lambda: tfm.init_cache(cfg, batch, seq_len,
                               window_override=window_override))


def lower_one(arch: str, shape: InputShape, *, multi_pod: bool,
              cfg_override: Optional[ModelConfig] = None,
              verbose: bool = True):
    """Lower + compile one (arch, shape, mesh) combo; return the record."""
    cfg = cfg_override or configs.get_config(arch)
    longctx = configs.needs_longctx_variant(cfg, shape)
    window_override = cfg.longctx_window if longctx else None

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    policy = sharding.MeshPolicy(mesh, cfg)
    in_specs = configs.input_specs(cfg, shape)
    params_abs = _abstract_params(cfg)
    p_specs = sharding.to_named(sharding.param_specs(cfg=cfg, mesh=mesh,
                                                     params=params_abs), mesh)
    b_specs = sharding.to_named(sharding.batch_specs(in_specs, mesh, policy), mesh)

    step = steps.step_for_shape(cfg, shape, policy,
                                window_override=window_override)

    t0 = time.time()
    with mesh:
        if shape.kind == "decode":
            cache_abs = _abstract_cache(cfg, shape.global_batch,
                                        shape.seq_len, window_override)
            c_specs = sharding.to_named(
                sharding.cache_specs(cache_abs, cfg, mesh), mesh)
            pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
            fn = jax.jit(step,
                         in_shardings=(p_specs, c_specs, b_specs, None),
                         out_shardings=(None, c_specs),
                         donate_argnums=(1,))
            lowered = fn.lower(params_abs, cache_abs, in_specs, pos_abs)
        elif shape.kind == "train":
            fn = jax.jit(step, in_shardings=(p_specs, b_specs),
                         out_shardings=(p_specs, None),
                         donate_argnums=(0,))
            lowered = fn.lower(params_abs, in_specs)
        else:  # prefill
            cache_abs = _abstract_cache(cfg, shape.global_batch,
                                        shape.seq_len, window_override)
            pc_specs = sharding.to_named(
                sharding.cache_specs(cache_abs, cfg, mesh), mesh)
            fn = jax.jit(step, in_shardings=(p_specs, b_specs),
                         out_shardings=(None, pc_specs))
            lowered = fn.lower(params_abs, in_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    p_bytes = sharding.bytes_per_chip(
        params_abs, sharding.param_specs(params_abs, cfg, mesh), mesh)
    c_bytes = 0
    if shape.kind == "decode":
        c_bytes = sharding.bytes_per_chip(
            cache_abs, sharding.cache_specs(cache_abs, cfg, mesh), mesh)
    elif shape.kind == "prefill":
        cache_abs = _abstract_cache(cfg, shape.global_batch, shape.seq_len,
                                    window_override)
        c_bytes = sharding.bytes_per_chip(
            cache_abs, sharding.cache_specs(cache_abs, cfg, mesh), mesh)
    rec = analysis.make_record(
        arch=cfg.name, shape=shape, mesh_name="2x16x16" if multi_pod
        else "16x16", chips=chips, device_kind=hw.TARGET_KIND, cost=cost,
        mem=mem, hlo_text=hlo, cfg=cfg,
        longctx_variant=longctx, param_bytes_chip=p_bytes,
        cache_bytes_chip=c_bytes)
    d = rec.to_dict()
    d["t_lower_s"] = round(t_lower, 1)
    d["t_compile_s"] = round(t_compile, 1)
    if verbose:
        peak_gb = rec.peak_memory_per_chip / 2 ** 30
        print(f"[dryrun] {cfg.name} x {shape.name} x {d['mesh']}: OK  "
              f"flops/chip={rec.flops_per_chip:.3e}  "
              f"peak={peak_gb:.2f}GiB  "
              f"coll={rec.coll_bytes_per_chip / 2**20:.1f}MiB  "
              f"bottleneck={rec.bottleneck}  "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)",
              flush=True)
    return d


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="comma list or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    help="single,multi")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--stop-on-error", action="store_true")
    ap.add_argument("--override", default="",
                    help="comma list of cfg overrides, e.g. "
                         "attn_shard=seq2d,mlstm_chunk=512 (perf variants)")
    args = ap.parse_args(argv)

    overrides = {}
    moe_overrides = {}
    for kv in args.override.split(","):
        if not kv:
            continue
        k, v = kv.split("=")
        v = int(v) if v.lstrip("-").isdigit() else v
        if k.startswith("moe_"):
            moe_overrides[k[4:]] = v
        else:
            overrides[k] = v

    archs = list(configs.ARCH_NAMES) if args.arch == "all" \
        else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" \
        else args.shape.split(",")
    meshes = args.mesh.split(",")

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            shape = INPUT_SHAPES[shape_name]
            for mesh_name in meshes:
                tag = f"{arch}_{shape_name}_{mesh_name}"
                out_path = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_path):
                    print(f"[dryrun] {tag}: cached, skipping", flush=True)
                    continue
                try:
                    cfg_override = None
                    if overrides or moe_overrides:
                        cfg_override = configs.get_config(arch) \
                            .with_overrides(**overrides)
                        if moe_overrides and cfg_override.moe:
                            import dataclasses as _dc
                            cfg_override = cfg_override.with_overrides(
                                moe=_dc.replace(cfg_override.moe,
                                                **moe_overrides))
                    rec = lower_one(arch, shape,
                                    multi_pod=(mesh_name == "multi"),
                                    cfg_override=cfg_override)
                    with open(out_path, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] {tag}: FAILED {e!r}", flush=True)
                    traceback.print_exc()
                    if args.stop_on_error:
                        return 1
    print(f"[dryrun] done; {len(failures)} failures", flush=True)
    for tag, err in failures:
        print(f"  FAIL {tag}: {err}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

"""Roofline-term derivation from a compiled dry-run artifact.

Sources:
* ``compiled.cost_analysis()`` — per-device HLO FLOPs and bytes accessed.
* ``compiled.as_text()`` — post-SPMD per-device HLO; collective bytes are
  summed from the *result shapes* of all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute ops (an upper bound on
  per-chip bytes moved; documented in EXPERIMENTS.md).

Terms (seconds, per step, per chip), against the peaks of the record's
``device_kind`` (``roofline/hw.py``):
    compute    = HLO_FLOPs / flops_bf16
    memory     = HLO_bytes / hbm_bw
    collective = collective_bytes / ici_link_bw
"""

from __future__ import annotations

import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Optional

from repro.roofline import hw

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %ar.1 = f32[256,128]{1,0} all-reduce(...)
#        %t = (bf16[8]{0}, bf16[8]{0}) all-gather(...)
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-type result bytes (per device)."""
    out: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        out[op] += _shape_bytes(shape_str)
        counts[op] += 1
    out["_counts"] = counts  # type: ignore
    return out


@dataclass
class RooflineRecord:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str               # the chip whose peaks the terms use
    flops_per_chip: float          # HLO, per device, per step
    bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_breakdown: Dict[str, int] = field(default_factory=dict)
    peak_memory_per_chip: float = 0.0
    argument_bytes_per_chip: float = 0.0
    model_flops: float = 0.0       # analytical 6ND / 2ND (global)
    longctx_variant: bool = False
    param_bytes_per_chip: float = 0.0
    cache_bytes_per_chip: float = 0.0
    hbm_analytic_per_chip: float = 0.0   # traffic model (see analytic_hbm)

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / hw.peaks(self.device_kind).flops_bf16

    @property
    def t_memory(self) -> float:
        """Analytic HBM traffic (weights + activations + caches) / HBM bw.
        The HLO byte proxy (``bytes_per_chip``) is kept as a diagnostic but
        over-materializes on the CPU backend (weak fusion)."""
        return self.hbm_analytic_per_chip / hw.peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        return (self.coll_bytes_per_chip
                / hw.peaks(self.device_kind).ici_link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips): how much compiled compute is
        'useful' (catches remat/redundancy/padding waste)."""
        total = self.flops_per_chip * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def to_dict(self) -> Dict:
        d = asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_flops_ratio=self.useful_flops_ratio)
        return d


def analytic_hbm(cfg, shape, param_bytes_chip: float,
                 cache_bytes_chip: float, chips: int) -> float:
    """Per-chip HBM traffic model for one step.

    train:  weights are read 3x (fwd, remat re-fwd, bwd) and written once
            with gradients read+written once -> ~6x param bytes; plus saved
            period activations written+read.
    prefill: weights 1x + cache write + layer activations streamed 2x.
    decode:  weights 1x + cache read + write (the classic decode bound).
    """
    act_bytes = 2  # bf16
    data_shards = max(chips // 16, 1)  # data(+pod) axes of the mesh
    if shape.kind == "train":
        tokens_chip = shape.global_batch * shape.seq_len / data_shards
        saved = cfg.n_periods * tokens_chip * cfg.d_model * act_bytes
        return 6.0 * param_bytes_chip + 2.0 * saved
    if shape.kind == "prefill":
        tokens_chip = shape.global_batch * shape.seq_len / data_shards
        stream = 2.0 * cfg.n_layers * tokens_chip * cfg.d_model * act_bytes
        return param_bytes_chip + cache_bytes_chip + stream
    # decode: one token; MoE reads only the experts the batch touches
    weight_read = param_bytes_chip
    if cfg.moe is not None and cfg.moe.n_experts > cfg.moe.top_k:
        e, k = cfg.moe.n_experts, cfg.moe.top_k
        inactive_frac = 1.0 - cfg.active_param_count() / cfg.param_count()
        expert_frac = min(inactive_frac * e / (e - k), 0.99)
        touched = min(1.0, shape.global_batch * k / e)
        weight_read = param_bytes_chip * (
            (1.0 - expert_frac) + expert_frac * touched)
    return weight_read + 2.0 * cache_bytes_chip


def model_flops(cfg, shape) -> float:
    """Analytical 'useful' FLOPs per step (global, all chips).

    train: 6 * N_active * tokens ; prefill: 2 * N_active * tokens ;
    decode: 2 * N_active * batch (one token per sequence).
    """
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def make_record(*, arch: str, shape, mesh_name: str, chips: int,
                device_kind: str, cost: Dict, mem, hlo_text: str, cfg,
                longctx_variant: bool = False,
                param_bytes_chip: float = 0.0,
                cache_bytes_chip: float = 0.0) -> RooflineRecord:
    """Loop-aware costs come from roofline.hlo_walk (XLA's cost_analysis
    counts while bodies once — kept only as a cross-reference field)."""
    from repro.roofline import hlo_walk
    walk = hlo_walk.analyze(hlo_text)
    hbm = analytic_hbm(cfg, shape, param_bytes_chip, cache_bytes_chip, chips)
    return RooflineRecord(
        param_bytes_per_chip=param_bytes_chip,
        cache_bytes_per_chip=cache_bytes_chip,
        hbm_analytic_per_chip=hbm,
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        device_kind=device_kind,
        flops_per_chip=float(walk["flops"]),
        bytes_per_chip=float(walk["hbm_bytes"]),
        coll_bytes_per_chip=float(walk["total_collective_bytes"]),
        coll_breakdown={**walk["collective_bytes"],
                        "counts": walk["collective_counts"],
                        "xla_cost_flops": float(cost.get("flops", 0.0)),
                        "xla_cost_bytes":
                            float(cost.get("bytes accessed", 0.0))},
        peak_memory_per_chip=float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)),
        argument_bytes_per_chip=float(getattr(mem, "argument_size_in_bytes", 0)),
        model_flops=model_flops(cfg, shape),
        longctx_variant=longctx_variant)

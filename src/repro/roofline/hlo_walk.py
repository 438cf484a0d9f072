"""Loop-aware HLO cost walker.

``compiled.cost_analysis()`` on the CPU backend counts while-loop bodies
ONCE (verified: a 10-iteration scan of matmuls reports 1x the flops), which
makes it useless for scan-based layer stacks.  This walker parses the
post-SPMD per-device HLO text and computes:

* ``flops``            — dot/convolution FLOPs (2*m*n*k convention), with
  while bodies multiplied by their trip count (parsed from the loop
  condition's comparison constant);
* ``collective_bytes`` — per collective type, result-shape bytes, loop-aware;
* ``hbm_bytes``        — an HBM-traffic proxy: operand + result bytes of
  materialization-boundary ops (fusions, dots, convs, copies, collectives),
  loop-aware.  Fusion-internal ops are not double counted.

Because the input is the *post-partitioning* module, per-device shapes
already reflect replication waste (e.g. attention replicated when heads
don't divide the model axis) — so per-chip numbers are honest.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
    "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_BOUNDARY_OPS = {"fusion", "dot", "convolution", "copy", "transpose",
                 "reshape", "broadcast", "reduce", "scatter", "gather",
                 "dynamic-slice", "dynamic-update-slice", "concatenate",
                 "slice", "pad", "select-and-scatter", "reduce-window",
                 "sort", "iota", "rng", "convert", "add", "multiply",
                 "subtract", "divide", "select", "compare", "tanh", "exponential",
                 } | set(_COLLECTIVES)


def _shape_numel_bytes(shape_str: str) -> Tuple[int, int]:
    """Total (numel, bytes) over all array components in a shape string."""
    numel = 0
    nbytes = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        numel += n
        nbytes += n * _DTYPE_BYTES[dtype]
    return numel, nbytes


def _first_shape_dims(shape_str: str) -> Optional[Tuple[str, List[int]]]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None
    dtype, dims = m.groups()
    return dtype, [int(d) for d in dims.split(",") if d]


@dataclass
class Instruction:
    name: str
    op: str
    shape_str: str
    line: str
    operands: List[str] = field(default_factory=list)


@dataclass
class Computation:
    name: str
    instructions: Dict[str, Instruction] = field(default_factory=dict)
    order: List[str] = field(default_factory=list)
    param_shapes: Dict[str, str] = field(default_factory=dict)


_COMP_HEADER_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w\.\-~]+)\s*\((.*?)\)\s*->\s*.*\{\s*$")
_INST_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-~]+)\s*=\s*(.*)$")
_OP_NAME_RE = re.compile(r"^\s*([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w\.\-~]+)")


def _split_shape_op(rest: str):
    """Split '<shape> <op>(<args...>' — shape may be a tuple containing
    parens and '/*index=N*/' comments, so match parens by depth."""
    rest = rest.lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    shape, tail = rest[:i + 1], rest[i + 1:]
                    m = _OP_NAME_RE.match(tail)
                    if m:
                        return shape, m.group(1), tail[m.end():]
                    return None
        return None
    parts = rest.split(None, 1)
    if len(parts) != 2:
        return None
    shape, tail = parts
    m = _OP_NAME_RE.match(tail)
    if m:
        return shape, m.group(1), tail[m.end():]
    return None
_CALLED_RE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)="
                        r"[{]?%?([\w\.\-~,%\s]+)[}]?")


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    for raw in text.splitlines():
        header = _COMP_HEADER_RE.match(raw.strip()) if "{" in raw else None
        if header and "=" not in raw.split("(")[0]:
            current = Computation(header.group(1))
            comps[current.name] = current
            # parameter shapes from the header signature
            for pm in re.finditer(r"%?([\w\.\-~]+):\s*"
                                  r"(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)",
                                  header.group(2)):
                current.param_shapes[pm.group(1)] = pm.group(2)
            continue
        if current is None:
            continue
        if raw.strip() == "}":
            current = None
            continue
        m = _INST_HEAD_RE.match(raw)
        if m:
            name, rest = m.groups()
            split = _split_shape_op(rest)
            if split is None:
                continue
            shape_str, op, args = split
            args_part = args.split("),")[0]
            operands = _OPERAND_RE.findall(args_part)
            inst = Instruction(name=name, op=op, shape_str=shape_str,
                               line=raw, operands=operands)
            current.instructions[name] = inst
            current.order.append(name)
    return comps


def _operand_shape(comp: Computation, operand: str) -> Optional[str]:
    if operand in comp.instructions:
        return comp.instructions[operand].shape_str
    if operand in comp.param_shapes:
        return comp.param_shapes[operand]
    return None


def _dot_flops(comp: Computation, inst: Instruction) -> float:
    _, out_bytes = _shape_numel_bytes(inst.shape_str)
    out = _first_shape_dims(inst.shape_str)
    if out is None:
        return 0.0
    out_numel = math.prod(out[1]) if out[1] else 1
    k = 1
    mm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", inst.line)
    if mm and inst.operands:
        lhs_shape = _operand_shape(comp, inst.operands[0])
        if lhs_shape:
            parsed = _first_shape_dims(lhs_shape)
            if parsed:
                dims = parsed[1]
                for idx in mm.group(1).split(","):
                    if idx and int(idx) < len(dims):
                        k *= dims[int(idx)]
    return 2.0 * out_numel * k


def _conv_flops(comp: Computation, inst: Instruction) -> float:
    out = _first_shape_dims(inst.shape_str)
    if out is None or len(inst.operands) < 2:
        return 0.0
    out_numel = math.prod(out[1]) if out[1] else 1
    rhs_shape = _operand_shape(comp, inst.operands[1])
    if not rhs_shape:
        return 0.0
    parsed = _first_shape_dims(rhs_shape)
    if not parsed:
        return 0.0
    kernel = parsed[1]
    # per output element: 2 * prod(kernel dims except output-feature dim)
    dn = re.search(r"dim_labels=\S*", inst.line)
    per_out = 2 * math.prod(kernel)
    # divide by output feature count (one kernel dim indexes output features)
    if kernel:
        per_out //= max(kernel[-1], 1)   # HWIO default: last dim = O
    return float(out_numel * per_out)


_TRIP_CONST_RE = re.compile(r"constant\((\d+)\)")


def _trip_count(comps: Dict[str, Computation], cond_name: str) -> int:
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    best = 1
    for iname in cond.order:
        inst = cond.instructions[iname]
        if inst.op == "constant":
            m = _TRIP_CONST_RE.search(inst.line)
            if m:
                best = max(best, int(m.group(1)))
        m = _TRIP_CONST_RE.search(inst.line)
        if m and inst.op in ("compare", "fusion"):
            best = max(best, int(m.group(1)))
    return best


@dataclass
class Costs:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    collective_counts: Dict[str, float] = field(default_factory=dict)

    def add(self, other: "Costs", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0.0) \
                + v * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0.0) \
                + v * mult


def _called_comps(inst: Instruction) -> List[str]:
    out = []
    for key in ("calls", "to_apply", "body", "condition"):
        m = re.search(rf"{key}=%?([\w\.\-~]+)", inst.line)
        if m:
            out.append((key, m.group(1)))
    m = re.search(r"branch_computations=\{([^}]*)\}", inst.line)
    if m:
        for b in m.group(1).split(","):
            out.append(("branch", b.strip().lstrip("%")))
    return out


class HloCostModel:
    def __init__(self, text: str):
        self.comps = parse_module(text)
        self._cache: Dict[str, Costs] = {}
        entry = None
        for name in self.comps:
            if re.search(r"^(main|entry)", name) or entry is None:
                pass
        # entry = computation referenced by none (topmost) — find by name
        called = set()
        for c in self.comps.values():
            for iname in c.order:
                for _, cal in _called_comps(c.instructions[iname]):
                    called.add(cal)
        candidates = [n for n in self.comps if n not in called]
        # prefer one containing 'main'
        main = [n for n in candidates if "main" in n]
        self.entry = main[0] if main else (candidates[0] if candidates
                                           else next(iter(self.comps)))

    def comp_costs(self, name: str) -> Costs:
        if name in self._cache:
            return self._cache[name]
        comp = self.comps.get(name)
        total = Costs()
        self._cache[name] = total          # cycle guard (shouldn't happen)
        if comp is None:
            return total
        inside_fusion = name.startswith("fused_") or "fused" in name
        for iname in comp.order:
            inst = comp.instructions[iname]
            op = inst.op
            if op == "dot":
                total.flops += _dot_flops(comp, inst)
            elif op == "convolution":
                total.flops += _conv_flops(comp, inst)
            if op in _COLLECTIVES or op.replace("-start", "") in _COLLECTIVES:
                base = op.replace("-start", "")
                _, nbytes = _shape_numel_bytes(inst.shape_str)
                total.collective_bytes[base] = \
                    total.collective_bytes.get(base, 0.0) + nbytes
                total.collective_counts[base] = \
                    total.collective_counts.get(base, 0.0) + 1

            calls = _called_comps(inst)
            if op == "while":
                body = next((c for k, c in calls if k == "body"), None)
                cond = next((c for k, c in calls if k == "condition"), None)
                trips = _trip_count(self.comps, cond) if cond else 1
                if body:
                    total.add(self.comp_costs(body), trips)
                if cond:
                    total.add(self.comp_costs(cond), trips)
            elif op == "conditional":
                branches = [c for k, c in calls if k == "branch"]
                sub = [self.comp_costs(b) for b in branches]
                if sub:
                    # take the max-flops branch as the executed one
                    total.add(max(sub, key=lambda c: c.flops))
            else:
                for _, cal in calls:
                    total.add(self.comp_costs(cal))

            # HBM-traffic proxy: boundary ops only, skip inside fusions
            if not inside_fusion and op in _BOUNDARY_OPS:
                _, out_b = _shape_numel_bytes(inst.shape_str)
                total.hbm_bytes += out_b
                for operand in inst.operands:
                    oshape = _operand_shape(comp, operand)
                    if oshape:
                        _, ob = _shape_numel_bytes(oshape)
                        total.hbm_bytes += ob
        self._cache[name] = total
        return total

    def entry_costs(self) -> Costs:
        return self.comp_costs(self.entry)


def analyze(text: str) -> Dict:
    model = HloCostModel(text)
    c = model.entry_costs()
    return {
        "flops": c.flops,
        "hbm_bytes": c.hbm_bytes,
        "collective_bytes": dict(c.collective_bytes),
        "collective_counts": dict(c.collective_counts),
        "total_collective_bytes": sum(c.collective_bytes.values()),
    }


def xla_cost_analysis(compiled) -> Dict:
    """XLA's own cost analysis of a compiled executable, as a plain dict
    (``compiled.cost_analysis()`` returns one dict for the executable).
    The number it reports is loop-UNAWARE (while bodies counted once);
    use :func:`analyze` for trip-count-corrected costs.
    """
    return dict(compiled.cost_analysis())

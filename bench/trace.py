"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The run wraps its measured window in a host annotation (``WINDOW``); the
reduction takes that annotation's interval from the host plane and, on each
device plane, the events of its XLA op lines inside it.  From those:

* ``busy_s``: the length of the union of the device's op intervals within
  the window, averaged over the devices traced;
* ``ops``: the device time of each op name, summed;
* ``gaps``: the device's idle intervals within the window, each labeled by
  the innermost host event open at its midpoint (what the host was doing
  while the device waited).

On a TPU an op's name is its HLO instruction text (``%name = type
op(operands), attributes``); a reader's predicate picks its kernels from
that text.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench_window"
_OP_LINES = ("XLA Ops",)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events: List[Tuple[str, float, float]], w0: float,
                w1: float) -> Dict[str, float]:
    """Seconds of each op name inside ``[w0, w1]``, less the time of the
    ops nested in it (a while loop's own time excludes its body's ops)."""
    out: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []       # (name, end) of open events
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        a2, b2 = max(a, w0), min(b, w1)
        if b2 <= a2:
            continue
        dur = (b2 - a2) * 1e-9
        out[name] = out.get(name, 0.0) + dur
        if stack and b <= stack[-1][1]:        # nested, not overlapping
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - dur
        stack.append((name, b))
    return out


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_events: List[Tuple[str, float, float, int]],
                  window: Tuple[float, float], n_gaps: int = 10) -> dict:
    """The reduction on plain tuples (times in ns).

    ``device_ops``: per device, ``(op name, start, end)``.  ``host_events``:
    ``(name, start, end, depth)`` of every host event, ``depth`` larger for
    inner ones.  ``window``: the measured window's ``(start, end)``.  Op
    times are self times, summed over the devices; only the ``n_gaps``
    longest idle gaps are labeled."""
    w0, w1 = window
    busy, ops, gaps = [], {}, []
    for events in device_ops.values():
        for name, t in _self_times(events, w0, w1).items():
            ops[name] = ops.get(name, 0.0) + t
        merged = _union([(max(a, w0), min(b, w1)) for _, a, b in events
                         if min(b, w1) > max(a, w0)])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    labeled = []
    for a, b in gaps[:n_gaps]:
        mid = 0.5 * (a + b)
        best: Optional[Tuple[int, str]] = None
        for name, ha, hb, depth in host_events:
            if ha <= mid <= hb and name != WINDOW and (
                    best is None or depth > best[0]):
                best = (depth, name)
        labeled.append((best[1] if best else "host idle", (b - a) * 1e-9))
    return {"busy_s": sum(busy) / max(len(busy), 1),
            "window_s": (w1 - w0) * 1e-9,
            "ops": ops, "gaps": labeled}


def load(path: str) -> dict:
    """Read one ``.xplane.pb`` and reduce it (see :func:`reduce_events`)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host_events: List[Tuple[str, float, float, int]] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            evs = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in _OP_LINES:
                    evs.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                stack: List[float] = []
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    while stack and stack[-1] <= a:
                        stack.pop()
                    if e.name == WINDOW:
                        window = (a, b)
                    host_events.append((e.name, a, b, len(stack)))
                    stack.append(b)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    if not any(device_ops.values()):
        raise ValueError(f"no device op events in {path}")
    return reduce_events(device_ops, host_events, window)


def kernel_seconds(ops: Dict[str, float], match) -> float:
    """Device seconds of every op for which ``match(op text)`` is true."""
    return sum(t for op, t in ops.items() if match(op))


def short_name(op: str) -> str:
    """``%fusion.12 = f32[..] fusion(..), ..`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


def top(items: Iterable[Tuple[str, float]], n: int = 10) -> list:
    """The ``n`` names with the most seconds, summed by name."""
    total: Dict[str, float] = {}
    for k, v in items:
        total[k] = total.get(k, 0.0) + v
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

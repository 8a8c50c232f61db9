"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
per-layer metrics read: the device operations of each chip inside the
measured window, on the profiler's clock, and the offset that puts the
program's host spans (``time.perf_counter_ns``) on that clock.

The benchmark brackets its window with two ``jax.profiler.TraceAnnotation``
events: ``bench.marker``, entered right after a ``perf_counter_ns`` reading
(so the two clocks meet there), and ``bench.window``, the measured call.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

MARKER = "bench.marker"
WINDOW = "bench.window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
# The line of a device plane that holds one event per executed HLO op.
OPS_LINE = "XLA Ops"


@dataclass
class DeviceOp:
    start_ns: int
    end_ns: int
    name: str           # the HLO instruction's text, e.g. "%fusion.73 = f32[...] fusion(...)"


@dataclass
class Trace:
    window: Tuple[int, int]                       # trace-clock ns
    marker_ns: int                                # trace-clock ns of the marker
    ops: Dict[int, List[DeviceOp]] = field(default_factory=dict)   # chip -> ops

    def to_trace_ns(self, perf_ns: int, marker_perf_ns: int) -> int:
        """A host ``perf_counter_ns`` reading on the trace clock."""
        return perf_ns - marker_perf_ns + self.marker_ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _chip_index(plane_name: str) -> Optional[int]:
    if not plane_name.startswith(DEVICE_PLANE_PREFIX):
        return None
    tail = plane_name[len(DEVICE_PLANE_PREFIX):]
    return int(tail) if tail.isdigit() else None


def load(path: str) -> Trace:
    """Read the window, the clock marker and every chip's ops in the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    marker = window = None
    device_planes = []
    for plane in data.planes:
        chip = _chip_index(plane.name)
        if chip is not None:
            device_planes.append((chip, plane))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER and marker is None:
                    marker = int(ev.start_ns)
                elif ev.name == WINDOW and window is None:
                    window = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
    if marker is None or window is None:
        raise ValueError(f"{path}: no {MARKER!r} or {WINDOW!r} annotation")
    trace = Trace(window=window, marker_ns=marker)
    lo, hi = window
    for chip, plane in device_planes:
        ops: List[DeviceOp] = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e <= lo or s >= hi:
                    continue
                ops.append(DeviceOp(max(s, lo), min(e, hi), ev.name))
        ops.sort(key=lambda o: o.start_ns)
        trace.ops[chip] = ops
    if not trace.ops:
        raise ValueError(f"{path}: no {DEVICE_PLANE_PREFIX}* plane with an {OPS_LINE!r} line")
    return trace


def busy_intervals(ops: Sequence[DeviceOp]) -> List[Tuple[int, int]]:
    """Union of the ops' intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for op in ops:                      # sorted by start
        if out and op.start_ns <= out[-1][1]:
            if op.end_ns > out[-1][1]:
                out[-1] = (out[-1][0], op.end_ns)
        else:
            out.append((op.start_ns, op.end_ns))
    return out


def busy_ns(ops: Sequence[DeviceOp]) -> int:
    return sum(e - s for s, e in busy_intervals(ops))


def idle_gaps(ops: Sequence[DeviceOp], window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The window's intervals in which no op runs, longest first."""
    gaps = []
    cur = window[0]
    for s, e in busy_intervals(ops):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if window[1] > cur:
        gaps.append((cur, window[1]))
    return sorted(gaps, key=lambda g: g[0] - g[1])


# Ops that only contain others (a loop, a branch, a call): their time is
# their body's, so per-op sums leave them out.
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """An HLO op event's name is its instruction text; keep the op's name
    and its result type, e.g. ``%fusion.73 = f32[3,100000,16]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    kind = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} = {kind}"


def is_container(name: str) -> bool:
    op = name.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]
    return op in CONTAINERS


def op_seconds(ops: Sequence[DeviceOp]) -> Dict[str, float]:
    """Summed device seconds per op (short name), containers left out."""
    out: Dict[str, float] = {}
    for op in ops:
        if is_container(op.name):
            continue
        key = short_name(op.name)
        out[key] = out.get(key, 0.0) + (op.end_ns - op.start_ns) / 1e9
    return out


def result_dims(name: str) -> List[int]:
    """Dimensions of an op's (first) result, from its instruction text."""
    _, sep, rest = name.partition(" = ")
    if not sep or "[" not in rest:
        return []
    dims = rest.split("[", 1)[1].split("]", 1)[0]
    return [int(x) for x in dims.split(",") if x.strip().isdigit()]

"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer readers need. Only JAX is used to read the file.

A device plane carries a line of executed programs ("XLA Modules") and
a line of the operations inside them ("XLA Ops"), with start and
duration in nanoseconds on the device's clock. Everything here works
on :class:`DevicePlane`, plain lists of ``(name, start_s, dur_s)``, so
the tests build planes by hand.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_s, dur_s
Interval = Tuple[float, float]

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SHORT_GAP_S = 20e-6


@dataclass
class DevicePlane:
    name: str
    modules: List[Event] = field(default_factory=list)
    ops: List[Event] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def program_name(raw: str) -> str:
    """``jit_step_fn(123456789)`` -> ``jit_step_fn``."""
    return re.sub(r"\(\d+\)$", "", raw.strip())


_HLO = re.compile(r"^%(?P<name>\S+) = .*? (?P<opcode>[a-z][a-z0-9-]*)\(")


def op_label(raw: str) -> str:
    """The trace names an operation by its HLO text,
    ``%step.7 = f32[16,25,1,64]{...} custom-call(...)``; the label is
    ``custom-call/step.7``: the opcode, then the instruction's name
    (for a Pallas kernel, the kernel function's)."""
    m = _HLO.match(raw)
    if m is None:
        return "?/" + raw.split(" ")[0].lstrip("%")
    return f"{m.group('opcode')}/{m.group('name')}"


def short_name(label: str) -> str:
    """``custom-call/step.7`` -> ``step``."""
    return re.sub(r"\.\d+$", "", label.split("/", 1)[1])


def load(path: str, device_prefix: str = "/device:TPU:") -> List[DevicePlane]:
    from jax.profiler import ProfileData
    planes = []
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith(device_prefix):
            continue
        dp = DevicePlane(pl.name)
        for line in pl.lines:
            if line.name == MODULES_LINE:
                dp.modules = [(program_name(e.name), e.start_ns / 1e9,
                               e.duration_ns / 1e9) for e in line.events]
            elif line.name == OPS_LINE:
                dp.ops = [(op_label(e.name), e.start_ns / 1e9,
                           e.duration_ns / 1e9) for e in line.events]
        if dp.ops or dp.modules:
            dp.modules.sort(key=lambda e: e[1])
            dp.ops.sort(key=lambda e: e[1])
            planes.append(dp)
    return planes


# -- interval arithmetic --------------------------------------------------
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of union ``a`` not covered by union ``b``."""
    out, b = [], list(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _iv(events: Sequence[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


# -- what the readers ask ---------------------------------------------------
def window(plane: DevicePlane) -> Interval:
    ev = plane.ops or plane.modules
    return (min(s for _, s, _ in ev), max(s + d for _, s, d in ev))


def busy_intervals(plane: DevicePlane) -> List[Interval]:
    return union(_iv(plane.ops or plane.modules))


def busy_and_window(planes: Sequence[DevicePlane]) -> Tuple[float, float]:
    """Seconds in which an operation ran, averaged over the devices,
    and the length of the traced window (the widest plane's)."""
    busy = [total(busy_intervals(p)) for p in planes]
    wins = [window(p) for p in planes]
    return sum(busy) / len(busy), max(b - a for a, b in wins)


def program_runs(plane: DevicePlane, pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in plane.modules if rx.search(e[0])]


def program_seconds(planes: Sequence[DevicePlane], pattern: str
                    ) -> Optional[Tuple[float, int]]:
    """(total device seconds, executions) of the programs matching
    ``pattern`` on the device that spent most in them."""
    best = None
    for p in planes:
        runs = program_runs(p, pattern)
        if runs:
            t = sum(d for _, _, d in runs)
            if best is None or t > best[0]:
                best = (t, len(runs))
    return best


def op_seconds(plane: DevicePlane, op_pattern: str,
               program_pattern: Optional[str] = None) -> float:
    """Device seconds of the operations matching ``op_pattern``, inside
    runs of the programs matching ``program_pattern`` if given.
    Overlapping matches (an op nested in another match) count once."""
    rx = re.compile(op_pattern)
    iv = union(_iv([e for e in plane.ops if rx.search(e[0])]))
    if program_pattern is not None:
        inside = union(_iv(program_runs(plane, program_pattern)))
        iv = subtract(iv, subtract(iv, inside))
    return total(iv)


def top_ops(plane: DevicePlane, n: int = 10) -> List[List]:
    agg: Dict[str, float] = {}
    for label, _, d in plane.ops:
        name = short_name(label)
        agg[name] = agg.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(agg.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(plane: DevicePlane, n: int = 10) -> List[List]:
    """Longest idle gaps, summed by the programs on either side."""
    busy = busy_intervals(plane)
    mods = plane.modules                    # sorted by start
    starts = [m[1] for m in mods]

    def around(t0: float, t1: float) -> str:
        i = bisect.bisect_right(starts, t0)     # modules started by t0
        j = bisect.bisect_left(starts, t1)      # first to start at t1 or later
        a = mods[i - 1][0] if i else "_trace_start_"
        b = mods[j][0] if j < len(mods) else "_trace_end_"
        return f"after_{a}_before_{b}"

    agg: Dict[str, float] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        g = s1 - e0
        key = ("_gaps_under_20_us_between_ops_" if g < SHORT_GAP_S
               else around(e0, s1))
        agg[key] = agg.get(key, 0.0) + g
    return [[k, v] for k, v in sorted(agg.items(),
                                      key=lambda kv: -kv[1])[:n]]


def fullest(planes: Sequence[DevicePlane]) -> DevicePlane:
    return max(planes, key=lambda p: total(busy_intervals(p)))

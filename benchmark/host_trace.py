"""The host's spans of a ``jax.profiler`` trace, on the device planes'
clock: the scheduler's ``gen.*`` phases, the front end's ``http.*``
writes and CPython's collections (``host.gc``), and each device-idle
gap of :mod:`benchmark.trace` split by what the host was doing.

One reader of host spans for the benchmark and for
``tools/sched_trace_table.py``. ``run.py`` does not call it yet: it
removes the trace directory before the readers run (PERF.md section 7,
a ``benchmark`` PR's to wire). Everything past :func:`load_host_spans`
works on plain dicts, so the tests build spans by hand.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.trace import (DevicePlane, Interval, SHORT_GAP_S,
                             busy_and_window, busy_intervals, fullest,
                             subtract, total, union)

HOST_PREFIXES = ("gen.", "http.", "host.gc")
SCHED_PREFIX = "gen."
GC_SPAN = "host.gc"
DISPATCH_SPAN = "gen.decode_dispatch"
STEP_PROGRAM = "^jit_step$"
# the labels during which the host, not the device, set the pace
HOST_LABELS = ("admit", "chunk_dispatch", "decode_dispatch", "emit",
               "fault", "other", "gc")


def load_host_spans(path: str, prefixes=HOST_PREFIXES) -> List[dict]:
    """Host spans of a profile whose names start with one of
    ``prefixes``: dicts of ``line`` (an index: one host thread each),
    ``name``, ``start_s``, ``end_s`` and the span's own stats
    (``step``, ``chunk``, ``slots``, ``generation``), sorted by start."""
    from jax.profiler import ProfileData
    out = []
    n_line = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n_line += 1
            for e in line.events:
                if e.name.startswith(tuple(prefixes)):
                    out.append({"line": n_line, "name": e.name,
                                "start_s": e.start_ns / 1e9,
                                "end_s": (e.start_ns + e.duration_ns) / 1e9,
                                **{k: v for k, v in e.stats}})
    out.sort(key=lambda s: s["start_s"])
    return out


def scheduler_line(spans: Sequence[dict]) -> Optional[int]:
    """The host line that holds the most ``gen.*`` seconds."""
    secs: Dict[int, float] = {}
    for s in spans:
        if s["name"].startswith(SCHED_PREFIX):
            secs[s["line"]] = secs.get(s["line"], 0.0) + s["end_s"] - s["start_s"]
    return max(secs, key=secs.get) if secs else None


def dispatch_leads(plane: DevicePlane, spans: Sequence[dict],
                   program: str = STEP_PROGRAM) -> List[float]:
    """``run start - dispatch start`` for each decode step: the n-th
    ``gen.decode_dispatch`` of the scheduler's line against the n-th
    run of ``program`` that starts after the first dispatch began (the
    match of ``tools/sched_trace_table.py::step_table``). On one clock
    each is at least 0: a step runs after it is dispatched."""
    line = scheduler_line(spans)
    disp = [s["start_s"] for s in spans
            if s["line"] == line and s["name"] == DISPATCH_SPAN]
    if not disp:
        return []
    rx = re.compile(program)
    runs = [s for n, s, _ in plane.modules if rx.search(n) and s >= disp[0]]
    return [r - d for d, r in zip(disp, runs)]


def _median(xs: Sequence[float]) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else 0.0


def host_clock(plane: DevicePlane, spans: Sequence[dict]) -> dict:
    """The clock check: the shared clock is trusted when every matched
    decode step's dispatch began before its run (``shift_s`` 0); else
    ``shift_s``, the median of the negative leads, is added to every
    host span, moving the host's clock back by that skew."""
    leads = dispatch_leads(plane, spans)
    late = [x for x in leads if x < 0]
    return {"steps": len(leads), "late": len(late),
            "lead_min_s": min(leads, default=0.0),
            "lead_median_s": _median(leads), "shift_s": _median(late)}


def _spans_over(iv: Sequence[Interval], a: float, b: float,
                ends: Sequence[float]) -> List[Tuple[int, Interval]]:
    """(index, overlap) of the disjoint sorted intervals ``iv`` (their
    ends ``ends``) that overlap ``(a, b)``."""
    out = []
    k = bisect.bisect_right(ends, a)
    while k < len(iv) and iv[k][0] < b:
        lo, hi = max(a, iv[k][0]), min(b, iv[k][1])
        if hi > lo:
            out.append((k, (lo, hi)))
        k += 1
    return out


def idle_by_host(plane: DevicePlane, spans: Sequence[dict],
                 n: Optional[int] = 10) -> List[List]:
    """The device-idle gaps of at least ``SHORT_GAP_S`` (those
    ``trace.idle_gaps`` names by programs), their seconds split by what
    the host was doing: a ``host.gc`` span on any thread first, as
    ``gc``; the rest by the ``gen.<phase>`` open on the scheduler's
    line, as ``<phase>``, and ``other`` where none is; ``no_scheduler``
    where no line holds ``gen.*`` spans. The labels' seconds add up to
    the gaps'. Longest first, the first ``n`` (all if None)."""
    shift = host_clock(plane, spans)["shift_s"]
    gc_iv = union([(s["start_s"] + shift, s["end_s"] + shift)
                   for s in spans if s["name"] == GC_SPAN])
    gc_ends = [e for _, e in gc_iv]
    line = scheduler_line(spans)
    sched: List[Interval] = []
    names: List[str] = []
    for s in spans:
        if s["line"] == line and s["name"].startswith(SCHED_PREFIX):
            a = max(s["start_s"] + shift, sched[-1][1] if sched else -1e300)
            if s["end_s"] + shift > a:
                sched.append((a, s["end_s"] + shift))
                names.append(s["name"][len(SCHED_PREFIX):])
    sched_ends = [e for _, e in sched]
    busy = busy_intervals(plane)
    agg: Dict[str, float] = {}

    def add(key, secs):
        agg[key] = agg.get(key, 0.0) + secs

    for (_, a), (b, _) in zip(busy, busy[1:]):
        if b - a < SHORT_GAP_S:
            continue
        in_gc = [o for _, o in _spans_over(gc_iv, a, b, gc_ends)]
        if in_gc:
            add("gc", total(in_gc))
        for lo, hi in subtract([(a, b)], in_gc):
            if line is None:
                add("no_scheduler", hi - lo)
                continue
            covered = 0.0
            for k, (x, y) in _spans_over(sched, lo, hi, sched_ends):
                add(names[k], y - x)
                covered += y - x
            if hi - lo - covered > 0:
                add("other", hi - lo - covered)
    rows = sorted(agg.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in (rows if n is None else rows[:n])]


def idle_on_host_share(planes: Sequence[DevicePlane], spans: Sequence[dict],
                       labels: Sequence[str] = HOST_LABELS) -> Optional[float]:
    """100 x the seconds of the device-idle gaps of at least 20 us that
    :func:`idle_by_host` puts on ``labels``, over the traced window: the
    part of ``device_idle_share.decode`` during which the host, not the
    device, set the pace. None with no device plane."""
    if not planes:
        return None
    _, win = busy_and_window(planes)
    if win <= 0:
        return None
    by = dict(idle_by_host(fullest(planes), spans, n=None))
    return 100.0 * sum(by.get(k, 0.0) for k in labels) / win

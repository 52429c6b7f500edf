#!/usr/bin/env python3
"""One traced run of a serving cell with its ``.xplane.pb`` kept, and
the scheduler's ``gen.*`` spans laid beside the device's programs on
one clock.

    chiprun -- python3 tools/sched_trace_table.py \
        --workload gpt2-xl.decode_backlog --seed 9501 --seconds 45

It runs the cell through ``benchmark/run.py`` with ``--trace 1`` (the
result line is printed as usual), copies the profile before ``run.py``
removes it, and writes under ``chiprun_out/``:

- ``sched_trace_<seed>.xplane.pb``: the profile;
- ``sched_trace_<seed>.json``: the one-clock table (for each decode
  step in the traced seconds: ``gen.decode_dispatch`` start and end,
  the matching run of ``jit_step`` on the device plane, the end of
  ``gen.decode_wait`` and of ``gen.emit``, the first client stamp of
  the burst), every iteration of the scheduler's account in the window
  (``scheduler.slowest`` with the limit lifted), the oversleep probe's
  late wake-ups, and the result line.

``read_spans()`` and ``step_table()`` need only a profile: the CPU
tests use them on a trace of the tiny model.

A scratch tool of PR 26's finding (PERF.md section 5), kept for the
``perf_opt`` PRs that have to show where a device millisecond goes at
the client. ``benchmark/trace.py`` does not read host lines yet
(ROADMAP T1).
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SYNC_SPAN = "clock_sync"        # carries time.perf_counter() as a stat


def read_spans(path: str, prefixes=("gen.", "http.", SYNC_SPAN)):
    """Host spans of a profile whose names start with one of
    ``prefixes``: dicts of ``line`` (an index: one host thread each),
    ``name``, ``start_s``, ``end_s`` and the span's own stats
    (``step``, ``chunk``, ``slots``), sorted by start."""
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


def device_runs(path: str, program: str, device_prefix="/device:TPU:"):
    """(start_s, end_s) of every run of ``program`` on the first
    device plane that has one, in order."""
    from benchmark import trace as trace_mod
    for plane in trace_mod.load(path, device_prefix=device_prefix):
        runs = [(s, s + d) for name, s, d in plane.modules
                if name == program]
        if runs:
            return runs
    return []


def step_table(spans, runs, stamps=(), offset_s: float = 0.0):
    """One row a decode step whose dispatch lies in the trace. The
    n-th ``gen.decode_dispatch`` of the trace is matched with the n-th
    device run that starts after the first dispatch began; ``stamps``
    are the clients' token arrivals on the host's ``perf_counter``
    clock (a step's ``client_first`` is the first one after its emit
    began), ``offset_s`` what to add to them to reach the profile's
    clock."""
    by_step = {}
    for s in spans:
        if s["name"].startswith("gen.decode") or s["name"] == "gen.emit":
            if "step" in s:
                by_step.setdefault(int(s["step"]), {}).setdefault(
                    s["name"], s)
    disp = sorted(k for k, v in by_step.items()
                  if "gen.decode_dispatch" in v)
    if not disp:
        return []
    t_first = by_step[disp[0]]["gen.decode_dispatch"]["start_s"]
    runs = [r for r in runs if r[0] >= t_first]
    burst_t = sorted(b + offset_s for b in stamps)
    rows = []
    for i, n in enumerate(disp):
        ev = by_step[n]
        d = ev["gen.decode_dispatch"]
        row = {"step": n, "slots": d.get("slots"),
               "dispatch_start": d["start_s"], "dispatch_end": d["end_s"]}
        if i < len(runs):
            row["device_start"], row["device_end"] = runs[i]
        w, e = ev.get("gen.decode_wait"), ev.get("gen.emit")
        if w is not None:
            row["wait_start"], row["wait_end"] = w["start_s"], w["end_s"]
        if e is not None:
            row["emit_end"] = e["end_s"]
            j = bisect.bisect_left(burst_t, e["start_s"])
            if j < len(burst_t):
                row["client_first"] = burst_t[j]
        rows.append(row)
    return rows


def clock_offset(spans):
    """Seconds to add to a ``time.perf_counter()`` reading to reach
    the profile's clock, from the ``clock_sync`` spans."""
    d = [s["start_s"] - float(s["perf_counter"]) for s in spans
         if s["name"] == SYNC_SPAN and "perf_counter" in s]
    return sorted(d)[len(d) // 2] if d else None


def trace_cell(workload: str, seed: int, seconds: float, out_dir: str,
               trace: bool = True, **run_kw):
    """Run the cell, traced unless ``trace`` is false, keep its profile
    under ``out_dir`` and return (result line, table). Either way the
    table has both groups of metrics, every iteration of the account
    and the late wake-ups, so a traced and an untraced run of one seed
    can be laid side by side. ``run_kw`` goes to ``run_cell`` (the CPU
    rehearsal passes a tiny ``root`` and ``require_chip=False``)."""
    import jax
    import numpy as np
    from benchmark import run, trace as trace_mod
    from benchmark.kinds import serve_closed_loop as kind

    os.makedirs(out_dir, exist_ok=True)
    kept = os.path.join(out_dir, f"sched_trace_{seed}.xplane.pb")
    seen = {}

    # keep every iteration of the account, not the eight longest
    build = kind.build_server

    def build_and_lift(ctx):
        srv, gen = build(ctx)
        gen.engine.metrics.scheduler.keep_slowest = 1 << 20
        return srv, gen

    # the probe's wake-ups, which the runner reduces to one number
    class Probe(kind.Oversleep):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            seen["probe"] = self

    # perf_counter on the profile's clock; the profile kept
    stop = run.Context.trace_stop

    def stop_with_sync(ctx):
        for _ in range(5):
            with jax.profiler.TraceAnnotation(
                    SYNC_SPAN, perf_counter=repr(time.perf_counter())):
                pass
        stop(ctx)
        shutil.copy(trace_mod.find_xplane(ctx.trace_dir), kept)

    real = (kind.build_server, kind.Oversleep, run.Context.trace_stop)
    kind.build_server, kind.Oversleep = build_and_lift, Probe
    run.Context.trace_stop = stop_with_sync
    try:
        out, obs = run.run_cell(workload, seed, seconds, trace, **run_kw)
    finally:
        kind.build_server, kind.Oversleep, run.Context.trace_stop = real

    # the group of metrics that this kind of run does not print
    root = run_kw.get("root", ROOT)
    spec, cell, _, _ = run.load_cell(workload, root)
    other = {}
    for m in run.cell_metrics(spec, cell,
                              "end_to_end" if trace else "per_layer"):
        v = run.read_metric(m["name"], obs, os.path.join(root, "benchmark"))
        if v is not None:
            other[m["name"]] = float(v)
    spans = read_spans(kept) if trace else []
    offset = clock_offset(spans)
    t_open, t_close = obs["window"]["span"]
    gap = obs["traffic"]["burst_gap_ms"] / 1e3
    stamps = np.sort(np.asarray(
        [t for r in obs["requests"] for t in r["token_times"]]))
    first = stamps[np.diff(stamps, prepend=-np.inf) > gap]
    last = kind.burst_ends(stamps, gap)
    sched = obs["stats"]["close"].get("scheduler", {})
    probe = seen.get("probe")
    late = [[t, d] for t, d in zip(probe.wakes, probe.late)
            if d > 0.02 and t_open < t <= t_close] if probe else []
    names = sorted({s["name"] for s in spans})
    runs = {p: device_runs(kept, p) if trace else []
            for p in ("jit_step", "jit_chunk")}
    table = {
        "result": out,
        "other_metrics": other,
        "clock_offset_s": offset,
        "window": [t_open, t_close],
        "steps": step_table(spans, runs["jit_step"], stamps.tolist(),
                            offset or 0.0),
        "chunks": [s for s in spans if s["name"].startswith("gen.chunk")],
        "device_steps": runs["jit_step"],
        "device_chunks": runs["jit_chunk"],
        "span_counts": {n: sum(1 for s in spans if s["name"] == n)
                        for n in names},
        "span_lines": {n: sorted({s["line"] for s in spans
                                  if s["name"] == n}) for n in names},
        "http_write_s": [s["end_s"] - s["start_s"] for s in spans
                         if s["name"].startswith("http.")],
        "bursts_first": first.tolist(), "bursts_last": last.tolist(),
        "iterations": [e for e in sched.get("slowest", [])
                       if t_open <= e[0] <= t_close],
        "scheduler_open": obs["stats"]["open"].get("scheduler"),
        "scheduler_close": {k: v for k, v in sched.items()
                            if k != "slowest"},
        "stream_open": obs["stats"]["open"].get("stream"),
        "stream_close": obs["stats"]["close"].get("stream"),
        "occupancy_open": obs["stats"]["open"]["slots"]["occupancy_hist"],
        "occupancy_close": obs["stats"]["close"]["slots"]["occupancy_hist"],
        "late_wakeups": late,
    }
    return out, table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    a = ap.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out")
    out, table = trace_cell(a.workload, a.seed, a.seconds, out_dir,
                            trace=bool(a.trace))
    name = f"sched_trace_{a.seed}.json" if a.trace \
        else f"sched_plain_{a.seed}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(table, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)

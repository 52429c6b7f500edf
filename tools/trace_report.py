#!/usr/bin/env python
"""Summarize a ``GET /debug/traces`` dump — the second thing the
slow-request runbook (docs/observability.md) reaches for, after the
dump itself.

Input: one or more JSON files, each either a raw ``/debug/traces``
response (``{"traces": [...], "tracer": {...}}``), a bare list of
trace dicts, or a ``GET /events`` dump (``{"events": [...],
"counts": {...}}``) from the training UIServer. Passing SEVERAL files
merges traces by trace id — dump the router's
``/debug/traces?request_id=...`` and each replica's into separate
files and this tool stitches the cross-tier view back together,
exactly as the propagated ``X-Request-Id`` intended. Event dumps from
several workers merge into one wall-clock-ordered timeline.

Output:

- per-span-kind latency table (count, p50, p99, max) over every
  closed span in every trace — where fleet time goes in aggregate;
- the slowest trace's CRITICAL PATH: starting from its root span,
  repeatedly descend into the longest child (by ``parent_id``), so
  the one chain of spans that bounded the request's latency reads
  top to bottom;
- for TRAINING dumps (the FaultTolerantTrainer span kinds): the
  per-phase breakdown with data-wait and checkpoint-stall fractions,
  a per-worker straggler report over ``device_step`` spans, and the
  preemption→drain→checkpoint→resume event timeline.

Deliberately framework-free: reads JSON only (no jax, no numpy, no
package imports) — safe to run on a wedged host mid-incident, or on
a laptop against a dump scp'd out of production.

Usage::

    python tools/trace_report.py dump.json
    python tools/trace_report.py router.json replica_*.json --json
"""
from __future__ import annotations

import argparse
import json
import sys


def _pct(xs, p):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))] \
        if xs else 0.0


#: span kinds the training loop emits (FaultTolerantTrainer /
#: TrainingSupervisor / AsyncCheckpointWriter). ``fit`` is the per-fit
#: root; the rest are its children.
TRAINING_KINDS = ("fit", "data_wait", "device_step", "host_snapshot",
                  "checkpoint_submit", "checkpoint_write", "retry",
                  "rollback", "preemption_drain", "resume", "re_mesh")


def load_traces(paths):
    """Read dump files -> list of trace dicts, merged by trace id.
    Spans from the same trace in different files concatenate; span
    ids are namespaced per source file (each tier numbers its spans
    from 1, so raw ids would collide in a merged trace). Span time
    OFFSETS stay tier-local — the tiers' monotonic clocks are
    unrelated, which is why the span tree, not the offsets, carries
    the cross-tier structure."""
    by_id = {}
    order = []
    for fi, p in enumerate(paths):
        with open(p) as f:
            doc = json.load(f)
        traces = doc.get("traces", doc) if isinstance(doc, dict) else doc
        if not isinstance(traces, list):
            raise ValueError(f"{p}: not a /debug/traces dump")
        for t in traces:
            tid = t.get("trace_id")
            spans = []
            for s in t.get("spans", []):
                s = dict(s)
                s["span_id"] = f"{fi}.{s.get('span_id')}"
                if s.get("parent_id") is not None:
                    s["parent_id"] = f"{fi}.{s['parent_id']}"
                spans.append(s)
            have = by_id.get(tid)
            if have is None:
                by_id[tid] = dict(t, spans=spans)
                order.append(tid)
                continue
            have["spans"].extend(spans)
            if (have.get("duration_ms") or 0) < (t.get("duration_ms")
                                                 or 0):
                have["duration_ms"] = t["duration_ms"]
            have["error"] = bool(have.get("error") or t.get("error"))
    return [by_id[tid] for tid in order]


def kind_stats(traces):
    """Per-span-kind latency aggregate over all CLOSED spans."""
    by_kind = {}
    for t in traces:
        for s in t.get("spans", []):
            if s.get("duration_ms") is None:
                continue
            by_kind.setdefault(s.get("kind", "?"), []).append(
                s["duration_ms"])
    return {k: {"count": len(v),
                "p50_ms": round(_pct(v, 50), 3),
                "p99_ms": round(_pct(v, 99), 3),
                "max_ms": round(max(v), 3)}
            for k, v in sorted(by_kind.items())}


def prefix_savings(traces):
    """Aggregate the engine's ``prefix_match`` spans (emitted at
    admission when a request reuses cached KV blocks): how many
    requests hit, how much prefill they skipped, and the estimated
    milliseconds saved — split by match source (cross-request
    ``index`` vs persistent ``session``)."""
    by_src = {}
    for t in traces:
        for s in t.get("spans", []):
            if s.get("kind") != "prefix_match":
                continue
            a = s.get("attrs", {})
            agg = by_src.setdefault(a.get("source", "?"), {
                "count": 0, "matched_tokens": 0, "cow_copies": 0,
                "saved_est_ms": 0.0})
            agg["count"] += 1
            agg["matched_tokens"] += int(a.get("matched_tokens") or 0)
            agg["cow_copies"] += 1 if a.get("cow") else 0
            agg["saved_est_ms"] += float(a.get("saved_est_ms") or 0.0)
    return {k: dict(v, saved_est_ms=round(v["saved_est_ms"], 3))
            for k, v in sorted(by_src.items())}


def spec_savings(traces):
    """Aggregate the engine's speculative-decoding ``verify`` spans
    (one per request that ran at least one draft/verify round): rounds
    run, tokens proposed/accepted, the realized accept rate, and the
    estimated milliseconds of plain decode steps the accepted runs
    replaced — the speculation mirror of :func:`prefix_savings`."""
    agg = {"requests": 0, "rounds": 0, "proposed": 0, "accepted": 0,
           "spec_tokens": 0, "saved_est_ms": 0.0}
    for t in traces:
        for s in t.get("spans", []):
            if s.get("kind") != "verify":
                continue
            a = s.get("attrs", {})
            agg["requests"] += 1
            agg["rounds"] += int(a.get("rounds") or 0)
            agg["proposed"] += int(a.get("proposed") or 0)
            agg["accepted"] += int(a.get("accepted") or 0)
            agg["spec_tokens"] += int(a.get("spec_tokens") or 0)
            agg["saved_est_ms"] += float(a.get("saved_est_ms") or 0.0)
    if not agg["requests"]:
        return {}
    agg["accept_rate"] = round(agg["accepted"] / agg["proposed"], 4) \
        if agg["proposed"] else 0.0
    agg["saved_est_ms"] = round(agg["saved_est_ms"], 3)
    return agg


def step_pipeline(traces):
    """Aggregate the decode scheduler's ``step_pipeline`` spans (one
    per request that decoded): the scheduler loop's non-idle wall time
    over the request's decode lifetime (``wall_ms``), the part of it
    the scheduler thread spent BLOCKED in a step's or a chunk's fetch
    (``sync_wait_ms``), and the share that is left — both from the
    engine's time account (``scheduler`` in ``/stats``), so the parts
    add up. The gap between the two is the host's own work (admission,
    dispatch, token fan-out). ``overlap_frac`` near 0 reads as a loop
    that only waits for the device; near 1, the host never waited."""
    agg = {"requests": 0, "wall_ms": 0.0, "sync_wait_ms": 0.0}
    fracs = []
    for t in traces:
        for s in t.get("spans", []):
            if s.get("kind") != "step_pipeline":
                continue
            a = s.get("attrs", {})
            agg["requests"] += 1
            agg["wall_ms"] += float(a.get("wall_ms") or 0.0)
            agg["sync_wait_ms"] += float(a.get("sync_wait_ms") or 0.0)
            if a.get("overlap_frac") is not None:
                fracs.append(float(a["overlap_frac"]))
    if not agg["requests"]:
        return {}
    agg["overlap_frac"] = round(
        max(0.0, 1.0 - agg["sync_wait_ms"] / agg["wall_ms"]), 4) \
        if agg["wall_ms"] > 0 else 0.0
    agg["overlap_frac_p50"] = round(_pct(fracs, 50), 4)
    agg["overlap_frac_p99"] = round(_pct(fracs, 99), 4)
    agg["wall_ms"] = round(agg["wall_ms"], 3)
    agg["sync_wait_ms"] = round(agg["sync_wait_ms"], 3)
    return agg


def training_phases(traces):
    """Training step-phase breakdown over the trainer's span kinds:
    the per-kind latency table plus total milliseconds per phase and
    the two runbook fractions — how much of the step loop's wall time
    went to waiting on data, and how much to checkpoint work on the
    loop thread (host snapshot + submit; the background
    ``checkpoint_write`` spans ride the writer thread and are listed
    but excluded from the stall fraction)."""
    sums = {}
    for t in traces:
        for s in t.get("spans", []):
            k = s.get("kind")
            if k == "fit" or k not in TRAINING_KINDS \
                    or s.get("duration_ms") is None:
                continue
            sums[k] = sums.get(k, 0.0) + s["duration_ms"]
    if not sums:
        return {}
    ks = kind_stats(traces)
    out = {"kinds": {k: ks[k] for k in ks if k in TRAINING_KINDS},
           "totals_ms": {k: round(v, 3) for k, v in sorted(sums.items())}}
    wall = (sums.get("data_wait", 0.0) + sums.get("device_step", 0.0)
            + sums.get("host_snapshot", 0.0)
            + sums.get("checkpoint_submit", 0.0))
    if wall > 0:
        out["data_wait_frac"] = round(sums.get("data_wait", 0.0) / wall, 4)
        out["checkpoint_stall_frac"] = round(
            (sums.get("host_snapshot", 0.0)
             + sums.get("checkpoint_submit", 0.0)) / wall, 4)
    return out


def straggler_report(traces):
    """Per-worker ``device_step`` latency (count/p50/p99) and the
    straggler spread — the slowest worker's p50 over the fleet median
    p50, so 1.0 reads as an even fleet."""
    by_w = {}
    for t in traces:
        for s in t.get("spans", []):
            if s.get("kind") != "device_step" \
                    or s.get("duration_ms") is None:
                continue
            w = s.get("attrs", {}).get("worker")
            by_w.setdefault("?" if w is None else str(w), []).append(
                s["duration_ms"])
    if not by_w:
        return {}
    workers = {w: {"count": len(v),
                   "p50_ms": round(_pct(v, 50), 3),
                   "p99_ms": round(_pct(v, 99), 3)}
               for w, v in sorted(by_w.items())}
    p50s = sorted(st["p50_ms"] for st in workers.values())
    n = len(p50s)
    median = p50s[n // 2] if n % 2 else (p50s[n // 2 - 1]
                                         + p50s[n // 2]) / 2.0
    slowest = max(workers, key=lambda w: workers[w]["p50_ms"])
    return {"workers": workers,
            "slowest_worker": slowest,
            "slowest_p50_ms": workers[slowest]["p50_ms"],
            "median_p50_ms": round(median, 3),
            "spread": round(workers[slowest]["p50_ms"] / median, 4)
            if median > 0 else 0.0}


def event_timeline(events):
    """Merge ``/events`` dumps into one wall-clock-ordered timeline,
    re-based so the first event reads ``+0.000s`` — the
    preemption→drain→checkpoint→resume story top to bottom."""
    evs = sorted((e for e in events if isinstance(e, dict)),
                 key=lambda e: e.get("ts") or 0.0)
    if not evs:
        return []
    t0 = evs[0].get("ts") or 0.0
    out = []
    for e in evs:
        d = {"t_offset_s": round((e.get("ts") or 0.0) - t0, 3),
             "kind": e.get("kind"), "worker": e.get("worker")}
        attrs = {k: v for k, v in e.items()
                 if k not in ("ts", "kind", "worker")}
        if attrs:
            d["attrs"] = attrs
        out.append(d)
    return out


def critical_path(trace):
    """Root-to-leaf chain of longest spans: from each level's longest
    span, descend into its longest child (``parent_id`` links). Open
    spans (duration null — e.g. a discarded hedge arm still in
    flight when dumped) sort as zero but stay visible."""
    spans = trace.get("spans", [])
    if not spans:
        return []
    children = {}
    for s in spans:
        children.setdefault(s.get("parent_id"), []).append(s)
    dur = lambda s: s.get("duration_ms") or 0.0
    path = []
    # roots are parentless spans; a merged cross-tier trace has one
    # per tier (router "frontend", replica "http") — start from the
    # longest, the one that bounded the request
    node = max(children.get(None, spans), key=dur)
    while node is not None:
        path.append(node)
        kids = children.get(node.get("span_id"))
        node = max(kids, key=dur) if kids else None
    return path


def report(paths):
    # partition inputs: an /events dump is a dict with "events" and no
    # "traces"; everything else goes through the trace loader
    trace_paths, events = [], []
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        if isinstance(doc, dict) and "events" in doc \
                and "traces" not in doc:
            events.extend(doc.get("events") or [])
        else:
            trace_paths.append(p)
    traces = load_traces(trace_paths)
    slowest = (max(traces, key=lambda t: t.get("duration_ms") or 0.0)
               if traces else None)
    return {
        "files": list(paths),
        "n_traces": len(traces),
        "kinds": kind_stats(traces),
        "prefix_sharing": prefix_savings(traces),
        "speculation": spec_savings(traces),
        "step_pipeline": step_pipeline(traces),
        "training": training_phases(traces),
        "stragglers": straggler_report(traces),
        "events": event_timeline(events),
        "slowest": None if slowest is None else {
            "trace_id": slowest.get("trace_id"),
            "request_id": slowest.get("request_id"),
            "duration_ms": slowest.get("duration_ms"),
            "error": slowest.get("error"),
            "n_spans": len(slowest.get("spans", [])),
            "critical_path": [
                {"kind": s.get("kind"),
                 "t_offset_ms": s.get("t_offset_ms"),
                 "duration_ms": s.get("duration_ms"),
                 "attrs": s.get("attrs", {})}
                for s in critical_path(slowest)],
        },
    }


def _fmt_human(rep):
    lines = [f"{rep['n_traces']} trace(s) from "
             f"{len(rep['files'])} file(s)"]
    if rep["kinds"]:
        w = max(len(k) for k in rep["kinds"])
        lines.append(f"{'span kind':<{w}}  {'count':>6} {'p50 ms':>9} "
                     f"{'p99 ms':>9} {'max ms':>9}")
        for k, st in rep["kinds"].items():
            lines.append(f"{k:<{w}}  {st['count']:>6} "
                         f"{st['p50_ms']:>9.3f} {st['p99_ms']:>9.3f} "
                         f"{st['max_ms']:>9.3f}")
    if rep.get("prefix_sharing"):
        lines.append("-- prefix-cache savings (prefix_match spans)")
        for src, st in rep["prefix_sharing"].items():
            lines.append(
                f"   {src:<8} {st['count']:>5} hit(s)  "
                f"{st['matched_tokens']:>7} tokens matched  "
                f"{st['cow_copies']:>4} cow  "
                f"~{st['saved_est_ms']:.1f} ms prefill saved")
    sp = rep.get("speculation")
    if sp:
        lines.append("-- speculative-decoding savings (verify spans)")
        lines.append(
            f"   {sp['requests']:>5} request(s)  "
            f"{sp['rounds']:>6} rounds  "
            f"{sp['accepted']}/{sp['proposed']} accepted "
            f"({sp['accept_rate']:.1%})  "
            f"~{sp['saved_est_ms']:.1f} ms decode saved")
    pl = rep.get("step_pipeline")
    if pl:
        lines.append("-- decode pipelining (step_pipeline spans)")
        lines.append(
            f"   {pl['requests']:>5} request(s)  "
            f"loop wall {pl['wall_ms']:.1f} ms  "
            f"blocked in fetches {pl['sync_wait_ms']:.1f} ms  "
            f"overlap {pl['overlap_frac']:.1%} "
            f"(p50 {pl['overlap_frac_p50']:.1%}, "
            f"p99 {pl['overlap_frac_p99']:.1%})")
    tr = rep.get("training")
    if tr:
        lines.append("-- training phase breakdown")
        for k, ms in tr.get("totals_ms", {}).items():
            lines.append(f"   {k:<18} {ms:>12.3f} ms total")
        if "data_wait_frac" in tr:
            lines.append(
                f"   data-wait fraction {tr['data_wait_frac']:.2%}  "
                "checkpoint-stall fraction "
                f"{tr['checkpoint_stall_frac']:.2%}")
    st = rep.get("stragglers")
    if st:
        lines.append("-- stragglers (device_step spans per worker)")
        for w, s in st["workers"].items():
            lines.append(f"   worker {w:<4} {s['count']:>6} step(s)  "
                         f"p50 {s['p50_ms']:>9.3f} ms  "
                         f"p99 {s['p99_ms']:>9.3f} ms")
        lines.append(f"   slowest worker {st['slowest_worker']} "
                     f"(p50 {st['slowest_p50_ms']:.3f} ms) — spread "
                     f"{st['spread']:.2f}x vs median "
                     f"{st['median_p50_ms']:.3f} ms")
    evs = rep.get("events")
    if evs:
        lines.append(f"-- event timeline ({len(evs)} event(s))")
        for e in evs:
            w = e.get("worker")
            attrs = " ".join(f"{k}={v}" for k, v in
                             e.get("attrs", {}).items())
            lines.append(
                f"   +{e['t_offset_s']:>8.3f}s  "
                f"{'w' + str(w) if w is not None else '--':<4} "
                f"{e['kind']:<18} {attrs}".rstrip())
    s = rep.get("slowest")
    if s:
        lines.append(f"-- slowest trace {s['trace_id']} "
                     f"({s['duration_ms']} ms, {s['n_spans']} spans"
                     f"{', ERROR' if s.get('error') else ''})")
        for hop in s["critical_path"]:
            d = hop["duration_ms"]
            attrs = " ".join(f"{k}={v}" for k, v in hop["attrs"].items())
            lines.append(
                f"   +{hop['t_offset_ms']:>9.3f} ms  "
                f"{hop['kind']:<14} "
                f"{'(open)' if d is None else f'{d:.3f} ms':<12} "
                f"{attrs}".rstrip())
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="+",
                    help="/debug/traces dump file(s); several files "
                         "merge by trace id (router + replicas)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)
    try:
        rep = report(args.paths)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(rep, indent=2))
    else:
        print(_fmt_human(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())

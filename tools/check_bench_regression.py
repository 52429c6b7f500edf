#!/usr/bin/env python
"""CI gate: diff a fresh `bench.py` run against the latest recorded
``BENCH_*.json`` and fail (exit 1) on a >20% regression in any
recorded scenario metric.

Most scenario metrics are higher-is-better throughput numbers
(headline samples/sec plus the per-scenario extras); names listed in
``LOWER_IS_BETTER`` (latency percentiles, shed rates, queue waits)
gate in the opposite direction — a fresh value >20% ABOVE the
recorded baseline is the regression. Only
metrics present in BOTH the recorded and the fresh run are compared —
a scenario that didn't run is reported as
"skipped", never failed, so the gate can't be dodged by deleting a
scenario silently either: removed metrics are listed in the output.

Usage::

    python tools/check_bench_regression.py             # runs bench.py
    python tools/check_bench_regression.py --fresh out.json
    python tools/check_bench_regression.py --threshold 0.3
    python tools/check_bench_regression.py --list      # audit metrics
    python tools/check_bench_regression.py --list --fresh out.json

``--list`` prints every gated metric name with its recorded-baseline
and (if ``--fresh`` is given) fresh-run presence — so a newly added
metric's "new, skipped until a baseline records it" status is
auditable without reading the JSON blobs. It never runs bench.py and
never gates.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (path into the bench JSON) -> short metric name. All higher-is-better.
METRICS = {
    ("value",): "headline_samples_per_sec",
    ("extra", "serving", "requests_per_sec"): "serving_requests_per_sec",
    ("extra", "serving", "speedup_vs_unbatched"): "serving_speedup",
    ("extra", "generation", "tokens_per_sec"): "generation_tokens_per_sec",
    ("extra", "generation", "speedup_vs_sequential"): "generation_speedup",
    ("extra", "generation", "paged_tokens_per_sec"):
        "generation_paged_tokens_per_sec",
    # recovered-tokens/sec under the chaos probe (~1% injected
    # transient decode faults + scripted recoveries): "new, skipped"
    # until the next BENCH_*.json records a baseline, gated after
    ("extra", "generation", "chaos_tokens_per_sec"):
        "generation_chaos_tokens_per_sec",
    # training steps/sec with ~1% injected transient step faults + one
    # scripted preemption/resume mid-run (ISSUE 5): "new, skipped"
    # until the next BENCH_*.json records a baseline, gated after
    ("extra", "training_chaos", "steps_per_sec"):
        "training_chaos_steps_per_sec",
    # elastic leg (ISSUE 7): 4-worker compressed run, sharded v3
    # checkpoints, scripted preemption + RE-MESHED resume at 2 workers
    # inside the timed window — "new, skipped" until the next
    # BENCH_*.json records a baseline, gated after
    ("extra", "training_chaos", "elastic_steps_per_sec"):
        "training_elastic_steps_per_sec",
    # fleet requests/sec through the occupancy-aware router with one
    # scripted zero-loss rolling restart mid-run (ISSUE 6)
    ("extra", "fleet", "requests_per_sec"): "fleet_rps",
    ("extra", "word2vec", "tokens_per_sec"): "word2vec_tokens_per_sec",
    ("extra", "etl_pipeline", "rows_per_sec"): "etl_rows_per_sec",
    # open-loop overload harness (ISSUE 9): mixed predict+generate
    # Poisson traffic with a flat 2x-measured-capacity leg — "new,
    # skipped" until the next BENCH_*.json records a baseline
    ("extra", "overload", "capacity_rps"): "overload_capacity_rps",
    ("extra", "overload", "overload_goodput_ratio"):
        "overload_goodput_ratio",
    ("extra", "overload", "overload_shed_rate"): "overload_shed_rate",
    ("extra", "overload", "overload_interactive_p99_ms"):
        "overload_interactive_p99_ms",
    ("extra", "overload", "overload_ttft_ms_p99"):
        "overload_ttft_p99_ms",
    ("extra", "overload", "overload_itl_ms_p99"): "overload_itl_p99_ms",
    ("extra", "overload", "overload_queue_depth_max"):
        "overload_queue_depth_max",
    # admitted-request latency decomposition from traces (ISSUE 10):
    # where admitted time went under 2x overload, per component —
    # "new, skipped" until the next BENCH_*.json records a baseline
    ("extra", "overload", "latency_queue_ms_p99"):
        "overload_latency_queue_p99_ms",
    ("extra", "overload", "latency_admission_ms_p99"):
        "overload_latency_admission_p99_ms",
    ("extra", "overload", "latency_device_ms_p99"):
        "overload_latency_device_p99_ms",
    # traced-generation throughput (ISSUE 10): tokens/sec with
    # per-request tracing enabled — guards the <5% overhead claim
    ("extra", "generation", "traced_tokens_per_sec"):
        "generation_traced_tokens_per_sec",
    # host-side scheduler overhead (ISSUE 13): fraction of the
    # saturated continuous-batching wall clock NOT spent inside the
    # profiled device sections (prefill/decode/spec) — lower is
    # better; "new, skipped" until a BENCH_*.json records a baseline
    ("extra", "generation", "scheduler_overhead_frac"):
        "generation_scheduler_overhead_frac",
    # training-trace overhead (ISSUE 13): steps/sec cost of running
    # the clean supervised schedule with tracer + events + fleet
    # telemetry + StatsListener attached — guards the <5% claim
    ("extra", "training_chaos", "training_trace_overhead_frac"):
        "training_trace_overhead_frac",
    # closed-loop serving tail latency (recorded since BENCH_r05)
    ("extra", "serving", "p99_ms"): "serving_p99_ms",
    # block-level prefix sharing + persistent sessions (ISSUE 11):
    # shared-prefix burst and multi-turn session legs — "new, skipped"
    # until the next BENCH_*.json records a baseline, gated after
    ("extra", "generation", "prefix_hit_rate"): "prefix_hit_rate",
    ("extra", "generation", "prefix_prefill_tokens_saved_frac"):
        "prefix_prefill_tokens_saved_frac",
    ("extra", "generation", "prefix_users_capacity_ratio"):
        "prefix_users_capacity_ratio",
    ("extra", "generation", "prefix_kv_bytes_per_request"):
        "prefix_kv_bytes_per_request",
    ("extra", "generation", "prefix_ttft_ms_p50"): "prefix_ttft_p50_ms",
    ("extra", "generation", "prefix_ttft_ms_p99"): "prefix_ttft_p99_ms",
    ("extra", "generation", "session_ttft_turnN_ms"):
        "session_ttft_turnN_ms",
    ("extra", "generation", "session_turnN_speedup"):
        "session_turnN_speedup",
    # speculative decoding (ISSUE 12): decode-bound leg with a draft
    # model proposing k tokens per round — throughput AND inter-token
    # latency must both hold the line vs the recorded baseline (spec
    # is a latency optimization; a tokens/sec win that regresses ITL
    # p99 is a loss) — "new, skipped" until the next BENCH_*.json
    # records a baseline, gated after
    ("extra", "generation", "spec_tokens_per_sec"):
        "generation_spec_tokens_per_sec",
    ("extra", "generation", "spec_itl_ms_p99"): "spec_itl_p99_ms",
    ("extra", "generation", "spec_speedup_vs_plain"):
        "spec_speedup_vs_plain",
    # connection scale (ISSUE 14): idle streaming conns held open
    # through the event-loop front-end, and interactive probe p99
    # measured UNDER that load — "new, skipped" until the next
    # BENCH_*.json records a baseline, gated after
    ("extra", "connscale", "streaming_conns"): "connscale_streaming_conns",
    ("extra", "connscale", "p99_ms"): "connscale_p99_ms",
    # quantized KV pool (ISSUE 15): equal-pool-bytes legs across
    # kv_dtype — concurrent-user capacity ratio is the headline gate
    # (int8 >= 2x f32 at equal bytes), tokens/sec per dtype hold the
    # line, logit rel-err vs f32 is the documented tolerance (lower
    # is better) — "new, skipped" until the next BENCH_*.json records
    # a baseline, gated after
    ("extra", "generation", "kv_bf16_tokens_per_sec"):
        "kv_bf16_tokens_per_sec",
    ("extra", "generation", "kv_int8_tokens_per_sec"):
        "kv_int8_tokens_per_sec",
    ("extra", "generation", "kv_int8_concurrent_users_vs_f32"):
        "kv_int8_concurrent_users_vs_f32",
    ("extra", "generation", "kv_bf16_logit_rel_err"):
        "kv_bf16_logit_rel_err",
    ("extra", "generation", "kv_int8_logit_rel_err"):
        "kv_int8_logit_rel_err",
    # hierarchical KV tier (ISSUE 16): host-RAM/disk offload below the
    # device pool — live sessions per pool-resident session (>= 10x is
    # the acceptance bar), evicted-session re-prefills (must stay 0:
    # every turn-2 resume restores instead of re-prefilling),
    # restored-turn TTFT as a ratio of a hot resume (<= 2x), restore
    # count holds the line, post-warmup recompiles stay 0 (restores
    # reuse warmed gather/scatter executables), and the int8 host-byte
    # shrink per block vs f32 (~3.2x at head_dim 16) — "new, skipped"
    # until the next BENCH_*.json records a baseline, gated after
    ("extra", "generation", "offload_sessions_per_pool_ratio"):
        "offload_sessions_per_pool_ratio",
    ("extra", "generation", "offload_evicted_reprefills"):
        "offload_evicted_reprefills",
    ("extra", "generation", "offload_restores"): "offload_restores",
    ("extra", "generation", "offload_restore_ttft_ratio"):
        "offload_restore_ttft_ratio",
    ("extra", "generation", "offload_recompiles_post_warmup"):
        "offload_recompiles_post_warmup",
    ("extra", "generation", "offload_int8_capacity_vs_f32"):
        "offload_int8_capacity_vs_f32",
    # long-context generate class under the open-loop overload harness
    # (ISSUE 16 satellite): TTFT p99 of ~13-token prompts at 2x
    # capacity — lower is better
    ("extra", "overload", "overload_longctx_ttft_ms_p99"):
        "overload_longctx_ttft_p99_ms",
}

#: metric NAMES (values of METRICS) where LOWER is better — latency
#: percentiles, shed rates, queue depths/waits. Everything else gates
#: higher-is-better. compare() flips the regression test accordingly.
LOWER_IS_BETTER = {
    "overload_shed_rate",
    "overload_interactive_p99_ms",
    "overload_ttft_p99_ms",
    "overload_itl_p99_ms",
    "overload_queue_depth_max",
    "overload_latency_queue_p99_ms",
    "overload_latency_admission_p99_ms",
    "overload_latency_device_p99_ms",
    "serving_p99_ms",
    "generation_scheduler_overhead_frac",
    "training_trace_overhead_frac",
    "prefix_kv_bytes_per_request",
    "prefix_ttft_p50_ms",
    "prefix_ttft_p99_ms",
    "session_ttft_turnN_ms",
    "spec_itl_p99_ms",
    "connscale_p99_ms",
    "kv_bf16_logit_rel_err",
    "kv_int8_logit_rel_err",
    "offload_evicted_reprefills",
    "offload_restore_ttft_ratio",
    "offload_recompiles_post_warmup",
    "overload_longctx_ttft_p99_ms",
}

# A LOWER_IS_BETTER metric recorded at exactly 0.0 hit its FLOOR —
# e.g. an overhead fraction fully hidden by decode pipelining — which
# is an achievement to hold, not a degenerate run. Ratio gating is
# impossible from a zero baseline, so these gate on an absolute
# ceiling instead: a fresh value above the ceiling is a regression.
ABS_CEILING_FROM_ZERO = {
    "generation_scheduler_overhead_frac": 0.05,
    "training_trace_overhead_frac": 0.05,
    # recorded 0 is the acceptance state: ANY evicted-session
    # re-prefill or post-warmup recompile in a fresh run is a
    # regression (0.5 tolerates only float formatting, not one event)
    "offload_evicted_reprefills": 0.5,
    "offload_recompiles_post_warmup": 0.5,
}


def direction(name: str) -> str:
    return ("lower_is_better" if name in LOWER_IS_BETTER
            else "higher_is_better")


def _dig(d, path):
    for p in path:
        if not isinstance(d, dict) or p not in d:
            return None
        d = d[p]
    return d if isinstance(d, (int, float)) and not isinstance(
        d, bool) else None


def _parse_record(rec: dict, origin: str) -> dict:
    """Unwrap any of the recording formats into the bench line: the
    driver's {"parsed": {...}} or {"tail": "<json line>"}, or a bare
    bench line. Used for BOTH the baseline and --fresh inputs — a
    format mismatch must error, never degrade to 'all skipped'."""
    parsed = rec.get("parsed")
    if parsed is None and "tail" in rec:
        parsed = json.loads(rec["tail"].strip().splitlines()[-1])
    if parsed is None and "value" in rec:
        parsed = rec
    if parsed is None:
        raise SystemExit(f"{origin}: no parsable bench line")
    return parsed


def latest_recorded() -> tuple:
    """(path, parsed bench line) of the newest BENCH_r*.json."""
    paths = glob.glob(os.path.join(REPO, "BENCH_*.json"))
    if not paths:
        raise SystemExit("no recorded BENCH_*.json to compare against")

    def round_no(p):
        m = re.search(r"BENCH_r(\d+)", os.path.basename(p))
        return int(m.group(1)) if m else -1
    path = max(paths, key=round_no)
    with open(path) as f:
        rec = json.load(f)
    return path, _parse_record(rec, path)


def run_fresh(timeout_s: int) -> dict:
    """Run bench.py and parse its final JSON line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout_s, cwd=REPO)
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise SystemExit(f"bench.py produced no JSON line "
                     f"(rc={out.returncode}):\n{out.stderr[-2000:]}")


def compare(recorded: dict, fresh: dict, threshold: float) -> dict:
    """Returns {"regressions": [...], "ok": [...], "skipped": [...]}."""
    regressions, ok, skipped = [], [], []
    for path, name in METRICS.items():
        old = _dig(recorded, path)
        new = _dig(fresh, path)
        if old is None:
            # never recorded — nothing to hold the line on. But a
            # metric the FRESH run produces (a scenario added since
            # the last recording, e.g. the paged-generation one) must
            # be SAID to be unguarded, not silently passed over — the
            # next recorded BENCH_*.json picks it up
            if new is not None:
                skipped.append({"metric": name, "fresh": round(new, 3),
                                "note": "new, skipped (no recorded "
                                        "baseline yet)"})
            continue
        if old == 0 and name in ABS_CEILING_FROM_ZERO:
            if new is None:
                skipped.append({"metric": name, "recorded": old,
                                "note": "missing from fresh run"})
                continue
            cap = ABS_CEILING_FROM_ZERO[name]
            entry = {"metric": name, "recorded": 0.0,
                     "fresh": round(new, 3), "ceiling": cap,
                     "direction": direction(name)}
            (regressions if new > cap else ok).append(entry)
            continue
        if old <= 0:
            # recorded, but by a degenerate run — that is a broken
            # BASELINE, not a new metric; say which
            skipped.append({"metric": name, "recorded": old,
                            "note": "recorded baseline is non-positive,"
                                    " skipped"})
            continue
        if new is None:
            skipped.append({"metric": name, "recorded": old,
                            "note": "missing from fresh run"})
            continue
        ratio = new / old
        entry = {"metric": name, "recorded": round(old, 3),
                 "fresh": round(new, 3), "ratio": round(ratio, 3),
                 "direction": direction(name)}
        if name in LOWER_IS_BETTER:
            regressed = ratio > 1.0 + threshold
        else:
            regressed = ratio < 1.0 - threshold
        if regressed:
            regressions.append(entry)
        else:
            ok.append(entry)
    return {"regressions": regressions, "ok": ok, "skipped": skipped}


def list_metrics(recorded: dict, fresh: dict = None) -> list:
    """Rows for ``--list``: every gated metric name with its
    recorded / fresh presence and the resulting gate status."""
    rows = []
    for path, name in METRICS.items():
        old = _dig(recorded, path)
        new = _dig(fresh, path) if fresh is not None else None
        if old is not None and (old > 0 or (
                old == 0 and name in ABS_CEILING_FROM_ZERO)):
            status = "gated"
        elif old is not None:
            status = "recorded baseline non-positive, skipped"
        elif new is not None or fresh is None:
            status = "new, skipped until a BENCH_*.json records it"
        else:
            status = "absent from both"
        rows.append({"metric": name,
                     "path": ".".join(path),
                     "direction": direction(name),
                     "recorded": old,
                     "fresh": new,
                     "status": status})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fresh", help="path to a pre-existing fresh bench "
                    "JSON (skips running bench.py)")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="allowed fractional drop (default 0.20)")
    ap.add_argument("--timeout", type=int, default=7200,
                    help="bench.py timeout in seconds")
    ap.add_argument("--list", action="store_true",
                    help="print recorded-vs-fresh gated metric names "
                    "and exit 0 (never runs bench.py, never gates)")
    args = ap.parse_args(argv)
    rec_path, recorded = latest_recorded()
    if args.list:
        fresh = None
        if args.fresh:
            with open(args.fresh) as f:
                fresh = _parse_record(json.load(f), args.fresh)
        rows = list_metrics(recorded, fresh)
        print(json.dumps({"baseline_file": os.path.basename(rec_path),
                          "metrics": rows}, indent=2))
        return 0
    if args.fresh:
        with open(args.fresh) as f:
            fresh = _parse_record(json.load(f), args.fresh)
    else:
        fresh = run_fresh(args.timeout)
    result = compare(recorded, fresh, args.threshold)
    result["baseline_file"] = os.path.basename(rec_path)
    result["threshold"] = args.threshold
    result["fail"] = bool(result["regressions"])
    print(json.dumps(result, indent=2))
    return 1 if result["fail"] else 0


if __name__ == "__main__":
    sys.exit(main())

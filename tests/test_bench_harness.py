"""Bench-harness smoke (round 5): the flash-vs-XLA attention sweep only
executes on the chip, so a harness bug would burn chip time. Validate
the sweep code itself on CPU at tiny sizes — Pallas runs in interpret
mode here, so timings are meaningless but every code path (flash/xla,
masked/unmasked, grad chain, JSON emission) must complete."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def test_attention_sweep_harness_runs_on_cpu():
    import bench
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", bench.ATTENTION_CODE, "64"],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)["results"]
    expected = {"T64_flash", "T64_xla", "T64_flash_masked",
                "T64_xla_masked"}
    assert set(res) == expected, res
    for k, v in res.items():
        assert isinstance(v, float), f"{k} did not produce a timing: {v}"


def test_bench_without_a_chip_fails_and_runs_no_cpu_headline():
    """No chip is an error: bench.py must exit non-zero naming the
    headline leg and print no result line — not fall back to a CPU
    model."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=ROOT)
    assert r.returncode != 0
    assert "resnet50_b32" in r.stderr and "measures the chip" in r.stderr
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]


def test_bench_parent_stays_off_jax():
    """One process per chip: the parent that launches the legs must
    never import jax, or it would hold the chip its children need."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; from deeplearning4j_tpu.flags import flags; "
         "flags.bench_skip_secondary; sys.exit('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]


def test_bench_leg_failure_is_fatal_and_named():
    """A leg that crashes, or exits 0 without a JSON result, raises
    LegFailed naming it — never a silently missing entry."""
    import bench
    with pytest.raises(bench.LegFailed, match="'boom' exited 3"):
        bench._run("boom", "import sys; sys.exit(3)", {}, timeout=60)
    with pytest.raises(bench.LegFailed, match="'mute' printed no JSON"):
        bench._run("mute", "print('not json')", {}, timeout=60)
    assert bench._run("fine", "print('{\"a\": 1}')", {}, 60) == {"a": 1}


def test_mfu_rejects_an_unknown_device():
    import bench
    res = {"flops_per_step": 1e12, "ms_per_iter": 10.0}
    assert bench._mfu(dict(res, device_kind="TPU v5 lite")) == round(
        1e14 / 197e12, 4)
    with pytest.raises(KeyError, match="no peak FLOP/s"):
        bench._mfu(dict(res, device_kind="cpu"))


def test_generation_scenario_harness_runs_on_cpu():
    """The continuous-batching generation scenario at tiny scale: every
    code path (uncached baseline, cached-sequential reference, the
    concurrent engine, JSON emission) must complete, outputs must be
    token-identical across engine configurations, and the measured
    window must be compile-free."""
    import bench
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    # argv: N_REQ=8 requests, 4 slots — small enough for CI cadence
    r = subprocess.run([sys.executable, "-c", bench.GENERATION_CODE,
                        "8", "4"],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["total_tokens"] > 0
    assert res["tokens_per_sec"] > 0
    assert res["sequential_tokens_per_sec"] > 0
    # identity across DIFFERENT batch shapes rests on cross-shape XLA
    # reduction determinism — report-only in the bench, so here just
    # require the field to exist (engine-level reproducibility is
    # asserted exactly in tests/test_generation.py, same shapes)
    assert isinstance(res["tokens_identical_to_cached_sequential"],
                      bool)
    assert res["recompiles_post_warmup"] == 0
    assert res["mean_slot_occupancy"] > 1.0  # it actually batched
    # paged backend (ISSUE 3): same workload, token-identical to the
    # slot engine, compile-free, and the peak block footprint is the
    # measured memory number (same shapes here, so identity is exact)
    assert res["tokens_identical_paged_vs_slots"] is True
    assert res["paged_recompiles_post_warmup"] == 0
    assert res["paged_tokens_per_sec"] > 0
    assert 0 < res["paged_peak_kv_bytes"] <= res["paged_pool_bytes"]
    assert res["chunked_prefills"] >= 1  # the 160-token probes chunked
    assert res["itl_p95_short_ms_longprompt_unchunked"] > 0
    # chaos probe (ISSUE 4): the same engine absorbing injected
    # transient decode faults + a scripted recompute-recovery must
    # lose nothing, reproduce the fault-free tokens, and never
    # recompile — while still reporting a throughput for the gate
    assert res["chaos_tokens_per_sec"] > 0
    assert res["chaos_tokens_identical"] is True
    assert res["chaos_requests_lost"] == 0
    assert res["chaos_recompiles_post_warmup"] == 0
    assert res["chaos_recoveries"] >= 1
    # traced re-run (ISSUE 10): per-request tracing enabled must still
    # reproduce the tokens and record spans. What tracing costs is a
    # time, so it is measured on the chip (PERF.md section 6), never
    # bounded by a CPU timing here
    assert res["traced_tokens_per_sec"] > 0
    assert res["tokens_identical_traced"] is True
    assert res["trace_spans_recorded"] >= 8 * 3  # admission+queue+decode
    # speculative leg (ISSUE 12): k=3 same-weights draft vs k=0 on the
    # long-context mix — tokens must be identical (the bit-identity
    # contract, measured not assumed), the accept path must actually
    # run (same weights at temperature 0 accept most rounds), and the
    # measured window must stay compile-free; the speedup itself is
    # gated against the recorded baseline at full scale, not here
    assert res["spec_k"] == 3
    assert res["spec_tokens_identical_vs_plain"] is True
    assert res["spec_recompiles_post_warmup"] == 0
    assert res["spec_tokens_per_sec"] > 0
    assert res["spec_verify_batches"] >= 1
    assert res["spec_accept_rate"] > 0.3
    assert res["spec_itl_ms_p99"] > 0
    # hierarchical KV tier (ISSUE 16): 32 two-turn sessions against a
    # pool that pins ~3 — every turn-2 resume must restore its demoted
    # run from host RAM (zero evicted-session re-prefills), reproduce
    # the big-pool engine's tokens exactly, and stay compile-free; the
    # <=2x restored-TTFT bound is gated at full scale via the recorded
    # baseline, not at CI's noisy tiny sizes
    assert res["offload_live_sessions"] == 32
    assert res["offload_sessions_per_pool_ratio"] >= 10
    assert res["offload_evicted_reprefills"] == 0
    assert res["offload_demotions"] > 0
    assert res["offload_restores"] >= 32  # every turn 2 restored
    assert res["offload_tokens_identical"] is True
    assert res["offload_recompiles_post_warmup"] == 0
    assert res["offload_restore_ttft_ms_p50"] > 0
    assert res["offload_hot_ttft_ms_p50"] > 0
    # int8 host-byte shrink carries into the host tier (head_dim 16
    # -> 3.2x including scale sidecars)
    assert res["offload_int8_capacity_vs_f32"] >= 3.0


def test_fleet_scenario_harness_runs_on_cpu():
    """ISSUE 6 bench satellite at tiny scale (small MLP, 3 requests
    per client): the fleet scenario must complete its scripted rolling
    restart mid-traffic with ZERO client-visible failures and zero
    router-lost requests — the fleet-wide zero-loss bar — while still
    producing the gated requests/sec number."""
    import bench
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", bench.FLEET_CODE,
                        "64", "3"],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["requests_per_sec"] > 0
    assert res["requests_total"] == 48       # 16 clients x 3
    assert res["zero_loss"] is True
    assert res["client_failures"] == 0 and res["requests_lost"] == 0
    assert res["restart_clean"] is True and res["restarts"] == 3
    # budget bound counts the WARMUP pass's completed requests too
    # (the same router refills 0.05/request across both passes):
    # 4 burst + 0.05 * (32 warmup + 48 measured) = 8
    assert res["hedges"] <= 8
    # overlap is asserted at full scale via the recorded baseline;
    # here just require the honesty field to be present
    assert isinstance(res["restart_within_traffic"], bool)


def test_check_bench_regression_comparator():
    """tools/check_bench_regression.py: >20% drops fail, equal or
    missing metrics don't (missing is reported as skipped)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    rec = {"value": 100.0,
           "extra": {"word2vec": {"tokens_per_sec": 1000.0},
                     "generation": {"tokens_per_sec": 500.0,
                                    "speedup_vs_sequential": 4.0}}}
    same = json.loads(json.dumps(rec))
    r = cbr.compare(rec, same, 0.2)
    assert not r["regressions"] and len(r["ok"]) == 4
    bad = json.loads(json.dumps(rec))
    bad["extra"]["generation"]["tokens_per_sec"] = 350.0   # -30%
    r = cbr.compare(rec, bad, 0.2)
    assert [e["metric"] for e in r["regressions"]] == \
        ["generation_tokens_per_sec"]
    partial = {"value": 95.0, "extra": {}}                 # -5%: fine
    r = cbr.compare(rec, partial, 0.2)
    assert not r["regressions"]
    assert len(r["skipped"]) == 3  # the extras didn't run


def test_check_bench_regression_new_metric_is_reported_not_crashed():
    """ISSUE 3 satellite: a scenario present in the fresh bench but
    absent from the recorded baseline (the just-added paged scenario,
    until a BENCH_*.json records it) must surface as "new, skipped" —
    neither a crash nor a silent pass that hides the unguarded
    metric."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr2", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    rec = {"value": 100.0,
           "extra": {"generation": {"tokens_per_sec": 500.0}}}
    fresh = {"value": 100.0,
             "extra": {"generation": {"tokens_per_sec": 500.0,
                                      "paged_tokens_per_sec": 450.0}}}
    r = cbr.compare(rec, fresh, 0.2)
    assert not r["regressions"]
    news = [e for e in r["skipped"] if e.get("note", "").startswith("new")]
    assert [e["metric"] for e in news] == \
        ["generation_paged_tokens_per_sec"]
    assert news[0]["fresh"] == 450.0
    # and the new metric IS guarded once a baseline records it
    rec2 = {"value": 100.0,
            "extra": {"generation": {"tokens_per_sec": 500.0,
                                     "paged_tokens_per_sec": 450.0}}}
    bad = {"value": 100.0,
           "extra": {"generation": {"tokens_per_sec": 500.0,
                                    "paged_tokens_per_sec": 300.0}}}
    r = cbr.compare(rec2, bad, 0.2)
    assert [e["metric"] for e in r["regressions"]] == \
        ["generation_paged_tokens_per_sec"]


def test_training_chaos_scenario_harness_runs_on_cpu():
    """ISSUE 5 bench satellite at tiny scale (2 epochs = 128 steps):
    the supervised chaos run must absorb its scripted preemption,
    restart + resume, finish the full schedule, and land on params
    BIT-IDENTICAL to the uninterrupted clean run."""
    import bench
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", bench.TRAINING_CHAOS_CODE,
                        "2"],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["steps_per_sec"] > 0
    assert res["preempted"] is True and res["preemptions"] == 1
    assert res["total_steps"] == 128          # schedule completed
    assert res["async_checkpoints"] >= 1      # cadence really async
    assert res["params_identical_to_clean"] is True


def test_check_bench_regression_list_mode():
    """ISSUE 5 satellite: --list prints every gated metric with its
    recorded-vs-fresh presence, so a new metric's unguarded window is
    auditable without reading the BENCH JSON blobs."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr3", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    rec = {"value": 100.0,
           "extra": {"generation": {"tokens_per_sec": 500.0}}}
    fresh = {"value": 100.0,
             "extra": {"generation": {"tokens_per_sec": 480.0},
                       "training_chaos": {"steps_per_sec": 120.0}}}
    rows = {r["metric"]: r for r in cbr.list_metrics(rec, fresh)}
    assert set(rows) == set(cbr.METRICS.values())  # every gated metric
    assert rows["headline_samples_per_sec"]["status"] == "gated"
    assert rows["generation_tokens_per_sec"]["status"] == "gated"
    assert rows["generation_tokens_per_sec"]["fresh"] == 480.0
    tc = rows["training_chaos_steps_per_sec"]
    assert tc["recorded"] is None and tc["fresh"] == 120.0
    assert tc["status"].startswith("new, skipped")
    # without a fresh run the same metric still shows as unguarded
    rows2 = {r["metric"]: r for r in cbr.list_metrics(rec, None)}
    assert rows2["training_chaos_steps_per_sec"]["status"].startswith(
        "new, skipped")
    # and the CLI path: --list with --fresh exits 0, prints the table
    import io
    from contextlib import redirect_stdout
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(fresh, f)
        fpath = f.name
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cbr.main(["--list", "--fresh", fpath])
    os.unlink(fpath)
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert any(m["metric"] == "training_chaos_steps_per_sec"
               for m in out["metrics"])


def test_training_elastic_leg_runs_on_cpu():
    """ISSUE 7 bench satellite at tiny scale (2 epochs = 128 steps):
    the elastic leg must preempt its 4-worker compressed run, resume
    RE-MESHED onto 2 workers with sharded (v3) checkpoints, finish the
    schedule, and land within the documented tolerance of the
    fixed-shape trajectory."""
    import bench
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c",
                        bench.TRAINING_ELASTIC_CODE, "2"],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["elastic_steps_per_sec"] > 0
    assert res["elastic_preempted"] is True
    assert res["elastic_remeshed"] == [4, 2]
    assert res["elastic_total_steps"] == 128      # schedule completed
    assert res["elastic_sharded_checkpoints"] >= 1
    assert res["elastic_resume_wall_s"] > 0
    # docs/distributed.md's re-mesh tolerance contract
    assert res["elastic_params_rel_err_vs_fixed_shape"] <= 0.05


def test_training_elastic_metric_is_gated():
    """The elastic leg's steps/sec is wired into the regression gate:
    "new, skipped" until a BENCH_*.json records it, gated after."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr4", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    assert ("extra", "training_chaos", "elastic_steps_per_sec") \
        in cbr.METRICS
    rec = {"value": 100.0,
           "extra": {"training_chaos": {"steps_per_sec": 120.0}}}
    fresh = {"value": 100.0,
             "extra": {"training_chaos": {"steps_per_sec": 120.0,
                                          "elastic_steps_per_sec": 50.0}}}
    r = cbr.compare(rec, fresh, 0.2)
    assert not r["regressions"]
    news = [e for e in r["skipped"] if e.get("note", "").startswith("new")]
    assert any(e["metric"] == "training_elastic_steps_per_sec"
               for e in news)
    # and gated once recorded
    rec2 = {"value": 100.0,
            "extra": {"training_chaos": {"steps_per_sec": 120.0,
                                         "elastic_steps_per_sec": 50.0}}}
    bad = {"value": 100.0,
           "extra": {"training_chaos": {"steps_per_sec": 120.0,
                                        "elastic_steps_per_sec": 30.0}}}
    r2 = cbr.compare(rec2, bad, 0.2)
    assert [e["metric"] for e in r2["regressions"]] == \
        ["training_elastic_steps_per_sec"]


def test_check_bench_regression_direction_registry():
    """ISSUE 9 satellite: latency/shed/queue metrics gate in the
    opposite direction — a fresh value ABOVE the recorded baseline is
    the regression — via the LOWER_IS_BETTER direction registry."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr5", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    # every registered direction flip names a real gated metric
    assert cbr.LOWER_IS_BETTER <= set(cbr.METRICS.values())
    assert cbr.direction("serving_p99_ms") == "lower_is_better"
    assert cbr.direction("headline_samples_per_sec") == "higher_is_better"
    rec = {"value": 100.0,
           "extra": {"serving": {"p99_ms": 100.0},
                     "overload": {"overload_shed_rate": 0.2}}}
    # +30% on a lower-is-better metric REGRESSES...
    worse = {"value": 100.0,
             "extra": {"serving": {"p99_ms": 130.0},
                       "overload": {"overload_shed_rate": 0.2}}}
    r = cbr.compare(rec, worse, 0.2)
    assert [e["metric"] for e in r["regressions"]] == ["serving_p99_ms"]
    assert r["regressions"][0]["direction"] == "lower_is_better"
    # ...and -30% passes (it would regress a higher-is-better metric)
    better = {"value": 100.0,
              "extra": {"serving": {"p99_ms": 70.0},
                        "overload": {"overload_shed_rate": 0.14}}}
    r = cbr.compare(rec, better, 0.2)
    assert not r["regressions"]
    assert all(e["direction"] in ("lower_is_better", "higher_is_better")
               for e in r["ok"])
    # the --list audit surface carries the direction too
    rows = {row["metric"]: row for row in cbr.list_metrics(rec)}
    assert rows["serving_p99_ms"]["direction"] == "lower_is_better"
    assert rows["overload_shed_rate"]["direction"] == "lower_is_better"
    assert rows["overload_goodput_ratio"]["direction"] == \
        "higher_is_better"


def test_check_bench_regression_connscale_metrics_gated():
    """ISSUE 14 satellite: the connection-scale leg gates both ways —
    held streaming conns are higher-is-better, the interactive probe
    p99 measured UNDER that connection load flips direction."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr7", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    names = set(cbr.METRICS.values())
    assert {"connscale_streaming_conns", "connscale_p99_ms"} <= names
    assert cbr.direction("connscale_streaming_conns") == \
        "higher_is_better"
    assert cbr.direction("connscale_p99_ms") == "lower_is_better"
    rec = {"value": 100.0,
           "extra": {"connscale": {"streaming_conns": 1000,
                                   "p99_ms": 12.0}}}
    # fewer held conns AND a fatter probe tail both regress
    worse = {"value": 100.0,
             "extra": {"connscale": {"streaming_conns": 600,
                                     "p99_ms": 40.0}}}
    r = cbr.compare(rec, worse, 0.2)
    assert sorted(e["metric"] for e in r["regressions"]) == \
        ["connscale_p99_ms", "connscale_streaming_conns"]
    # holding more conns at a lower p99 passes
    better = {"value": 100.0,
              "extra": {"connscale": {"streaming_conns": 1200,
                                      "p99_ms": 9.0}}}
    assert not cbr.compare(rec, better, 0.2)["regressions"]


def test_check_bench_regression_zero_floor_overhead_gated():
    """ISSUE 14 satellite: a scheduler_overhead_frac recorded at its
    0.0 floor (pipelining fully hid the scheduler) must stay GATED via
    an absolute ceiling, not be skipped as a degenerate baseline — a
    fresh run re-exposing the overhead is a regression."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr8", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    assert "generation_scheduler_overhead_frac" in \
        cbr.ABS_CEILING_FROM_ZERO
    rec = {"value": 100.0,
           "extra": {"generation": {"scheduler_overhead_frac": 0.0}}}
    cap = cbr.ABS_CEILING_FROM_ZERO["generation_scheduler_overhead_frac"]
    worse = {"value": 100.0,
             "extra": {"generation":
                       {"scheduler_overhead_frac": cap + 0.2}}}
    r = cbr.compare(rec, worse, 0.2)
    assert [e["metric"] for e in r["regressions"]] == \
        ["generation_scheduler_overhead_frac"]
    assert r["regressions"][0]["ceiling"] == cap
    held = {"value": 100.0,
            "extra": {"generation": {"scheduler_overhead_frac": 0.0}}}
    r = cbr.compare(rec, held, 0.2)
    assert not r["regressions"]
    assert any(e["metric"] == "generation_scheduler_overhead_frac"
               for e in r["ok"])
    # the --list audit surface reports it as gated, not skipped
    rows = {row["metric"]: row for row in cbr.list_metrics(rec)}
    assert rows["generation_scheduler_overhead_frac"]["status"] == \
        "gated"
    # a throughput metric at zero is still a broken baseline
    rec0 = {"value": 0.0}
    r = cbr.compare(rec0, {"value": 50.0}, 0.2)
    assert any("non-positive" in e["note"] for e in r["skipped"])


def test_check_bench_regression_speculative_metrics_gated():
    """ISSUE 12 satellite: the speculative-decoding leg gates BOTH
    ways — tokens/sec and speedup-vs-plain are higher-is-better, but
    the per-request mean-ITL p99 flips (speculation is a latency
    optimization; a throughput win that regresses ITL is a loss)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr6", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    names = set(cbr.METRICS.values())
    assert {"generation_spec_tokens_per_sec", "spec_itl_p99_ms",
            "spec_speedup_vs_plain"} <= names
    assert cbr.METRICS[("extra", "generation", "spec_itl_ms_p99")] \
        == "spec_itl_p99_ms"
    assert cbr.direction("spec_itl_p99_ms") == "lower_is_better"
    assert cbr.direction("generation_spec_tokens_per_sec") == \
        "higher_is_better"
    assert cbr.direction("spec_speedup_vs_plain") == "higher_is_better"
    rec = {"value": 100.0,
           "extra": {"generation": {"spec_tokens_per_sec": 900.0,
                                    "spec_itl_ms_p99": 2.0,
                                    "spec_speedup_vs_plain": 1.2}}}
    # ITL p99 climbing 50% is the regression even with throughput flat
    worse = {"value": 100.0,
             "extra": {"generation": {"spec_tokens_per_sec": 900.0,
                                      "spec_itl_ms_p99": 3.0,
                                      "spec_speedup_vs_plain": 1.2}}}
    r = cbr.compare(rec, worse, 0.2)
    assert [e["metric"] for e in r["regressions"]] == ["spec_itl_p99_ms"]
    # faster tokens AND lower ITL both pass
    better = {"value": 100.0,
              "extra": {"generation": {"spec_tokens_per_sec": 1100.0,
                                       "spec_itl_ms_p99": 1.5,
                                       "spec_speedup_vs_plain": 1.3}}}
    assert not cbr.compare(rec, better, 0.2)["regressions"]
    # throughput dropping 30% regresses in the usual direction
    slow = {"value": 100.0,
            "extra": {"generation": {"spec_tokens_per_sec": 600.0,
                                     "spec_itl_ms_p99": 2.0,
                                     "spec_speedup_vs_plain": 1.2}}}
    r = cbr.compare(rec, slow, 0.2)
    assert [e["metric"] for e in r["regressions"]] == \
        ["generation_spec_tokens_per_sec"]


def test_overload_scenario_harness_runs_on_cpu():
    """ISSUE 9 tentpole at tiny scale (~1.2s legs): the open-loop
    overload harness must measure capacity closed-loop, run the
    Poisson diurnal + flat 2x-capacity legs, and emit every gated
    field with the degradation invariants intact — bounded queue,
    goodput above the documented floor, batch shed before
    interactive."""
    import bench
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", bench.OVERLOAD_CODE,
                        "1.2"],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["capacity_rps"] > 0
    assert res["overload_offered"] > 0
    assert res["overload_offered_rps"] > res["capacity_rps"]  # open loop
    assert 0.0 < res["overload_goodput_ratio"] <= 1.0
    assert res["overload_goodput_floor"] == 0.3
    # the graceful-degradation invariants the full run gates on
    assert res["overload_queue_bounded"] is True
    assert res["overload_goodput_ok"] is True
    assert res["overload_interactive_slo_ok"] is True
    # generation rode along: TTFT/ITL are first-class
    assert res["overload_ttft_ms_p99"] > 0
    assert res["overload_itl_ms_p99"] >= 0
    # fleet-level backpressure counters surfaced
    assert res["fleet_goodput"] > 0
    assert res["fleet_shed_total"] >= 0
    assert res["engine_shed_total"] >= 0
    # structural: shed accounting splits by class and cause
    for k in ("overload_batch_shed_rate", "overload_interactive_shed_rate",
              "overload_shed_rate", "overload_deadline_sheds",
              "engine_shed_batch_total", "engine_shed_deadline_total",
              "fleet_cooldowns", "fleet_breaker_trips"):
        assert k in res, k
    # latency decomposition from traces (ISSUE 10): admitted-request
    # time split into queue/admission/device components, each with a
    # count and percentiles, plus the flat p99 keys the regression
    # gate registers
    lb = res["latency_breakdown"]
    for comp in ("queue", "admission", "device"):
        assert set(lb[comp]) == {"count", "p50_ms", "p99_ms"}, lb
        assert lb[comp]["count"] > 0, (comp, lb)
        assert lb[comp]["p99_ms"] >= lb[comp]["p50_ms"] >= 0.0
    assert res["latency_queue_ms_p99"] == lb["queue"]["p99_ms"]
    assert res["latency_admission_ms_p99"] == lb["admission"]["p99_ms"]
    assert res["latency_device_ms_p99"] == lb["device"]["p99_ms"]
    # long-context prompt mix (ISSUE 16 satellite): half the
    # interactive generation probes carry a 13-token prompt that
    # chunks through prefill — its TTFT tail is tracked (and gated)
    # separately from the short-prompt probes
    for k in ("normal_longctx_ttft_ms_p99", "overload_longctx_completed",
              "overload_longctx_ttft_ms_p50",
              "overload_longctx_ttft_ms_p99"):
        assert k in res, k
    assert res["overload_longctx_completed"] >= 0
    if res["overload_longctx_completed"] > 0:
        assert res["overload_longctx_ttft_ms_p99"] >= \
            res["overload_longctx_ttft_ms_p50"] >= 0


def test_check_bench_regression_offload_metrics_gated():
    """ISSUE 16 satellite: the hierarchical-KV-tier leg gates its
    claims — zero evicted re-prefills and zero post-warmup recompiles
    hold via absolute ceilings even when recorded at their 0.0 floor,
    the restored-TTFT ratio and longctx tail flip to lower-is-better,
    and session capacity ratios gate in the usual direction."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cbr9", os.path.join(ROOT, "tools", "check_bench_regression.py"))
    cbr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cbr)
    names = set(cbr.METRICS.values())
    assert {"offload_sessions_per_pool_ratio",
            "offload_evicted_reprefills", "offload_restores",
            "offload_restore_ttft_ratio",
            "offload_recompiles_post_warmup",
            "offload_int8_capacity_vs_f32",
            "overload_longctx_ttft_p99_ms"} <= names
    # direction registry stays a subset of the gated metric names
    assert cbr.LOWER_IS_BETTER <= names
    for m in ("offload_evicted_reprefills", "offload_restore_ttft_ratio",
              "offload_recompiles_post_warmup",
              "overload_longctx_ttft_p99_ms"):
        assert cbr.direction(m) == "lower_is_better", m
    for m in ("offload_sessions_per_pool_ratio", "offload_restores",
              "offload_int8_capacity_vs_f32"):
        assert cbr.direction(m) == "higher_is_better", m
    # zero-floor counters stay GATED by absolute ceiling, not skipped
    assert cbr.ABS_CEILING_FROM_ZERO["offload_evicted_reprefills"] == 0.5
    assert cbr.ABS_CEILING_FROM_ZERO[
        "offload_recompiles_post_warmup"] == 0.5
    rec = {"value": 100.0,
           "extra": {"generation": {"offload_evicted_reprefills": 0,
                                    "offload_restore_ttft_ratio": 1.4,
                                    "offload_recompiles_post_warmup": 0,
                                    "offload_int8_capacity_vs_f32": 3.2}}}
    # a single evicted-session re-prefill appearing IS the regression
    worse = {"value": 100.0,
             "extra": {"generation": {"offload_evicted_reprefills": 1,
                                      "offload_restore_ttft_ratio": 1.4,
                                      "offload_recompiles_post_warmup": 0,
                                      "offload_int8_capacity_vs_f32":
                                          3.2}}}
    r = cbr.compare(rec, worse, 0.2)
    assert [e["metric"] for e in r["regressions"]] == \
        ["offload_evicted_reprefills"]
    # restored-TTFT ratio fattening 50% regresses (lower is better)...
    slow = {"value": 100.0,
            "extra": {"generation": {"offload_evicted_reprefills": 0,
                                     "offload_restore_ttft_ratio": 2.1,
                                     "offload_recompiles_post_warmup": 0,
                                     "offload_int8_capacity_vs_f32":
                                         3.2}}}
    r = cbr.compare(rec, slow, 0.2)
    assert [e["metric"] for e in r["regressions"]] == \
        ["offload_restore_ttft_ratio"]
    # ...and the int8 capacity edge eroding regresses the other way
    shrunk = {"value": 100.0,
              "extra": {"generation": {"offload_evicted_reprefills": 0,
                                       "offload_restore_ttft_ratio": 1.4,
                                       "offload_recompiles_post_warmup":
                                           0,
                                       "offload_int8_capacity_vs_f32":
                                           2.0}}}
    r = cbr.compare(rec, shrunk, 0.2)
    assert [e["metric"] for e in r["regressions"]] == \
        ["offload_int8_capacity_vs_f32"]
    # holding the floors passes clean
    assert not cbr.compare(rec, rec, 0.2)["regressions"]

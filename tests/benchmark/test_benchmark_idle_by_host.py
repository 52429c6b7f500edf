"""A traced run of the tiny cell end to end through ``run_cell``, its
profile kept as ``tools/sched_trace_table.py`` keeps it (ISSUE 39): the
two collector metrics are numbers, the host's spans of the run's own
profile split its device-idle gaps so that the labels add up to them,
and no older metric reads anything else for the program's new
``process.gc`` block. The CPU has no device plane, so the run's own
profile is given one on the host's clock: a ``jit_step`` run after each
``gen.decode_dispatch`` ends, half as long as the time to its
``gen.decode_wait``'s end, a ``jit_chunk`` run likewise, one at a
time."""
import shutil

import pytest

import benchmark_testlib as lib
from benchmark import host_trace, run, trace

NEW = ("gc_pause_share", "gc_full_collections")


def device_plane_from_host(path, device_prefix="/device:TPU:"):
    spans = host_trace.load_host_spans(path, prefixes=("gen.",))
    ends = {}
    for s in spans:
        key = s.get("step", s.get("chunk"))
        kind = s["name"].split("_")[0]          # gen.decode / gen.chunk
        ends.setdefault((kind, key), {})[s["name"]] = s
    runs = []
    for (kind, _), ev in ends.items():
        d, w = ev.get(kind + "_dispatch"), ev.get(kind + "_wait")
        if d and w and w["end_s"] > d["end_s"]:
            prog = "jit_step" if kind == "gen.decode" else "jit_chunk"
            runs.append((d["end_s"], w["end_s"], prog))
    mods, last = [], float("-inf")
    for a, b, prog in sorted(runs):     # one at a time, half the span each
        start = max(a, last)
        end = min(b, start + (b - a) / 2)
        if end > start:
            mods.append((prog, start, end - start))
            last = end
    ops = [("fusion/fusion.1", s, d) for _, s, d in mods]
    return [trace.DevicePlane(device_prefix + "0", mods, ops)] if mods else []


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    root = lib.make_root(tmp)
    kept = str(tmp / "kept.xplane.pb")
    stop = run.Context.trace_stop

    def stop_and_keep(ctx):
        stop(ctx)
        shutil.copy(trace.find_xplane(ctx.trace_dir), kept)

    mp = pytest.MonkeyPatch()
    mp.setattr(trace, "load", device_plane_from_host)
    mp.setattr(run.Context, "trace_stop", stop_and_keep)
    try:
        out, obs = run.run_cell("tiny-lm.decode", 2**31 + 39, 1.5, True,
                                require_chip=False, root=root)
    finally:
        mp.undo()
    return root, out, obs, host_trace.load_host_spans(kept)


def test_the_collector_metrics_are_numbers_in_a_traced_run(traced):
    _, out, obs, _ = traced
    assert out["correct"] is True and obs["trace"]
    for name in NEW:
        assert isinstance(out["metrics"][name]["value"], float), name
        assert out["metrics"][name]["value"] >= 0, name
    assert out["metrics"]["gc_full_collections"]["unit"] == "count"
    # run.py reports no host split: that is a benchmark PR's to wire
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_hosts_labels_add_up_to_the_gaps_over_20_us(traced):
    _, out, obs, spans = traced
    full = trace.fullest(obs["trace"])
    clock = host_trace.host_clock(full, spans)
    assert clock["steps"] > 0 and clock["late"] == 0
    rows = host_trace.idle_by_host(full, spans)
    assert rows and len(rows) <= 10
    assert [v for _, v in rows] == sorted((v for _, v in rows), reverse=True)
    by = host_trace.idle_by_host(full, spans, n=None)
    gaps = trace.idle_gaps(full, n=10**6)
    assert sum(v for _, v in by) == pytest.approx(sum(
        v for k, v in gaps if k != "_gaps_under_20_us_between_ops_"), abs=1e-6)
    assert {k for k, _ in by} <= {
        "admit", "chunk_dispatch", "chunk_wait", "decode_dispatch",
        "decode_wait", "emit", "idle", "fault", "other", "gc"}
    busy, win = trace.busy_and_window(obs["trace"])
    share = host_trace.idle_on_host_share(obs["trace"], spans)
    assert 0 <= share <= 100 * (win - busy) / win + 1e-9


def test_every_older_metric_reads_the_same_without_the_gc_block(traced):
    """The parent's program serves no ``process.gc``: the new metrics
    are left out of its line, and every older one reads as before."""
    root, out, obs, _ = traced
    spec, cell, _, _ = run.load_cell("tiny-lm.decode", root)
    here = root + "/benchmark"
    bare = dict(obs, stats={
        side: {k: v for k, v in st.items() if k != "process"}
        for side, st in obs["stats"].items()})
    older = [m["name"] for m in run.cell_metrics(spec, cell, "per_layer")
             if m["name"] not in NEW]
    assert len(older) > 10
    for name in older:
        assert run.read_metric(name, bare, here) == \
            run.read_metric(name, obs, here), name
    for name in NEW:
        assert run.read_metric(name, bare, here) is None, name
    full = trace.fullest(obs["trace"])
    assert out["breakdown"]["idle_gaps"] == [list(r) for r in trace.idle_gaps(full)]
    assert out["breakdown"]["device_ops"] == [list(r) for r in trace.top_ops(full)]

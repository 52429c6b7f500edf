"""The host's spans on the device's clock (``benchmark/host_trace.py``),
on hand-built planes and spans: each device-idle gap split by what the
host was doing, the clock check, and the host's share of the window."""
import pytest

import benchmark_testlib as lib  # noqa: F401  (puts the repo on the path)
from benchmark import host_trace, trace
from test_benchmark_trace import plane


def span(name, a, b, line=1, **stats):
    return {"line": line, "name": name, "start_s": a, "end_s": b, **stats}


def host_spans():
    """The scheduler's thread (line 1) around ``plane()``: each step
    dispatched before its run, an admission and 0.5 ms no phase claims
    in the 5 ms gap between the steps, an emit and a chunk's dispatch
    in the gap before the chunk, 2 ms of which a collection on the
    front-end's thread (line 2) holds."""
    return sorted([
        span("gen.decode_dispatch", -0.0025, -0.0005, step=0),
        span("gen.decode_wait", -0.0005, 0.010, step=0),
        span("gen.emit", 0.010, 0.012, step=0),
        span("gen.admit", 0.012, 0.0135),
        span("gen.decode_dispatch", 0.014, 0.0155, step=1),
        span("gen.decode_wait", 0.0155, 0.025, step=1),
        span("gen.emit", 0.025, 0.027, step=1),
        span("gen.chunk_dispatch", 0.027, 0.0305, chunk=0),
        span("gen.chunk_wait", 0.0305, 0.034, chunk=0),
        span("gen.idle", 0.034, 0.037),
        span("http.stream_write", 0.0255, 0.0256, line=2),
        span("host.gc", 0.026, 0.028, line=2, generation=2),
    ], key=lambda s: s["start_s"])


def gaps_over_20_us(p):
    return sum(s - e for (_, e), (s, _) in zip(trace.busy_intervals(p),
                                               trace.busy_intervals(p)[1:])
               if s - e >= trace.SHORT_GAP_S)


def test_idle_by_host_splits_each_gap_by_what_the_host_was_doing():
    p = plane()
    got = dict(host_trace.idle_by_host(p, host_spans(), n=None))
    want = {"decode_dispatch": 0.0025, "decode_wait": 0.0005,   # one gap, two phases
            "emit": 0.003, "admit": 0.0015, "other": 0.0005,    # no phase open: other
            "gc": 0.002,                                        # before the emit it overlaps
            "chunk_dispatch": 0.002, "idle": 0.002}
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v, abs=1e-12), k
    assert sum(got.values()) == pytest.approx(gaps_over_20_us(p), abs=1e-12)
    assert sum(got.values()) == pytest.approx(sum(
        v for k, v in trace.idle_gaps(p, n=100)
        if k != "_gaps_under_20_us_between_ops_"), abs=1e-12)
    rows = host_trace.idle_by_host(p, host_spans())
    assert [v for _, v in rows] == sorted((v for _, v in rows), reverse=True)
    assert rows[0] == ["emit", pytest.approx(0.003)]


def test_idle_by_host_without_a_scheduler_line_says_so():
    p = plane()
    only_gc = [s for s in host_spans() if not s["name"].startswith("gen.")]
    got = dict(host_trace.idle_by_host(p, only_gc, n=None))
    assert got == {"gc": pytest.approx(0.002),
                   "no_scheduler": pytest.approx(gaps_over_20_us(p) - 0.002)}
    assert dict(host_trace.idle_by_host(p, [], n=None)) == {
        "no_scheduler": pytest.approx(gaps_over_20_us(p))}


def test_the_clock_check_trusts_dispatches_that_lead_their_runs():
    p = plane()
    assert host_trace.dispatch_leads(p, host_spans()) == [
        pytest.approx(0.0025), pytest.approx(0.001)]
    clock = host_trace.host_clock(p, host_spans())
    assert clock["steps"] == 2 and clock["late"] == 0 and clock["shift_s"] == 0


def test_a_host_clock_behind_the_device_is_moved_by_the_median_lead():
    """2 ms behind: the second step's dispatch starts 1 ms after its
    run; the host's spans are moved 1 ms back before they are read."""
    p = plane()
    behind = [dict(s, start_s=s["start_s"] + 0.002, end_s=s["end_s"] + 0.002)
              for s in host_spans()]
    clock = host_trace.host_clock(p, behind)
    assert clock["steps"] == 2 and clock["late"] == 1
    assert clock["lead_min_s"] == pytest.approx(-0.001)
    assert clock["shift_s"] == pytest.approx(-0.001)
    got = dict(host_trace.idle_by_host(p, behind, n=None))
    assert sum(got.values()) == pytest.approx(gaps_over_20_us(p), abs=1e-12)
    moved = [dict(s, start_s=s["start_s"] - 0.001, end_s=s["end_s"] - 0.001)
             for s in behind]
    assert host_trace.host_clock(p, moved)["late"] == 0
    assert got == pytest.approx(dict(host_trace.idle_by_host(p, moved, n=None)))


def test_the_hosts_share_of_the_window_reads_the_hosts_labels():
    p = plane()
    got = host_trace.idle_on_host_share([p], host_spans())
    # decode_dispatch 2.5 + emit 3 + admit 1.5 + other 0.5 + gc 2 +
    # chunk_dispatch 2 ms of a 40 ms window; decode_wait and idle are not
    assert got == pytest.approx(100 * 0.0115 / 0.040)
    assert host_trace.idle_on_host_share([p], []) == 0
    assert host_trace.idle_on_host_share([], host_spans()) is None

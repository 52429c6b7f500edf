"""The ``stats_ratio`` reader on hand-built observations, and the six
per-layer metrics that read the scheduler's time account through it in
a traced run of the tiny decode cell (CPU: counts and identities)."""
import pytest

import benchmark_testlib as lib
from benchmark import run
from benchmark.readers import stats_ratio

NEW = ("sched_cycle_ms", "sched_device_wait_share", "sched_host_ms_per_step",
       "admit_blocked_on_pool_share", "kv_pool_live_share",
       "http_emit_to_write_ms")


def _obs(open_, close):
    return {"stats": {"open": open_, "close": close}}


OPEN = {"scheduler": {"loop_s": 10.0, "phase_s": {"idle": 1.0, "emit": 0.5},
                      "phase_n": {"decode_dispatch": 20},
                      "kv_live_token_steps": 1000},
        "paged": {"blocks_total": 10, "block_size": 8}}
CLOSE = {"scheduler": {"loop_s": 16.0, "phase_s": {"idle": 2.0, "emit": 1.5},
                       "phase_n": {"decode_dispatch": 30},
                       "kv_live_token_steps": 1400},
         "paged": {"blocks_total": 10, "block_size": 8}}


def test_delta_over_delta_with_parts_taken_off_and_a_scale():
    got = stats_ratio.read(
        _obs(OPEN, CLOSE), num=["scheduler.loop_s"],
        num_less=["scheduler.phase_s.idle"],
        den=["scheduler.phase_n.decode_dispatch"], scale=1000)
    assert got == pytest.approx((6.0 - 1.0) / 10 * 1000)
    share = stats_ratio.read(
        _obs(OPEN, CLOSE), num=["scheduler.phase_s.emit"],
        den=["scheduler.loop_s"], den_less=["scheduler.phase_s.idle"],
        scale=100)
    assert share == pytest.approx(1.0 / 5.0 * 100)


def test_several_paths_are_summed_and_values_at_the_close_multiply():
    two = stats_ratio.read(
        _obs(OPEN, CLOSE),
        num=["scheduler.phase_s.emit", "scheduler.phase_s.idle"],
        den=["scheduler.phase_n.decode_dispatch"])
    assert two == pytest.approx((1.0 + 1.0) / 10)
    live = stats_ratio.read(
        _obs(OPEN, CLOSE), num=["scheduler.kv_live_token_steps"],
        den=["scheduler.phase_n.decode_dispatch"],
        den_times_close=["paged.blocks_total", "paged.block_size"],
        scale=100)
    assert live == pytest.approx(400 / (10 * 10 * 8) * 100)


@pytest.mark.parametrize("args", [
    dict(num=["scheduler.no_such"], den=["scheduler.loop_s"]),
    dict(num=["scheduler.loop_s"], den=["no_block.at_all"]),
    dict(num=["scheduler.loop_s"], num_less=["scheduler.phase_s.absent"],
         den=["scheduler.loop_s"]),
    dict(num=["scheduler.loop_s"], den=["scheduler.loop_s"],
         den_times_close=["paged.absent"])],
    ids=["numerator", "denominator", "part_taken_off", "value_at_close"])
def test_a_path_the_program_does_not_serve_gives_none(args):
    """The parent commit has no ``scheduler`` block: the metric is left
    out of its line, nothing raises."""
    assert stats_ratio.read(_obs(OPEN, CLOSE), **args) is None
    assert stats_ratio.read(_obs({}, {}), **args) is None
    assert stats_ratio.read({}, **args) is None


def test_a_zero_denominator_gives_none():
    same = _obs(CLOSE, CLOSE)       # nothing happened inside the window
    assert stats_ratio.read(same, num=["scheduler.loop_s"],
                            den=["scheduler.phase_n.decode_dispatch"]) is None
    assert stats_ratio.read(
        _obs(OPEN, CLOSE), num=["scheduler.loop_s"],
        den=["scheduler.loop_s"], den_less=["scheduler.loop_s"]) is None


@pytest.fixture(scope="module")
def traced_decode(tmp_path_factory):
    root = lib.make_root(tmp_path_factory.mktemp("bench"))
    return run.run_cell("tiny-lm.decode", 5, 1.5, True, require_chip=False,
                        root=root)


@pytest.mark.parametrize("name", NEW)
def test_traced_decode_run_reports_the_metric(traced_decode, name):
    out, _ = traced_decode
    assert name in out["metrics"], sorted(out["metrics"])
    v = out["metrics"][name]["value"]
    assert v == v and v >= 0
    if out["metrics"][name]["unit"] == "%":
        assert v <= 100.0 + 1e-9


def test_the_account_closes_and_agrees_with_the_engines_own_counts(
        traced_decode):
    out, obs = traced_decode
    a, b = (obs["stats"][k] for k in ("open", "close"))
    for s in (a, b):
        sc = s["scheduler"]
        assert sum(sc["phase_s"].values()) == pytest.approx(sc["loop_s"],
                                                            abs=1e-6)
    steps = b["scheduler"]["phase_n"]["decode_dispatch"] \
        - a["scheduler"]["phase_n"]["decode_dispatch"]
    assert abs(steps - (b["decode_steps"] - a["decode_steps"])) <= 1
    m = {k: v["value"] for k, v in out["metrics"].items()}
    busy_s = m["sched_cycle_ms"] * steps / 1e3
    assert busy_s <= b["scheduler"]["loop_s"] - a["scheduler"]["loop_s"] + 1e-9
    # waiting and the host's own work are parts of one cycle
    assert m["sched_host_ms_per_step"] \
        + m["sched_device_wait_share"] / 100 * m["sched_cycle_ms"] \
        <= m["sched_cycle_ms"] * (1 + 1e-9)
    # every token a client read went through the front-end's write
    chunks = b["stream"]["chunks"] - a["stream"]["chunks"]
    read = sum(len(r["token_times"]) for r in obs["requests"])
    assert obs["window"]["work"]["tokens"] // 2 < chunks <= read

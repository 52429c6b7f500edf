"""``paged_blocks_attended_share`` (ISSUE 27): the metric file reads the
scheduler's two block counters through the ``stats_ratio`` reader the
benchmark has; a program without them (the parent commit) leaves the
metric out of its line."""
import pytest

import benchmark_testlib as lib
from benchmark import run

NAME = "paged_blocks_attended_share"


def _obs(open_, close):
    return {"stats": {"open": {"scheduler": open_},
                      "close": {"scheduler": close}}}


def test_metric_file_is_a_ratio_of_the_two_counters_deltas():
    obs = _obs({"kv_blocks_attended": 100, "kv_blocks_spanned": 1024},
               {"kv_blocks_attended": 340, "kv_blocks_spanned": 2048})
    assert run.read_metric(NAME, obs) == pytest.approx(240 / 1024 * 100)


@pytest.mark.parametrize("open_,close", [
    ({}, {}),
    ({"kv_live_token_steps": 1}, {"kv_live_token_steps": 2}),
    ({"kv_blocks_attended": 5, "kv_blocks_spanned": 64},
     {"kv_blocks_attended": 5, "kv_blocks_spanned": 64})],
    ids=["no_account", "parent_commit", "no_step_in_the_window"])
def test_nothing_to_read_leaves_the_metric_out(open_, close):
    assert run.read_metric(NAME, _obs(open_, close)) is None


def test_benchmark_json_declares_it_for_the_decode_cell():
    (m,) = [m for m in run.load_spec()["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "lower",
                 "source": "program_counter", "layer": "kernels",
                 "moves": "itl_ms_p95",
                 "workloads": ["gpt2-xl.decode_backlog"]}


def test_traced_tiny_decode_run_reports_it_from_the_counters(
        tmp_path_factory):
    root = lib.make_root(tmp_path_factory.mktemp("bench"))
    out, obs = run.run_cell("tiny-lm.decode", 11, 1.0, True,
                            require_chip=False, root=root)
    a, b = (obs["stats"][k]["scheduler"] for k in ("open", "close"))
    steps = b["phase_n"]["decode_dispatch"] - a["phase_n"]["decode_dispatch"]
    spanned = b["kv_blocks_spanned"] - a["kv_blocks_spanned"]
    attended = b["kv_blocks_attended"] - a["kv_blocks_attended"]
    assert steps > 0 and spanned == steps * 4 * (64 // 8)   # slots x table
    assert steps <= attended < spanned      # a lane attends a block or more
    assert out["metrics"][NAME] == {"value": pytest.approx(
        100.0 * attended / spanned), "unit": "%"}

"""BENCHMARK.json and every data file under benchmark/: they load, use
only permitted names and units, agree with one another, and a new
cell, configuration, traffic mix and metric are found as new files."""
import glob
import importlib
import os
import re

import pytest

import benchmark_testlib as lib
from benchmark import run

SPEC = lib.load(lib.REPO, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DATA = sorted(glob.glob(os.path.join(lib.BENCH, "*", "*.json")))


@pytest.mark.parametrize("path", DATA, ids=[os.path.relpath(p, lib.BENCH)
                                            for p in DATA])
def test_data_file_loads_and_is_named_from_permitted_characters(path):
    assert isinstance(lib.load(path), dict)
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", os.path.relpath(path, lib.REPO))


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(lib.REPO, p))


def test_every_name_and_unit_uses_permitted_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group[:3], e["name"]))
    assert len(set(names)) == len(names)
    for c in SPEC["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


def test_cells_configs_and_bounds():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    assert {c["config"] for c in SPEC["workloads"]} == set(cfgs)
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [c for c in SPEC["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)
    for c in cfgs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        f = lib.load(lib.REPO, c["file"])
        assert f["reduced"] == c["reduced"] == []
        assert f["assumed"] and f["source"] and f["bytes"] and f["precision"]
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    for cell in SPEC["workloads"]:
        e2e = [m["name"] for m in run.cell_metrics(SPEC, cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert run.cell_metrics(SPEC, cell, "per_layer"), cell["name"]


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_lists_cells_that_report_what_it_moves(metric):
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = {m["name"]: m for m in SPEC["end_to_end"]}[metric["moves"]]
    cells = {c["name"] for c in SPEC["workloads"]}
    assert metric["workloads"] and set(metric["workloads"]) <= cells
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


METRIC_FILES = sorted(glob.glob(os.path.join(lib.BENCH, "metrics", "*.json")))


@pytest.mark.parametrize("path", METRIC_FILES,
                         ids=[os.path.basename(p)[:-5] for p in METRIC_FILES])
def test_metric_file_names_a_reader_and_nothing_benchmark_json_holds(path):
    """What BENCHMARK.json says of a metric (unit, layer, moves, the
    cells) is not repeated in its file: a later PR that adds a cell to
    a metric's list edits no file of the benchmark."""
    mf = lib.load(path)
    assert set(mf) == {"name", "what", "reader", "args"}
    assert mf["name"] == os.path.basename(path)[:-5] and NAME.match(mf["name"])
    reader = importlib.import_module("benchmark.readers." + mf["reader"])
    assert callable(reader.read)


def test_every_metric_of_benchmark_json_has_a_file():
    files = {os.path.basename(p)[:-5] for p in METRIC_FILES}
    assert {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]} <= files


def test_stored_lengths_do_not_depend_on_the_seed():
    from benchmark.kinds import serve_closed_loop as k
    for cell in SPEC["workloads"]:
        t = run.load_traffic(cell)
        if t["kind"] != "serve_closed_loop":
            continue
        assert len(t["lengths"]) == 64
        cfg = run.load_config(SPEC, cell)
        top = cfg["engine"]["max_seq_len"]
        assert all(p + m <= top for p, m in t["lengths"])
        assert [k.request_lengths(t, i) for i in range(64, 128)] == \
            [tuple(x) for x in t["lengths"]]
        a = k.prompt_ids(1, 5, 40, 50257)
        assert a == k.prompt_ids(1, 5, 40, 50257) != k.prompt_ids(2, 5, 40, 50257)
        assert k.prompt_ids(2**31 + 7, 0, 8, 50257)      # seeds past int32


def test_new_cell_config_traffic_and_metric_are_found_as_new_files(tmp_path):
    """A later PR adds files and BENCHMARK.json entries only."""
    root = lib.make_root(tmp_path)
    spec = run.load_spec(root)
    here = os.path.join(root, "benchmark")
    cfg = lib.load(here, "configs", "tiny-lm.json")
    cfg["engine"]["num_slots"] = 2
    lib.dump(cfg, here, "configs", "new-lm.json")
    tr = lib.load(here, "traffic", "tiny_score.json")
    tr["clients"] = 3
    lib.dump(tr, here, "traffic", "new_mix.json")
    lib.dump({"name": "ttft_ms_p99", "what": "t", "reader": "client_stamps",
              "args": {"what": "ttft", "percentile": 99}},
             here, "metrics", "ttft_ms_p99.json")
    spec["configs"].append({"name": "new-lm", "source": "test", "reduced": [],
                            "file": "benchmark/configs/new-lm.json", "why": "t"})
    cell = {"name": "new-lm.new_mix", "config": "new-lm",
            "traffic": "new_mix", "chips": 1, "why": "t"}
    spec["workloads"].append(cell)
    spec["per_layer"].append({"name": "ttft_ms_p99", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "scheduler and cache manager",
                              "moves": "ttft_ms_p90",
                              "workloads": ["new-lm.new_mix"]})
    for m in spec["end_to_end"]:
        if m["name"] == "ttft_ms_p90":
            m["workloads"].append("new-lm.new_mix")
    lib.dump(spec, root, "BENCHMARK.json")
    spec = run.load_spec(root)
    found = run.find_cell(spec, "new-lm.new_mix")
    assert run.load_config(spec, found, root)["engine"]["num_slots"] == 2
    assert run.load_traffic(found, here)["clients"] == 3
    assert [m["name"] for m in run.cell_metrics(spec, found, "per_layer")] \
        == ["ttft_ms_p99"]
    obs = {"window": {"span": (0.0, 10.0)},
           "requests": [{"t_send": 1.0, "token_times": [1.5]},
                        {"t_send": 2.0, "token_times": [2.25]}]}
    assert run.read_metric("ttft_ms_p99", obs, here) == pytest.approx(497.5)

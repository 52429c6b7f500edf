"""Shared by the benchmark's CPU tests: a root directory that holds
tiny data files (a 2-layer LM under two traffic mixes) beside copies
of the real metric files, so that ``run_cell(root=...)`` drives the
runner end to end through the real code."""
import copy
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
BENCH = os.path.join(REPO, "benchmark")

TINY_LM = {"vocab_size": 211, "max_seq_len": 64, "d_model": 32,
           "n_layers": 2, "n_heads": 4, "d_ff": 64}
CELLS = {"tiny-lm.decode": ("tiny-lm", "tiny_decode", 1),
         "tiny-lm.score": ("tiny-lm", "tiny_score", 1)}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


# A second mix of the same kind, one token back after a long prompt,
# has no cell in BENCHMARK.json (PERF.md, Open questions, row 1). The
# tiny one brings its metrics as a later PR would: new metric files,
# new BENCHMARK.json entries, no edit to a file that is there.
SCORE_CELL = "tiny-lm.score"
SCORE_END_TO_END = [
    {"name": "ttft_ms_p90", "unit": "ms", "better": "lower", "bound": 0.01,
     "source": "host_clock", "workloads": [SCORE_CELL]}]
SCORE_PER_LAYER = [
    {"name": n, "unit": u, "better": b, "source": s, "layer": layer,
     "moves": "ttft_ms_p90", "workloads": [SCORE_CELL]}
    for n, u, b, s, layer in (
        ("ttft_ms_p50", "ms", "lower", "host_clock",
         "scheduler and cache manager"),
        ("prefill_chunk_device_ms", "ms", "lower", "device_trace",
         "model forwards"),
        ("prefill_mfu", "%", "higher", "host_clock", "model forwards"),
        ("device_idle_share.prefill", "%", "lower", "device_trace",
         "device"))]
SCORE_METRIC_FILES = {
    "ttft_ms_p90": ("client_stamps", {"what": "ttft", "percentile": 90}),
    "ttft_ms_p50": ("client_stamps", {"what": "ttft", "percentile": 50}),
    "prefill_chunk_device_ms": ("trace_program_time",
                                {"program": "^jit_chunk$"}),
    "prefill_mfu": ("mfu", {"flops": "prefill_flops", "per": "window"}),
    "device_idle_share.prefill": ("idle_share", {})}


def make_root(tmp) -> str:
    """``tmp``/BENCHMARK.json + benchmark/{configs,traffic,metrics}:
    new files only, found by name; no file of the repo is edited."""
    root = str(tmp)
    b = os.path.join(root, "benchmark")
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(b, d))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(b, "metrics"))
    for name, (reader, args) in SCORE_METRIC_FILES.items():
        dump({"name": name, "what": "tiny", "reader": reader, "args": args},
             b, "metrics", name + ".json")
    spec = load(REPO, "BENCHMARK.json")
    stands_for = {"tiny-lm.decode": "gpt2-xl.decode_backlog"}
    spec["configs"] = [
        {"name": "tiny-lm", "source": "test", "reduced": [], "why": "tiny",
         "file": "benchmark/configs/tiny-lm.json"}]
    spec["workloads"] = [
        {"name": name, "config": c, "traffic": t, "chips": chips,
         "why": "tiny"} for name, (c, t, chips) in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = [t for t, r in stands_for.items()
                                  if r in m["workloads"]]
    spec["end_to_end"] += copy.deepcopy(SCORE_END_TO_END)
    spec["per_layer"] += copy.deepcopy(SCORE_PER_LAYER)
    dump(spec, root, "BENCHMARK.json")

    lm = load(BENCH, "configs", "gpt2-xl.json")
    lm["model"] = TINY_LM
    lm["engine"].update(num_slots=4, max_seq_len=64, prompt_buckets=[16],
                        block_size=8, num_blocks=33,
                        prefill_chunk_tokens=16)
    lm["warmup"] = {"buckets": [16]}
    dump(lm, b, "configs", "tiny-lm.json")
    t = load(BENCH, "traffic", "decode_backlog.json")
    t.update(clients=8, lengths=[[9, 6], [20, 8], [5, 4], [30, 10], [12, 5]],
             lead_in={"finished_requests": 1, "tokens": 16, "give_up_s": 120},
             check_requests=TINY_CHECK_REQUESTS, trace_seconds=0.5)
    assert set(t["limits"]) == {COMPARED}   # the real cell's number and limit
    dump(t, b, "traffic", "tiny_decode.json")
    t.update(clients=2, lengths=[[40, 1], [55, 1], [33, 1]],
             lead_in={"finished_requests": 2, "tokens": 2, "give_up_s": 120},
             wait_first_tokens=True)
    dump(t, b, "traffic", "tiny_score.json")
    return root


TINY_CHECK_REQUESTS = 4
COMPARED = "served_gap_over_control"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

"""The third configuration's counts against hand-worked numbers at a
tiny shape (``benchmark/counts/smallthinker.py``), and the data files of
its cell: new files only."""
import json
import os

import pytest

import benchmark_testlib as lib
from benchmark import run

COUNTS = run.load_module(lib.REPO, "counts", "smallthinker")
CELL = "smallthinker-21b-a3b.long_context_backlog"

# hidden 8, 4 query heads of 2 over 2 KV heads, layers global, window x 3
# (window 4), 4 experts of width 3, 2 a token, vocabulary 10
M = dict(hidden_size=8, head_dim=2, num_attention_heads=4,
         num_key_value_heads=2, num_hidden_layers=4,
         sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
         sliding_window_size=4, moe_ffn_hidden_size=3,
         moe_num_primary_experts=4, moe_num_active_primary_experts=2,
         vocab_size=10, dtype="bfloat16")


def test_matmul_params_by_hand():
    # a layer: Wq, Wo 2 x 8 x 8; Wk, Wv 2 x 8 x 4; router 8 x 4; two
    # experts of 3 x 8 x 3
    assert COUNTS.expert_params(M) == 72
    assert COUNTS.token_matmul_params(M, head=False) == 4 * (
        128 + 64 + 32 + 144)
    assert COUNTS.token_matmul_params(M) == 1472 + 80


def test_published_widths_give_the_issues_numbers():
    real = lib.load(lib.BENCH, "configs", "smallthinker-21b-a3b.json")
    m = real["model"]
    assert COUNTS.expert_params(m) == 5_898_240
    assert COUNTS.token_matmul_params(m, head=False) == 12 * 56_524_800
    assert COUNTS.token_matmul_params(m) - COUNTS.token_matmul_params(
        m, head=False) == 388_956_160


@pytest.mark.parametrize("context,glob,win", [(3, 3, 3), (4, 4, 4),
                                              (10, 10, 4)])
def test_a_decode_token_attends_its_length_or_its_window(context, glob, win):
    assert COUNTS.keys_read(M, context) == (glob, win)
    assert COUNTS.attention_flops(M, context) == 4 * 8 * (glob + 3 * win)
    assert COUNTS.decode_token_flops(M, context) == 2 * 1552 + 32 * (
        glob + 3 * win)


def test_prefill_sums_the_triangle_and_the_band():
    # 6 rows: 1 + .. + 6 = 21 keys in the global layer; 1 + 2 + 3 + 4 +
    # 4 + 4 = 18 in each window layer; the head once
    assert COUNTS.prefill_prompt_flops(M, 6) == (
        2 * 1472 * 6 + 32 * (21 + 3 * 18) + 2 * 8 * 10)


def test_a_chunks_attention_by_layer_kind():
    # rows 4..7: global 5 + 6 + 7 + 8 = 26 keys; window 4 each = 16;
    # K and V rows read once: 8 (global), min(8, 4 + 4 - 1) = 7 (window)
    flops, nbytes = COUNTS.chunk_attention_cost(M, 4, 4, 2)
    assert flops == 32 * (26 + 3 * 16)
    assert nbytes == 2 * 4 * 2 * (8 + 3 * 7) + 4 * 2 * 8 * 4 * 4
    assert COUNTS.chunk_plan(10, 4) == [(0, 4), (4, 4), (8, 2)]


def _obs():
    return {"config": {"model": M, "kv_bytes_per_element": 2,
                       "engine": {"prefill_chunk_tokens": 4}},
            "requests": [{"prompt_len": 5, "token_times": [0.5, 1.5, 2.5]},
                         {"prompt_len": 9, "token_times": [1.2]}],
            "stats": {"open": {"paged": {"prefill_chunks": 10}},
                      "close": {"paged": {"prefill_chunks": 16}}}}


def test_decode_attention_cost_by_group_over_a_span():
    # tokens 1 and 2 of the first request arrive in (1, 3]: 6 and 7 keys
    # of sequence; a window layer reads 4 of each
    obs, span = _obs(), (1.0, 3.0)
    assert COUNTS.decode_steps_global_attention_cost(obs, span) == (
        32.0 * 13, 2 * 4 * 2 * 13 + 2 * 8 * 4 * 2)
    assert COUNTS.decode_steps_window_attention_cost(obs, span) == (
        3 * 32.0 * 8, 3 * (2 * 4 * 2 * 8 + 2 * 8 * 4 * 2))
    assert COUNTS.decode_tokens_flops(obs, span) == 2 * 3104 + 32 * (
        6 + 12 + 7 + 12)


def test_the_windows_chunks_cost_the_mean_chunk_times_the_counter():
    # the second request's first token arrives in the span: its 9 rows
    # are chunks (0, 4), (4, 4), (8, 1); the program counted 6 chunks
    obs = _obs()
    plan = [COUNTS.chunk_attention_cost(M, p0, rows, 2)
            for p0, rows in ((0, 4), (4, 4), (8, 1))]
    fl, by = COUNTS.prefill_chunks_attention_cost(obs, (1.0, 3.0))
    assert fl == sum(c[0] for c in plan) * 6 / 3
    assert by == sum(c[1] for c in plan) * 6 / 3
    assert COUNTS.prefill_flops(obs, (1.0, 3.0)) == \
        COUNTS.prefill_prompt_flops(M, 9)


def test_the_cell_and_its_traffic_are_what_the_issue_gives():
    spec = lib.load(lib.REPO, "BENCHMARK.json")
    (cell,) = [c for c in spec["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "long_context_backlog", 1)
    t = lib.load(lib.BENCH, "traffic", "long_context_backlog.json")
    assert t["kind"] == "serve_closed_loop" and t["clients"] == 32
    assert len(t["lengths"]) == 64 and t["check_requests"] == 8
    prompts = [p for p, _ in t["lengths"]]
    new = [n for _, n in t["lengths"]]
    cfg = lib.load(lib.BENCH, "configs", "smallthinker-21b-a3b.json")
    assert min(prompts) >= 4608 > cfg["sliding_window_size"]
    assert max(prompts) <= 14336 and 160 <= min(new) and max(new) <= 640
    assert max(p + n for p, n in t["lengths"]) < cfg["engine"]["max_seq_len"]
    for k in range(0, 64, 16):      # any 16 in a row hold about the mean
        assert abs(sum(prompts[k:k + 16]) / 16 - sum(prompts) / 64) < 100
        assert abs(sum(new[k:k + 16]) / 16 - sum(new) / 64) < 5


def test_the_configuration_keeps_every_published_width():
    cfg = lib.load(lib.BENCH, "configs", "smallthinker-21b-a3b.json")
    pub = cfg["published"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "rope_layout", "sliding_window_layout"}
    assert cfg["sliding_window_layout"] == pub["sliding_window_layout"][:12]
    assert cfg["rope_layout"] == pub["rope_layout"][:12]
    for k, v in cfg["model"].items():
        if k != "dtype":
            assert v == cfg[k], k
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(ln) for ln in f]
    (row,) = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    assert pub == row["config"] and cfg["source"] == row["source_url"]

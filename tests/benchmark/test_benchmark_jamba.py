"""The fourth configuration's counts against hand-worked numbers at a
tiny shape (``benchmark/counts/jamba.py``), and the data files of its
cell: new files only."""
import json
import os

import pytest

import benchmark_testlib as lib
from benchmark import run

COUNTS = run.load_module(lib.REPO, "counts", "jamba")
CELL = "jamba2-3b.long_context_backlog"
NEW_METRICS = ("ssm_scan_roofline", "ssm_scan_share_of_chunk")

# hidden 8, 4 query heads of 2 over 1 KV head, layers Mamba, attention,
# Mamba (period 2, offset 1), MLP of 6, 16 channels of 4 states, dt rank
# 2, vocabulary 10
M = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=1,
         num_hidden_layers=3, attn_layer_period=2, attn_layer_offset=1,
         intermediate_size=6, mamba_expand=2, mamba_d_state=4,
         mamba_dt_rank=2, mamba_d_conv=4, vocab_size=10, dtype="bfloat16")


def test_matmul_params_by_hand():
    # a Mamba layer: W_in 8 x 32, W_x 16 x (2 + 8), W_dt 2 x 16, W_out
    # 16 x 8; an attention layer: Wq, Wo 8 x 8, Wk, Wv 8 x 2; an MLP
    # 3 x 8 x 6 in every layer
    assert COUNTS.mamba_matmul_params(M) == 256 + 160 + 32 + 128
    assert COUNTS.token_matmul_params(M, head=False) == (
        2 * 576 + (128 + 32) + 3 * 144)
    assert COUNTS.token_matmul_params(M) == 1744 + 80
    # a row of one Mamba layer: 16 channels x (7 x 4 states + 3)
    assert COUNTS.scan_row_flops(M) == 16 * 31


def test_published_widths_give_the_issues_numbers():
    m = lib.load(lib.BENCH, "configs", "jamba2-3b.json")["model"]
    assert COUNTS._kinds(m) == (26, 2)
    assert COUNTS.mamba_matmul_params(m) == 41_123_840
    assert COUNTS.token_matmul_params(m, head=False) == 2_858_352_640
    assert COUNTS.token_matmul_params(m) - COUNTS.token_matmul_params(
        m, head=False) == 167_772_160
    assert COUNTS.scan_row_flops(m) == 5120 * 115
    # 4 x 20 x 128 x context over the two attention layers
    assert COUNTS.attention_flops(m, 1000) == 2 * 4 * 20 * 128 * 1000


def test_a_token_and_a_prompt_by_hand():
    # a decode token at 10 keys: matmuls, the head, attention in one
    # layer (4 x 8 x 10), the scan in two
    assert COUNTS.decode_token_flops(M, 10) == 2 * 1824 + 320 + 2 * 496
    # 6 rows: 21 keys of triangle; the head once
    assert COUNTS.prefill_prompt_flops(M, 6) == (
        (2 * 1744 + 2 * 496) * 6 + 32 * 21 + 2 * 8 * 10)


def test_a_chunks_attention_reads_the_prefix_once():
    # rows 4..7 of one attention layer: 5 + 6 + 7 + 8 = 26 keys; K and V
    # rows 0..7 of the one KV head (2 lanes each), q in and out float32
    flops, nbytes = COUNTS.chunk_attention_cost(M, 4, 4, 2)
    assert flops == 32 * 26
    assert nbytes == 2 * 2 * 2 * 8 + 2 * 8 * 4 * 4
    assert COUNTS.chunk_plan(10, 4) == [(0, 4), (4, 4), (8, 2)]


def _obs():
    return {"config": {"model": M, "kv_bytes_per_element": 2,
                       "engine": {"prefill_chunk_tokens": 4}},
            "requests": [{"prompt_len": 5, "token_times": [0.5, 1.5, 2.5]},
                         {"prompt_len": 9, "token_times": [1.2]}],
            "stats": {"open": {"paged": {"prefill_chunks": 10},
                               "decode_steps": 100,
                               "ssm": {"chunk_rows": 40, "decode_rows": 60}},
                      "close": {"paged": {"prefill_chunks": 16},
                                "decode_steps": 103,
                                "ssm": {"chunk_rows": 58,
                                        "decode_rows": 70}}}}


def test_decode_costs_over_a_span():
    # tokens 1 and 2 of the first request arrive in (1, 3]: 6 and 7 keys
    obs, span = _obs(), (1.0, 3.0)
    assert COUNTS.decode_steps_attention_cost(obs, span) == (
        32.0 * 13, 2 * 2 * 2 * 13 + 2 * 8 * 4 * 2)
    assert COUNTS.decode_tokens_flops(obs, span) == (
        2 * (2 * 1824 + 2 * 496) + 32 * 13)


def test_the_windows_chunks_cost_what_the_program_counted():
    # 18 live row-layers in 6 chunks of 2 Mamba layers: four float32
    # rows of 16 channels and B, C a row; the state in and out, A and D
    # a call
    obs = _obs()
    fl, by = COUNTS.prefill_chunks_scan_cost(obs, (1.0, 3.0))
    assert fl == 18 * 496
    assert by == 18 * (4 * 16 + 2 * 4) * 4 + 12 * (2 * 64 + 64 + 16) * 4
    # attention: the second request's 9 rows are chunks (0, 4), (4, 4),
    # (8, 1); the program counted 6 chunks
    plan = [COUNTS.chunk_attention_cost(M, p0, rows, 2)
            for p0, rows in ((0, 4), (4, 4), (8, 1))]
    fl, by = COUNTS.prefill_chunks_attention_cost(obs, (1.0, 3.0))
    assert fl == sum(c[0] for c in plan) * 6 / 3
    assert by == sum(c[1] for c in plan) * 6 / 3
    assert COUNTS.prefill_flops(obs, (1.0, 3.0)) == \
        COUNTS.prefill_prompt_flops(M, 9)


def test_a_program_without_the_counters_gives_nothing_to_read():
    """The parent's ``/stats`` has no ``ssm`` block: the costs are zero
    and the roofline readers leave their metrics out."""
    obs = _obs()
    for side in ("open", "close"):
        del obs["stats"][side]["ssm"]
    assert COUNTS.prefill_chunks_scan_cost(obs, (1.0, 3.0)) == (0.0, 0.0)
    for name in NEW_METRICS:
        assert run.read_metric(name, obs) is None


def test_the_scan_roofline_reads_the_kernel_by_name():
    """The real metric files' readers over a hand-built device plane:
    the chunk kernel inside ``jit_chunk`` against this module's cost."""
    from benchmark import trace
    obs = dict(_obs(), counts=COUNTS, window={"span": (1.0, 3.0)},
               peaks={"flops_per_s": 1e9, "bytes_per_s": 1e6})
    runs = [("jit_chunk", 0.1 * i, 0.05) for i in range(1, 4)]
    # XLA fuses the call with the state's write: either opcode is read
    ops = [(kind + "/selective_scan_chunk.3", s + 0.001, 0.01)
           for kind, (_, s, _) in zip(("custom-call", "fusion", "fusion"),
                                      runs)]
    obs["trace"] = [trace.DevicePlane("/device:TPU:0", runs, ops)]
    fl, by = COUNTS.prefill_chunks_scan_cost(obs, (1.0, 3.0))
    least = max(fl / 6 / 1e9, by / 6 / 1e6)
    assert run.read_metric("ssm_scan_roofline", obs) == pytest.approx(
        100 * least / 0.01)
    assert run.read_metric("ssm_scan_share_of_chunk", obs) == pytest.approx(
        100 * 0.01 / 0.05)
    assert obs["roofline_binds"] == {
        "^(custom-call|fusion)/selective_scan_chunk": "memory"}


def test_the_cell_and_its_traffic_are_what_the_issue_gives():
    spec = lib.load(lib.REPO, "BENCHMARK.json")
    (cell,) = [c for c in spec["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b", "long_context_backlog", 1)
    assert spec["workloads"][-1] == cell and len(cell["why"]) <= 200
    (entry,) = [c for c in spec["configs"] if c["name"] == "jamba2-3b"]
    lib.check_configuration(lib.REPO, spec, entry)
    cfg = lib.load(lib.BENCH, "configs", "jamba2-3b.json")
    t = lib.load(lib.BENCH, "traffic", "long_context_backlog.json")
    assert max(p + n for p, n in t["lengths"]) < cfg["engine"]["max_seq_len"]
    # the pool holds every slot at the engine's longest sequence
    e = cfg["engine"]
    assert (e["num_blocks"] - 1) * e["block_size"] == \
        e["num_slots"] * e["max_seq_len"]
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in spec[g] if CELL in m.get("workloads", [])}
    assert {"gen_tokens_per_s", "itl_ms_p95", "decode_step_mfu",
            "paged_attn_gqa_roofline", "prefill_attn_roofline",
            "prefill_chunk_device_ms.long_context", "kv_pool_live_share",
            "kv_blocks_peak_share"} <= reported
    assert set(NEW_METRICS) <= reported
    assert len([n for n in reported if "mfu" in n]) == 1
    for name in NEW_METRICS:
        (m,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["unit"] == "%" and m["source"] == "device_trace"


def test_the_configuration_is_the_published_one_uncut():
    cfg = lib.load(lib.BENCH, "configs", "jamba2-3b.json")
    pub = cfg["published"]
    assert cfg["reduced"] == [] and "deployment" not in cfg
    for k, v in pub.items():
        assert cfg[k] == v and cfg["model"][k] == v, k
    assert set(cfg["model"]) == set(pub) | {"dtype"}
    assert cfg["model"]["dtype"] == "bfloat16"
    assert cfg["num_hidden_layers"] == 28 and cfg["mamba_d_state"] == 16
    for key in ("assumed", "bytes", "precision", "departures"):
        assert cfg[key], key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(ln) for ln in f]
    (row,) = [r for r in rows if r["name"] == "AI21-Jamba2-3B"]
    assert pub == row["config"] and cfg["source"] == row["source_url"]


def test_the_bytes_the_file_states_follow_from_its_shapes():
    """Parameters by kind of layer, KV a token and state a slot, from
    the ``model`` block alone."""
    m = lib.load(lib.BENCH, "configs", "jamba2-3b.json")["model"]
    d, f = m["hidden_size"], m["intermediate_size"]
    di, n, r = 2 * d, m["mamba_d_state"], m["mamba_dt_rank"]
    mixer = (COUNTS.mamba_matmul_params(m) + di            # b_dt
             + di * n + di * m["mamba_d_conv"] + di        # A_log, conv
             + di + r + 2 * n)                             # D, inner norms
    assert mixer == 41_241_792
    mamba = mixer + 3 * d * f + 2 * d
    attn = 2 * d * d + 2 * d * 128 + 3 * d * f + 2 * d
    assert (mamba, attn) == (104_161_472, 76_682_240)
    assert 26 * mamba + 2 * attn + m["vocab_size"] * d + d == 3_029_337_472
    assert 2 * 1 * 2 * 128 * 2 == 1024                     # KV B a token
    assert 26 * (di * n * 4 + di * 3 * 2) == 9_318_400     # state a slot

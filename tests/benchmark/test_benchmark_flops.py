"""flops.py and the roofline byte counts against hand-worked numbers."""
import pytest

import benchmark_testlib as lib
from benchmark import flops

XL = lib.load(lib.BENCH, "configs", "gpt2-xl.json")["model"]


def test_gpt2_xl_matmul_params_by_hand():
    per_layer = 4 * 1600 * 1600 + 2 * 1600 * 6400
    assert per_layer == 30_720_000
    assert flops.lm_matmul_params(XL) == 48 * per_layer + 1600 * 50257


@pytest.mark.parametrize("context", [1, 300, 1024])
def test_gpt2_xl_flops_a_decode_token(context):
    by_hand = 2 * 1_554_971_200 + 48 * 4 * 1600 * context
    assert flops.lm_decode_token_flops(XL, context) == by_hand


def test_gpt2_xl_prefill_flops_sum_the_causal_triangle():
    p = 640
    body = 2 * 48 * 30_720_000 * p
    attn = 48 * 4 * 1600 * (p * (p + 1) // 2)
    head = 2 * 1600 * 50257
    assert flops.lm_prefill_flops(XL, p) == body + attn + head
    # the same as summing token by token, head counted once
    by_token = sum(flops.lm_decode_token_flops(XL, i + 1)
                   - 2 * 1600 * 50257 for i in range(p)) + head
    assert flops.lm_prefill_flops(XL, p) == by_token


def test_paged_attention_bytes_and_flops_at_given_lengths():
    lengths = [100, 17, 640]
    fl, by = flops.paged_attention_cost(XL, 4, lengths)
    assert fl == 48 * 4 * 1600 * 757
    # K and V rows of 1600 f32 a live token, q in and out a sequence
    assert by == 48 * (2 * 1600 * 4 * 757 + 2 * 1600 * 4 * 3)
    half, _ = flops.paged_attention_cost(XL, 2, lengths)
    assert half == fl       # FLOPs do not depend on the KV type
    assert flops.paged_attention_cost(XL, 2, lengths)[1] < by


def test_roofline_says_which_side_binds():
    peaks = flops.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["bytes_per_s"] == 819e9
    fl, by = flops.paged_attention_cost(XL, 4, [300] * 16)
    t, side = flops.roofline_seconds(fl, by, peaks)
    assert side == "memory" and t == pytest.approx(by / 819e9)
    assert flops.roofline_seconds(1e15, 1.0, peaks)[1] == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        flops.peaks_for("TPU v9 imaginary")


def test_interval_totals_count_only_what_arrived_in_the_span():
    obs = {"config": {"model": XL, "kv_bytes_per_element": 4},
           "requests": [{"prompt_len": 10, "token_times": [1.0, 2.0, 3.0]},
                        {"prompt_len": 20, "token_times": [2.5, 9.0]}]}
    span = (1.5, 3.0)
    # decode tokens in the span: request 0's tokens 1 and 2 (contexts
    # 11 and 12); request 1's token 0 came out of its prefill
    assert flops.decode_tokens_flops(obs, span) == (
        flops.lm_decode_token_flops(XL, 11)
        + flops.lm_decode_token_flops(XL, 12))
    assert flops.decode_steps_attention_cost(obs, span) == tuple(
        float(v) for v in flops.paged_attention_cost(XL, 4, [11, 12]))
    assert flops.prefill_flops(obs, span) == flops.lm_prefill_flops(XL, 20)

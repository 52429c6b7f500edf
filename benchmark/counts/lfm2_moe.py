"""Counts for an LFM2-MoE-shaped causal LM (``configs/*.json`` with
``"counts": "lfm2_moe"``): the FLOPs and bytes the algorithm needs,
from the configuration's ``model`` block (the layers this chip holds),
the requests' lengths and, for the expert layer, what the router chose
(the program's ``moe.*`` counters: pairs computed and experts that
received a token are facts of the routing, whatever implements it).

The functions at the bottom are the ones metric files name (``flops``,
``cost``); they take ``obs`` and a span and return totals for it.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from benchmark.flops import in_span
from benchmark.readers.stats_counter import window_value

ACT_BYTES = 2          # bfloat16 rows into and between the expert matmuls
OUT_BYTES = 4          # float32 rows out of them


def _kinds(m: dict) -> Tuple[int, int, int, int]:
    """(conv layers, attention layers, dense FF layers, expert layers)."""
    n_attn = sum(t == "full_attention" for t in m["layer_types"])
    return (m["num_hidden_layers"] - n_attn, n_attn, m["num_dense_layers"],
            m["num_hidden_layers"] - m["num_dense_layers"])


def expert_params(m: dict) -> int:
    """One expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def token_matmul_params(m: dict, head: bool = True) -> int:
    """Weights one token is multiplied with: the operators' projections,
    the dense MLP, the router, ``num_experts_per_tok`` experts an expert
    layer, and the tied head."""
    d = m["hidden_size"]
    kv = m["num_key_value_heads"] * (d // m["num_attention_heads"])
    n_conv, n_attn, n_dense, n_moe = _kinds(m)
    n = n_conv * (4 * d * d + m["conv_L_cache"] * d)
    n += n_attn * (2 * d * d + 2 * d * kv)
    n += n_dense * 3 * d * m["intermediate_size"]
    n += n_moe * (d * m["num_experts"]
                  + m["num_experts_per_tok"] * expert_params(m))
    return n + (d * m["vocab_size"] if head else 0)


def attention_flops(m: dict, context: int) -> int:
    """QK^T and PV for one query over ``context`` keys, every query
    head, the attention layers this chip holds."""
    return _kinds(m)[1] * 4 * m["hidden_size"] * int(context)


def decode_token_flops(m: dict, context: int) -> int:
    return 2 * token_matmul_params(m) + attention_flops(m, context)


def prefill_prompt_flops(m: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, causal; only the last row
    needs the head."""
    p = int(prompt_len)
    return (2 * token_matmul_params(m, head=False) * p
            + attention_flops(m, 1) * (p * (p + 1) // 2)
            + 2 * m["hidden_size"] * m["vocab_size"])


def _decode_lengths(obs: dict, span: Sequence[float]):
    """Keys attended by every decode token that arrived in ``span``
    (token i >= 1 of a request attends prompt + i)."""
    return [r["prompt_len"] + i for r in obs["requests"]
            for i, t in enumerate(r["token_times"])
            if i >= 1 and in_span(t, span)]


# -- totals over an interval, named by metric files ----------------------
def decode_tokens_flops(obs: dict, span: Sequence[float]) -> float:
    m = obs["config"]["model"]
    return float(sum(decode_token_flops(m, n)
                     for n in _decode_lengths(obs, span)))


def prefill_flops(obs: dict, span: Sequence[float]) -> float:
    m = obs["config"]["model"]
    return float(sum(prefill_prompt_flops(m, r["prompt_len"])
                     for r in obs["requests"]
                     if r["token_times"]
                     and in_span(r["token_times"][0], span)))


def decode_steps_attention_cost(obs: dict, span: Sequence[float]
                                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode tokens' attention at their live
    lengths: every live K and V row of the KV heads read once, q read
    and the output written (float32), over the attention layers."""
    m = obs["config"]["model"]
    d = m["hidden_size"]
    kv = m["num_key_value_heads"] * (d // m["num_attention_heads"])
    ls = _decode_lengths(obs, span)
    n_attn = _kinds(m)[1]
    kvb = obs["config"]["kv_bytes_per_element"]
    return (float(n_attn * 4 * d * sum(ls)),
            float(n_attn * (2 * kv * kvb * sum(ls) + 2 * d * 4 * len(ls))))


def decode_steps_moe_cost(obs: dict, span: Sequence[float]
                          ) -> Tuple[float, float]:
    """(FLOPs, bytes) the routing of the window's decode steps needs of
    the expert matmuls: three products a token-expert pair; every
    expert that received a token read once a step and layer, plus each
    pair's rows in, between and out. From the deltas of the program's
    ``moe.decode_pairs`` and ``moe.decode_experts_touched``."""
    m = obs["config"]["model"]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    pairs = window_value(obs, "moe.decode_pairs", "delta")
    touched = window_value(obs, "moe.decode_experts_touched", "delta")
    if not pairs or not touched:
        return 0.0, 0.0
    w_bytes = {"bfloat16": 2, "float32": 4}[m["dtype"]]
    rows = pairs * (d * ACT_BYTES + 2 * f * ACT_BYTES + d * OUT_BYTES)
    return (float(pairs * 2 * expert_params(m)),
            float(touched * expert_params(m) * w_bytes + rows))

"""Counts for a SmallThinker-shaped causal LM (``configs/*.json`` with
``"counts": "smallthinker"``): the FLOPs and bytes the algorithm needs,
from the configuration's ``model`` block (the layers this chip holds),
the requests' lengths and, for the expert layer, what the router chose
(the program's ``moe.*`` counters: pairs computed and experts that
received a token are facts of the routing, whatever implements it).

A layer is global (``sliding_window_layout`` 0: every key up to the
query's) or a window layer (the last ``sliding_window_size`` keys, the
query's own included). Attention is counted at those keys: what a
cache that kept every position for every layer would read is not the
algorithm's.

The functions at the bottom are the ones metric files name (``flops``,
``cost``); they take ``obs`` and a span and return totals for it.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from benchmark.flops import in_span
from benchmark.readers.stats_counter import window_value

ACT_BYTES = 2          # bfloat16 rows into and between the expert matmuls
OUT_BYTES = 4          # float32 rows out of them, and attention's q and out


def _kinds(m: dict) -> Tuple[int, int]:
    """(global layers, window layers)."""
    n_win = sum(int(f) == 1 for f in m["sliding_window_layout"])
    return m["num_hidden_layers"] - n_win, n_win


def _widths(m: dict) -> Tuple[int, int, int]:
    """(hidden, query width, key/value width)."""
    return (m["hidden_size"], m["num_attention_heads"] * m["head_dim"],
            m["num_key_value_heads"] * m["head_dim"])


def expert_params(m: dict) -> int:
    """One expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_ffn_hidden_size"]


def token_matmul_params(m: dict, head: bool = True) -> int:
    """Weights one token is multiplied with: a layer's four attention
    projections, its router and ``moe_num_active_primary_experts``
    experts, and the untied head."""
    d, q, kv = _widths(m)
    layer = (2 * d * q + 2 * d * kv + d * m["moe_num_primary_experts"]
             + m["moe_num_active_primary_experts"] * expert_params(m))
    return m["num_hidden_layers"] * layer + (
        d * m["vocab_size"] if head else 0)


def keys_read(m: dict, context: int) -> Tuple[int, int]:
    """Keys one query at ``context`` keys of sequence attends, a global
    layer and a window layer."""
    return int(context), min(int(context), m["sliding_window_size"])


def attention_flops(m: dict, context: int) -> int:
    """QK^T and PV for one query, every query head, every layer, each
    at the keys its kind reads."""
    n_glob, n_win = _kinds(m)
    g, w = keys_read(m, context)
    return 4 * _widths(m)[1] * (n_glob * g + n_win * w)


def decode_token_flops(m: dict, context: int) -> int:
    return 2 * token_matmul_params(m) + attention_flops(m, context)


def _triangle(p: int, reach: int = None) -> int:
    """sum over rows i < p of the keys row i reads: ``i + 1``, or
    ``min(i + 1, reach)``."""
    if reach is None or p <= reach:
        return p * (p + 1) // 2
    return reach * (reach + 1) // 2 + (p - reach) * reach


def prefill_prompt_flops(m: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, causal (and windowed); only
    the last row needs the head."""
    p = int(prompt_len)
    n_glob, n_win = _kinds(m)
    d, q, _ = _widths(m)
    return (2 * token_matmul_params(m, head=False) * p
            + 4 * q * (n_glob * _triangle(p)
                       + n_win * _triangle(p, m["sliding_window_size"]))
            + 2 * d * m["vocab_size"])


def _decode_lengths(obs: dict, span: Sequence[float]):
    """Keys of sequence behind every decode token that arrived in
    ``span`` (token i >= 1 of a request attends prompt + i)."""
    return [r["prompt_len"] + i for r in obs["requests"]
            for i, t in enumerate(r["token_times"])
            if i >= 1 and in_span(t, span)]


def _decode_attention_cost(obs, span, windowed: bool) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode tokens' attention in the layers of
    one kind: every K and V row attended read once a KV head, q read
    and the output written (float32)."""
    m = obs["config"]["model"]
    _, q, kv = _widths(m)
    n = _kinds(m)[1 if windowed else 0]
    keys = [keys_read(m, c)[1 if windowed else 0]
            for c in _decode_lengths(obs, span)]
    kvb = obs["config"]["kv_bytes_per_element"]
    return (float(n * 4 * q * sum(keys)),
            float(n * (2 * kv * kvb * sum(keys)
                       + 2 * q * OUT_BYTES * len(keys))))


def chunk_plan(prompt_len: int, chunk: int):
    """(p0, rows) of each chunk a prompt is prefilled in."""
    return [(p0, min(chunk, prompt_len - p0))
            for p0 in range(0, int(prompt_len), int(chunk))]


def chunk_attention_cost(m: dict, p0: int, rows: int, kvb: int
                         ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one chunk's attention over all layers: row i
    reads the keys its kind of layer gives it; every K and V row some
    row of the chunk reads is read once a KV head and chunk; q read and
    the output written (float32)."""
    n_glob, n_win = _kinds(m)
    _, q, kv = _widths(m)
    W = m["sliding_window_size"]
    end = p0 + rows
    flops = 4 * q * (
        n_glob * (_triangle(end) - _triangle(p0))
        + n_win * (_triangle(end, W) - _triangle(p0, W)))
    rows_read = n_glob * end + n_win * min(end, W + rows - 1)
    return flops, (2 * kv * kvb * rows_read
                   + (n_glob + n_win) * 2 * q * OUT_BYTES * rows)


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


def decode_steps_global_attention_cost(obs, span) -> Tuple[float, float]:
    return _decode_attention_cost(obs, span, windowed=False)


def decode_steps_window_attention_cost(obs, span) -> Tuple[float, float]:
    return _decode_attention_cost(obs, span, windowed=True)


def prefill_chunks_attention_cost(obs: dict, span: Sequence[float]
                                  ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the attention of the window's prefill chunks:
    the mean chunk of the prompts whose first token arrived in the
    span, times the chunks the program counted in the window
    (``paged.prefill_chunks``), so that the reader's division by that
    count gives the mean chunk back."""
    m = obs["config"]["model"]
    chunk = obs["config"]["engine"]["prefill_chunk_tokens"]
    kvb = obs["config"]["kv_bytes_per_element"]
    costs = [chunk_attention_cost(m, p0, rows, kvb)
             for r in obs["requests"]
             if r["token_times"] and in_span(r["token_times"][0], span)
             for p0, rows in chunk_plan(r["prompt_len"], chunk)]
    n = window_value(obs, "paged.prefill_chunks", "delta")
    if not costs or not n:
        return 0.0, 0.0
    return (float(sum(c[0] for c in costs)) * n / len(costs),
            float(sum(c[1] for c in costs)) * n / len(costs))


def decode_steps_moe_cost(obs: dict, span: Sequence[float]
                          ) -> Tuple[float, float]:
    """(FLOPs, bytes) the routing of the window's decode steps needs of
    the expert matmuls: three products a token-expert pair; every
    expert that received a token read once a step and layer, plus each
    pair's rows in, between and out. From the deltas of the program's
    ``moe.decode_pairs`` and ``moe.decode_experts_touched``."""
    m = obs["config"]["model"]
    d, f = m["hidden_size"], m["moe_ffn_hidden_size"]
    pairs = window_value(obs, "moe.decode_pairs", "delta")
    touched = window_value(obs, "moe.decode_experts_touched", "delta")
    if not pairs or not touched:
        return 0.0, 0.0
    w_bytes = {"bfloat16": 2, "float32": 4}[m["dtype"]]
    rows = pairs * (d * ACT_BYTES + 2 * f * ACT_BYTES + d * OUT_BYTES)
    return (float(pairs * 2 * expert_params(m)),
            float(touched * expert_params(m) * w_bytes + rows))

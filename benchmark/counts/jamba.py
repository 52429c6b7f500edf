"""Counts for a Jamba-shaped causal LM (``configs/*.json`` with
``"counts": "jamba"``): the FLOPs and bytes the algorithm needs, from
the configuration's ``model`` block, the requests' lengths and, for the
state-space recurrence, the rows the program counted (``ssm.*``: live
chunk rows and live decode lanes, each times the Mamba layers, are
facts of the traffic and the scheduler, whatever implements the scan).

Layer ``l`` is attention where ``l % attn_layer_period ==
attn_layer_offset`` and Mamba elsewhere; every layer has the dense
gated MLP (``num_experts`` 1). A Mamba layer of ``Di = mamba_expand *
hidden_size`` channels and ``N = mamba_d_state`` states:

- matmuls a token: ``W_in`` D x 2Di, ``W_x`` Di x (R + 2N), ``W_dt``
  R x Di, ``W_out`` Di x D;
- the recurrence a token: for each of ``Di * N`` state elements
  ``dt * A``, ``exp``, ``* h``, ``(dt c) * B``, ``+``, ``* C``, ``+``
  (7), and for each channel ``dt * c``, ``D * c``, ``+`` (3);
- bytes of a chunk: float32 rows ``c``, ``dt``, ``z`` in and ``y`` out,
  the ``[N, Di]`` float32 state in and out, ``A`` and ``D`` once a call.
  (A decode step's recurrence is XLA's fusion, which no reader can
  name: it has no cost function here.)

The functions at the bottom are the ones metric files name (``flops``,
``cost``); they take ``obs`` and a span and return totals for it.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from benchmark.flops import in_span
from benchmark.readers.stats_counter import window_value

F32 = 4
STATE_FLOPS = 7        # a state element a row: see the module docstring
CHANNEL_FLOPS = 3      # a channel a row beside its states


def _kinds(m: dict) -> Tuple[int, int]:
    """(Mamba layers, attention layers)."""
    n_attn = sum(layer % m["attn_layer_period"] == m["attn_layer_offset"]
                 for layer in range(m["num_hidden_layers"]))
    return m["num_hidden_layers"] - n_attn, n_attn


def _widths(m: dict) -> Tuple[int, int, int, int]:
    """(hidden, key/value width, inner channels, states)."""
    d = m["hidden_size"]
    return (d, m["num_key_value_heads"] * (d // m["num_attention_heads"]),
            m["mamba_expand"] * d, m["mamba_d_state"])


def mamba_matmul_params(m: dict) -> int:
    d, _, di, n = _widths(m)
    r = m["mamba_dt_rank"]
    return 2 * d * di + di * (r + 2 * n) + r * di + di * d


def token_matmul_params(m: dict, head: bool = True) -> int:
    """Weights one token is multiplied with: a Mamba layer's four
    projections or an attention layer's, the MLP's three matrices in
    every layer, and the tied head."""
    d, kv, _, _ = _widths(m)
    n_mamba, n_attn = _kinds(m)
    n = n_mamba * mamba_matmul_params(m) + n_attn * (2 * d * d + 2 * d * kv)
    n += m["num_hidden_layers"] * 3 * d * m["intermediate_size"]
    return n + (d * m["vocab_size"] if head else 0)


def scan_row_flops(m: dict) -> int:
    """The recurrence of one row of one Mamba layer."""
    _, _, di, n = _widths(m)
    return di * (STATE_FLOPS * n + CHANNEL_FLOPS)


def attention_flops(m: dict, context: int) -> int:
    """QK^T and PV for one query over ``context`` keys, every query
    head, the attention layers."""
    return _kinds(m)[1] * 4 * m["hidden_size"] * int(context)


def decode_token_flops(m: dict, context: int) -> int:
    return (2 * token_matmul_params(m) + attention_flops(m, context)
            + _kinds(m)[0] * scan_row_flops(m))


def prefill_prompt_flops(m: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, causal; only the last row
    needs the head."""
    p = int(prompt_len)
    return ((2 * token_matmul_params(m, head=False)
             + _kinds(m)[0] * scan_row_flops(m)) * p
            + attention_flops(m, 1) * (p * (p + 1) // 2)
            + 2 * m["hidden_size"] * m["vocab_size"])


def _decode_lengths(obs: dict, span: Sequence[float]):
    """Keys attended by every decode token that arrived in ``span``
    (token i >= 1 of a request attends prompt + i)."""
    return [r["prompt_len"] + i for r in obs["requests"]
            for i, t in enumerate(r["token_times"])
            if i >= 1 and in_span(t, span)]


def chunk_plan(prompt_len: int, chunk: int):
    """(p0, rows) of each chunk a prompt is prefilled in."""
    return [(p0, min(chunk, prompt_len - p0))
            for p0 in range(0, int(prompt_len), int(chunk))]


def chunk_attention_cost(m: dict, p0: int, rows: int, kvb: int
                         ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one chunk's attention over the attention
    layers: row i reads keys ``0 .. p0 + i``; every K and V row up to
    the chunk's end is read once a KV head; q read and the output
    written (float32)."""
    d, kv, _, _ = _widths(m)
    end = p0 + rows
    tri = end * (end + 1) // 2 - p0 * (p0 + 1) // 2
    n_attn = _kinds(m)[1]
    return (n_attn * 4 * d * tri,
            n_attn * (2 * kv * kvb * end + 2 * d * F32 * rows))


def scan_call_bytes(m: dict) -> int:
    """What one call of the recurrence reads beside its rows and its
    state: ``A`` and ``D``."""
    _, _, di, n = _widths(m)
    return (n * di + di) * F32


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
    d, kv, _, _ = _widths(m)
    ls = _decode_lengths(obs, span)
    n_attn = _kinds(m)[1]
    kvb = obs["config"]["kv_bytes_per_element"]
    return (float(n_attn * 4 * d * sum(ls)),
            float(n_attn * (2 * kv * kvb * sum(ls) + 2 * d * F32 * len(ls))))


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


def prefill_chunks_scan_cost(obs: dict, span: Sequence[float]
                             ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the recurrence in the window's prefill chunks:
    every live row of every Mamba layer (the delta of the program's
    ``ssm.chunk_rows``) reads ``c``, ``dt`` and ``z`` and writes ``y``
    (float32) with its ``B`` and ``C``; every call (chunks x Mamba
    layers) takes the state in and out and reads ``A`` and ``D``."""
    m = obs["config"]["model"]
    _, _, di, n = _widths(m)
    rows = window_value(obs, "ssm.chunk_rows", "delta")
    chunks = window_value(obs, "paged.prefill_chunks", "delta")
    if not rows or not chunks:
        return 0.0, 0.0
    calls = chunks * _kinds(m)[0]
    return (float(rows * scan_row_flops(m)),
            float(rows * (4 * di + 2 * n) * F32
                  + calls * (2 * n * di * F32 + scan_call_bytes(m))))

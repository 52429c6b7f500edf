"""Counts of the work an algorithm needs, from shapes alone.

Every roofline and MFU metric divides one of these counts by a measured
time, so the count must not depend on how the program implements the
step: no XLA ``cost_analysis()``, no recomputation, no padding. A
multiply-add is two FLOPs. Functions named in a metric file's ``flops``
or ``cost`` argument are looked up here by name and take ``obs`` (what
the runner observed) and return totals for the interval they are asked
about.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; unknown is an error,
    never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


# -- causal transformer LM ---------------------------------------------
def lm_matmul_params(cfg: dict, head: bool = True) -> int:
    """Weights every token is multiplied with: per layer the four
    attention projections and the two MLP matrices, plus the head."""
    d, f = cfg["d_model"], cfg["d_ff"]
    n = cfg["n_layers"] * (4 * d * d + 2 * d * f)
    return n + (d * cfg["vocab_size"] if head else 0)


def lm_attention_flops(cfg: dict, context: int) -> int:
    """QK^T and PV for one query over ``context`` keys, all layers."""
    return cfg["n_layers"] * 4 * cfg["d_model"] * int(context)


def lm_decode_token_flops(cfg: dict, context: int) -> int:
    """One generated token whose query attends ``context`` keys
    (itself included)."""
    return 2 * lm_matmul_params(cfg) + lm_attention_flops(cfg, context)


def lm_prefill_flops(cfg: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, causal: token i attends i+1
    keys; only the last row needs the head."""
    p = int(prompt_len)
    body = 2 * lm_matmul_params(cfg, head=False) * p
    attn = lm_attention_flops(cfg, 1) * (p * (p + 1) // 2)
    return body + attn + 2 * cfg["d_model"] * cfg["vocab_size"]


def paged_attention_cost(cfg: dict, kv_bytes: int,
                         lengths: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) one decode step's attention needs over ALL
    layers, for sequences whose live lengths (keys attended, the new
    token included) are ``lengths``: read every live K and V row once,
    read q and write the output, two multiply-adds a key and channel."""
    d, n = cfg["d_model"], cfg["n_layers"]
    ls = [int(x) for x in lengths]
    flops = n * 4 * d * sum(ls)
    nbytes = n * (2 * d * kv_bytes * sum(ls) + 2 * d * 4 * len(ls))
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take and which side binds."""
    tc, tm = flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# -- totals over an interval, named by metric files ----------------------
def _in(t: float, span: Sequence[float]) -> bool:
    return span[0] < t <= span[1]


def decode_tokens_flops(obs: dict, span: Sequence[float]) -> float:
    """Model FLOPs of every generated token that arrived in ``span``.
    Token i of a request (i >= 1; token 0 comes out of the prefill)
    attends prompt + i keys."""
    cfg = obs["config"]["model"]
    total = 0
    for r in obs["requests"]:
        for i, t in enumerate(r["token_times"]):
            if i >= 1 and _in(t, span):
                total += lm_decode_token_flops(cfg, r["prompt_len"] + i)
    return float(total)


def decode_steps_attention_cost(obs: dict, span: Sequence[float]
                                ) -> Tuple[float, float]:
    """(FLOPs, bytes) the attention of every decode token that arrived
    in ``span`` needs, at its live length."""
    cfg = obs["config"]["model"]
    kv_bytes = obs["config"]["kv_bytes_per_element"]
    lengths = [r["prompt_len"] + i for r in obs["requests"]
               for i, t in enumerate(r["token_times"])
               if i >= 1 and _in(t, span)]
    f, b = paged_attention_cost(cfg, kv_bytes, lengths)
    return float(f), float(b)


def prefill_flops(obs: dict, span: Sequence[float]) -> float:
    """Model FLOPs of the prompts whose first token arrived in
    ``span``."""
    cfg = obs["config"]["model"]
    return float(sum(lm_prefill_flops(cfg, r["prompt_len"])
                     for r in obs["requests"]
                     if r["token_times"] and _in(r["token_times"][0], span)))

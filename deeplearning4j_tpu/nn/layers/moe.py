"""A routed mixture-of-experts feed-forward layer, as pure functions
over an explicit parameter dict (so a served forward and, later, a
training step can both trace it: ROADMAP D5).

Every token picks ``top_k`` of ``E`` experts by a sigmoid router and
gets the weighted sum of their SwiGLU outputs. No capacity, no token
dropped, no shared expert. The shapes are static whatever the routing:
the ``T * top_k`` token-expert pairs are sorted by expert and the three
matmuls run as grouped products over the sorted rows
(:mod:`deeplearning4j_tpu.kernels.moe_experts`), so an expert that
received no token is not read. A DEAD token (a decode lane with no
request, a chunk row past the chunk's length) routes to no expert: its
pairs sort to the end, belong to no group, and count nowhere.

``parallel/moe.py`` is the training-side sibling (top-1, capacity,
inside a ``shard_map``); it does not serve.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ...kernels.moe_experts import expert_ffn

#: what the denominator of the renormalised weights is padded with
NORM_EPS = 1e-6


def route(x, w_gate, expert_bias, top_k: int, live=None,
          norm_topk_prob: bool = True, scaling: float = 1.0
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The router, in float32 at full precision (a bf16 pass would flip
    choices the reference makes): scores ``s = sigmoid(x W_g)``; the
    ``top_k`` experts with the largest ``s + expert_bias`` are chosen
    (the bias selects and does not weigh); their weights are ``s`` of
    the chosen, over their sum plus 1e-6 where ``norm_topk_prob``,
    times ``scaling``.

    x [T, D]; w_gate [D, E]; expert_bias [E]; live [T] bool or None.
    Returns (experts [T, top_k] int32, weights [T, top_k] float32); a
    dead token's experts are ``E`` (no expert) and its weights 0."""
    E = w_gate.shape[1]
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + expert_bias.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, experts, axis=1)
    if norm_topk_prob:
        g = g / (g.sum(-1, keepdims=True) + NORM_EPS)
    g = g * scaling
    if live is not None:
        experts = jnp.where(live[:, None], experts, E)
        g = jnp.where(live[:, None], g, 0.0)
    return experts.astype(jnp.int32), g


def moe_ffn(params: Dict, x, top_k: int, live=None,
            norm_topk_prob: bool = True, scaling: float = 1.0):
    """The expert layer over x [T, D] (float32, already normed).

    params: ``W_g`` [D, E], ``expert_bias`` [E], ``W1``/``W3``
    [E, D, F], ``W2`` [E, F, D]. The matmul operands take the experts'
    dtype (bfloat16 weights: bf16 operands, f32 accumulation).

    Returns (y [T, D] float32, counts) where ``counts`` is
    ``{"pairs": live pairs, "experts_touched": experts that received at
    least one, "expert_tokens": [E] pairs of each}``, int32, for the
    serving engine's account."""
    T, D = x.shape
    E = params["W_g"].shape[1]
    with jax.named_scope("lfm2.moe.route"):
        experts, g = route(x, params["W_g"], params["expert_bias"], top_k,
                           live, norm_topk_prob, scaling)
        flat = experts.reshape(-1)                     # [T * k]
        order = jnp.argsort(flat, stable=True)         # dead pairs last
        sizes = jnp.zeros(E + 1, jnp.int32).at[flat].add(1)[:E]
    with jax.named_scope("lfm2.moe.experts"):
        xs = x.astype(params["W1"].dtype)[order // top_k]   # [T * k, D]
        ys = expert_ffn(xs, params["W1"], params["W3"], params["W2"],
                        sizes)
        # rows of no expert are undefined: mask, never multiply away
        ys = jnp.where((flat[order] < E)[:, None], ys, 0.0)
        pairs = jnp.zeros_like(ys).at[order].set(ys).reshape(T, top_k, D)
        y = (pairs * g[..., None]).sum(1)
    counts = {"pairs": sizes.sum(), "experts_touched": (sizes > 0).sum(),
              "expert_tokens": sizes}
    return y, counts

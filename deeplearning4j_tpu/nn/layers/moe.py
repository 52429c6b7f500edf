"""A routed mixture-of-experts feed-forward layer, as pure functions
over an explicit parameter dict (so a served forward and, later, a
training step can both trace it: ROADMAP D5).

Every token picks ``top_k`` of ``E`` experts by a router (sigmoid scores
with a selection bias, or softmax over the chosen logits) and gets the
weighted sum of their gated units' outputs (SwiGLU or ReGLU). No capacity, no token
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

import threading
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


def route_softmax(logits, top_k: int, live=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The router of a model that hands its logits in: the ``top_k``
    largest of ``logits`` [T, E] (float32) are chosen, and their
    weights are the softmax over those ``top_k`` logits alone. Dead
    tokens as :func:`route`."""
    E = logits.shape[1]
    chosen, experts = jax.lax.top_k(logits.astype(jnp.float32), top_k)
    g = jax.nn.softmax(chosen, axis=-1)
    if live is not None:
        experts = jnp.where(live[:, None], experts, E)
        g = jnp.where(live[:, None], g, 0.0)
    return experts.astype(jnp.int32), g


def moe_ffn(params: Dict, x, top_k: int, live=None,
            norm_topk_prob: bool = True, scaling: float = 1.0, *,
            router_logits=None, scoring: str = "sigmoid",
            gate: str = "silu", scope: str = "lfm2"):
    """The expert layer over x [T, D] (float32, already normed).

    params: ``W_g`` [D, E], ``expert_bias`` [E], ``W1``/``W3``
    [E, D, F], ``W2`` [E, F, D]. The matmul operands take the experts'
    dtype (bfloat16 weights: bf16 operands, f32 accumulation).

    ``scoring`` ``sigmoid`` is :func:`route` over ``x``; ``softmax`` is
    :func:`route_softmax` over ``router_logits`` [T, E], which the
    caller computed from whatever its router reads (``W_g`` and
    ``expert_bias`` are then not looked at). ``gate`` is the experts'
    activation (:data:`~...kernels.moe_experts.GATES`); ``scope``
    prefixes the two named scopes.

    Returns (y [T, D] float32, counts) where ``counts`` is
    ``{"pairs": live pairs, "experts_touched": experts that received at
    least one, "expert_tokens": [E] pairs of each}``, int32, for the
    serving engine's account."""
    T, D = x.shape
    E = params["W1"].shape[0]
    with jax.named_scope(scope + ".moe.route"):
        if scoring == "sigmoid":
            experts, g = route(x, params["W_g"], params["expert_bias"],
                               top_k, live, norm_topk_prob, scaling)
        elif scoring == "softmax":
            experts, g = route_softmax(router_logits, top_k, live)
        else:
            raise ValueError(f"unknown scoring {scoring!r}")
        flat = experts.reshape(-1)                     # [T * k]
        order = jnp.argsort(flat, stable=True)         # dead pairs last
        sizes = jnp.zeros(E + 1, jnp.int32).at[flat].add(1)[:E]
    with jax.named_scope(scope + ".moe.experts"):
        xs = x.astype(params["W1"].dtype)[order // top_k]   # [T * k, D]
        ys = expert_ffn(xs, params["W1"], params["W3"], params["W2"],
                        sizes, gate=gate)
        # rows of no expert are undefined: mask, never multiply away
        ys = jnp.where((flat[order] < E)[:, None], ys, 0.0)
        pairs = jnp.zeros_like(ys).at[order].set(ys).reshape(T, top_k, D)
        y = (pairs * g[..., None]).sum(1)
    counts = {"pairs": sizes.sum(), "experts_touched": (sizes > 0).sum(),
              "expert_tokens": sizes}
    return y, counts


#: the ``moe`` block of a generator's ``/stats``: what the routing of
#: a served mixture-of-experts model did, from the small integer vector
#: (pairs, experts touched, pairs of each expert) that every decode
#: step and prefill chunk returns beside its tokens
MOE_COUNTERS = ("decode_pairs", "decode_experts_touched",
                "decode_expert_slots", "chunk_pairs")


class MoeAccount:
    """Routing counters of a served model with experts: the account a
    model hands the engine (``model.step_account()``), which feeds it
    the vector every step and chunk returns and publishes its snapshot
    under :attr:`block`. ``expert_slots_per_step`` is expert layers x
    experts held: what one step could touch. Written by the scheduler
    thread when a step's or a chunk's results reach the host; all
    counters of one step are committed together under the lock, so a
    ratio of two deltas (``decode_experts_touched`` over
    ``decode_expert_slots``) is exact over whole steps."""

    #: the account's key in a generator's ``/stats``
    block = "moe"

    def __init__(self, expert_slots_per_step: int):
        self._lock = threading.Lock()
        self._slots_per_step = int(expert_slots_per_step)
        self.decode_pairs = 0            # token-expert pairs computed
        self.decode_experts_touched = 0  # experts with >= 1 live token
        self.decode_expert_slots = 0     # expert layers x experts x steps
        self.chunk_pairs = 0
        self.expert_tokens = None        # [E] pairs of each expert

    def _add_tokens(self, per_expert) -> None:
        if self.expert_tokens is None:
            self.expert_tokens = per_expert.astype("int64")
        else:
            self.expert_tokens += per_expert

    def decode_step(self, counters) -> None:
        with self._lock:
            self.decode_pairs += int(counters[0])
            self.decode_experts_touched += int(counters[1])
            self.decode_expert_slots += self._slots_per_step
            self._add_tokens(counters[2:])

    def chunk(self, counters) -> None:
        with self._lock:
            self.chunk_pairs += int(counters[0])
            self._add_tokens(counters[2:])

    def snapshot(self) -> Dict:
        with self._lock:
            tokens = [] if self.expert_tokens is None \
                else self.expert_tokens.tolist()
            out = {k: getattr(self, k) for k in MOE_COUNTERS}
        total = sum(tokens)
        out["expert_tokens"] = {str(e): n for e, n in enumerate(tokens)}
        # load balance: the fullest expert over the mean (1.0 = even)
        out["expert_tokens_max_over_mean"] = round(
            max(tokens) * len(tokens) / total, 4) if total else 0.0
        return out

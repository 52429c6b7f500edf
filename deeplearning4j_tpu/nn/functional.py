"""Shared stateless NN math used by both the layer DSL and the
distributed transformer — one definition so numerics cannot diverge."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_norm(x, gain, bias, eps: float = 1e-5):
    """LayerNorm over the last axis: (x - mean)/sqrt(var + eps)*g + b."""
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * gain + bias


#: rows the sampled row's head is computed over. A product with ONE
#: row is no matmul to the TPU's compiler: it rewrites ``[1, d] x
#: [d, V]`` as a multiply and a reduce over the weights converted to
#: float32, on the VPU (at ``[2560, 151936]`` bfloat16 that cost more
#: than the 1,024-row head it replaced: PERF.md section 6, PR 36). A
#: sublane tile of rows stays on the MXU and reads the same weights.
HEAD_ROWS = 8


def sampled_row_logits(x, n_live, head):
    """Logits ``[1, V]`` of the one row a prefill chunk samples from:
    row ``n_live - 1`` of the chunk's final hidden state ``x`` [C, d],
    through ``head`` (the final norm and the head, ``[R, d] -> [R, V]``,
    row by row) over the :data:`HEAD_ROWS` rows that end at it and not
    over the chunk (a traced index: one program whatever ``n_live``).
    They come back NaN when any of the first ``n_live`` rows of ``x``
    holds a non-finite value, so they fail the engine's finite guard
    exactly where the chunk's ``[C, V]`` logits would have; rows from
    ``n_live`` on may hold anything (blocks are never zeroed)."""
    rows = min(HEAD_ROWS, x.shape[0])
    live = (jnp.arange(x.shape[0]) < n_live)[:, None]
    ok = jnp.all(jnp.where(live, jnp.isfinite(x), True))
    start = jnp.clip(n_live - rows, 0, x.shape[0] - rows)
    logits = head(jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0))
    row = jax.lax.dynamic_index_in_dim(logits, n_live - 1 - start, axis=0)
    return jnp.where(ok, row, jnp.nan)

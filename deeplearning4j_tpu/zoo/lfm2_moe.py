"""LFM2-MoE-shaped causal LM for the serving path: gated short
convolutions beside grouped-query attention, a dense SwiGLU MLP in the
leading layers and a sigmoid-routed mixture of experts in the rest,
RMSNorm, rotary positions, a head tied to the embedding, bfloat16
weights.

The class has the surface ``GenerationEngine`` serves
(``vocab_size``, ``max_seq_len``, ``eos_id``, ``_params``, ``init``,
``cache_shapes``, ``forward_decode_paged``, ``forward_prefill_chunk``)
plus the one addition of a model whose layers keep state that is not
keys and values: :meth:`slot_state_shapes` declares the arrays kept a
SLOT (here the last ``conv_L_cache - 1`` inputs of each short
convolution), the engine allocates them beside the paged pools, donates
them with the pools, and hands them to both forwards, which return them
updated together with a small vector of counters
(:data:`STEP_COUNTERS`). ``cache_shapes`` lists the attention layers
only. See docs/generation.md, "Models with state a slot".

Block ``l`` (x [T, D] float32 residual stream; RMSNorm in float32):

    h = x + Op_l(RMSNorm(x; operator_norm))
    y = h + FF_l(RMSNorm(h; ffn_norm))

``Op`` is the short convolution (``layer_types[l] == "conv"``):
``[B, C, u] = split3(x W_in)``, ``v = B * u``, a depthwise causal
convolution of ``v`` over ``conv_L_cache`` positions, ``(C * c) W_out``;
or attention: 32 query heads over 8 key/value heads of 64, RMSNorm of q
and k over each head's lanes, rotary positions (rotate-half), keys
stored in the pool after norm and rotation. ``FF`` is
``(silu(x W1) * x W3) W2`` below ``num_dense_layers`` and the expert
layer (:mod:`deeplearning4j_tpu.nn.layers.moe`) above.

Matmul operands take ``dtype`` (bfloat16 as published; float32 in the
CPU tests) with float32 accumulation; norms, router, softmax, rotary
and the convolution's sum are float32; logits are float32.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention import (kv_pool_set, kv_pool_set_span,
                                       paged_attention,
                                       paged_prefill_attention)
from ..nn.functional import sampled_row_logits
from ..nn.layers.moe import MoeAccount, moe_ffn

#: layout of the int32 vector both forwards return beside the logits:
#: token-expert pairs computed, experts that received at least one live
#: token (summed over expert layers), then the pairs of each expert
STEP_COUNTERS = ("pairs", "experts_touched", "expert_tokens")


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rope(x, pos, theta: float):
    """Rotary positions over all lanes of x [T, H, D] at ``pos`` [T],
    the rotate-half convention (lane i pairs with lane i + D/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]          # [T, half]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


class Lfm2MoeLM:
    """The served class. Constructor keys are those of the published
    ``config.json`` (``model_type`` ``lfm2_moe``); ``max_seq_len``
    bounds what an engine may ask of it (there is no position table:
    any length up to ``max_position_embeddings`` costs nothing here)."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 num_hidden_layers: int, num_dense_layers: int,
                 layer_types: Sequence[str], num_attention_heads: int,
                 num_key_value_heads: int, num_experts: int,
                 num_experts_per_tok: int, conv_L_cache: int = 3,
                 norm_eps: float = 1e-5, rope_theta: float = 1e6,
                 norm_topk_prob: bool = True, use_expert_bias: bool = True,
                 routed_scaling_factor: float = 1.0,
                 max_position_embeddings: int = 128000,
                 conv_bias: bool = False, dtype: str = "bfloat16",
                 eos_id: Optional[int] = None, seed: int = 0, **_):
        if len(layer_types) != num_hidden_layers:
            raise ValueError(f"{len(layer_types)} layer_types for "
                             f"{num_hidden_layers} layers")
        if conv_bias:
            raise ValueError("conv_bias is not supported")
        self.vocab_size = int(vocab_size)
        self.d_model = int(hidden_size)
        self.d_ff = int(intermediate_size)
        self.d_expert = int(moe_intermediate_size)
        self.n_layers = int(num_hidden_layers)
        self.n_dense = int(num_dense_layers)
        self.layer_types = list(layer_types)
        self.n_heads = int(num_attention_heads)
        self.n_kv_heads = int(num_key_value_heads)
        self.head_dim = self.d_model // self.n_heads
        self.n_experts = int(num_experts)
        self.top_k = int(num_experts_per_tok)
        self.conv_taps = int(conv_L_cache)
        self.norm_eps = float(norm_eps)
        self.rope_theta = float(rope_theta)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.use_expert_bias = bool(use_expert_bias)
        self.routed_scaling = float(routed_scaling_factor)
        self.max_seq_len = int(max_position_embeddings)
        self.dtype = jnp.dtype(dtype)
        self.eos_id = eos_id
        self.seed = int(seed)
        self.attn_layers = [i for i, t in enumerate(self.layer_types)
                            if t == "full_attention"]
        self.conv_layers = [i for i, t in enumerate(self.layer_types)
                            if t == "conv"]
        self.n_moe_layers = self.n_layers - self.n_dense
        self._params = None

    # -- lifecycle -----------------------------------------------------
    def init(self) -> "Lfm2MoeLM":
        """N(0, 0.02) matrices, norm weights 1, expert_bias N(0, 0.01)."""
        D, dt = self.d_model, self.dtype
        keys = iter(jax.random.split(jax.random.PRNGKey(self.seed),
                                     16 * (self.n_layers + 1)))

        def mat(*shape, std=0.02, dtype=dt):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        layers = []
        for i, kind in enumerate(self.layer_types):
            w = {"operator_norm": jnp.ones((D,), jnp.float32),
                 "ffn_norm": jnp.ones((D,), jnp.float32)}
            if kind == "conv":
                w.update(W_in=mat(D, 3 * D), W_out=mat(D, D),
                         conv_w=mat(D, self.conv_taps, dtype=jnp.float32))
            else:
                kv = self.n_kv_heads * self.head_dim
                w.update(Wq=mat(D, D), Wk=mat(D, kv), Wv=mat(D, kv),
                         Wo=mat(D, D),
                         q_norm=jnp.ones((self.head_dim,), jnp.float32),
                         k_norm=jnp.ones((self.head_dim,), jnp.float32))
            if i < self.n_dense:
                F = self.d_ff
                w.update(W1=mat(D, F), W3=mat(D, F), W2=mat(F, D))
            else:
                E, F = self.n_experts, self.d_expert
                w.update(W_g=mat(D, E, dtype=jnp.float32),
                         expert_bias=mat(E, std=0.01, dtype=jnp.float32),
                         W1=mat(E, D, F), W3=mat(E, D, F), W2=mat(E, F, D))
            layers.append(w)
        self._params = {"embed": mat(self.vocab_size, D),
                        "embedding_norm": jnp.ones((D,), jnp.float32),
                        "layers": layers}
        return self

    # -- what the cache manager allocates --------------------------------
    def cache_shapes(self, max_seq_len: Optional[int] = None
                     ) -> List[Tuple[int, int, int]]:
        """K (== V) shape a sequence, for the layers that HAVE paged
        keys and values: ``[n_kv_heads, max_seq_len, head_dim]`` an
        attention layer, nothing for a convolution."""
        n = self.max_seq_len if max_seq_len is None else int(max_seq_len)
        return [(self.n_kv_heads, n, self.head_dim)] * len(self.attn_layers)

    def slot_state_shapes(self, num_slots: int):
        """The arrays kept a SLOT, as (shape, dtype) with the slot
        first (a model may declare arrays of several types: the engine
        takes each as it is given): here one a short convolution, the
        last ``conv_L_cache - 1`` values of its ``v = B * u``. A
        request's first chunk starts from zeros whatever the slot held;
        the engine never clears it."""
        return [((int(num_slots), self.conv_taps - 1, self.d_model),
                 self.dtype)] * len(self.conv_layers)

    def step_account(self):
        """What the :data:`STEP_COUNTERS` vectors add up into, an engine:
        the ``moe`` block of its ``/stats``."""
        return MoeAccount(self.n_moe_layers * self.n_experts)

    # -- pieces ------------------------------------------------------------
    def _mm(self, x, w):
        return jnp.dot(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)

    def _conv(self, w, x, prev):
        """x [T, D] normed; prev [taps-1, D] the inputs before row 0.
        Returns (out [T, D], v [T, D] float32)."""
        D = self.d_model
        bcu = self._mm(x, w["W_in"])
        v = bcu[:, :D] * bcu[:, 2 * D:]
        ext = jnp.concatenate([prev.astype(jnp.float32), v], 0)
        T, taps = x.shape[0], self.conv_taps
        c = sum(w["conv_w"][:, j][None] * ext[j:j + T] for j in range(taps))
        return self._mm(bcu[:, D:2 * D] * c, w["W_out"]), v

    def _conv_step(self, w, x, st):
        """One row a lane: x [S, D] normed; st [S, taps-1, D] each
        lane's last inputs. Returns (out [S, D], the state after)."""
        D = self.d_model
        bcu = self._mm(x, w["W_in"])
        v = bcu[:, :D] * bcu[:, 2 * D:]
        ext = jnp.concatenate([st.astype(jnp.float32), v[:, None]], 1)
        c = (ext * w["conv_w"].T[None]).sum(1)
        return (self._mm(bcu[:, D:2 * D] * c, w["W_out"]),
                ext[:, 1:].astype(st.dtype))

    def _qkv(self, w, x, pos):
        T = x.shape[0]
        q = self._mm(x, w["Wq"]).reshape(T, self.n_heads, self.head_dim)
        k = self._mm(x, w["Wk"]).reshape(T, self.n_kv_heads, self.head_dim)
        v = self._mm(x, w["Wv"]).reshape(T, self.n_kv_heads, self.head_dim)
        q = rope(rms_norm(q, w["q_norm"], self.norm_eps), pos,
                 self.rope_theta)
        k = rope(rms_norm(k, w["k_norm"], self.norm_eps), pos,
                 self.rope_theta)
        return q, k, v

    def _ff(self, i, w, x, live, counts):
        if i < self.n_dense:
            h = jax.nn.silu(self._mm(x, w["W1"])) * self._mm(x, w["W3"])
            return self._mm(h, w["W2"])
        bias = w["expert_bias"] if self.use_expert_bias \
            else jnp.zeros_like(w["expert_bias"])
        y, c = moe_ffn(dict(w, expert_bias=bias), x, self.top_k, live,
                       self.norm_topk_prob, self.routed_scaling)
        counts.append(c)
        return y

    def _counters(self, counts):
        if not counts:
            return jnp.zeros(2 + self.n_experts, jnp.int32)
        return jnp.concatenate([
            jnp.stack([sum(c["pairs"] for c in counts),
                       sum(c["experts_touched"] for c in counts)]),
            sum(c["expert_tokens"] for c in counts)]).astype(jnp.int32)

    def _logits(self, params, x):
        h = rms_norm(x, params["embedding_norm"], self.norm_eps)
        e = params["embed"]
        return jax.lax.dot_general(h.astype(e.dtype), e,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    # -- the two served forwards -----------------------------------------
    def forward_decode_paged(self, params, tokens, pos, pools,
                             block_tables, impl: str = "auto", *,
                             state, live):
        """One decode step for the slot batch. tokens, pos [S]; pools
        [N, H_kv, Bs, 2 * D] an attention layer (key and value side by
        side: `kernels/paged_attention.py`); block_tables [S, B];
        ``state`` as :meth:`slot_state_shapes` declares; ``live`` [S]
        bool: a lane that is not live writes no state, routes to no
        expert and counts nowhere (its K/V write lands in the null
        block, as its table says). Returns (logits [S, V], pools,
        state, counters)."""
        S = tokens.shape[0]
        Bs = pools[0].shape[2] if pools else 1
        x = params["embed"][tokens].astype(jnp.float32)
        pools, state = list(pools), list(state)
        counts: List[Dict] = []
        ai = ci = 0
        for i, w in enumerate(params["layers"]):
            h = rms_norm(x, w["operator_norm"], self.norm_eps)
            if self.layer_types[i] == "conv":
                with jax.named_scope("lfm2.conv"):
                    st = state[ci]
                    op, new = self._conv_step(w, h, st)
                    state[ci] = jnp.where(live[:, None, None], new, st)
                ci += 1
            else:
                with jax.named_scope("lfm2.attn"):
                    q, k, v = self._qkv(w, h, pos)
                    blk = jnp.take_along_axis(
                        block_tables, (pos // Bs)[:, None], axis=1)[:, 0]
                    at = (blk[:, None],
                          jnp.arange(self.n_kv_heads)[None, :],
                          (pos % Bs)[:, None])
                    pools[ai] = kv_pool_set(pools[ai], at, k, v)
                    att = paged_attention(q, pools[ai], block_tables,
                                          pos + 1, impl=impl)
                    op = self._mm(att.reshape(S, self.d_model), w["Wo"])
                ai += 1
            x = x + op
            x = x + self._ff(i, w, rms_norm(x, w["ffn_norm"], self.norm_eps),
                             live, counts)
        return (self._logits(params, x), pools, state,
                self._counters(counts))

    def forward_prefill_chunk(self, params, tokens, p0, chunk_len, pools,
                              block_table, *, state, slot,
                              last_only: bool = False):
        """One prefill chunk of the request in ``slot``. tokens [1, C];
        p0, chunk_len scalars; block_table [n_blocks]. The chunk reads
        row ``slot`` of every state array (zeros instead where
        ``p0 == 0``: a request never inherits its slot's last occupant)
        and writes back the state after its last valid row; an attention
        layer writes the chunk's K and V into its pool by blocks and
        attends over the sequence's span as it comes back out
        (:func:`~..kernels.paged_attention.paged_prefill_attention`).
        Rows past ``chunk_len`` route to no expert. Returns (logits
        [C, V], pools, state, counters); with ``last_only`` the final
        norm and the head run for the sampled row and not for the chunk
        (:func:`~..nn.functional.sampled_row_logits`) and the logits
        are ``[1, V]``."""
        C = tokens.shape[1]
        gpos = p0 + jnp.arange(C)
        live = jnp.arange(C) < chunk_len
        x = params["embed"][tokens[0]].astype(jnp.float32)
        x = jnp.where(live[:, None], x, 0.0)
        pools, state = list(pools), list(state)
        counts: List[Dict] = []
        keep = self.conv_taps - 1
        ai = ci = 0
        for i, w in enumerate(params["layers"]):
            h = rms_norm(x, w["operator_norm"], self.norm_eps)
            if self.layer_types[i] == "conv":
                with jax.named_scope("lfm2.conv"):
                    st = state[ci]
                    prev = jnp.where(p0 == 0, jnp.zeros_like(st[0]),
                                     st[slot])
                    op, v = self._conv(w, h, prev)
                    # the inputs before position p0 + chunk_len
                    ext = jnp.concatenate([prev, v.astype(st.dtype)], 0)
                    last = jax.lax.dynamic_slice_in_dim(ext, chunk_len,
                                                        keep, 0)
                    state[ci] = jax.lax.dynamic_update_slice_in_dim(
                        st, last[None], slot, 0)
                ci += 1
            else:
                with jax.named_scope("lfm2.attn"):
                    q, k, v = self._qkv(w, h, gpos)
                    pools[ai] = kv_pool_set_span(pools[ai], block_table,
                                                 p0, k, v)
                    att = paged_prefill_attention(q, pools[ai],
                                                  block_table, p0)
                    op = self._mm(att.reshape(C, self.d_model), w["Wo"])
                ai += 1
            x = x + op
            x = x + self._ff(i, w, rms_norm(x, w["ffn_norm"], self.norm_eps),
                             live, counts)
        head = functools.partial(self._logits, params)
        logits = (sampled_row_logits(x, chunk_len, head) if last_only
                  else head(x))
        return logits, pools, state, self._counters(counts)

"""SmallThinker-shaped causal LM for the serving path: attention layers
of two kinds in one stack (global layers that keep every position and
carry no positional encoding; window layers that attend the last
``sliding_window_size`` positions with rotary positions), grouped-query
heads, a routed mixture of ReLU-gated experts in every layer whose
router reads the BLOCK'S INPUT, RMSNorm, an untied head, bfloat16
weights.

The class has the surface ``GenerationEngine`` serves (``vocab_size``,
``max_seq_len``, ``eos_id``, ``_params``, ``init``, ``cache_shapes``,
``forward_decode_paged``, ``forward_prefill_chunk``, ``step_account``)
plus the one addition of a model whose layers do not all keep the same
positions: :meth:`cache_groups` declares which layers' pools share a
block table and an allocator, and which of those groups is a window
(the engine then keeps that group's table as a RING a slot and hands
both forwards one table a group, in the declared order). See
docs/generation.md, "Cache groups".

Block ``l`` (x [T, D] float32 residual stream; RMSNorm in float32, no
bias anywhere):

    r = x W_r                                   # router logits, from x
    a = RMSNorm(x; input_layernorm)
    q, k, v = a Wq, a Wk, a Wv                  # no q/k norm
    q, k = rope(q), rope(k)   where rope_layout[l] == 1 (rotate-half)
    h = x + Attn(q, k, v) Wo                    # j <= i, and j > i - W
                                                # where sliding_window_layout[l] == 1
    m = RMSNorm(h; post_attention_layernorm)
    E = top_k(r);  w = softmax(r[E])
    y = h + sum_{e in E} w_e (relu(m W1_e) * (m W3_e)) W2_e

then ``RMSNorm(y; norm) head``. Matmul operands take ``dtype``
(bfloat16 as published; float32 in the CPU tests) with float32
accumulation; the residual stream, norms, router (at full precision),
rotary and softmax are float32; the pools take the engine's
``kv_dtype``; logits are float32.
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
from .lfm2_moe import rms_norm, rope

#: the two cache groups, in the order both forwards take their tables
GLOBAL, WINDOW = "global", "window"


class SmallThinkerLM:
    """The served class. Constructor keys are those of the published
    ``config.json`` (``model_name`` ``smallthinker_*``) plus ``dtype``;
    ``max_seq_len`` bounds what an engine may ask of it (there is no
    position table)."""

    def __init__(self, vocab_size: int, hidden_size: int, head_dim: int,
                 num_hidden_layers: int, num_attention_heads: int,
                 num_key_value_heads: int, moe_ffn_hidden_size: int,
                 moe_num_primary_experts: int,
                 moe_num_active_primary_experts: int,
                 sliding_window_layout: Sequence[int],
                 rope_layout: Sequence[int],
                 sliding_window_size: int = 4096,
                 rope_theta: float = 1.5e6, rms_norm_eps: float = 1e-6,
                 moe_primary_router_apply_softmax: bool = True,
                 norm_topk_prob: bool = True,
                 tie_word_embeddings: bool = False,
                 max_position_embeddings: int = 16384,
                 dtype: str = "bfloat16", eos_id: Optional[int] = None,
                 seed: int = 0, **_):
        n = int(num_hidden_layers)
        if len(sliding_window_layout) != n or len(rope_layout) != n:
            raise ValueError(
                f"{len(sliding_window_layout)} window flags and "
                f"{len(rope_layout)} rope flags for {n} layers")
        if not moe_primary_router_apply_softmax:
            raise ValueError("only the softmax-over-chosen router is "
                             "supported")
        if tie_word_embeddings:
            raise ValueError("a tied head is not supported")
        self.vocab_size = int(vocab_size)
        self.d_model = int(hidden_size)
        self.head_dim = int(head_dim)
        self.n_layers = n
        self.n_heads = int(num_attention_heads)
        self.n_kv_heads = int(num_key_value_heads)
        self.d_expert = int(moe_ffn_hidden_size)
        self.n_experts = int(moe_num_primary_experts)
        self.top_k = int(moe_num_active_primary_experts)
        self.window_layout = [int(f) for f in sliding_window_layout]
        self.rope_layout = [int(f) for f in rope_layout]
        self.window = int(sliding_window_size)
        self.rope_theta = float(rope_theta)
        self.norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_position_embeddings)
        self.dtype = jnp.dtype(dtype)
        self.eos_id = eos_id
        self.seed = int(seed)
        self._params = None

    # -- lifecycle -----------------------------------------------------
    def init(self) -> "SmallThinkerLM":
        """N(0, 0.02) matrices, norm weights 1."""
        D, dt = self.d_model, self.dtype
        keys = iter(jax.random.split(jax.random.PRNGKey(self.seed),
                                     16 * (self.n_layers + 1)))

        def mat(*shape, dtype=dt):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * 0.02).astype(dtype)

        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        E, F = self.n_experts, self.d_expert
        layers = [{"input_layernorm": jnp.ones((D,), jnp.float32),
                   "post_attention_layernorm": jnp.ones((D,), jnp.float32),
                   "W_r": mat(D, E, dtype=jnp.float32),
                   "Wq": mat(D, q), "Wk": mat(D, kv), "Wv": mat(D, kv),
                   "Wo": mat(q, D), "W1": mat(E, D, F), "W3": mat(E, D, F),
                   "W2": mat(E, F, D)} for _ in range(self.n_layers)]
        self._params = {"embed": mat(self.vocab_size, D),
                        "norm": jnp.ones((D,), jnp.float32),
                        "head": mat(D, self.vocab_size), "layers": layers}
        return self

    # -- what the cache manager allocates --------------------------------
    def cache_shapes(self, max_seq_len: Optional[int] = None
                     ) -> List[Tuple[int, int, int]]:
        """K (== V) shape a sequence, a layer: every layer attends."""
        n = self.max_seq_len if max_seq_len is None else int(max_seq_len)
        return [(self.n_kv_heads, n, self.head_dim)] * self.n_layers

    def cache_groups(self) -> List[Dict]:
        """The layers of :meth:`cache_shapes` by what they keep: a
        group's pools share one block table a sequence; ``window``
        positions at most are read of a window group's, whose table is
        a ring. Both forwards take one table a group, in this order."""
        kinds = ((GLOBAL, 0, None), (WINDOW, 1, self.window))
        return [{"name": name, "window": window,
                 "layers": [i for i, f in enumerate(self.window_layout)
                            if f == flag]}
                for name, flag, window in kinds
                if flag in self.window_layout]

    def step_account(self):
        """What the counter vectors both forwards return (laid out as
        :data:`.lfm2_moe.STEP_COUNTERS`) add up into, an engine: the
        ``moe`` block of its ``/stats``."""
        return MoeAccount(self.n_layers * self.n_experts)

    # -- pieces ------------------------------------------------------------
    def _mm(self, x, w):
        return jnp.dot(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)

    def _group_of(self) -> List[int]:
        """Each layer's index into the tables both forwards take."""
        names = [g["name"] for g in self.cache_groups()]
        return [names.index(WINDOW if f else GLOBAL)
                for f in self.window_layout]

    def _router(self, w, x):
        """Logits over the experts from the block's INPUT (before any
        norm), in float32 at full precision: a bf16 pass would flip
        choices the reference makes."""
        return jnp.dot(x, w["W_r"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)

    def _qkv(self, i, w, x, pos):
        T = x.shape[0]
        q = self._mm(x, w["Wq"]).reshape(T, self.n_heads, self.head_dim)
        k = self._mm(x, w["Wk"]).reshape(T, self.n_kv_heads, self.head_dim)
        v = self._mm(x, w["Wv"]).reshape(T, self.n_kv_heads, self.head_dim)
        if self.rope_layout[i]:
            q, k = rope(q, pos, self.rope_theta), rope(k, pos,
                                                       self.rope_theta)
        return q, k, v

    def _experts(self, w, h, logits, live, counts):
        y, c = moe_ffn(w, rms_norm(h, w["post_attention_layernorm"],
                                   self.norm_eps),
                       self.top_k, live, router_logits=logits,
                       scoring="softmax", gate="relu", scope="smallthinker")
        counts.append(c)
        return y

    def _counters(self, counts):
        return jnp.concatenate([
            jnp.stack([sum(c["pairs"] for c in counts),
                       sum(c["experts_touched"] for c in counts)]),
            sum(c["expert_tokens"] for c in counts)]).astype(jnp.int32)

    def _logits(self, params, x):
        return self._mm(rms_norm(x, params["norm"], self.norm_eps),
                        params["head"])

    # -- the two served forwards -----------------------------------------
    def forward_decode_paged(self, params, tokens, pos, pools,
                             block_tables, impl: str = "auto", *,
                             state=(), live):
        """One decode step for the slot batch. tokens, pos [S]; pools
        [N_group, H_kv, Bs, 2 * D] a layer; ``block_tables`` one
        ``[S, B_group]`` a group of :meth:`cache_groups` (the window
        group's a ring: position p in entry ``(p // Bs) % B``); ``live``
        [S] bool: a lane that is not live routes to no expert and counts
        nowhere (its K/V write lands in the null block, as its tables
        say). Returns (logits [S, V], pools, state, counters)."""
        S = tokens.shape[0]
        Bs = pools[0].shape[2]
        x = params["embed"][tokens].astype(jnp.float32)
        pools = list(pools)
        group = self._group_of()
        counts: List[Dict] = []
        for i, w in enumerate(params["layers"]):
            windowed = bool(self.window_layout[i])
            tbl = block_tables[group[i]]
            with jax.named_scope("smallthinker.moe.route"):
                logits = self._router(w, x)
            with jax.named_scope("smallthinker.attn.window" if windowed
                                 else "smallthinker.attn.global"):
                q, k, v = self._qkv(
                    i, w, rms_norm(x, w["input_layernorm"], self.norm_eps),
                    pos)
                entry = pos // Bs
                if windowed:
                    entry = entry % tbl.shape[1]
                blk = jnp.take_along_axis(tbl, entry[:, None], axis=1)[:, 0]
                at = (blk[:, None], jnp.arange(self.n_kv_heads)[None, :],
                      (pos % Bs)[:, None])
                pools[i] = kv_pool_set(pools[i], at, k, v)
                att = paged_attention(
                    q, pools[i], tbl, pos + 1, impl=impl,
                    window=self.window if windowed else None)
                x = x + self._mm(att.reshape(S, -1), w["Wo"])
            x = x + self._experts(w, x, logits, live, counts)
        return (self._logits(params, x), pools, list(state),
                self._counters(counts))

    def forward_prefill_chunk(self, params, tokens, p0, chunk_len, pools,
                              block_table, *, state=(), slot=None,
                              last_only: bool = False):
        """One prefill chunk of a request. tokens [1, C]; p0, chunk_len
        scalars; ``block_table`` one table a group: the global group's
        ``[n_blocks]`` bucket and the window group's ring. A layer
        writes the chunk's K and V into its pool by blocks and attends
        over the sequence's span (its window) as it comes back out.
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
        pools = list(pools)
        group = self._group_of()
        counts: List[Dict] = []
        for i, w in enumerate(params["layers"]):
            windowed = bool(self.window_layout[i])
            tbl = block_table[group[i]]
            with jax.named_scope("smallthinker.moe.route"):
                logits = self._router(w, x)
            with jax.named_scope("smallthinker.attn.window" if windowed
                                 else "smallthinker.attn.global"):
                q, k, v = self._qkv(
                    i, w, rms_norm(x, w["input_layernorm"], self.norm_eps),
                    gpos)
                pools[i] = kv_pool_set_span(pools[i], tbl, p0, k, v,
                                            ring=windowed)
                att = paged_prefill_attention(
                    q, pools[i], tbl, p0, chunk_len,
                    window=self.window if windowed else None)
                x = x + self._mm(att.reshape(C, -1), w["Wo"])
            x = x + self._experts(w, x, logits, live, counts)
        head = functools.partial(self._logits, params)
        out = (sampled_row_logits(x, chunk_len, head) if last_only
               else head(x))
        return out, pools, list(state), self._counters(counts)

"""Jamba-shaped causal LM for the serving path: Mamba-1 (selective
state-space) layers beside a few multi-query attention layers, a dense
gated MLP in every layer, RMSNorm, no positional encoding of any kind,
a head tied to the embedding, bfloat16 weights.

The class has the surface ``GenerationEngine`` serves (``vocab_size``,
``max_seq_len``, ``eos_id``, ``_params``, ``init``, ``cache_shapes``,
``slot_state_shapes``, ``forward_decode_paged``,
``forward_prefill_chunk``, ``step_account``) and keeps two kinds of
layer state in one model: keys and values of the attention layers in
the paged pool (``cache_shapes`` lists those layers only), and for
every Mamba layer two arrays a SLOT of different types
(:meth:`slot_state_shapes`): the recurrence's ``h`` ``[N, Di]`` float32
and the last ``mamba_d_conv - 1`` inputs of its convolution in the
weights' dtype. See docs/generation.md, "Models with state a slot".

Layer ``l`` is attention where ``l % attn_layer_period ==
attn_layer_offset`` and Mamba elsewhere. Block (x [T, D] float32
residual stream; RMSNorm in float32, no bias but the convolution's and
``dt``'s):

    h = x + Mixer_l(RMSNorm(x; input_layernorm))
    y = h + (silu(n W_gate) * (n W_up)) W_down,  n = RMSNorm(h; pre_ff_layernorm)

then ``RMSNorm(y; final_layernorm)`` against the embedding. The Mamba
mixer is :mod:`deeplearning4j_tpu.nn.layers.mamba`; attention is
``num_attention_heads`` query heads over ``num_key_value_heads`` KV
heads, keys stored as computed (no rotation, no q/k norm), causal.

Matmul operands take ``dtype`` (bfloat16 as published; float32 in the
CPU tests) with float32 accumulation; the residual stream, norms, the
convolution, ``dt``, ``A``, the recurrence and its state are float32;
the pools take the engine's ``kv_dtype``; logits are float32.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention import (kv_pool_set, kv_pool_set_span,
                                       paged_attention,
                                       paged_prefill_attention)
from ..nn.functional import sampled_row_logits
from ..nn.layers.mamba import SsmAccount, mamba_chunk, mamba_step
from .lfm2_moe import rms_norm

#: both forwards return one int32 beside their logits: live rows (a
#: chunk's rows, a step's lanes) x Mamba layers
STEP_COUNTERS = ("ssm_rows",)


class JambaLM:
    """The served class. Constructor keys are those of the published
    ``config.json`` (``model_type`` ``jamba``) plus ``dtype``;
    ``max_seq_len`` bounds what an engine may ask of it (there is no
    position table)."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 intermediate_size: int, num_hidden_layers: int,
                 num_attention_heads: int, num_key_value_heads: int,
                 attn_layer_period: int, attn_layer_offset: int,
                 mamba_d_state: int = 16, mamba_d_conv: int = 4,
                 mamba_dt_rank: int = 160, mamba_expand: int = 2,
                 mamba_conv_bias: bool = True,
                 mamba_proj_bias: bool = False, num_experts: int = 1,
                 rms_norm_eps: float = 1e-6,
                 tie_word_embeddings: bool = True,
                 sliding_window: Optional[int] = None,
                 max_position_embeddings: int = 262144,
                 dtype: str = "bfloat16", eos_id: Optional[int] = None,
                 seed: int = 0, **_):
        if num_experts != 1:
            raise ValueError("only the dense MLP (num_experts 1) is "
                             "supported")
        if mamba_proj_bias or not mamba_conv_bias:
            raise ValueError("the mixer has a convolution bias and no "
                             "projection bias")
        if not tie_word_embeddings or sliding_window is not None:
            raise ValueError("an untied head or a sliding window is not "
                             "supported")
        self.vocab_size = int(vocab_size)
        self.d_model = int(hidden_size)
        self.d_ff = int(intermediate_size)
        self.n_layers = int(num_hidden_layers)
        self.n_heads = int(num_attention_heads)
        self.n_kv_heads = int(num_key_value_heads)
        self.head_dim = self.d_model // self.n_heads
        self.d_inner = int(mamba_expand) * self.d_model
        self.d_state = int(mamba_d_state)
        self.conv_taps = int(mamba_d_conv)
        self.dt_rank = int(mamba_dt_rank)
        self.norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_position_embeddings)
        self.dtype = jnp.dtype(dtype)
        self.eos_id = eos_id
        self.seed = int(seed)
        self.attn_layers = [
            i for i in range(self.n_layers)
            if i % int(attn_layer_period) == int(attn_layer_offset)]
        self.mamba_layers = [i for i in range(self.n_layers)
                             if i not in self.attn_layers]
        self._params = None

    # -- lifecycle -----------------------------------------------------
    def init(self) -> "JambaLM":
        """N(0, 0.02) matrices, norm weights 1, and the Mamba paper's
        own start for what shapes the recurrence: ``A = -(1 .. N)`` a
        channel, ``D`` 1, ``b_dt`` the inverse softplus of a step size
        log-uniform in [0.001, 0.1], convolution taps U(-0.5, 0.5) and
        bias 0."""
        D, Di, N, R, F = (self.d_model, self.d_inner, self.d_state,
                          self.dt_rank, self.d_ff)
        dt = self.dtype
        keys = iter(jax.random.split(jax.random.PRNGKey(self.seed),
                                     16 * (self.n_layers + 1)))

        def mat(*shape, dtype=dt):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * 0.02).astype(dtype)

        def uniform(lo, hi, *shape):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        ones = lambda n: jnp.ones((n,), jnp.float32)      # noqa: E731
        layers = []
        for i in range(self.n_layers):
            w = {"input_layernorm": ones(D), "pre_ff_layernorm": ones(D),
                 "W_gate": mat(D, F), "W_up": mat(D, F), "W_down": mat(F, D)}
            if i in self.attn_layers:
                q, kv = D, self.n_kv_heads * self.head_dim
                w.update(Wq=mat(D, q), Wk=mat(D, kv), Wv=mat(D, kv),
                         Wo=mat(q, D))
            else:
                step = jnp.exp(uniform(math.log(1e-3), math.log(0.1), Di))
                w.update(
                    W_in=mat(D, 2 * Di), W_x=mat(Di, R + 2 * N),
                    W_dt=mat(R, Di), W_out=mat(Di, D),
                    conv_w=uniform(-0.5, 0.5, Di, self.conv_taps),
                    conv_b=jnp.zeros((Di,), jnp.float32),
                    b_dt=step + jnp.log(-jnp.expm1(-step)),
                    dt_norm=ones(R), b_norm=ones(N), c_norm=ones(N),
                    A=-jnp.broadcast_to(
                        jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                        (N, Di)),
                    D=ones(Di))
            layers.append(w)
        self._params = {"embed": mat(self.vocab_size, D),
                        "final_layernorm": ones(D), "layers": layers}
        return self

    # -- what the cache manager allocates --------------------------------
    def cache_shapes(self, max_seq_len: Optional[int] = None
                     ) -> List[Tuple[int, int, int]]:
        """K (== V) shape a sequence, for the layers that HAVE paged
        keys and values: the attention layers."""
        n = self.max_seq_len if max_seq_len is None else int(max_seq_len)
        return [(self.n_kv_heads, n, self.head_dim)] * len(self.attn_layers)

    def slot_state_shapes(self, num_slots: int):
        """The arrays kept a SLOT, as (shape, dtype) with the slot
        first, two a Mamba layer in layer order: the recurrence's
        ``h`` ``[N, Di]`` float32, then the convolution's last
        ``mamba_d_conv - 1`` inputs in the weights' dtype. A request's
        first chunk starts from zeros whatever the slot held; the
        engine never clears them."""
        S = int(num_slots)
        return [spec for _ in self.mamba_layers for spec in (
            ((S, self.d_state, self.d_inner), jnp.float32),
            ((S, self.conv_taps - 1, self.d_inner), self.dtype))]

    def step_account(self):
        """What the :data:`STEP_COUNTERS` vectors add up into, an
        engine: the ``ssm`` block of its ``/stats``."""
        return SsmAccount(sum(
            math.prod(shape[1:]) * jnp.dtype(dtype).itemsize
            for shape, dtype in self.slot_state_shapes(1)))

    # -- pieces ------------------------------------------------------------
    def _mm(self, x, w):
        return jnp.dot(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)

    def _qkv(self, w, x):
        T = x.shape[0]
        return (self._mm(x, w["Wq"]).reshape(T, self.n_heads, self.head_dim),
                self._mm(x, w["Wk"]).reshape(T, self.n_kv_heads,
                                             self.head_dim),
                self._mm(x, w["Wv"]).reshape(T, self.n_kv_heads,
                                             self.head_dim))

    def _mlp(self, w, x):
        with jax.named_scope("jamba.mlp"):
            n = rms_norm(x, w["pre_ff_layernorm"], self.norm_eps)
            return self._mm(jax.nn.silu(self._mm(n, w["W_gate"]))
                            * self._mm(n, w["W_up"]), w["W_down"])

    def _logits(self, params, x):
        h = rms_norm(x, params["final_layernorm"], self.norm_eps)
        e = params["embed"]
        return jax.lax.dot_general(h.astype(e.dtype), e,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def _stack(self, params, x, attend, mix):
        """The blocks over x: ``attend(w, n, a)`` is attention layer
        number ``a``'s mixer over normed rows ``n``, ``mix(w, n, m)``
        Mamba layer number ``m``'s."""
        a = m = 0
        for i, w in enumerate(params["layers"]):
            n = rms_norm(x, w["input_layernorm"], self.norm_eps)
            if i in self.attn_layers:
                with jax.named_scope("jamba.attn"):
                    x = x + attend(w, n, a)
                a += 1
            else:
                x = x + mix(w, n, m)
                m += 1
            x = x + self._mlp(w, x)
        return x

    def _counters(self, rows):
        return (rows * len(self.mamba_layers)).astype(jnp.int32).reshape(1)

    # -- the two served forwards -----------------------------------------
    def forward_decode_paged(self, params, tokens, pos, pools,
                             block_tables, impl: str = "auto", *,
                             state, live):
        """One decode step for the slot batch. tokens, pos [S]; pools
        [N, H_kv, Bs, 2 * D] an attention layer; block_tables [S, B];
        ``state`` as :meth:`slot_state_shapes` declares; ``live`` [S]
        bool: a lane that is not live writes no state and counts
        nowhere (its K/V write lands in the null block, as its table
        says). Returns (logits [S, V], pools, state, counters)."""
        S = tokens.shape[0]
        Bs = pools[0].shape[2]
        pools, state = list(pools), list(state)

        def attend(w, n, a):
            q, k, v = self._qkv(w, n)
            blk = jnp.take_along_axis(
                block_tables, (pos // Bs)[:, None], axis=1)[:, 0]
            at = (blk[:, None], jnp.arange(self.n_kv_heads)[None, :],
                  (pos % Bs)[:, None])
            pools[a] = kv_pool_set(pools[a], at, k, v)
            att = paged_attention(q, pools[a], block_tables, pos + 1,
                                  impl=impl)
            return self._mm(att.reshape(S, self.d_model), w["Wo"])

        def mix(w, n, m):
            out, state[2 * m + 1], state[2 * m] = mamba_step(
                w, n, state[2 * m + 1], state[2 * m], live, self.norm_eps)
            return out

        x = self._stack(params, params["embed"][tokens].astype(jnp.float32),
                        attend, mix)
        return (self._logits(params, x), pools, state,
                self._counters(live.sum()))

    def forward_prefill_chunk(self, params, tokens, p0, chunk_len, pools,
                              block_table, *, state, slot,
                              last_only: bool = False):
        """One prefill chunk of the request in ``slot``. tokens [1, C];
        p0, chunk_len scalars; block_table [n_blocks]. A Mamba layer
        reads row ``slot`` of its two state arrays (zeros instead where
        ``p0 == 0``: a request never inherits its slot's last occupant)
        and writes back the state after its last valid row; an
        attention layer writes the chunk's K and V into its pool by
        blocks and attends over the sequence's span as it comes back
        out. Returns (logits [C, V], pools, state, counters); with
        ``last_only`` the final norm and the head run for the sampled
        row and not for the chunk and the logits are ``[1, V]``."""
        C = tokens.shape[1]
        live = jnp.arange(C) < chunk_len
        pools, state = list(pools), list(state)

        def attend(w, n, a):
            q, k, v = self._qkv(w, n)
            pools[a] = kv_pool_set_span(pools[a], block_table, p0, k, v)
            att = paged_prefill_attention(q, pools[a], block_table, p0,
                                          chunk_len)
            return self._mm(att.reshape(C, self.d_model), w["Wo"])

        def mix(w, n, m):
            hs, cs = state[2 * m], state[2 * m + 1]
            out, conv, h = mamba_chunk(
                w, n, jnp.where(p0 == 0, jnp.zeros_like(cs[0]), cs[slot]),
                jnp.where(p0 == 0, jnp.zeros_like(hs[0]), hs[slot]),
                chunk_len, self.norm_eps)
            state[2 * m] = jax.lax.dynamic_update_slice_in_dim(
                hs, h[None], slot, 0)
            state[2 * m + 1] = jax.lax.dynamic_update_slice_in_dim(
                cs, conv[None], slot, 0)
            return out

        x = params["embed"][tokens[0]].astype(jnp.float32)
        x = self._stack(params, jnp.where(live[:, None], x, 0.0), attend,
                        mix)
        head = functools.partial(self._logits, params)
        logits = (sampled_row_logits(x, chunk_len, head) if last_only
                  else head(x))
        return logits, pools, state, self._counters(chunk_len)

"""Decoder-only causal transformer LM — the generation-serving workload.

Ref role: `zoo/model/TextGenerationLSTM.java` is the reference's
autoregressive text model (LSTM char-level, sampled token by token in
the GravesLSTM example loop). TPU-native, the same capability is a
causal transformer built from the layer DSL's attention blocks
(`nn/layers/attention.py`), with an explicit CACHED decode path so the
serving runtime (`serving/generation.py`) can run token-by-token
generation against a static-shape KV cache instead of re-running the
full prefix every step (O(T) per token instead of O(T^2) per sequence).

Two forward surfaces, both pure functions over an explicit params
pytree (so the serving engine can AOT-compile them with the weights as
executable ARGUMENTS, never baked-in constants):

- :meth:`forward_prefill`: full-prompt causal pass → per-position
  logits plus each block's K/V rows for the cache.
- :meth:`forward_decode`: one token per sequence against the cache
  (write K/V at ``pos``, attend over the prefix) → next-token logits.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..nn.functional import layer_norm, sampled_row_logits
from ..nn.layers.attention import TransformerEncoderLayer


class CausalTransformerLM:
    """Token-in/logits-out causal LM with a cached decode path.

    Learned token + position embeddings, ``n_layers`` pre-LN
    transformer blocks (causal self-attention), final LayerNorm, and a
    linear head to vocab logits. ``max_seq_len`` bounds the position
    table AND the decode cache capacity — the static shape everything
    downstream compiles against.
    """

    def __init__(self, vocab_size: int, d_model: int = 128,
                 n_layers: int = 2, n_heads: int = 4,
                 d_ff: Optional[int] = None, max_seq_len: int = 256,
                 eos_id: Optional[int] = None, seed: int = 0,
                 implementation: str = "auto"):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.max_seq_len = int(max_seq_len)
        self.eos_id = eos_id
        self.seed = int(seed)
        self.blocks: List[TransformerEncoderLayer] = []
        for _ in range(self.n_layers):
            blk = TransformerEncoderLayer(n_heads=n_heads, d_ff=d_ff,
                                          causal=True,
                                          implementation=implementation)
            blk.build((self.max_seq_len, self.d_model))
            self.blocks.append(blk)
        self._params = None

    # -- lifecycle -----------------------------------------------------
    def init(self) -> "CausalTransformerLM":
        rng = jax.random.PRNGKey(self.seed)
        k_tok, k_pos, k_head, k_blocks = jax.random.split(rng, 4)
        V, D = self.vocab_size, self.d_model
        params = {
            "tok": jax.random.normal(k_tok, (V, D)) * 0.02,
            "pos": jax.random.normal(k_pos, (self.max_seq_len, D)) * 0.02,
            "lnf_g": jnp.ones((D,)), "lnf_b": jnp.zeros((D,)),
            "head": jax.random.normal(k_head, (D, V)) * 0.02,
            "blocks": [blk.init_params(k)
                       for blk, k in zip(self.blocks,
                                         jax.random.split(k_blocks,
                                                          self.n_layers))],
        }
        self._params = params
        return self

    def cache_shapes(self,
                     max_seq_len: Optional[int] = None
                     ) -> List[Tuple[int, int, int]]:
        """Per-layer per-sequence K (== V) cache shape:
        [n_heads, max_seq_len, head_dim]. Pass a smaller
        ``max_seq_len`` to size a cache below the model's position
        table (the serving engine does — decode cost scans the full
        cache capacity every step, so capacity should match the
        configured sequence bound, not the architectural one)."""
        n = self.max_seq_len if max_seq_len is None else int(max_seq_len)
        if n > self.max_seq_len:
            raise ValueError(f"cache length {n} exceeds the position "
                             f"table ({self.max_seq_len})")
        return [blk.cache_shape(n) for blk in self.blocks]

    # -- pure forwards -------------------------------------------------
    def forward_prefill(self, params, tokens, key_mask=None):
        """Full-prompt causal pass. tokens: [B, T] int32 (T <= the
        compiled bucket); key_mask: optional [B, T] validity for padded
        prompts. Returns (logits [B, T, V], ks, vs) where ks/vs are
        per-layer [B, H, T, Dh] slabs in decode-cache layout."""
        B, T = tokens.shape
        x = params["tok"][tokens] + params["pos"][jnp.arange(T)][None]
        if key_mask is not None:
            x = x * key_mask[..., None]
        ks, vs = [], []
        for blk, bp in zip(self.blocks, params["blocks"]):
            x, k, v = blk.apply_prefill(bp, x, key_mask)
            ks.append(k)
            vs.append(v)
        x = layer_norm(x, params["lnf_g"], params["lnf_b"])
        return x @ params["head"], ks, vs

    def forward_decode(self, params, tokens, pos, k_caches, v_caches,
                       impl: str = "auto"):
        """One cached decode step for a batch of sequences (slots).
        tokens: [S] int32 current token per slot; pos: [S] int32 its
        position; k_caches/v_caches: per-layer [S, H, T_max, Dh].
        Returns (logits [S, V], k_caches, v_caches) with each layer's
        K/V written at ``pos``."""
        x = params["tok"][tokens] + params["pos"][pos]
        new_k, new_v = [], []
        for blk, bp, kc, vc in zip(self.blocks, params["blocks"],
                                   k_caches, v_caches):
            x, kc, vc = blk.apply_decode(bp, x, kc, vc, pos, impl)
            new_k.append(kc)
            new_v.append(vc)
        x = layer_norm(x, params["lnf_g"], params["lnf_b"])
        return x @ params["head"], new_k, new_v

    # -- paged KV cache (serving/paging) --------------------------------
    def forward_decode_paged(self, params, tokens, pos, pools,
                             block_tables, impl: str = "auto", state=()):
        """One cached decode step against the PAGED pools. Same
        contract as :meth:`forward_decode` with one pool a layer,
        [num_blocks, H, block_size, 2 * Dh] (a position's key and value
        side by side: `kernels/paged_attention.py`), addressed through
        ``block_tables`` [S, n_blocks] (NULL_BLOCK-padded; inactive
        rows must be all-NULL so their writes land in the null block).
        ``state`` is what the engine threads through every paged
        program for a model that keeps arrays a slot; this one keeps
        none and hands it back as it came.
        Returns (logits [S, V], pools, state)."""
        x = params["tok"][tokens] + params["pos"][pos]
        new_pools = []
        for blk, bp, pool in zip(self.blocks, params["blocks"], pools):
            x, pool = blk.apply_decode_paged(bp, x, pool, block_tables,
                                             pos, impl)
            new_pools.append(pool)
        x = layer_norm(x, params["lnf_g"], params["lnf_b"])
        return x @ params["head"], new_pools, state

    def forward_prefill_chunk(self, params, tokens, p0, chunk_len,
                              pools, block_table, state=(),
                              last_only: bool = False):
        """One prefill CHUNK against the paged pools: embed the chunk
        at its global positions, run every block's
        ``apply_prefill_paged`` (scatter K/V into the owning blocks,
        attend causally over the prefix in the pool), and return the
        chunk's logits. The caller splits a prompt into chunks and
        feeds them in order; on the final chunk it samples from row
        ``chunk_len - 1``. With ``last_only`` the final norm and the
        head run for that row and not for the chunk
        (:func:`~..nn.functional.sampled_row_logits`) and the logits
        are ``[1, V]``: what the engine's chunk asks for; a caller that
        reads every row (speculative verification) leaves it off.

        tokens: [1, C] int32 (C = chunk bucket); p0: scalar int32
        chunk start; chunk_len: scalar int32 valid tokens in this
        chunk; block_table: [n_blocks] int32 covering at least
        ``p0 + C`` positions; ``state`` as in
        :meth:`forward_decode_paged`.
        Returns (logits [C, V] or [1, V], pools, state)."""
        C = tokens.shape[1]
        gpos = p0 + jnp.arange(C)
        # padded tail rows can run past the position table; clamp the
        # lookup — their embeddings are zeroed below and their K/V
        # lands beyond the live length, where the mask keeps it dark
        x = (params["tok"][tokens[0]]
             + params["pos"][jnp.clip(gpos, 0, self.max_seq_len - 1)])
        row_mask = (jnp.arange(C) < chunk_len).astype(x.dtype)
        x = (x * row_mask[:, None])[None]
        new_pools = []
        for blk, bp, pool in zip(self.blocks, params["blocks"], pools):
            x, pool = blk.apply_prefill_paged(bp, x, pool, block_table,
                                              p0, chunk_len)
            new_pools.append(pool)

        def head(h):
            return (layer_norm(h, params["lnf_g"], params["lnf_b"])
                    @ params["head"])
        logits = (sampled_row_logits(x[0], chunk_len, head) if last_only
                  else head(x[0]))
        return logits, new_pools, state

    def forward_verify(self, params, tokens, p0, chunk_len, k_caches,
                       v_caches, slot):
        """Multi-token verification span against the DENSE slot cache —
        the slot-backend sibling of :meth:`forward_prefill_chunk`, used
        by speculative decoding (serving/speculative.py) to score a
        draft's proposals in one causal pass. Same embedding/masking
        math as the chunk path; the paged scatter/gather is replaced by
        one slot panel.

        tokens: [1, C] int32 (C = verify bucket); p0: scalar int32 span
        start; chunk_len: scalar int32 valid tokens; slot: scalar int32
        cache row. Returns (logits [C, V], k_caches, v_caches)."""
        C = tokens.shape[1]
        gpos = p0 + jnp.arange(C)
        x = (params["tok"][tokens[0]]
             + params["pos"][jnp.clip(gpos, 0, self.max_seq_len - 1)])
        row_mask = (jnp.arange(C) < chunk_len).astype(x.dtype)
        x = (x * row_mask[:, None])[None]
        new_k, new_v = [], []
        for blk, bp, kc, vc in zip(self.blocks, params["blocks"],
                                   k_caches, v_caches):
            x, kc, vc = blk.apply_verify(bp, x, kc, vc, slot, p0,
                                         chunk_len)
            new_k.append(kc)
            new_v.append(vc)
        x = layer_norm(x[0], params["lnf_g"], params["lnf_b"])
        return x @ params["head"], new_k, new_v

    def logits(self, tokens) -> jnp.ndarray:
        """Convenience uncached full-sequence logits (tests/training
        harnesses; the serving path never calls this)."""
        if self._params is None:
            self.init()
        return self.forward_prefill(self._params,
                                    jnp.asarray(tokens, jnp.int32))[0]


def quantize_mlp_weights(model: CausalTransformerLM
                         ) -> CausalTransformerLM:
    """Convert every block's MLP weights (W1/W2) to int8 weight-only
    :class:`~deeplearning4j_tpu.kernels.kv_quant.QuantWeight` matrices
    in place (per-output-channel scales; biases, attention projections
    and norms stay f32). The serving-path MLP
    (`nn/layers/attention.py::TransformerEncoderLayer._mlp`) dispatches
    on the type — bf16-operand dots, f32 accumulation, dequant fused
    after the dot — so the quantized params pytree threads through the
    existing compiled-executable signatures unchanged. Idempotent.
    Returns the model for chaining."""
    from ..kernels.kv_quant import QuantWeight, quantize_weight
    if model._params is None:
        model.init()
    for bp in model._params["blocks"]:
        for name in ("W1", "W2"):
            if not isinstance(bp[name], QuantWeight):
                bp[name] = quantize_weight(bp[name])
    return model


def make_draft_lm(target: CausalTransformerLM, d_model: int = 32,
                  n_layers: int = 1, n_heads: int = 2,
                  d_ff: Optional[int] = None,
                  seed: Optional[int] = None) -> CausalTransformerLM:
    """Build a narrow/shallow draft LM for speculative decoding
    (serving/speculative.py), sharing the TARGET's token space — same
    vocab, same ``eos_id``, same position-table reach — so every draft
    proposal is a legal target token and the draft's cache cursor can
    track the target's positions one-for-one. Architecture is the
    knob: fewer/narrower layers make proposing k tokens cheaper than
    one target decode step; the accept rate (how often the target's
    sample agrees) is what the draft's capacity buys. Initialized and
    ready to serve; pass it to ``GenerationEngine(draft_model=...)``.

    ``seed`` defaults to ``target.seed + 1`` — a DIFFERENT stream than
    the target on purpose (a same-seed same-config draft would be the
    target itself: a valid identity-test rig, a pointless draft)."""
    draft = CausalTransformerLM(
        vocab_size=target.vocab_size, d_model=d_model,
        n_layers=n_layers, n_heads=n_heads, d_ff=d_ff,
        max_seq_len=target.max_seq_len, eos_id=target.eos_id,
        seed=target.seed + 1 if seed is None else seed)
    return draft.init()

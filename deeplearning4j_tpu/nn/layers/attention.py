"""Attention layers for the layer-DSL API.

The reference snapshot has NO attention op or layer (SURVEY.md §5.7 —
sequence capability = RNN family + TBPTT + masks; BERT only runs as an
imported TF graph of primitives). Long context is first-class here, so
the layer DSL exposes attention directly:

- :class:`SelfAttentionLayer`: multi-head self-attention over [B, T, C]
  sequence activations, masking-aware, with selectable compute path —
  plain fused XLA attention, the Pallas flash kernel
  (`kernels.flash_attention`), or chunked `blockwise_attention` for
  long sequences on one chip.
- :class:`TransformerEncoderLayer`: pre-LN block (attention + MLP with
  residuals) — the building block the reference reaches only via Keras/
  TF import.

Sequence parallelism (ring attention over a mesh axis) lives in
`parallel.longseq` / `parallel.transformer`; these layers are the
single-chip / data-parallel form of the same capability.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ...kernels.kv_quant import QuantArray, is_quantized, kv_set, mm
from ...weightinit import init_weights
from . import Layer, register


def _cache_row(cache, i):
    """Leading-axis row of a pool — for int8 QuantArrays the scale
    row rides along (same index: scale drops only the trailing axis)."""
    if is_quantized(cache):
        return QuantArray(cache.q[i], cache.scale[i])
    return cache[i]


@register
class SelfAttentionLayer(Layer):
    """Multi-head self-attention over recurrent-format [B, T, C] input."""

    kind = "selfattention"
    is_rnn = True

    def __init__(self, n_heads: int = 4, n_out: Optional[int] = None,
                 causal: bool = False, implementation: str = "auto",
                 **kw):
        kw.setdefault("activation", "identity")
        super().__init__(**kw)
        self.n_heads = int(n_heads)
        self.n_out = n_out
        self.causal = bool(causal)
        if implementation not in ("auto", "plain", "flash", "blockwise"):
            raise ValueError(f"unknown implementation {implementation!r}")
        self.implementation = implementation
        self.n_in: Optional[int] = None

    def build(self, input_shape, defaults=None):
        super().build(input_shape, defaults)
        self.n_in = int(input_shape[-1])
        if self.n_out is None:
            self.n_out = self.n_in
        if self.n_out % self.n_heads:
            raise ValueError(f"n_heads={self.n_heads} must divide "
                             f"n_out={self.n_out}")

    def param_shapes(self):
        d, o = self.n_in, self.n_out
        return {"Wq": (d, o), "Wk": (d, o), "Wv": (d, o), "Wo": (o, o),
                "b": (o,)}

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, 4)
        d, o = self.n_in, self.n_out
        p = {n: init_weights(k, (din, o), din, o, self.weight_init, dtype)
             for (n, din), k in zip(
                 [("Wq", d), ("Wk", d), ("Wv", d), ("Wo", o)], ks)}
        p["b"] = jnp.zeros((o,), dtype)
        return p

    def _attend(self, q, k, v, mask):
        from ...parallel.longseq import (blockwise_attention,
                                         dot_product_attention)
        impl = self.implementation
        if impl == "auto":
            # TPU: the Pallas flash kernel is the default once the
            # sequence is long enough to amortize the grid launch; it
            # handles key-padding masks natively. Elsewhere (CPU mesh)
            # the interpreter is slow, so use fused-XLA plain/blockwise.
            from ...flags import flags as _flags
            from ...kernels.flash_attention import default_platform
            on_tpu = default_platform() == "tpu"
            if (on_tpu and _flags.flash_attention
                    and q.shape[1] >= _flags.flash_min_seq):
                impl = "flash"
            else:
                impl = "blockwise" if q.shape[1] > 2048 else "plain"
        if impl == "flash":
            from ...kernels import flash_attention
            return flash_attention(q, k, v, causal=self.causal,
                                   key_mask=mask)
        if impl == "blockwise":
            return blockwise_attention(q, k, v, causal=self.causal,
                                       key_mask=mask)
        return dot_product_attention(
            q, k, v,
            mask=None if mask is None else mask[:, None, None, :] > 0,
            causal=self.causal)

    def apply_seq(self, params, x, state, train, rng, carry, mask):
        B, T, _ = x.shape
        H = self.n_heads
        Dh = self.n_out // H
        x = self._maybe_dropout(x, train, rng)
        q = (x @ params["Wq"]).reshape(B, T, H, Dh)
        k = (x @ params["Wk"]).reshape(B, T, H, Dh)
        v = (x @ params["Wv"]).reshape(B, T, H, Dh)
        att = self._attend(q, k, v, mask)
        out = att.reshape(B, T, self.n_out) @ params["Wo"] + params["b"]
        if mask is not None:
            out = out * mask[..., None]
        return self.activation(out), state, carry

    def apply(self, params, x, state, train, rng):
        out, st, _ = self.apply_seq(params, x, state, train, rng, None,
                                    None)
        return out, st

    # -- cached autoregressive decode (serving/generation) -------------
    def cache_shape(self, max_seq_len: int):
        """Per-sequence K (== V) cache shape for this layer:
        [n_heads, max_seq_len, head_dim] — T contiguous per head, so
        decode attention streams contiguous [T, Dh] panels."""
        return (self.n_heads, int(max_seq_len), self.n_out // self.n_heads)

    def apply_prefill(self, params, x, key_mask=None):
        """Prompt pass that also returns per-position K/V for the decode
        cache. Inference-only (no dropout); requires ``causal=True`` —
        an acausal prefix would make the cached continuation attend to
        tokens that didn't exist when the cache row was written.

        x: [B, T, C]; key_mask: optional [B, T] validity.
        Returns (out [B, T, n_out], k [B, H, T, Dh], v [B, H, T, Dh])
        — K/V already in cache layout.
        """
        if not self.causal:
            raise ValueError("cached decode needs causal=True attention")
        B, T, _ = x.shape
        H = self.n_heads
        Dh = self.n_out // H
        q = (x @ params["Wq"]).reshape(B, T, H, Dh)
        k = (x @ params["Wk"]).reshape(B, T, H, Dh)
        v = (x @ params["Wv"]).reshape(B, T, H, Dh)
        att = self._attend(q, k, v, key_mask)
        out = att.reshape(B, T, self.n_out) @ params["Wo"] + params["b"]
        if key_mask is not None:
            out = out * key_mask[..., None]
        return (self.activation(out), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2))

    def apply_decode(self, params, x, k_cache, v_cache, pos,
                     impl: str = "auto"):
        """One cached decode step: project the current token, write its
        K/V at ``pos``, attend over positions 0..pos. All shapes are
        static in the cache CAPACITY, so one compiled program serves
        every step of every sequence.

        x: [B, C] current-token activations; k_cache/v_cache:
        [B, H, T_max, Dh]; pos: [B] int32 write position per row.
        Returns (out [B, n_out], k_cache, v_cache).
        """
        from ...kernels.decode_attention import decode_attention
        B = x.shape[0]
        H = self.n_heads
        Dh = self.n_out // H
        q = (x @ params["Wq"]).reshape(B, H, Dh)
        k_t = (x @ params["Wk"]).reshape(B, H, Dh)
        v_t = (x @ params["Wv"]).reshape(B, H, Dh)
        rows = jnp.arange(B)[:, None]
        heads = jnp.arange(H)[None, :]
        k_cache = kv_set(k_cache, (rows, heads, pos[:, None]), k_t)
        v_cache = kv_set(v_cache, (rows, heads, pos[:, None]), v_t)
        att = decode_attention(q, k_cache, v_cache, pos + 1, impl=impl)
        out = att.reshape(B, self.n_out) @ params["Wo"] + params["b"]
        return self.activation(out), k_cache, v_cache

    # -- paged KV cache (serving/paging) --------------------------------
    def apply_decode_paged(self, params, x, pool, block_tables, pos,
                           impl: str = "auto"):
        """One cached decode step against the PAGED pool: write the
        current token's K and V, one row side by side, at
        ``pool[table[pos // Bs], :, pos % Bs]``, attend over the prefix
        through the block table. Same contract as :meth:`apply_decode`
        with the per-slot panels replaced by shared pool blocks.

        x: [B, C]; pool: [N, H, Bs, 2 * Dh] (the layout of
        `kernels/paged_attention.py`); block_tables: [B, n_blocks]
        int32 (NULL_BLOCK-padded); pos: [B] int32. Inactive rows must
        carry NULL_BLOCK tables — their writes then land in the
        reserved null block instead of live memory.
        Returns (out [B, n_out], pool).
        """
        from ...kernels.paged_attention import kv_pool_set, paged_attention
        B = x.shape[0]
        H = self.n_heads
        Dh = self.n_out // H
        Bs = pool.shape[2]
        q = (x @ params["Wq"]).reshape(B, H, Dh)
        k_t = (x @ params["Wk"]).reshape(B, H, Dh)
        v_t = (x @ params["Wv"]).reshape(B, H, Dh)
        blk = jnp.take_along_axis(block_tables, (pos // Bs)[:, None],
                                  axis=1)[:, 0]
        off = pos % Bs
        heads = jnp.arange(H)[None, :]
        pool = kv_pool_set(pool, (blk[:, None], heads, off[:, None]),
                           k_t, v_t)
        att = paged_attention(q, pool, block_tables, pos + 1, impl=impl)
        out = att.reshape(B, self.n_out) @ params["Wo"] + params["b"]
        return self.activation(out), pool

    def apply_verify(self, params, x, k_cache, v_cache, slot, p0,
                     chunk_len):
        """Multi-token verification span against the DENSE slot cache —
        the slot-backend sibling of :meth:`apply_prefill_paged`, used by
        speculative decoding to score a draft's k proposals (plus the
        committed current token) in one causal pass. Write the span's
        K/V at positions ``p0 + i`` of ``slot``'s panel, then attend
        each row causally over the slot's whole prefix.

        x: [1, C, Cin] span activations (C = verify bucket);
        k_cache/v_cache: [S, H, T_max, Dh]; slot: scalar int32; p0:
        scalar int32 global start; chunk_len: scalar int32 valid rows.
        Padded rows (>= chunk_len) write junk K/V beyond the live
        length, where every reader's mask keeps it dark and the next
        accepted write overwrites it — the same no-zeroing stale-tail
        contract as the paged chunk path (rows past ``T_max`` are
        dropped by the scatter). Returns (out [1, C, n_out], k_cache,
        v_cache)."""
        from ...kernels.paged_attention import span_attend
        if not self.causal:
            raise ValueError("cached decode needs causal=True attention")
        C = x.shape[1]
        H = self.n_heads
        Dh = self.n_out // H
        xx = x[0]
        q = (xx @ params["Wq"]).reshape(C, H, Dh)
        k_t = (xx @ params["Wk"]).reshape(C, H, Dh)
        v_t = (xx @ params["Wv"]).reshape(C, H, Dh)
        gpos = p0 + jnp.arange(C)
        heads = jnp.arange(H)[None, :]
        k_cache = kv_set(k_cache, (slot, heads, gpos[:, None]), k_t)
        v_cache = kv_set(v_cache, (slot, heads, gpos[:, None]), v_t)
        # the slot's whole panel is the gathered span: row c (global
        # position p0+c) sees keys j <= p0+c, exactly the paged math
        # with the block-table gather replaced by one dense panel
        kk = _cache_row(k_cache, slot)
        vv = _cache_row(v_cache, slot)
        att = span_attend(q, kk, vv, gpos, p0 + C, x.dtype)
        out = att.reshape(C, self.n_out) @ params["Wo"] + params["b"]
        return self.activation(out)[None], k_cache, v_cache

    def apply_prefill_paged(self, params, x, pool, block_table, p0,
                            chunk_len):
        """One prefill CHUNK against the paged pool: project the chunk,
        write its K/V rows into the owning blocks, and attend each
        chunk query causally over the prefix (earlier chunks + this
        one) as it comes back out of the pool
        (:func:`~...kernels.paged_attention.kv_pool_set_span`, a write
        by blocks, then
        :func:`~...kernels.paged_attention.paged_prefill_attention`).
        Chunked prefill is what keeps a long prompt from monopolizing
        the decode loop — the scheduler interleaves these with decode
        steps (Sarathi-Serve, OSDI '24; PAPERS.md).

        x: [1, C, Cin] chunk activations (C is the chunk bucket);
        pool: [N, H, Bs, 2 * Dh]; block_table: [n_blocks] int32, sized
        by the CALLER so that ``n_blocks * Bs >= p0 + C``; p0: scalar
        int32 global start; chunk_len: scalar int32 valid rows. Padded
        rows (>= chunk_len) write junk K/V, harmlessly: rows inside the
        sequence's allocation land at positions beyond its live length
        — masked by every reader, and overwritten by the decode step's
        write at ``pos`` before that position is ever unmasked — and
        rows past the allocation land on NULL-padded table entries,
        i.e. the reserved null block; their output rows are nobody's to
        read. The size contract above is the caller's to uphold: rows
        past an UNDERSIZED table go to the null block, that is, are
        lost.
        Returns (out [1, C, n_out], pool).
        """
        from ...kernels.paged_attention import (kv_pool_set_span,
                                                paged_prefill_attention)
        if not self.causal:
            raise ValueError("cached decode needs causal=True attention")
        C = x.shape[1]
        H = self.n_heads
        Dh = self.n_out // H
        xx = x[0]
        q = (xx @ params["Wq"]).reshape(C, H, Dh)
        k_t = (xx @ params["Wk"]).reshape(C, H, Dh)
        v_t = (xx @ params["Wv"]).reshape(C, H, Dh)
        pool = kv_pool_set_span(pool, block_table, p0, k_t, v_t)
        # chunk query c (global position p0+c) sees keys j <= p0+c —
        # earlier chunks' K/V, and this chunk's own, come back out of
        # the pool they went into (quantized on write)
        att = paged_prefill_attention(q, pool, block_table, p0)
        out = att.reshape(C, self.n_out) @ params["Wo"] + params["b"]
        return self.activation(out)[None], pool

    def init_carry(self, batch, dtype=jnp.float32):
        return ()

    def output_shape(self, input_shape):
        return (input_shape[0], self.n_out)

    def _extra_json(self):
        return {"n_heads": self.n_heads, "n_out": self.n_out,
                "causal": self.causal,
                "implementation": self.implementation}


@register
class TransformerEncoderLayer(Layer):
    """Pre-LN transformer block: x + MHA(LN(x)); x + MLP(LN(x))."""

    kind = "transformerencoder"
    is_rnn = True

    def __init__(self, n_heads: int = 4, d_ff: Optional[int] = None,
                 causal: bool = False, implementation: str = "auto",
                 **kw):
        kw.setdefault("activation", "identity")
        super().__init__(**kw)
        self.n_heads = int(n_heads)
        self.d_ff = d_ff
        self.causal = causal
        self.implementation = implementation
        self.attn: Optional[SelfAttentionLayer] = None
        self.d_model: Optional[int] = None

    def build(self, input_shape, defaults=None):
        super().build(input_shape, defaults)
        self.d_model = int(input_shape[-1])
        if self.d_ff is None:
            self.d_ff = 4 * self.d_model
        # forward this layer's regularization/init settings to the inner
        # attention so the block behaves as one unit
        self.attn = SelfAttentionLayer(
            n_heads=self.n_heads, causal=self.causal,
            implementation=self.implementation, dropout=self.dropout,
            weight_init=self.weight_init)
        self.attn.build(input_shape, defaults)

    def param_shapes(self):
        d, f = self.d_model, self.d_ff
        sh = {f"attn_{k}": v for k, v in self.attn.param_shapes().items()}
        sh.update({"ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,),
                   "ln2_b": (d,), "W1": (d, f), "b1": (f,),
                   "W2": (f, d), "b2": (d,)})
        return sh

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(rng, 3)
        d, f = self.d_model, self.d_ff
        p = {f"attn_{k}": v
             for k, v in self.attn.init_params(k1, dtype).items()}
        p.update({
            "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
            "W1": init_weights(k2, (d, f), d, f, self.weight_init, dtype),
            "b1": jnp.zeros((f,), dtype),
            "W2": init_weights(k3, (f, d), f, d, self.weight_init, dtype),
            "b2": jnp.zeros((d,), dtype)})
        return p

    def apply_seq(self, params, x, state, train, rng, carry, mask):
        from ..functional import layer_norm as _ln
        ap = {k[len("attn_"):]: v for k, v in params.items()
              if k.startswith("attn_")}
        h = _ln(x, params["ln1_g"], params["ln1_b"])
        att, _, _ = self.attn.apply_seq(ap, h, None, train, rng, (), mask)
        x = x + att
        h = _ln(x, params["ln2_g"], params["ln2_b"])
        h = jax.nn.gelu(h @ params["W1"] + params["b1"])
        # fold the rng so the MLP dropout mask is independent of the
        # attention dropout mask above (same key would correlate them)
        mlp_rng = None if rng is None else jax.random.fold_in(rng, 1)
        h = self._maybe_dropout(h, train, mlp_rng)
        x = x + (h @ params["W2"] + params["b2"])
        if mask is not None:
            x = x * mask[..., None]
        return x, state, carry

    def apply(self, params, x, state, train, rng):
        out, st, _ = self.apply_seq(params, x, state, train, rng, None,
                                    None)
        return out, st

    # -- cached autoregressive decode (serving/generation) -------------
    def cache_shape(self, max_seq_len: int):
        return self.attn.cache_shape(max_seq_len)

    def _attn_params(self, params):
        return {k[len("attn_"):]: v for k, v in params.items()
                if k.startswith("attn_")}

    def _mlp(self, params, x):
        # serving-path MLP: kv_quant.mm dispatches int8 weight-only
        # matmuls (bf16 operands, f32 accumulation, per-output-channel
        # dequant after the dot) when W1/W2 are QuantWeights — plain
        # f32 weights fall through to the ordinary `@` unchanged. The
        # training MLP (apply_seq) never sees QuantWeights.
        from ..functional import layer_norm as _ln
        h = _ln(x, params["ln2_g"], params["ln2_b"])
        h = jax.nn.gelu(mm(h, params["W1"]) + params["b1"])
        return x + (mm(h, params["W2"]) + params["b2"])

    def apply_prefill(self, params, x, key_mask=None):
        """Block prefill: the apply_seq math without dropout, also
        returning this block's K/V rows for the decode cache."""
        from ..functional import layer_norm as _ln
        h = _ln(x, params["ln1_g"], params["ln1_b"])
        att, k, v = self.attn.apply_prefill(self._attn_params(params), h,
                                            key_mask)
        x = self._mlp(params, x + att)
        if key_mask is not None:
            x = x * key_mask[..., None]
        return x, k, v

    def apply_decode(self, params, x, k_cache, v_cache, pos,
                     impl: str = "auto"):
        """One cached decode step through the full block (x: [B, C])."""
        from ..functional import layer_norm as _ln
        h = _ln(x, params["ln1_g"], params["ln1_b"])
        att, k_cache, v_cache = self.attn.apply_decode(
            self._attn_params(params), h, k_cache, v_cache, pos, impl)
        return self._mlp(params, x + att), k_cache, v_cache

    # -- paged KV cache (serving/paging) --------------------------------
    def apply_decode_paged(self, params, x, pool, block_tables, pos,
                           impl: str = "auto"):
        """One cached decode step through the full block against the
        paged pool (see :meth:`SelfAttentionLayer.apply_decode_paged`)."""
        from ..functional import layer_norm as _ln
        h = _ln(x, params["ln1_g"], params["ln1_b"])
        att, pool = self.attn.apply_decode_paged(
            self._attn_params(params), h, pool, block_tables, pos, impl)
        return self._mlp(params, x + att), pool

    def apply_prefill_paged(self, params, x, pool, block_table, p0,
                            chunk_len):
        """One prefill chunk through the full block against the paged
        pool (see :meth:`SelfAttentionLayer.apply_prefill_paged`)."""
        from ..functional import layer_norm as _ln
        h = _ln(x, params["ln1_g"], params["ln1_b"])
        att, pool = self.attn.apply_prefill_paged(
            self._attn_params(params), h, pool, block_table, p0,
            chunk_len)
        return self._mlp(params, x + att), pool

    def apply_verify(self, params, x, k_cache, v_cache, slot, p0,
                     chunk_len):
        """One verification span through the full block against the
        dense slot cache (see :meth:`SelfAttentionLayer.apply_verify`)."""
        from ..functional import layer_norm as _ln
        h = _ln(x, params["ln1_g"], params["ln1_b"])
        att, k_cache, v_cache = self.attn.apply_verify(
            self._attn_params(params), h, k_cache, v_cache, slot, p0,
            chunk_len)
        return self._mlp(params, x + att), k_cache, v_cache

    def init_carry(self, batch, dtype=jnp.float32):
        return ()

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _extra_json(self):
        return {"n_heads": self.n_heads, "d_ff": self.d_ff,
                "causal": self.causal,
                "implementation": self.implementation}

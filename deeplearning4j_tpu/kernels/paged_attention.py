"""Paged KV-cache attention: a decode step's (Pallas TPU + XLA
fallback) and a prefill chunk's (XLA), and the one place that knows how
a pool block is laid out.

The paged sibling of :mod:`.decode_attention`: one query row per
sequence (:func:`paged_attention`), or the rows of one sequence's
prefill chunk (:func:`paged_prefill_attention`), attend over a prefix
whose K/V lives in POOL BLOCKS (`serving/paging.py`) addressed through
a per-sequence block table, instead of a contiguous per-slot panel.

**The pool's layout.** One array a layer, ``[num_blocks, H_kv, Bs,
2 * D]``: a position's key in lanes ``0 .. D - 1`` of its row and its
value in lanes ``D .. 2 * D - 1``. For ``D = 64`` a row is exactly one
128-lane vector row, so the array's default device layout on a TPU is
the row-major tiled one (``{3,2,1,0:T(8,128)}``) that a scatter and a
Pallas call ask for: a program takes the donated pool and hands it back
with no relayout. (Two arrays ``[N, H, Bs, 64]`` were kept as
``{0,3,2,1:T(8,128)}`` and copied whole to row-major and back around
every decode step and prefill chunk: PERF.md section 6, PR 31.) An int8
pool is a :class:`~.kv_quant.QuantArray` whose values have that shape
and whose f32 sidecar is ``[num_blocks, 2, H_kv, Bs]``: the keys'
scales, then the values'. Everything else addresses a pool by its
leading block axis alone. :func:`kv_pool_zeros`, :func:`fuse_kv`,
:func:`split_kv`, :func:`kv_pool_set`, :func:`kv_pool_set_span`,
:func:`gather_blocks` and :func:`gather_span` are the layout's whole
surface; the kernel below is its other reader.

**The decode kernel.** The op is HBM-bandwidth bound by its bytes, but
a Pallas grid step has a cost of its own (~0.35 us with three small
operands on a v5e), so the kernel's job is to stream the LIVE K/V once
in few, large steps and keep the online-softmax state in VMEM.

The grid is ``(S, ceil(B / G))``: one grid step takes one slot, ALL its
heads, and ``G`` table entries. A pool block ``[H, Bs, 2 * D]`` is
contiguous in the pool, so it is one DMA; the pool enters the call as
``G`` operands whose index maps read a scalar-prefetched table
(`pltpu.PrefetchScalarGridSpec`, pallas guide section 12) and aim each
at ``pool[tbl[s, c * G + g]]`` -- the gather costs no extra pass over
memory, and Pallas's pipeline fetches chunk ``c + 1`` (or the next
slot's first) while chunk ``c`` is computed. ``G`` follows from the
block's VMEM footprint (:func:`blocks_per_chunk`). Past a slot's last
live block every operand is aimed at the block it already holds, which
starts no DMA, and the body is skipped (``pl.when``): a step costs its
live keys plus ~70 ns an operand for each table entry walked. The
scores of a block are a ``[H, Bs]`` tile made on the VPU (multiply the
block by the head's query row, zero in the value lanes, and reduce over
lanes) in f32; the MXU has no use for one query row a head. The
accumulator is as wide as a row, and its value half is the output.

Layout: q [S, H, D]; pool [N, H_kv, Bs, 2 * D] (positions contiguous
per head inside a block, same reasoning as the slot cache's
[S, H, T, D]); block_tables [S, B] int32 pool indices
(NULL_BLOCK-padded); lengths [S]. Position ``j`` of sequence ``s``
lives at ``pool[block_tables[s, j // Bs], :, j % Bs]``; positions >=
lengths[s] are masked, so padded table entries are never READ into the
result -- they only keep the shapes static.

Elsewhere the fused-XLA path gathers the blocks with ``jnp.take``,
splits the lanes of what it gathered, and reuses
:func:`~.decode_attention.decode_attention_xla` -- the gathered
[S, H, B*Bs, D] panels are bit-identical to a slot cache holding the
same prefix, which is what makes paged-vs-slot token parity testable.

**A chunk's write** (:func:`kv_pool_set_span`). The ``C`` rows of a
prefill chunk are consecutive positions of one table, so they are
written by blocks: the ``C / Bs + 1`` blocks they lie in are read,
overlaid and written back whole. The row-by-row scatter of
:func:`kv_pool_set` is for a decode step's rows, one a sequence.

**A chunk's attention** (:func:`paged_prefill_attention`) is XLA's:
the table's span gathered (:func:`gather_span`) and attended densely
(:func:`span_attend`, the mathematics the slot backend's verify shares).
Its cost follows the table's bucket, not the live length; what a tiled
kernel over the blocks read beside it on a v5e is in PERF.md section 7.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import decode_attention_xla
from .flash_attention import _NEG_INF, _cdiv, default_platform
from .kv_quant import (QuantArray, canonical_kv_dtype, is_quantized,
                       kv_zeros, quantize_rows)

#: the Pallas kernel's name: its custom call in the HLO, and the
#: operation a device trace shows inside ``jit_step``
KERNEL_NAME = "paged_attention_decode"


# ---------------------------------------------------------------------------
# The pool's layout
# ---------------------------------------------------------------------------
def kv_pool_zeros(shape: Sequence[int], kv_dtype: str):
    """One layer's pool for K (== V) blocks of ``shape`` ``[N, H, Bs,
    D]``: ``[N, H, Bs, 2 * D]`` zeros at ``kv_dtype``, for int8 a
    QuantArray with the ``[N, 2, H, Bs]`` scale sidecar."""
    N, H, Bs, D = (int(d) for d in shape)
    if canonical_kv_dtype(kv_dtype) == "int8":
        return QuantArray(jnp.zeros((N, H, Bs, 2 * D), jnp.int8),
                          jnp.zeros((N, 2, H, Bs), jnp.float32))
    return kv_zeros((N, H, Bs, 2 * D), kv_dtype)


def fuse_kv(k, v):
    """Separate K and V blocks ``[N, H, Bs, D]`` (arrays, or QuantArrays
    with ``[N, H, Bs]`` scales) as one pool in the stored layout."""
    if is_quantized(k) != is_quantized(v):
        raise ValueError("K and V must be quantized together")
    if is_quantized(k):
        return QuantArray(jnp.concatenate([k.q, v.q], axis=-1),
                          jnp.stack([k.scale, v.scale], axis=1))
    return jnp.concatenate([k, v], axis=-1)


def split_kv(rows):
    """The key lanes and the value lanes of rows ``[..., 2 * D]`` taken
    out of a pool (plain values: a QuantArray's scales are split by
    whoever gathered them, :func:`gather_blocks`, :func:`gather_span`)."""
    D = rows.shape[-1] // 2
    return rows[..., :D], rows[..., D:]


def kv_pool_set(pool, idx, k, v):
    """Write the rows ``k`` and ``v`` ``[..., D]`` of some positions
    into ``pool`` at ``idx``, an index tuple ``(block, head, offset)``
    of arrays that broadcast to the rows' leading shape: ONE scatter of
    full ``2 * D``-lane rows, quantized on the way into an int8 pool
    (each half by its own row scale)."""
    if is_quantized(pool):
        qk, qv = quantize_rows(k), quantize_rows(v)
        blk, head, off = (jnp.asarray(i)[..., None] for i in idx)
        return QuantArray(
            pool.q.at[idx].set(jnp.concatenate([qk.q, qv.q], axis=-1)),
            pool.scale.at[blk, jnp.arange(2), head, off].set(
                jnp.stack([qk.scale, qv.scale], axis=-1)))
    return pool.at[idx].set(
        jnp.concatenate([k, v], axis=-1).astype(pool.dtype))


def kv_pool_set_span(pool, block_table, p0, k, v):
    """Write the rows ``k`` and ``v`` ``[C, H, D]`` of the ``C``
    consecutive positions ``p0 .. p0 + C - 1`` of one sequence (a
    prefill chunk, a verify span) into ``pool`` through its
    ``block_table`` ``[n_blocks]``: what :func:`kv_pool_set` writes at
    ``(block_table[j // Bs], :, j % Bs)``, by BLOCKS. The positions lie
    in ``C / Bs + 1`` blocks at most: those are read, the rows laid
    over them at ``p0 % Bs``, and written back whole, one ``[H, Bs,
    2 * D]`` update a block. (A scatter a row costs a v5e ~70 ns for
    each of ``C * H`` rows whatever their width: 449 us a layer for 256
    positions of 25 heads, 21.6 of GPT-2 XL's 35.6 ms a chunk; PERF.md
    section 6, PR 33.) A block read and written back unchanged in its
    other rows is the sequence's own (a shared block is copied before
    its sharer writes into it); a position past the table goes to the
    null block, as one on a NULL-padded entry does."""
    quant = is_quantized(pool)
    vals = pool.q if quant else pool
    Bs = vals.shape[2]
    C = k.shape[0]
    B = block_table.shape[0]
    nb = (C + Bs - 2) // Bs + 1
    i = p0 // Bs + jnp.arange(nb)
    ids = jnp.where(i < B, block_table[jnp.minimum(i, B - 1)], 0)
    at = (jnp.asarray(p0) % Bs).astype(jnp.int32)

    def lay(old, rows, axis):
        """``rows`` [C, ...] over the blocks ``old`` [nb, ...] whose
        axis ``axis`` is the block's positions."""
        win = jnp.moveaxis(old, axis, 1)
        shape = win.shape
        win = lax.dynamic_update_slice(
            win.reshape((nb * Bs,) + shape[2:]), rows.astype(old.dtype),
            (at,) + (0,) * (rows.ndim - 1))
        return jnp.moveaxis(win.reshape(shape), 1, axis)

    if quant:
        qk, qv = quantize_rows(k), quantize_rows(v)
        return QuantArray(
            pool.q.at[ids].set(lay(
                pool.q[ids], jnp.concatenate([qk.q, qv.q], axis=-1), 2)),
            pool.scale.at[ids].set(lay(
                pool.scale[ids], jnp.stack([qk.scale, qv.scale], axis=1),
                3)))
    return pool.at[ids].set(lay(pool[ids],
                                jnp.concatenate([k, v], axis=-1), 2))


def gather_blocks(pool, block_tables):
    """Pool + [S, B] tables -> the dense per-sequence K and V panels
    ``[S, H, B*Bs, D]`` (the slot-cache layout), via one gather whose
    lanes are split afterwards. A QuantArray pool gathers values and
    scale rows together: each panel is itself a QuantArray in
    slot-cache layout."""
    S, B = block_tables.shape
    flat = block_tables.reshape(-1)
    vals = pool.q if is_quantized(pool) else pool
    N, H, Bs, D2 = vals.shape
    g = jnp.take(vals, flat, axis=0)                     # [S*B,H,Bs,2D]
    g = g.reshape(S, B, H, Bs, D2).transpose(0, 2, 1, 3, 4)
    k, v = split_kv(g.reshape(S, H, B * Bs, D2))
    if not is_quantized(pool):
        return k, v
    gs = jnp.take(pool.scale, flat, axis=0)               # [S*B,2,H,Bs]
    gs = gs.reshape(S, B, 2, H, Bs).transpose(2, 0, 3, 1, 4)
    gs = gs.reshape(2, S, H, B * Bs)
    return QuantArray(k, gs[0]), QuantArray(v, gs[1])


def gather_span(pool, block_table):
    """One sequence's table span ``[n_blocks]`` out of a pool as K and V
    panels ``[H, T, D]`` (T = n_blocks * Bs); a QuantArray pool's come
    with their ``[H, T]`` scales."""
    k, v = gather_blocks(pool, block_table[None])
    if is_quantized(pool):
        return (QuantArray(k.q[0], k.scale[0]),
                QuantArray(v.q[0], v.scale[0]))
    return k[0], v[0]


def paged_attention_xla(q, pool, block_tables, lengths):
    """Fused-XLA paged decode attention (CPU/GPU and reference path).

    q: [S, H_q, D]; pool: [N, H_kv, Bs, 2 * D] with
    ``H_q = g * H_kv`` (query head i reads KV head i // g);
    block_tables: [S, B]; lengths: [S] — positions >= lengths[s] (stale
    block tails, padded table entries) are masked out. Shapes depend
    only on (S, B, Bs), never on live lengths or which blocks a request
    owns.
    """
    k, v = gather_blocks(pool, block_tables)
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    if Hq == Hkv:
        return decode_attention_xla(q, k, v, lengths)
    # grouped-query heads: query head i reads KV head i // g. The g
    # members of a group are mapped over one gathered panel
    out = jax.vmap(lambda qg: decode_attention_xla(qg, k, v, lengths),
                   in_axes=2, out_axes=2)(q.reshape(S, Hkv, Hq // Hkv, D))
    return out.reshape(S, Hq, D)


def span_attend(q, kk, vv, gpos, p0c, out_dtype):
    """Causal span attention over one gathered K/V panel: the
    mathematics of :func:`paged_prefill_attention` (a block-table
    gather) and of ``SelfAttentionLayer.apply_verify`` (the dense slot
    panel).

    q: [C, H_q, Dh] span queries (H_q a multiple of H: grouped-query
    heads); kk/vv: [H, T, Dh] panels — plain f32
    (bit-identical to the pre-quantization math), bf16, or int8
    QuantArrays with [H, T] scales; gpos: [C] global positions (row c
    sees keys j <= gpos[c]); p0c: scalar — first position NOT written
    by this sequence (p0 + C): V beyond it is a previous occupant's
    stale leavings and may be non-finite, so it is where-masked
    (0 * NaN = NaN). Quantized legs run bf16-operand dots with f32
    accumulation, K scales applied post-dot and V scales folded into
    the probabilities — the same scale placement as the decode kernels
    (kernels/decode_attention.py), checked in StableHLO
    (tests/test_kv_quant.py::TestDotOperandAudit)."""
    H, T, Dh = kk.shape
    C, Hq = q.shape[:2]
    if Hq != H:
        # grouped-query heads (query head i reads KV head i // g): the
        # g members of a group are mapped over the one gathered panel
        out = jax.vmap(
            lambda qg: span_attend(qg, kk, vv, gpos, p0c, out_dtype),
            in_axes=2, out_axes=2)(q.reshape(C, H, Hq // H, Dh))
        return out.reshape(C, Hq, Dh)
    scale = 1.0 / jnp.sqrt(jnp.float32(Dh))
    valid = jnp.arange(T)[None, None, :] <= gpos[None, :, None]
    written = (jnp.arange(T) < p0c)[None, :, None]
    if is_quantized(kk) or kk.dtype == jnp.bfloat16:
        kb = (kk.q if is_quantized(kk) else kk).astype(jnp.bfloat16)
        vb = (vv.q if is_quantized(vv) else vv).astype(jnp.bfloat16)
        s = jnp.einsum("chd,htd->hct", q.astype(jnp.bfloat16), kb,
                       preferred_element_type=jnp.float32) * scale
        if is_quantized(kk):              # [H, T] per-position scales
            s = s * kk.scale[:, None, :]
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if is_quantized(vv):
            # fold V scales into p. The where-guard matters: a stale
            # row's scale may be NaN (poison is scale-carried, see
            # kv_quant.quantize_rows) and 0 * NaN = NaN
            p = jnp.where(valid, p * vv.scale[:, None, :], 0.0)
        else:
            p = jnp.where(valid, p, 0.0)
        vb = jnp.where(written, vb, jnp.bfloat16(0))
        att = jnp.einsum("hct,htd->chd", p.astype(jnp.bfloat16), vb,
                         preferred_element_type=jnp.float32)
        return att.astype(out_dtype)
    s = jnp.einsum("chd,htd->hct", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    vv = jnp.where(written, vv.astype(jnp.float32), 0.0)
    return jnp.einsum("hct,htd->chd", p, vv).astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
#: VMEM the kernel fills with pool blocks in flight (two buffers a
#: block); the blocks a chunk holds follow from it. On a v5e at H 25,
#: Bs 16, D 64, f32 (a block of 200 KiB), 48 calls over 4,400 live keys
#: take 9.4 ms at 2 blocks a chunk, 7.8 at 4, 8.1 at 8 and 10.0 at 16
#: (PERF.md, PR 31; the same order as PR 27's): a larger chunk saves
#: grid steps and wastes more of its last tile
_VMEM_BLOCK_BUDGET = 2 << 20
#: and no more than this many, however small a block is: the body is
#: unrolled over a chunk's blocks (and the members of a query group).
#: Swept where it binds, on a v5e at 32 query heads over H 8, Bs 16,
#: D 64, bf16 (a block of 32 KiB), 3 calls over 4,400 live keys: 0.99
#: ms at 2 blocks a chunk, 0.71 at 4, 0.64 at 8, 0.66 at 16 (PERF.md,
#: PR 31)
_MAX_BLOCKS = 8


def blocks_per_chunk(H: int, Bs: int, D: int, itemsize: int, B: int) -> int:
    """Pool blocks one chunk of the kernel attends (``G``): the largest
    power of two whose double buffers fit the VMEM budget, as Mosaic
    tiles a ``[H, Bs, 2 * D]`` block there (``H`` the KV heads; rows
    padded to the sublane tile of the item size, the ``2 * D`` lanes to
    128), and no more than the table holds or :data:`_MAX_BLOCKS`."""
    sublanes = 8 * 4 // itemsize
    block = H * _cdiv(Bs, sublanes) * sublanes * _cdiv(2 * D, 128) * 128 \
        * itemsize
    g = max(1, min(_VMEM_BLOCK_BUDGET // (2 * block), B, _MAX_BLOCKS))
    return 1 << (g.bit_length() - 1)


def _paged_kernel(tbl_ref, len_ref, q_ref, *refs, quant: bool, G: int,
                  scale: float, g: int = 1):
    """One grid step (slot ``s``, chunk ``c``) of paged decode
    attention: every head of the slot against the ``G`` pool blocks of
    table entries ``c * G .. c * G + G - 1``.

    Refs (the slot dim squeezed): tbl_ref [S, C * G] (the table entry
    each operand fetches: the index maps alone read it) and len_ref
    [S], scalar-prefetched; q_ref [H, 2 * D], the query row in the key
    lanes and zeros in the value lanes; ``G`` pool blocks [H, Bs,
    2 * D]; for an int8 pool ``G`` K then ``G`` V scale tiles [H, Bs];
    o_ref [H, D]; scratch m, l [H, 1] and acc [H, 2 * D].

    The scores of a block are a [H, Bs] tile: a VPU multiply by the
    head's padded query row and a lane reduction, in f32 whatever the
    pool holds (the value lanes meet zeros). A chunk whose first
    position is past the length runs no body (and fetched nothing: the
    index maps repeat a block they already hold). Inside the last live
    chunk, what a block holds past the length (a stale tail, or another
    position's rows where the index map repeated a block) is masked by
    position with ``where``, never multiplied away: it may be NaN. The
    accumulator takes whole rows; its key half is never read.

    Grouped-query heads (``g`` query heads to a KV head, ``H`` the KV
    heads): q, o and the scratch hold ``g * H`` rows, member ``j`` of
    every group in rows ``j * H .. (j + 1) * H`` (the wrapper lays them
    out so), and each member multiplies the same tiles, loaded once."""
    kv_refs = refs[:G]
    ks_refs, vs_refs = (refs[G:2 * G], refs[2 * G:3 * G]) if quant \
        else (None, None)
    o_ref, m_s, l_s, acc_s = refs[-4:]
    H, Bs, D2 = kv_refs[0].shape
    c = pl.program_id(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(c == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(c * (G * Bs) < length)
    def _chunk():
        first = [(c * G + b) * Bs for b in range(G)]
        lane = lax.broadcasted_iota(jnp.int32, (H, Bs), 1)
        mask = [p0 + lane < length for p0 in first]
        row = lax.broadcasted_iota(jnp.int32, (H, Bs, 1), 1)

        def tile(b):
            # for the scores: a masked row's is replaced below,
            # whatever it is
            return kv_refs[b][...].astype(jnp.float32)

        def live_tile(b):
            # for the sum: masked rows zeroed, 0 * NaN = NaN would leak
            # a stale tail
            return jnp.where(first[b] + row < length, tile(b), 0.0)

        if g > 1:       # the members of a group share the tiles
            tf, lf = ([tile(b) for b in range(G)],
                      [live_tile(b) for b in range(G)])
            tile, live_tile = tf.__getitem__, lf.__getitem__
        for j in range(g):
            rows = slice(None) if g == 1 else pl.ds(j * H, H)
            q = q_ref[rows, :].astype(jnp.float32)[:, None, :] * scale
            sc = []
            for b in range(G):
                x = jnp.sum(tile(b) * q, axis=-1)
                if quant:
                    x = x * ks_refs[b][...]                   # K dequant
                sc.append(jnp.where(mask[b], x, _NEG_INF))    # [H, Bs]
            m_prev = m_s[rows, :]
            m_new = jnp.maximum(m_prev, functools.reduce(
                jnp.maximum, sc).max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            l_new, acc = l_s[rows, :] * corr, acc_s[rows, :] * corr
            for b in range(G):
                # where-guard keeps fully-masked rows at p=0 (exp(-inf
                # - -inf) = 1 would fabricate uniform attention)
                p = jnp.where(mask[b], jnp.exp(sc[b] - m_new), 0.0)
                l_new = l_new + p.sum(axis=-1, keepdims=True)
                if quant:
                    # V dequant folds into p; a stale scale may be NaN
                    p = jnp.where(mask[b], p * vs_refs[b][...], 0.0)
                acc = acc + jnp.sum(p[:, :, None] * live_tile(b), axis=1)
            m_s[rows, :], l_s[rows, :], acc_s[rows, :] = m_new, l_new, acc

    @pl.when(c == pl.num_programs(1) - 1)
    def _finalize():
        # a free lane (length 0) ran no chunk: acc is 0 and so is its row
        o_ref[...] = (acc_s[:, D2 // 2:] / jnp.maximum(l_s[...], 1e-30)
                      ).astype(o_ref.dtype)


def paged_attention_pallas(q, pool, block_tables, lengths,
                           interpret: Optional[bool] = None):
    """Pallas paged decode attention. Same contract as
    :func:`paged_attention_xla` (grouped-query heads included: the
    query heads of a group are more rows against the same tile).
    Grid ``(S, ceil(B / G))``: one grid
    step attends all heads of a slot over ``G`` table entries
    (:func:`blocks_per_chunk`). The pool enters as ``G`` operands, each
    one whole block ``[H, Bs, 2 * D]`` (contiguous in the pool) that
    the scalar-prefetched table aims at ``pool[tbl[s, c * G + g]]``.
    Past the slot's last live block an operand is aimed at the block it
    fetched last, so the pipeline fetches nothing new, and the body is
    skipped: the cost follows the live length, not the table span. An
    int8 QuantArray pool brings its ``[H, Bs]`` scale tiles the same
    way (one for K, one for V) and is dequantized in VMEM."""
    if interpret is None:
        interpret = default_platform() != "tpu"
    quant = is_quantized(pool)
    S, H, D = q.shape
    vals = pool.q if quant else pool
    Hkv, Bs, D2 = vals.shape[1:]
    if D2 != 2 * D:
        raise ValueError(f"a pool row of {D2} lanes for heads of {D}")
    g = H // Hkv
    if g * Hkv != H:
        raise ValueError(f"{H} query heads over {Hkv} KV heads")
    if g > 1:       # member j of every group in rows j * Hkv ..
        q = q.reshape(S, Hkv, g, D).swapaxes(1, 2).reshape(S, H, D)
    # the query row meets whole pool rows: zeros against the value lanes
    q_pad = jnp.pad(q, ((0, 0), (0, 0), (0, D)))
    B = block_tables.shape[1]
    G = blocks_per_chunk(Hkv, Bs, D, vals.dtype.itemsize, B)
    C = _cdiv(B, G)
    # The table entry each of a chunk's G operands fetches, [S, C * G]:
    # its own (c * G + g) while that is live, then the last live one
    # this operand had, its first if it has none: an index that does
    # not change starts no DMA. Worked out here, once a step (every
    # layer's call shares it), so that an index map is one SMEM read.
    lengths = jnp.minimum(jnp.asarray(lengths, jnp.int32), B * Bs)
    last = (jnp.maximum(lengths, 1) - 1)[:, None] // Bs
    ci, gi = jnp.divmod(jnp.arange(C * G, dtype=jnp.int32), G)
    ci = jnp.minimum(ci, jnp.maximum(last - gi, 0) // G)
    fetched = jnp.take_along_axis(
        jnp.asarray(block_tables, jnp.int32),
        jnp.minimum(ci * G + gi, B - 1), axis=1)

    def entry(g, tail):
        return lambda s, c, tbl, lens: (tbl[s, c * G + g],) + tail

    def row_spec(width):
        return pl.BlockSpec((None, H, width),
                            lambda s, c, tbl, lens: (s, 0, 0))

    operands, in_specs = [q_pad], [row_spec(D2)]
    operands += [vals] * G
    in_specs += [pl.BlockSpec((None, Hkv, Bs, D2), entry(g, (0, 0, 0)))
                 for g in range(G)]
    if quant:
        for half in (0, 1):             # the keys' scales, the values'
            operands += [pool.scale] * G
            in_specs += [pl.BlockSpec((None, None, Hkv, Bs),
                                      entry(g, (half, 0, 0)))
                         for g in range(G)]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, quant=quant, G=G,
                          scale=1.0 / (D ** 0.5), g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # fetched, lengths
            grid=(S, C),
            in_specs=in_specs, out_specs=row_spec(D),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),   # max
                            pltpu.VMEM((H, 1), jnp.float32),   # sum
                            pltpu.VMEM((H, D2), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        interpret=interpret,
        # the custom call's instruction name in the HLO and so in a
        # device trace (else it is named after the enclosing jit)
        name=KERNEL_NAME,
    )(fetched, lengths, *operands)
    if g > 1:
        out = out.reshape(S, g, Hkv, D).swapaxes(1, 2).reshape(S, H, D)
    return out


def paged_attention(q, pool, block_tables, lengths, impl: str = "auto",
                    **kw):
    """Dispatch: ``auto`` runs the Pallas kernel on TPU (scalar-
    prefetched block gather bounded by the live lengths, VMEM-resident
    softmax state), fused XLA elsewhere. ``pallas`` / ``xla`` force a
    path (parity tests run pallas in interpret mode on CPU so one
    kernel is tested everywhere)."""
    if impl == "auto":
        impl = "pallas" if default_platform() == "tpu" else "xla"
    if impl == "pallas":
        return paged_attention_pallas(q, pool, block_tables, lengths, **kw)
    if impl == "xla":
        return paged_attention_xla(q, pool, block_tables, lengths)
    raise ValueError(f"unknown paged attention impl {impl!r}")


def paged_prefill_attention(q, pool, block_table, p0):
    """A prefill chunk's causal attention over its sequence's prefix in
    the paged pool.

    q: [C, H_q, D], the chunk's queries, row ``c`` at position ``p0 +
    c``; pool: [N, H_kv, Bs, 2 * D] (any pool type) AFTER the chunk's
    own rows were written into it (the chunk's K and V come back out of
    the pool they went into, so a start at ``p0 > 0`` -- a second
    chunk, a shared prefix, a session, a recovery -- needs nothing
    special); block_table: [n_blocks] with ``n_blocks * Bs >= p0 + C``,
    NULL-padded past the sequence's allocation; p0: scalar. Returns
    [C, H_q, D]: row ``c`` attends keys ``j <= p0 + c``; a row of
    padding attends like any other and is nobody's to read.

    The table's whole span is gathered out of the pool as ``[H, T, D]``
    panels and attended densely (:func:`span_attend`): XLA fuses the
    pair, and on a v5e it costs GPT-2 XL's chunk ~0.9 of its 17 ms at
    the tables the benchmark's traffic meets (PERF.md section 5,
    PR 33)."""
    C = q.shape[0]
    kk, vv = gather_span(pool, block_table)
    return span_attend(q, kk, vv, p0 + jnp.arange(C), p0 + C, q.dtype)

"""Paged KV-cache attention: a decode step's and a prefill chunk's
(each a Pallas TPU kernel and an XLA form), and the one place that
knows how a pool block is laid out.

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

**A chunk's attention** (:func:`paged_prefill_attention`) has two
forms. XLA's: the table's span gathered (:func:`gather_span`) and
attended densely (:func:`span_attend`, the mathematics the slot
backend's verify shares); its cost and its ``[H, C, T]`` scores follow
the table's bucket, not the live length. And a tiled Pallas kernel
(:func:`paged_prefill_attention_pallas`): grid (query tiles x key chunks
of whole blocks through the scalar-prefetched table), f32 online
softmax in VMEM, key chunks above the diagonal, past ``p0 +
chunk_len`` or before the window skipped with no fetch, so its cost
follows the live length. On a TPU the kernel runs where the table
spans more than :data:`_DENSE_SPAN_MAX` keys and a head fills whole
128-lane rows; below that line XLA's fused span path is as fast or
faster (PERF.md section 5, PR 33) and the programs stay the ones the
benchmark's short-context cells were accepted with.

**A window and a ring** (``window=W``). A layer that attends the last
``W`` positions only keeps them in a RING of table entries: position
``p`` lives in entry ``(p // Bs) % R`` of an ``R``-entry table, so a
sequence never holds more than ``R`` blocks however long it grows.
Every reader here takes ``window`` (a static int) and then reads keys
``max(0, len - W) <= j < len`` (decode) or ``i - W < j <= i`` (a
chunk's row ``i``) through the ring; blocks wholly before the window
are neither fetched nor walked. ``R * Bs`` must cover the window plus
whatever a program writes ahead of the oldest key it still reads (a
chunk: ``W + C`` positions and one block). What a ring entry holds
outside the window -- an older lap's rows, a previous owner's -- is
masked by POSITION with ``where``, never multiplied away.
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
#: the same kernel called with a window (a ring table, a lower bound):
#: a name of its own, so a trace tells the two kinds of layer apart
KERNEL_NAME_WINDOW = "paged_attention_decode_window"
#: the chunk kernel, without and with a window
PREFILL_KERNEL_NAME = "paged_prefill_attention"
PREFILL_KERNEL_NAME_WINDOW = "paged_prefill_attention_window"


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


def kv_pool_set_span(pool, block_table, p0, k, v, ring: bool = False):
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
    if ring:
        if nb > B:
            raise ValueError(f"a ring of {B} blocks for a span that lies "
                             f"in {nb}")
        ids = block_table[i % B]
    else:
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


def ring_positions(R: int, Bs: int, last_pos):
    """The position each slot of a gathered ring ``[.., R * Bs]`` holds
    when the newest position written is ``last_pos`` (any shape): entry
    ``e`` holds the latest logical block ``lb <= last_pos // Bs`` with
    ``lb % R == e``. Returns ``last_pos.shape + (R * Bs,)``; a slot no
    lap has reached yet reads negative."""
    t = jnp.arange(R * Bs)
    last = (jnp.asarray(last_pos, jnp.int32) // Bs)[..., None]
    lb = last - (last - t // Bs) % R
    return lb * Bs + t % Bs


def _window_decode_xla(q, pool, block_tables, lengths, window: int):
    """The XLA form of windowed decode attention over ring tables
    ``[S, R]``: the ring gathered whole, every slot masked by the
    position it holds (``max(0, len - W) <= j < len``)."""
    if is_quantized(pool):
        raise ValueError("a windowed layer's pool is f32 or bf16")
    k, v = gather_blocks(pool, block_tables)          # [S, Hkv, T, D]
    S, Hq, D = q.shape
    Hkv, Bs = k.shape[1], pool.shape[2]
    lengths = jnp.asarray(lengths, jnp.int32)
    kpos = ring_positions(block_tables.shape[1], Bs,
                          jnp.maximum(lengths, 1) - 1)            # [S, T]
    valid = ((kpos >= 0) & (kpos < lengths[:, None])
             & (kpos >= lengths[:, None] - window))[:, None, None, :]
    od = jnp.bfloat16 if k.dtype == jnp.bfloat16 else jnp.float32
    qg = q.reshape(S, Hkv, Hq // Hkv, D)
    s = jnp.einsum("shgd,shtd->shgt", qg.astype(od), k.astype(od),
                   preferred_element_type=jnp.float32) / (D ** 0.5)
    s = jnp.where(valid, s, _NEG_INF)
    p = jnp.where(valid, jax.nn.softmax(s, axis=-1), 0.0)
    # a masked slot's V may be an older lap's or a stranger's NaN
    vv = jnp.where(valid[:, :, 0, :, None], v, jnp.zeros((), v.dtype))
    out = jnp.einsum("shgt,shtd->shgd", p.astype(od), vv.astype(od),
                     preferred_element_type=jnp.float32)
    return out.reshape(S, Hq, D).astype(q.dtype)


def paged_attention_xla(q, pool, block_tables, lengths,
                        window: Optional[int] = None):
    """Fused-XLA paged decode attention (CPU/GPU and reference path).

    q: [S, H_q, D]; pool: [N, H_kv, Bs, 2 * D] with
    ``H_q = g * H_kv`` (query head i reads KV head i // g);
    block_tables: [S, B]; lengths: [S] — positions >= lengths[s] (stale
    block tails, padded table entries) are masked out. Shapes depend
    only on (S, B, Bs), never on live lengths or which blocks a request
    owns. With ``window`` the tables are rings (module docstring).
    """
    if window is not None:
        return _window_decode_xla(q, pool, block_tables, lengths, window)
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


def span_attend(q, kk, vv, gpos, p0c, out_dtype, kpos=None,
                window: Optional[int] = None):
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
    (tests/test_kv_quant.py::TestDotOperandAudit).

    ``kpos`` [T]: the position each panel slot holds where that is not
    its index (a gathered ring, :func:`ring_positions`; a negative one
    holds nothing); ``window``: row c sees keys ``j > gpos[c] - window``
    only."""
    H, T, Dh = kk.shape
    C, Hq = q.shape[:2]
    if Hq != H:
        # grouped-query heads (query head i reads KV head i // g): the
        # g members of a group are mapped over the one gathered panel
        out = jax.vmap(
            lambda qg: span_attend(qg, kk, vv, gpos, p0c, out_dtype,
                                   kpos, window),
            in_axes=2, out_axes=2)(q.reshape(C, H, Hq // H, Dh))
        return out.reshape(C, Hq, Dh)
    scale = 1.0 / jnp.sqrt(jnp.float32(Dh))
    kp = jnp.arange(T) if kpos is None else kpos
    valid = kp[None, None, :] <= gpos[None, :, None]
    # (a second arange where the slots hold their own indices: the
    # lowering the short-context programs were accepted with)
    written = ((jnp.arange(T) if kpos is None else kpos) < p0c)[None, :, None]
    if window is not None:
        valid = valid & (kp[None, None, :] > gpos[None, :, None] - window)
    if kpos is not None:
        valid = valid & (kp >= 0)[None, None, :]
        written = written & (kp >= 0)[None, :, None]
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


def _paged_kernel(tbl_ref, len_ref, *refs, quant: bool, G: int,
                  scale: float, g: int = 1, windowed: bool = False):
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
    out so), and each member multiplies the same tiles, loaded once.

    ``windowed``: a third scalar-prefetched ref comes first, the slot's
    first live position ``lo``; chunk 0 starts at the block that holds
    it, and a position below it is masked like one past the length."""
    lo_ref, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    q_ref, refs = refs[0], refs[1:]
    kv_refs = refs[:G]
    ks_refs, vs_refs = (refs[G:2 * G], refs[2 * G:3 * G]) if quant \
        else (None, None)
    o_ref, m_s, l_s, acc_s = refs[-4:]
    H, Bs, D2 = kv_refs[0].shape
    c = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    if windowed:
        lo = lo_ref[pl.program_id(0)]
        blk0 = lo // Bs + c * G         # the chunk's first logical block
        start = blk0 * Bs
    else:
        blk0, start = c * G, c * (G * Bs)

    @pl.when(c == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(start < length)
    def _chunk():
        first = [(blk0 + b) * Bs for b in range(G)]
        lane = lax.broadcasted_iota(jnp.int32, (H, Bs), 1)
        mask = [p0 + lane < length for p0 in first]
        row = lax.broadcasted_iota(jnp.int32, (H, Bs, 1), 1)
        if windowed:
            mask = [m & (p0 + lane >= lo) for m, p0 in zip(mask, first)]

        def tile(b):
            # for the scores: a masked row's is replaced below,
            # whatever it is
            return kv_refs[b][...].astype(jnp.float32)

        def live_tile(b):
            # for the sum: masked rows zeroed, 0 * NaN = NaN would leak
            # a stale tail
            live = first[b] + row < length
            if windowed:
                live = live & (first[b] + row >= lo)
            return jnp.where(live, tile(b), 0.0)

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


def _paged_kernel_wide(tbl_ref, len_ref, *refs, G: int, scale: float,
                       windowed: bool = False):
    """The same grid step for heads that fill whole 128-lane rows
    (``D % 128 == 0``, an f32 or bf16 pool): the key lanes and the value
    lanes of a block are aligned slices, so the scores and the sum run
    on the MXU. With ``g`` query heads a KV head the VPU form above
    multiplies every pool row ``2 * g`` times, which at ``g = 7`` and
    256-lane rows is several times the block's DMA; here a KV head's
    ``g`` query rows (padded to a sublane tile, ``gp``) meet its
    ``[Bs, D]`` keys in one product a block.

    Refs: tbl_ref, len_ref (and ``lo`` where ``windowed``),
    scalar-prefetched; q_ref [H, gp, D] in the pool's type; ``G`` pool
    blocks [H, Bs, 2 * D]; o_ref [H, gp, D]; scratch m, l [H, gp, 1] and
    acc [H, gp, D] (``H`` the KV heads). Masks as above: by position,
    with ``where``."""
    lo_ref, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    q_ref, kv_refs = refs[0], refs[1:1 + G]
    o_ref, m_s, l_s, acc_s = refs[-4:]
    H, Bs, D2 = kv_refs[0].shape
    D = D2 // 2
    gp = q_ref.shape[1]
    c = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    lo = lo_ref[pl.program_id(0)] if windowed else 0
    blk0 = (lo // Bs if windowed else 0) + c * G

    @pl.when(c == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(blk0 * Bs < length)
    def _chunk():
        lane = lax.broadcasted_iota(jnp.int32, (gp, Bs), 1)
        row = lax.broadcasted_iota(jnp.int32, (Bs, 1), 0)
        first = [(blk0 + b) * Bs for b in range(G)]
        mask = [(p0 + lane < length) & (p0 + lane >= lo) for p0 in first]
        live = [(p0 + row < length) & (p0 + row >= lo) for p0 in first]
        for h in range(H):
            q = q_ref[h]
            sc = []
            for b in range(G):
                x = lax.dot_general(
                    q, kv_refs[b][h, :, :D], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sc.append(jnp.where(mask[b], x, _NEG_INF))       # [gp, Bs]
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, functools.reduce(
                jnp.maximum, sc).max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            l_new, acc = l_s[h] * corr, acc_s[h] * corr
            for b in range(G):
                p = jnp.where(mask[b], jnp.exp(sc[b] - m_new), 0.0)
                l_new = l_new + p.sum(axis=-1, keepdims=True)
                v = kv_refs[b][h, :, D:]
                v = jnp.where(live[b], v, jnp.zeros((), v.dtype))
                acc = acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
            m_s[h], l_s[h], acc_s[h] = m_new, l_new, acc

    @pl.when(c == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                      ).astype(o_ref.dtype)


def paged_attention_pallas(q, pool, block_tables, lengths,
                           window: Optional[int] = None,
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
    way (one for K, one for V) and is dequantized in VMEM.

    With ``window`` the tables are rings (module docstring) and the
    grid's second axis covers the blocks a window can touch, counted
    from the block of ``max(0, len - window)``: the cost follows
    ``min(len, window)``. Heads of 128 lanes (an f32 or bf16 pool) run
    :func:`_paged_kernel_wide`, on the MXU."""
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
    windowed = window is not None
    if windowed and quant:
        raise ValueError("a windowed layer's pool is f32 or bf16")
    wide = D % 128 == 0 and not quant
    B = block_tables.shape[1]
    # the table entries one slot can have live: all of them, or the
    # blocks a window touches (one more where it starts inside a block)
    span = min(B, _cdiv(int(window), Bs) + 1) if windowed else B
    G = blocks_per_chunk(Hkv, Bs, D, vals.dtype.itemsize, span)
    C = _cdiv(span, G)
    # The table entry each of a chunk's G operands fetches, [S, C * G]:
    # its own (c * G + g) while that is live, then the last live one
    # this operand had, its first if it has none: an index that does
    # not change starts no DMA. Worked out here, once a step (every
    # layer's call shares it), so that an index map is one SMEM read.
    lengths = jnp.asarray(lengths, jnp.int32)
    if not windowed:
        lengths = jnp.minimum(lengths, B * Bs)
    last = (jnp.maximum(lengths, 1) - 1)[:, None] // Bs
    ci, gi = jnp.divmod(jnp.arange(C * G, dtype=jnp.int32), G)
    prefetch = [lengths]
    if windowed:
        lo = jnp.maximum(lengths - int(window), 0)
        blk_lo = (lo // Bs)[:, None]
        ci = jnp.minimum(ci, jnp.maximum(last - blk_lo - gi, 0) // G)
        entry = (blk_lo + ci * G + gi) % B
        prefetch.append(lo)
    else:
        ci = jnp.minimum(ci, jnp.maximum(last - gi, 0) // G)
        entry = jnp.minimum(ci * G + gi, B - 1)
    fetched = jnp.take_along_axis(
        jnp.asarray(block_tables, jnp.int32), entry, axis=1)
    n_pre = 1 + len(prefetch)

    def entry_map(b, tail):
        return lambda s, c, tbl, *_: (tbl[s, c * G + b],) + tail

    if wide:
        # a KV head's g query rows, padded to a sublane tile of the
        # pool's type, as the MXU's left operand
        tile = 32 // vals.dtype.itemsize
        gp = _cdiv(g, tile) * tile
        qw = jnp.pad(q.reshape(S, Hkv, g, D),
                     ((0, 0), (0, 0), (0, gp - g), (0, 0))).astype(vals.dtype)
        q_spec = pl.BlockSpec((None, Hkv, gp, D),
                              lambda s, c, *_: (s, 0, 0, 0))
        out = pl.pallas_call(
            functools.partial(_paged_kernel_wide, G=G,
                              scale=1.0 / (D ** 0.5), windowed=windowed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=n_pre, grid=(S, C),
                in_specs=[q_spec] + [
                    pl.BlockSpec((None, Hkv, Bs, D2),
                                 entry_map(b, (0, 0, 0)))
                    for b in range(G)],
                out_specs=q_spec,
                scratch_shapes=[pltpu.VMEM((Hkv, gp, 1), jnp.float32),
                                pltpu.VMEM((Hkv, gp, 1), jnp.float32),
                                pltpu.VMEM((Hkv, gp, D), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((S, Hkv, gp, D), q.dtype),
            interpret=interpret,
            name=KERNEL_NAME_WINDOW if windowed else KERNEL_NAME,
        )(fetched, *prefetch, qw, *([vals] * G))
        return out[:, :, :g].reshape(S, H, D)

    if g > 1:       # member j of every group in rows j * Hkv ..
        q = q.reshape(S, Hkv, g, D).swapaxes(1, 2).reshape(S, H, D)
    # the query row meets whole pool rows: zeros against the value lanes
    q_pad = jnp.pad(q, ((0, 0), (0, 0), (0, D)))

    def row_spec(width):
        return pl.BlockSpec((None, H, width),
                            lambda s, c, *_: (s, 0, 0))

    operands, in_specs = [q_pad], [row_spec(D2)]
    operands += [vals] * G
    in_specs += [pl.BlockSpec((None, Hkv, Bs, D2), entry_map(b, (0, 0, 0)))
                 for b in range(G)]
    if quant:
        for half in (0, 1):             # the keys' scales, the values'
            operands += [pool.scale] * G
            in_specs += [pl.BlockSpec((None, None, Hkv, Bs),
                                      entry_map(b, (half, 0, 0)))
                         for b in range(G)]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, quant=quant, G=G,
                          scale=1.0 / (D ** 0.5), g=g, windowed=windowed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,      # fetched, lengths (, lo)
            grid=(S, C),
            in_specs=in_specs, out_specs=row_spec(D),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),   # max
                            pltpu.VMEM((H, 1), jnp.float32),   # sum
                            pltpu.VMEM((H, D2), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        interpret=interpret,
        # the custom call's instruction name in the HLO and so in a
        # device trace (else it is named after the enclosing jit)
        name=KERNEL_NAME_WINDOW if windowed else KERNEL_NAME,
    )(fetched, *prefetch, *operands)
    if g > 1:
        out = out.reshape(S, g, Hkv, D).swapaxes(1, 2).reshape(S, H, D)
    return out


def paged_attention(q, pool, block_tables, lengths, impl: str = "auto",
                    window: Optional[int] = None, **kw):
    """Dispatch: ``auto`` runs the Pallas kernel on TPU (scalar-
    prefetched block gather bounded by the live lengths, VMEM-resident
    softmax state), fused XLA elsewhere. ``pallas`` / ``xla`` force a
    path (parity tests run pallas in interpret mode on CPU so one
    kernel is tested everywhere). ``window``: the tables are rings and
    a lane reads its last ``window`` positions (module docstring)."""
    if impl == "auto":
        impl = "pallas" if default_platform() == "tpu" else "xla"
    if impl == "pallas":
        return paged_attention_pallas(q, pool, block_tables, lengths,
                                      window=window, **kw)
    if impl == "xla":
        return paged_attention_xla(q, pool, block_tables, lengths, window)
    raise ValueError(f"unknown paged attention impl {impl!r}")


#: table spans (keys) up to which a chunk attends through XLA's dense
#: span path on a TPU: there its fused gather and ``[H, C, T]`` scores
#: are as fast as the tiled kernel or faster (PR 33: the kernel won only
#: at 1,024 keys, by 3 %), and the largest table of a short-context
#: engine (``max_seq_len`` 1,024 plus a 256 chunk, rounded up) is 2,048.
#: Past it the dense scores grow with the table (1.9 GB a layer at
#: 16,384 keys, 1,024 rows and 28 heads) and the kernel takes over
_DENSE_SPAN_MAX = 2048
#: query rows a grid step of the chunk kernel takes (of every member of
#: a group), and the VMEM its score tiles may fill
_PREFILL_Q_TILE = 128
_PREFILL_VMEM_LIMIT = 64 << 20


def _prefill_kernel(tbl_ref, base_ref, info_ref, q_ref, *refs, G: int,
                    g: int, tq: int, scale: float,
                    window: Optional[int]):
    """One grid step (query tile ``i``, key chunk ``c``) of a chunk's
    attention: ``tq`` query positions of every head against the ``G``
    pool blocks from logical block ``base[i] + c * G`` on.

    Refs: tbl_ref [nq, nk * G] (the pool block each operand fetches),
    base_ref [nq] (a tile's first logical key block) and info_ref
    ``(p0, chunk_len)``, scalar-prefetched; q_ref [H, g * tq, D] in the
    pool's type, member ``j`` of a KV head's group in rows ``j * tq ..``;
    ``G`` pool blocks [H, Bs, 2 * D]; o_ref [H, g * tq, D]; scratch m, l
    [H, g * tq, 1], acc [H, g * tq, D]. A step whose keys start past the
    tile's last live row runs no body (and fetched nothing)."""
    kv_refs = refs[:G]
    o_ref, m_s, l_s, acc_s = refs[-4:]
    H, Bs, D2 = kv_refs[0].shape
    D = D2 // 2
    K = G * Bs
    i, c = pl.program_id(0), pl.program_id(1)
    p0, clen = info_ref[0], info_ref[1]
    q0 = p0 + i * tq                          # the tile's first position
    q_end = jnp.minimum(q0 + tq, p0 + clen)   # past its last live one
    k0 = (base_ref[i] + c * G) * Bs           # the chunk's first key

    @pl.when(c == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # a tile wholly of padding attends nothing and comes back zero
    @pl.when((k0 < q_end) & (q0 < q_end))
    def _step():
        r = lax.broadcasted_iota(jnp.int32, (g * tq, K), 0)
        qpos = q0 + (r & (tq - 1))
        kpos = k0 + lax.broadcasted_iota(jnp.int32, (g * tq, K), 1)
        mask = (kpos <= qpos) & (kpos < q_end)
        krow = k0 + lax.broadcasted_iota(jnp.int32, (K, 1), 0)
        live = krow < q_end
        if window is not None:
            mask = mask & (kpos > qpos - window)
            live = live & (krow > q0 - window)
        for h in range(H):
            q = q_ref[h]
            kk = jnp.concatenate([kv_refs[b][h, :, :D] for b in range(G)], 0)
            vv = jnp.concatenate([kv_refs[b][h, :, D:] for b in range(G)], 0)
            s = lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_s[h] = l_s[h] * corr + p.sum(axis=-1, keepdims=True)
            # a row nobody may read yet (past the chunk, an older lap
            # of a ring entry, a stranger's) can hold anything
            vv = jnp.where(live, vv, jnp.zeros((), vv.dtype))
            acc_s[h] = acc_s[h] * corr + jnp.dot(
                p.astype(vv.dtype), vv, preferred_element_type=jnp.float32)
            m_s[h] = m_new

    @pl.when(c == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_attention_pallas(q, pool, block_table, p0, chunk_len,
                                   window: Optional[int] = None,
                                   interpret: bool = False):
    """The tiled kernel form of :func:`paged_prefill_attention` (same
    contract; an f32 or bf16 pool). Grid ``(C / tq, key chunks)``: a
    step takes ``tq`` query rows of every head and ``G`` whole pool
    blocks (:func:`blocks_per_chunk`) found through the
    scalar-prefetched table; key chunks above the tile's diagonal, past
    ``p0 + chunk_len`` or (``window``) before the tile's window are
    re-aimed at the block their operand already holds and run no body.
    Rows past ``chunk_len`` come back zero. Jitted by its shapes: a
    model's layers share one lowering."""
    if is_quantized(pool):
        raise ValueError("the chunk kernel reads an f32 or bf16 pool")
    C, Hq, D = q.shape
    N, Hkv, Bs, D2 = pool.shape
    g = Hq // Hkv
    B = block_table.shape[0]
    tq = min(C, _PREFILL_Q_TILE)
    if C % tq or tq & (tq - 1):
        raise ValueError(f"a chunk of {C} rows: not a power of two")
    nq = C // tq
    p0 = jnp.asarray(p0, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    # the logical key blocks one query tile can need
    span = min(B, _cdiv(int(window) + tq, Bs) + 1) if window is not None \
        else B
    G = blocks_per_chunk(Hkv, Bs, D, pool.dtype.itemsize, span)
    nk = _cdiv(span, G)
    q0 = p0 + jnp.arange(nq, dtype=jnp.int32) * tq
    q_end = jnp.minimum(q0 + tq, p0 + chunk_len)
    base = jnp.maximum(q0 - int(window) + 1, 0) // Bs \
        if window is not None else jnp.zeros(nq, jnp.int32)
    last = (jnp.maximum(q_end, 1) - 1) // Bs - base       # [nq], >= 0
    ci, gi = jnp.divmod(jnp.arange(nk * G, dtype=jnp.int32), G)
    ci = jnp.minimum(ci[None], jnp.maximum(last[:, None] - gi[None], 0) // G)
    blk = base[:, None] + ci * G + gi[None]
    entry = blk % B if window is not None else jnp.minimum(blk, B - 1)
    fetched = jnp.asarray(block_table, jnp.int32)[entry]      # [nq, nk*G]
    # member j of a KV head's group in rows j * tq .. of its tile
    qk = q.reshape(nq, tq, Hkv, g, D).transpose(0, 2, 3, 1, 4) \
        .reshape(nq, Hkv, g * tq, D).astype(pool.dtype)
    q_spec = pl.BlockSpec((None, Hkv, g * tq, D),
                          lambda i, c, *_: (i, 0, 0, 0))

    def entry_map(b):
        return lambda i, c, tbl, *_: (tbl[i, c * G + b], 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_prefill_kernel, G=G, g=g, tq=tq,
                          scale=1.0 / (D ** 0.5), window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(nq, nk),
            in_specs=[q_spec] + [pl.BlockSpec((None, Hkv, Bs, D2),
                                              entry_map(b))
                                 for b in range(G)],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((Hkv, g * tq, 1), jnp.float32),
                            pltpu.VMEM((Hkv, g * tq, 1), jnp.float32),
                            pltpu.VMEM((Hkv, g * tq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nq, Hkv, g * tq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT),
        interpret=interpret,
        name=PREFILL_KERNEL_NAME_WINDOW if window is not None
        else PREFILL_KERNEL_NAME,
    )(fetched, base, jnp.stack([p0, chunk_len]), qk, *([pool] * G))
    return out.reshape(nq, Hkv, g, tq, D).transpose(0, 3, 1, 2, 4) \
        .reshape(C, Hq, D)


def paged_prefill_attention(q, pool, block_table, p0, chunk_len=None,
                            window: Optional[int] = None):
    """A prefill chunk's causal attention over its sequence's prefix in
    the paged pool.

    q: [C, H_q, D], the chunk's queries, row ``c`` at position ``p0 +
    c``; pool: [N, H_kv, Bs, 2 * D] (any pool type) AFTER the chunk's
    own rows were written into it (the chunk's K and V come back out of
    the pool they went into, so a start at ``p0 > 0`` -- a second
    chunk, a shared prefix, a session, a recovery -- needs nothing
    special); block_table: [n_blocks] with ``n_blocks * Bs >= p0 + C``,
    NULL-padded past the sequence's allocation (with ``window``: a
    ring, module docstring); p0: scalar; chunk_len: the rows that are
    not padding (all of them where None). Returns [C, H_q, D]: row
    ``c`` attends keys ``j <= p0 + c`` (``j > p0 + c - window``); a
    row of padding is nobody's to read.

    Up to :data:`_DENSE_SPAN_MAX` keys of table, off a TPU, and for
    heads that do not fill 128 lanes or an int8 pool, the table's whole
    span is gathered out of the pool as ``[H, T, D]`` panels and
    attended densely (:func:`span_attend`): XLA fuses the pair, and on
    a v5e it costs GPT-2 XL's chunk ~0.9 of its 17 ms at the tables
    the benchmark's traffic meets (PERF.md section 5, PR 33). Past that
    line a TPU runs :func:`paged_prefill_attention_pallas`."""
    C, _, D = q.shape
    B, Bs = block_table.shape[0], (pool.q if is_quantized(pool)
                                   else pool).shape[2]
    if (B * Bs > _DENSE_SPAN_MAX and D % 128 == 0
            and not is_quantized(pool) and default_platform() == "tpu"):
        return paged_prefill_attention_pallas(
            q, pool, block_table, p0,
            C if chunk_len is None else chunk_len, window=window)
    kk, vv = gather_span(pool, block_table)
    gpos = p0 + jnp.arange(C)
    if window is None:
        return span_attend(q, kk, vv, gpos, p0 + C, q.dtype)
    return span_attend(q, kk, vv, gpos, p0 + C, q.dtype,
                       kpos=ring_positions(B, Bs, p0 + C - 1),
                       window=window)

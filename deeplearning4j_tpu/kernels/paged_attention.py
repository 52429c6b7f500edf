"""Paged KV-cache decode attention (Pallas TPU + XLA fallback).

The paged sibling of :mod:`.decode_attention`: one query row per
sequence attends over a prefix whose K/V lives in POOL BLOCKS
(``[num_blocks, H, block_size, D]``, `serving/paging.py`) addressed
through a per-sequence block table, instead of a contiguous per-slot
panel. The op stays HBM-bandwidth bound, so the kernel's job is
unchanged — stream K/V once, keep online-softmax state in VMEM — with
one addition: the block table drives WHICH pool block each grid step
pulls. On TPU that is scalar prefetch (`pltpu.PrefetchScalarGridSpec`,
pallas guide §12): the int32 tables land in SMEM before the kernel
body runs, and the K/V BlockSpec index maps read them to aim the
HBM→VMEM DMA at the right pool block — the gather costs no extra pass
over memory.

Layout: q [S, H, D]; pools [N, H, Bs, D] (positions contiguous per
head inside a block, same reasoning as the slot cache's [S, H, T, D]);
block_tables [S, B] int32 pool indices (NULL_BLOCK-padded); lengths
[S]. Key position ``j`` of sequence ``s`` lives at
``pool[block_tables[s, j // Bs], :, j % Bs]``; positions >= lengths[s]
are masked, so padded table entries are never READ into the result —
they only keep the gather shape static.

Elsewhere the fused-XLA path gathers the blocks with ``jnp.take`` and
reuses :func:`~.decode_attention.decode_attention_xla` — the gathered
[S, H, B*Bs, D] view is bit-identical to a slot cache holding the same
prefix, which is what makes paged-vs-slot token parity testable.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (decode_attention_xla, decode_kernel,
                               decode_scratch)
from .flash_attention import default_platform
from .kv_quant import QuantArray, is_quantized

#: the Pallas kernel's name: its custom call in the HLO, and the
#: operation a device trace shows inside ``jit_step``
KERNEL_NAME = "paged_attention_decode"


def gather_blocks(pool, block_tables):
    """[N, H, Bs, D] pool + [S, B] tables -> [S, H, B*Bs, D] dense
    per-sequence panels (the slot-cache layout), via one fused gather.
    QuantArray pools gather values and their scale rows together — the
    gathered view is itself a QuantArray in slot-cache layout."""
    if is_quantized(pool):
        S, B = block_tables.shape
        N, H, Bs = pool.scale.shape
        gs = jnp.take(pool.scale, block_tables.reshape(-1), axis=0)
        gs = gs.reshape(S, B, H, Bs).transpose(0, 2, 1, 3)
        return QuantArray(gather_blocks(pool.q, block_tables),
                          gs.reshape(S, H, B * Bs))
    S, B = block_tables.shape
    N, H, Bs, D = pool.shape
    g = jnp.take(pool, block_tables.reshape(-1), axis=0)   # [S*B,H,Bs,D]
    g = g.reshape(S, B, H, Bs, D).transpose(0, 2, 1, 3, 4)
    return g.reshape(S, H, B * Bs, D)


def paged_attention_xla(q, k_pool, v_pool, block_tables, lengths):
    """Fused-XLA paged decode attention (CPU/GPU and reference path).

    q: [S, H, D]; k_pool/v_pool: [N, H, Bs, D]; block_tables: [S, B];
    lengths: [S] — positions >= lengths[s] (stale block tails, padded
    table entries) are masked out. Shapes depend only on (S, B, Bs),
    never on live lengths or which blocks a request owns.
    """
    return decode_attention_xla(q, gather_blocks(k_pool, block_tables),
                                gather_blocks(v_pool, block_tables),
                                lengths)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _paged_kernel(tbl_ref, *refs, **kw):
    # the tables are consumed by the index maps alone: by the time the
    # body runs they have already steered the DMA, and the only
    # per-position fact left is "is j < length" (covers stale tails AND
    # padded table entries), which the shared body computes
    decode_kernel(*refs, **kw)


def paged_attention_pallas(q, k_pool, v_pool, block_tables, lengths,
                           precision=lax.Precision.DEFAULT,
                           interpret: Optional[bool] = None):
    """Pallas paged decode attention. Same contract as
    :func:`paged_attention_xla`; grid (S, H, blocks-per-seq) with the
    block tables scalar-prefetched so the K/V index maps aim each grid
    step's DMA at ``pool[tbl[s, bi]]`` directly — no materialized
    gather. The body is the slot kernel's
    (:func:`~.decode_attention.decode_kernel`) with one pool block as
    the key tile. int8 QuantArray pools add their per-block-per-head
    scale rows as two more operands riding the SAME table index maps,
    so each grid step pulls one int8 block plus its [Bs] scale row and
    dequantizes in VMEM."""
    if interpret is None:
        interpret = default_platform() != "tpu"
    quant = is_quantized(k_pool)
    if quant != is_quantized(v_pool):
        raise ValueError("K and V pools must be quantized together")
    S, H, D = q.shape
    N, _, Bs, _ = k_pool.shape
    B = block_tables.shape[1]
    # q/out/scales carry a unit second-minor dim: a one-row tile of an
    # [.., H, D] array is not a block shape the TPU lowering takes
    # (second-minor must be a multiple of 8 or the whole dim)
    q_spec = pl.BlockSpec((None, None, 1, D),
                          lambda s, h, bi, tbl, lens: (s, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, Bs, D),
                           lambda s, h, bi, tbl, lens:
                           (tbl[s, bi], h, 0, 0))
    operands, in_specs = [q.reshape(S, H, 1, D)], [q_spec]
    if quant:
        sc_spec = pl.BlockSpec((None, None, 1, Bs),
                               lambda s, h, bi, tbl, lens:
                               (tbl[s, bi], h, 0, 0))
        operands += [k_pool.q, v_pool.q,
                     k_pool.scale.reshape(N, H, 1, Bs),
                     v_pool.scale.reshape(N, H, 1, Bs)]
        in_specs += [kv_spec, kv_spec, sc_spec, sc_spec]
    else:
        operands += [k_pool, v_pool]
        in_specs += [kv_spec, kv_spec]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, quant=quant, blk_k=Bs,
                          scale=1.0 / (D ** 0.5), precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # block_tables, lengths
            grid=(S, H, B),
            in_specs=in_specs, out_specs=q_spec,
            scratch_shapes=decode_scratch(D)),
        out_shape=jax.ShapeDtypeStruct((S, H, 1, D), q.dtype),
        interpret=interpret,
        # the custom call's instruction name in the HLO and so in a
        # device trace (else it is named after the enclosing jit)
        name=KERNEL_NAME,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(lengths, jnp.int32), *operands)
    return out.reshape(S, H, D)


def paged_attention(q, k_pool, v_pool, block_tables, lengths,
                    impl: str = "auto", **kw):
    """Dispatch: ``auto`` runs the Pallas kernel on TPU (scalar-
    prefetched block gather + VMEM-resident softmax state), fused XLA
    elsewhere. ``pallas`` / ``xla`` force a path (parity tests run
    pallas in interpret mode on CPU so one kernel is tested
    everywhere)."""
    if impl == "auto":
        impl = "pallas" if default_platform() == "tpu" else "xla"
    if impl == "pallas":
        return paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                      lengths, **kw)
    if impl == "xla":
        return paged_attention_xla(q, k_pool, v_pool, block_tables,
                                   lengths)
    raise ValueError(f"unknown paged attention impl {impl!r}")

"""Grouped expert matmuls of a routed mixture-of-experts layer (Pallas
TPU + XLA forms).

The token-expert pairs of a step arrive SORTED BY EXPERT: rows
``offsets[e] .. offsets[e + 1]`` of ``x`` belong to expert ``e``, and
rows past ``offsets[E]`` (pairs of dead lanes) to none. The layer needs
``h = silu(x W1[e]) * (x W3[e])`` and then ``h W2[e]``, each row with
its own expert's matrices. At a decode step (a few rows an expert) the
op is bound by the bytes of the experts it reads, so the kernel's job
is to read every expert that received a row ONCE and an expert that
received none NOT AT ALL.

One kernel body (:func:`_grouped_kernel`) serves both products and both
programs (the decode step's 64 rows, a prefill chunk's 1,024). Its grid
is ``(N / tn, V)``: ``V`` visits, each one (expert, row tile) pair of
``jax.experimental.pallas.ops.tpu.megablox``'s group metadata (a row
tile is visited once for the expert that owns its first row and once
more for every expert that starts inside it; an expert with no rows has
no visit). The visit's expert and row tile are scalar-prefetched, so a
weight block ``[K, tn]`` of ``W[e]`` is one DMA that Pallas's pipeline
starts while the visit before it computes. The grid is static
(``M / tm + E - 1`` visits, the most there can be); visits past the
live count are aimed at the blocks the last live visit held, which
starts no DMA, and run no body (``pl.when``): the trick of
:mod:`.paged_attention`. A visit computes its whole row tile on the MXU
(bf16 operands, f32 accumulation) and stores the rows of its own expert
under a mask, so consecutive visits of one tile fill it in together.
Rows of no expert are never stored: the caller masks them.

The XLA form is ``jax.lax.ragged_dot``: what runs off a TPU, and one of
the two the tests hold the kernel to (the other is their own plain sum).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from .flash_attention import default_platform

#: the kernel's name: its custom call in the HLO, and the operation a
#: device trace shows inside ``jit_step`` and ``jit_chunk``
KERNEL_NAME = "moe_experts"

#: scoped VMEM the kernel may fill: two weight blocks of [2048, 896]
#: bf16 (3.7 MB each), double-buffered, beside the row tile
_VMEM_LIMIT = 48 << 20


def silu(x):
    return x * jax.nn.sigmoid(x)


#: what a gated pair puts on its first product: ``act(x W1) * (x W3)``
GATES = {"silu": silu, "relu": lambda x: jnp.maximum(x, 0.0)}


def _row_tile(M: int) -> int:
    """Rows a visit computes: the whole of a decode step's pairs, 128
    of a chunk's (a chunk's experts hold ~32 rows each, so a larger
    tile only multiplies rows by matrices that are not theirs)."""
    for tm in (128, 64, 32, 16, 8):
        if M % tm == 0:
            return tm
    raise ValueError(f"{M} token-expert pairs: not a multiple of 8")


def _col_tile(N: int) -> int:
    """Output columns a visit computes: the largest multiple of 128
    that divides ``N`` and is at most 1,024 (a [2048, 896] bf16 block
    is 3.7 MB)."""
    best = None
    for tn in range(128, min(N, 1024) + 1, 128):
        if N % tn == 0:
            best = tn
    return best or N


def _grouped_kernel(gid_ref, mt_ref, off_ref, nv_ref, x_ref, *refs,
                    gated: bool, tm: int, gate: str = "silu"):
    """One visit: row tile ``mt[v]`` of ``x`` against expert ``gid[v]``'s
    ``[K, tn]`` block(s). ``gated`` takes two weight blocks and stores
    ``gate(x W1) * (x W3)`` (:data:`GATES`); else one, and stores ``x W``. Only the rows
    of this visit's expert are stored (``off`` holds every expert's
    first row); what the output tile holds elsewhere is another visit's
    work, or nothing yet."""
    o_ref = refs[-1]
    v = pl.program_id(1)

    @pl.when(v < nv_ref[0])
    def _visit():
        x = x_ref[...]
        y = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
        if gated:
            y = GATES[gate](y) * jnp.dot(
                x, refs[1][...], preferred_element_type=jnp.float32)
        e = gid_ref[v]
        row = mt_ref[v] * tm + lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (row >= off_ref[e]) & (row < off_ref[e + 1])
        o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def visit_metadata(group_sizes, M: int, tm: int):
    """(expert of each visit, row tile of each visit, first row of each
    expert, live visits) for ``M`` sorted rows cut into tiles of ``tm``:
    megablox's metadata with empty experts squeezed out, its dead tail
    re-aimed at the last live visit so that it fetches nothing."""
    E = group_sizes.shape[0]
    (offsets, gids, mts), n = make_group_metadata(
        group_sizes=group_sizes, m=M, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=E,
        visit_empty_groups=False)
    last = jnp.maximum(n - 1, 0)
    keep = jnp.minimum(jnp.arange(gids.shape[0], dtype=jnp.int32), last)
    return (gids[keep].astype(jnp.int32), mts[keep].astype(jnp.int32),
            offsets.astype(jnp.int32), n.astype(jnp.int32).reshape(1))


def grouped_matmul_pallas(x, ws, meta, out_dtype,
                          interpret: Optional[bool] = None,
                          gate: str = "silu"):
    """``x`` [M, K] (rows sorted by expert) against ``ws``: one
    ``[E, K, N]`` stack (plain product) or two (the gated pair).
    ``meta`` is :func:`visit_metadata`'s. Rows of no expert come back
    undefined."""
    if interpret is None:
        interpret = default_platform() != "tpu"
    M, K = x.shape
    N = ws[0].shape[2]
    tm, tn = _row_tile(M), _col_tile(N)
    gids, mts, offsets, n = meta
    V = gids.shape[0]
    x_spec = pl.BlockSpec((tm, K), lambda j, v, g, t, o, n: (t[v], 0))
    w_spec = pl.BlockSpec((None, K, tn),
                          lambda j, v, g, t, o, n: (g[v], 0, j))
    o_spec = pl.BlockSpec((tm, tn), lambda j, v, g, t, o, n: (t[v], j))
    return pl.pallas_call(
        functools.partial(_grouped_kernel, gated=len(ws) == 2, tm=tm,
                          gate=gate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(N // tn, V),
            in_specs=[x_spec] + [w_spec] * len(ws), out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_NAME + ("_up" if len(ws) == 2 else "_down"),
    )(gids, mts, offsets, n, x, *ws)


def expert_ffn(x, w1, w3, w2, group_sizes, impl: str = "auto",
               gate: str = "silu", **kw):
    """The experts' gated unit (``gate`` ``silu``: SwiGLU; ``relu``:
    ReGLU) over rows sorted by expert: x [M, D];
    w1, w3 [E, D, F]; w2 [E, F, D]; group_sizes [E] int32 (rows of each
    expert, in order; rows past their sum belong to none and come back
    undefined). Returns [M, D] float32.

    ``auto`` is the Pallas kernel on a TPU and ``jax.lax.ragged_dot``
    (``ragged``) elsewhere."""
    if impl == "auto":
        impl = "pallas" if default_platform() == "tpu" else "ragged"
    M = x.shape[0]
    if impl == "pallas":
        meta = visit_metadata(group_sizes, M, _row_tile(M))
        h = grouped_matmul_pallas(x, (w1, w3), meta, x.dtype, gate=gate,
                                  **kw)
        return grouped_matmul_pallas(h, (w2,), meta, jnp.float32, **kw)
    if impl == "ragged":
        dot = functools.partial(lax.ragged_dot, group_sizes=group_sizes,
                                preferred_element_type=jnp.float32)
        h = (GATES[gate](dot(x, w1)) * dot(x, w3)).astype(x.dtype)
        return dot(h, w2)
    raise ValueError(f"unknown expert impl {impl!r}")

"""Single-token KV-cache decode attention (Pallas TPU + XLA fallback).

The autoregressive decode hot-op: one query row per sequence attends
over that sequence's cached K/V prefix. Unlike training attention the
arithmetic intensity is O(1) FLOPs per byte — the op is HBM-bandwidth
bound on streaming the KV cache — so the kernel's job is pure
streaming: pull K/V blocks HBM→VMEM once, keep the online-softmax
running state (m, l, acc) in VMEM scratch, and never materialize the
[T] score row in HBM (the vLLM/PagedAttention decode regime, PAPERS.md;
same construction as `flash_attention`'s forward with blk_q == 1).

Layout: q [S, H, D], k/v caches [S, H, T_max, D], lengths [S] (valid
prefix per slot, i.e. pos + 1). The cache keeps T contiguous per head
— decode attention is then a batched matvec over contiguous [T, D]
panels (measured ~2x over the [S, T, H, D] layout on CPU, and the
kernel's per-(slot, head) [blk_k, D] tile is a contiguous slab).
Inactive or short slots mask by their length — the executable shape
never changes, which is what keeps the serving decode loop at zero
recompiles.

On TPU this runs the Pallas kernel; elsewhere the fused-XLA einsum path
is the default (the Pallas interpreter is for parity tests only).
Matmuls use preferred_element_type=f32 (pallas guide: pitfalls #5);
validity is computed in-kernel from the scalar-prefetched lengths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NEG_INF, _cdiv, default_platform
from .kv_quant import is_quantized, kv_operands


def _quantized_leg(x) -> bool:
    """True when a cache operand is int8 (QuantArray) or bf16 — the
    legs whose dots must run on bf16 operands so no f32 cache read
    round-trips through HBM (checked in StableHLO, by dot OPERAND
    dtypes: tests/test_kv_quant.py::TestDotOperandAudit)."""
    return is_quantized(x) or x.dtype == jnp.bfloat16


def decode_attention_xla(q, k, v, lengths):
    """Fused-XLA decode attention (the CPU/GPU and reference path).

    q: [S, H, D]; k/v: [S, H, T, D] arrays or int8 QuantArrays with
    per-position scales; lengths: [S] — keys at positions >= lengths[s]
    (unwritten cache tail) are masked out. Fully static shapes: T is
    the cache capacity, not the live length. The f32 path is
    bit-identical to the pre-quantization kernel; bf16/int8 legs use
    bf16-operand dots with f32 accumulation and fold the int8 scales
    around the dots (K post-dot, V into the probabilities).
    """
    S, H, T, D = k.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    valid = jnp.arange(T)[None, None, :] < lengths[:, None, None]
    if _quantized_leg(k) or _quantized_leg(v):
        kb, kscale = kv_operands(k)
        vb, vscale = kv_operands(v)
        s = jnp.einsum("shd,shtd->sht", q.astype(jnp.bfloat16), kb,
                       preferred_element_type=jnp.float32) * scale
        if kscale is not None:            # [S, H, T] per-position scales
            s = s * kscale
        s = jnp.where(valid, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(valid, p, 0.0)
        if vscale is not None:
            # fold V scales into p. The where-guard matters: a stale
            # tail's scale may be NaN (poison is scale-carried, see
            # kv_quant.quantize_rows) and 0 * NaN = NaN
            p = jnp.where(valid, p * vscale, 0.0)
        # bf16 pools can hold a non-finite stale tail directly
        vb = jnp.where(valid[..., None], vb, jnp.bfloat16(0))
        out = jnp.einsum("sht,shtd->shd", p.astype(jnp.bfloat16), vb,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype)
    s = jnp.einsum("shd,shtd->sht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (length 0: a free slot riding the batch) would
    # softmax to uniform and read garbage V — zero them instead
    p = jnp.where(valid, p, 0.0)
    # V must be masked as well: p is 0 past the live length, but
    # 0 * NaN = NaN, and a recycled slot's stale tail may hold
    # non-finite K/V (e.g. a quarantined poison request's leavings)
    v = jnp.where(valid[..., None], v.astype(jnp.float32), 0.0)
    return jnp.einsum("sht,shtd->shd", p, v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def decode_kernel(len_ref, q_ref, k_ref, v_ref, *refs, quant: bool,
                  blk_k: int, scale: float, precision):
    """One grid step (sequence, head, key-block) of online-softmax
    decode attention; shared with :mod:`.paged_attention`, whose only
    difference is WHICH [blk_k, D] tile the index maps fetch.

    Refs (leading grid dims squeezed): len_ref [S] scalar-prefetched
    lengths; q [1, D]; k/v [blk_k, D]; then, for an int8 cache
    (``quant``), ks/vs [1, blk_k] per-position f32 scales; then the
    output [1, D] and the :func:`decode_scratch` tiles.

    Everything stays 2-D — the running max/sum are (1, 1) tiles and the
    reductions keep their dims — because Mosaic has no layout for the
    1-D result of reducing a one-row tile. Validity is computed from
    the length twice, as a lane row (scores) and a sublane column (V
    rows), instead of transposing one into the other."""
    ks_ref, vs_ref = refs[:2] if quant else (None, None)
    o_ref, m_s, l_s, acc_s = refs[-4:]
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[pl.program_id(0)]
    mask = kb * blk_k + lax.broadcasted_iota(
        jnp.int32, (1, blk_k), 1) < length
    # bf16/int8 caches keep bf16 operands (MXU-native, f32 accumulation;
    # int8 in [-127, 127] casts to bf16 exactly); only a true f32 cache
    # runs f32 dots
    od = jnp.float32 if k_ref.dtype == jnp.float32 else jnp.bfloat16
    s = lax.dot_general(q_ref[...].astype(od), k_ref[...].astype(od),
                        (((1,), (1,)), ((), ())), precision=precision,
                        preferred_element_type=jnp.float32) * scale
    if quant:
        s = s * ks_ref[...]                               # K dequant
    s = jnp.where(mask, s, _NEG_INF)                      # [1, blk_k]
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # where-guard keeps fully-masked rows at p=0 (exp(-inf - -inf) = 1
    # would fabricate uniform attention for an empty slot)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_s[...] = m_new
    l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
    if quant:
        # V dequant folds into p. Where-guard required: a poisoned stale
        # tail carries NaN in its SCALE (kv_quant.quantize_rows) and
        # 0 * NaN = NaN; the int8 values themselves are always finite,
        # so a masked lane contributes exactly 0
        p = jnp.where(mask, p * vs_ref[...], 0.0)
        v_blk = v_ref[...].astype(od)
    else:
        # zero masked V rows: p=0 there, but 0 * NaN = NaN would leak a
        # recycled slot's non-finite stale tail into the accumulator
        col = kb * blk_k + lax.broadcasted_iota(
            jnp.int32, (blk_k, 1), 0) < length
        v_blk = jnp.where(col, v_ref[...], 0).astype(od)
    acc_s[...] = acc_s[...] * corr + jnp.dot(
        p.astype(od), v_blk, precision=precision,
        preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                      ).astype(o_ref.dtype)


def decode_scratch(D: int):
    """VMEM scratch of :func:`decode_kernel`."""
    return [pltpu.VMEM((1, 1), jnp.float32),    # running max
            pltpu.VMEM((1, 1), jnp.float32),    # running sum
            pltpu.VMEM((1, D), jnp.float32)]    # output accumulator


def decode_attention_pallas(q, k, v, lengths, block_k: int = 128,
                            precision=lax.Precision.DEFAULT,
                            interpret: Optional[bool] = None):
    """Pallas decode attention. Same contract as
    :func:`decode_attention_xla`; grid (S, H, k-blocks) with the
    lengths scalar-prefetched, so validity is computed in-kernel and a
    ragged last k-block needs no padded copy of the cache (whatever the
    edge tile reads past T sits at positions >= length, masked). int8
    QuantArray caches add their per-position scale rows as two more
    operands riding the K/V index maps; dequant happens in VMEM, HBM
    only ever streams int8."""
    if interpret is None:
        interpret = default_platform() != "tpu"
    quant = is_quantized(k)
    if quant != is_quantized(v):
        raise ValueError("K and V caches must be quantized together")
    S, H, T, D = k.shape
    blk_k = min(block_k, T)
    q_spec = pl.BlockSpec((None, None, 1, D),
                          lambda s, h, kb, lens: (s, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, blk_k, D),
                           lambda s, h, kb, lens: (s, h, kb, 0))
    operands, in_specs = [q.reshape(S, H, 1, D)], [q_spec]
    if quant:
        sc_spec = pl.BlockSpec((None, None, 1, blk_k),
                               lambda s, h, kb, lens: (s, h, 0, kb))
        operands += [k.q, v.q, k.scale.reshape(S, H, 1, T),
                     v.scale.reshape(S, H, 1, T)]
        in_specs += [kv_spec, kv_spec, sc_spec, sc_spec]
    else:
        operands += [k, v]
        in_specs += [kv_spec, kv_spec]
    out = pl.pallas_call(
        functools.partial(decode_kernel, quant=quant, blk_k=blk_k,
                          scale=1.0 / (D ** 0.5), precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,          # lengths
            grid=(S, H, _cdiv(T, blk_k)),
            in_specs=in_specs, out_specs=q_spec,
            scratch_shapes=decode_scratch(D)),
        out_shape=jax.ShapeDtypeStruct((S, H, 1, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), *operands)
    return out.reshape(S, H, D)


def decode_attention(q, k, v, lengths, impl: str = "auto", **kw):
    """Dispatch: ``auto`` runs the Pallas kernel on TPU (KV streaming
    with VMEM-resident softmax state), fused XLA elsewhere. ``pallas``
    / ``xla`` force a path (parity tests run pallas in interpret mode
    on CPU so one kernel is tested everywhere)."""
    if impl == "auto":
        impl = "pallas" if default_platform() == "tpu" else "xla"
    if impl == "pallas":
        return decode_attention_pallas(q, k, v, lengths, **kw)
    if impl == "xla":
        return decode_attention_xla(q, k, v, lengths)
    raise ValueError(f"unknown decode attention impl {impl!r}")

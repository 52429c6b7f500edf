"""Quantized KV-cache plumbing: int8 pools with per-position scales.

The serving plane's decode loop is HBM-bound — every generated token
re-reads the whole live KV cache — so cache BYTES are the binding
resource (ROADMAP item 3). This module makes the pool dtype a
first-class knob: ``f32`` (exact, default), ``bf16`` (half the bytes,
stored natively), and ``int8`` (quarter the bytes, per-position
per-head scales in a small f32 sidecar).

Design points:

* **QuantArray is a registered pytree** of ``(q: int8, scale: f32)``
  with ``scale.shape == q.shape[:-1]`` — one scale per (…, position)
  row over ``head_dim``. The paged pool keeps a position's key and
  value in one row (`kernels/paged_attention.py`), each half under its
  own scale, so its sidecar is ``[num_blocks, 2, H, block_size]``:
  still per-block-per-head scales indexed by block id (the block is
  the quantization granule ISSUE 15 asks for), and the one QuantArray
  whose ``scale`` is not ``q.shape[:-1]``. Because executables thread caches as pytrees, the int8
  pool slots into every existing prefill/decode/verify signature AND
  the donation tuple with zero signature churn in the engine.

* **Quantize-on-write, dequantize in-kernel.** All scatter sites
  (decode token writes, prefill slab writes, paged chunk writes) go
  through :func:`kv_set` / :func:`kv_update_slice` (the paged pool's
  through ``paged_attention.kv_pool_set``), which compute the row
  abs-max scale and store int8; the attention kernels apply the
  scale inside their online-softmax loop, so f32 K/V never round-trips
  through HBM.

* **NaN transparency.** ``scale = where(amax == 0, 1, amax/127)``
  deliberately uses ``== 0`` and not ``> 0``: for a NaN row, amax is
  NaN, NaN == 0 is False, so the scale itself carries the NaN and any
  reader dequantizes back to NaN. This keeps the engine's in-graph
  isfinite quarantine firing on poisoned activations — quantization
  must never launder a NaN into finite garbage
  (tests/test_kv_quant.py::TestQuarantine).

* **bf16 operands, f32 accumulation.** Quantized legs run their dots
  with bf16 operands and ``preferred_element_type=f32`` (int8 values
  in [-127, 127] cast to bf16 exactly, and MXU natively accumulates
  bf16xbf16 into f32). That makes "zero unintended f32 dots" a
  checked property of the lowered StableHLO
  (tests/test_kv_quant.py::TestDotOperandAudit) instead of a hope.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KV_DTYPES = ("f32", "bf16", "int8")

_STORAGE = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


def canonical_kv_dtype(kv_dtype: str) -> str:
    d = {"float32": "f32", "bfloat16": "bf16"}.get(str(kv_dtype),
                                                   str(kv_dtype))
    if d not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                         f"got {kv_dtype!r}")
    return d


@jax.tree_util.register_pytree_node_class
class QuantArray:
    """int8 values + f32 per-row scales (``scale.shape == q.shape[:-1]``,
    the trailing axis — head_dim — shares one scale). Registered as a
    pytree so jit/donation thread it exactly like a plain array."""

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"QuantArray(q={self.q.shape}, scale={self.scale.shape})"


def is_quantized(x) -> bool:
    return isinstance(x, QuantArray)


def quantize_rows(x: jnp.ndarray) -> QuantArray:
    """Symmetric per-row int8 quantization over the trailing axis.

    NaN-transparent by construction: a non-finite row yields a
    non-finite scale (NaN == 0 is False), so dequantization reproduces
    the poison instead of crushing it — required by the quarantine
    invariant (module docstring)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax == 0, jnp.float32(1.0), amax / 127.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return QuantArray(q.astype(jnp.int8), scale)


def dequantize(x: QuantArray) -> jnp.ndarray:
    return x.q.astype(jnp.float32) * x.scale[..., None]


def kv_zeros(shape: Sequence[int], kv_dtype: str):
    """Allocate one pool array of ``shape`` for ``kv_dtype`` — a plain
    array for f32/bf16, a QuantArray (int8 + f32 sidecar) for int8."""
    kv_dtype = canonical_kv_dtype(kv_dtype)
    if kv_dtype == "int8":
        return QuantArray(jnp.zeros(shape, jnp.int8),
                          jnp.zeros(shape[:-1], jnp.float32))
    return jnp.zeros(shape, _STORAGE[kv_dtype])


def kv_nbytes(shape: Sequence[int], kv_dtype: str) -> int:
    """Device bytes one pool array of ``shape`` pins, sidecar
    included — the dtype-aware pool-sizing formula."""
    kv_dtype = canonical_kv_dtype(kv_dtype)
    n = int(np.prod(shape))
    if kv_dtype == "int8":
        return n + int(np.prod(shape[:-1])) * 4  # int8 values + f32 scales
    return n * jnp.dtype(_STORAGE[kv_dtype]).itemsize


def kv_bytes_per_token(layer_shapes, kv_dtype: str) -> int:
    """K+V bytes one token position costs across all layers."""
    kv_dtype = canonical_kv_dtype(kv_dtype)
    total = 0
    for s in layer_shapes:            # (H, T_or_Bs, Dh)
        h, _, dh = s
        per_tok = h * dh
        if kv_dtype == "int8":
            total += 2 * (per_tok + h * 4)
        else:
            total += 2 * per_tok * jnp.dtype(_STORAGE[kv_dtype]).itemsize
    return total


def kv_set(cache, idx, values: jnp.ndarray):
    """Scatter ``values`` (f32, trailing axis = head_dim) into a pool
    at ``idx`` (an index tuple over the non-trailing axes), quantizing
    on write when the pool is int8. The same ``idx`` addresses the
    scale sidecar because scale drops only the trailing axis."""
    if is_quantized(cache):
        qv = quantize_rows(values)
        return QuantArray(cache.q.at[idx].set(qv.q),
                          cache.scale.at[idx].set(qv.scale))
    return cache.at[idx].set(values.astype(cache.dtype))


def kv_update_slice(cache, slab: jnp.ndarray, start: Sequence[int]):
    """dynamic_update_slice of a prefill slab into a pool row,
    quantize-on-write for int8. ``start`` indexes the full pool shape;
    the sidecar update drops its trailing 0."""
    if is_quantized(cache):
        qv = quantize_rows(slab)
        return QuantArray(
            jax.lax.dynamic_update_slice(cache.q, qv.q, tuple(start)),
            jax.lax.dynamic_update_slice(cache.scale, qv.scale,
                                         tuple(start[:-1])))
    return jax.lax.dynamic_update_slice(cache, slab.astype(cache.dtype),
                                        tuple(start))


def kv_copy_row(cache, src, dst):
    """Copy leading-axis row ``src`` -> ``dst`` (COW block copy). For
    int8 pools this copies the block AND its scale row together — the
    prefix-sharing invariant ISSUE 15 calls out."""
    if is_quantized(cache):
        return QuantArray(cache.q.at[dst].set(cache.q[src]),
                          cache.scale.at[dst].set(cache.scale[src]))
    return cache.at[dst].set(cache[src])


# ------------------------------------------- block-run gather / scatter
#
# The hierarchical KV tier (PR 16, serving/offload.py) moves RUNS of
# pool rows between device and host. Device-side movement is two tiny
# pure fns — gather rows out (demotion, pools NOT donated) and scatter
# rows back in (restore, pools donated) — compiled once per pow2 idx
# bucket through the engine's compile_memoized path, exactly like the
# COW copy. Host-side, a run becomes contiguous numpy copies (int8
# values + f32 scale sidecars for quantized pools) so the byte budget
# and the disk ring see plain buffers.

def kv_gather_rows(cache, idx):
    """Gather leading-axis rows ``idx`` out of a pool (demotion read).
    For int8 pools the scale rows ride along — a demoted run is always
    (values, scales) at pool dtype, never a dequantized f32 blow-up."""
    if is_quantized(cache):
        return QuantArray(jnp.take(cache.q, idx, axis=0),
                          jnp.take(cache.scale, idx, axis=0))
    return jnp.take(cache, idx, axis=0)


def kv_scatter_rows(cache, rows, idx):
    """Scatter ``rows`` (as produced by :func:`kv_gather_rows`) back
    into pool rows ``idx`` (restore write). Padded idx entries may
    repeat a junk destination (the engine points them at NULL_BLOCK);
    ``.at[].set`` keeps that well-defined — last write wins and the
    null block is never read."""
    if is_quantized(cache):
        return QuantArray(cache.q.at[idx].set(rows.q),
                          cache.scale.at[idx].set(rows.scale))
    return cache.at[idx].set(rows)


def kv_pack_host(rows, n: int):
    """Materialize the first ``n`` gathered rows as contiguous HOST
    numpy arrays: ``(values,)`` for plain pools, ``(q, scale)`` for
    int8. ``np.asarray`` forces the device→host transfer AND the sync,
    so once this returns the source pool rows may be freed/reused."""
    if is_quantized(rows):
        return (np.ascontiguousarray(np.asarray(rows.q)[:n]),
                np.ascontiguousarray(np.asarray(rows.scale)[:n]))
    return (np.ascontiguousarray(np.asarray(rows)[:n]),)


def kv_unpack_host(parts, bucket: int):
    """Rebuild scatter operands from :func:`kv_pack_host` output,
    zero-padded up to ``bucket`` rows so every restore of the same
    bucket reuses one compiled scatter executable (runtime operands
    only — the zero-recompile contract)."""
    vals = parts[0]
    n = vals.shape[0]
    pad = [(0, bucket - n)] + [(0, 0)] * (vals.ndim - 1)
    padded = np.pad(vals, pad)
    if len(parts) == 2:
        scale = np.pad(parts[1],
                       [(0, bucket - n)] + [(0, 0)] * (parts[1].ndim - 1))
        return QuantArray(jnp.asarray(padded), jnp.asarray(scale))
    return jnp.asarray(padded)


def kv_host_nbytes(parts) -> int:
    """Host bytes one packed run occupies (budget accounting)."""
    return int(sum(p.nbytes for p in parts))


# ---------------------------------------------------------------- reads

def kv_dequant_f32(cache) -> jnp.ndarray:
    """Full f32 view of a pool — reference/XLA paths and tests. The
    fused kernels never call this on the whole pool."""
    if is_quantized(cache):
        return dequantize(cache)
    return cache.astype(jnp.float32)


def kv_operands(cache) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(values_bf16, scale_f32_or_None) pair for scale-folded fused
    paths: the dot runs on bf16 operands (int8 casts to bf16 exactly)
    and the per-row scale is applied OUTSIDE the dot — post-dot for K
    (scores scale linearly in k) and folded into the probabilities for
    V. ``None`` scale means "already the right magnitude" (bf16 pool)
    so callers skip the multiply instead of streaming a ones array."""
    if is_quantized(cache):
        return cache.q.astype(jnp.bfloat16), cache.scale
    return cache.astype(jnp.bfloat16), None


# ------------------------------------------------- weight-only matmul

@jax.tree_util.register_pytree_node_class
class QuantWeight:
    """int8 weight-only matrix for MLP matmuls: ``q[in, out]`` int8
    with one f32 scale per OUTPUT channel. Registered pytree so it
    rides inside the params dict unchanged."""

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    @property
    def shape(self):
        return self.q.shape

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"QuantWeight(q={self.q.shape})"


def quantize_weight(w: jnp.ndarray) -> QuantWeight:
    """Per-output-channel symmetric int8 (LLM.int8()-style weight-only
    path, minus the outlier decomposition — these MLPs have none)."""
    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)               # per out-channel
    scale = jnp.where(amax == 0, jnp.float32(1.0), amax / 127.0)
    q = jnp.clip(jnp.round(wf / scale[None, :]), -127, 127)
    return QuantWeight(q.astype(jnp.int8), scale)


def mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` with weight-only int8 dispatch: bf16 operands,
    f32 accumulation, per-output-channel dequant fused after the dot.
    Plain arrays fall through to the ordinary matmul."""
    if isinstance(w, QuantWeight):
        y = jax.lax.dot_general(
            x.astype(jnp.bfloat16), w.q.astype(jnp.bfloat16),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return y * w.scale
    return x @ w

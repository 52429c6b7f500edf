"""The selective state-space recurrence of a Mamba-1 layer : a prefill
chunk's rows (a Pallas TPU kernel and an XLA form), and a decode step's
one row a slot.

For every channel ``d`` of ``Di`` and every state ``n`` of ``N``:

    h_t[n, d] = exp(dt_t[d] * A[n, d]) * h_{t-1}[n, d]
                + dt_t[d] * c_t[d] * B_t[n]
    y_t[d]    = sum_n h_t[n, d] * C_t[n]  +  D[d] * c_t[d]

``c`` is the convolved, activated input, ``dt`` the step size after its
softplus, ``B`` and ``C`` the row's input and output projections of the
state. Everything is float32. **The state's layout here is ``[N, Di]``**
(states on sublanes, channels on lanes: with ``N = 16`` a state of 128
channels is two vector registers, where ``[Di, N]`` would fill an eighth
of each), and ``A`` comes in the same layout. A row with ``dt = 0``
leaves the state exactly as it was (``exp(0) = 1``, nothing added):
that is how a chunk's padded rows and a step's dead lanes stand still.

**The chunk kernel** (:func:`selective_scan_chunk`). The rows of a
chunk depend on each other, so the kernel's job is to keep ``h`` out of
HBM: a ``lax.scan`` over rows is one device operation a row, and an
associative scan writes ``[T, Di, N]`` several times. The grid is (row
blocks, channel tiles), both walked in order with the rows outermost;
``h`` for all channels lives in a VMEM scratch across the whole call
(``N * Di * 4`` bytes: 328 KB at 5,120 channels). A grid step loads its
tile's ``h`` into registers, walks ``Tb`` rows eight at a time (an
aligned ``[8, tc]`` tile of ``dt`` and ``c`` a time; each row's
``exp(dt * A)`` and ``dt * c * B`` formed in registers, never in HBM;
the eight rows' sums over ``n`` gathered into one ``[8, tc]`` tile of
``y``) and puts ``h`` back. ``B`` and ``C`` enter broadcast over 128
lanes (``[T, N, 128]``, made by XLA: a row's ``[N, 128]`` is then two
plain loads that every lane group of the tile shares; their block
follows the row block alone, so with the rows outermost it is fetched
once). Rows at or past ``chunk_len`` take ``dt = 0``; a row block
wholly past it runs no body. ``D * c`` and, where ``z`` is given, the
gate ``y * silu(z)`` are applied to the ``[8, tc]`` tile before it is
stored. The arithmetic is VPU and EUP work along a dependent chain
(per row and 128 channels: ~18 vector operations and two ``exp``), the
bytes are four ``[T, Di]`` passes: compute binds, not HBM.

**The step** (:func:`selective_scan_step`): one row a slot. The work is
the state's bytes, read and written once (``2 * S * N * Di * 4``), and
XLA's fusion of the five elementwise operations does it at 75 % of the
HBM roofline (0.50 ms for the 26 layers of 16 slots x 5,120 channels on
a v5e, the bytes' time 0.375; a Pallas body of its own, grid (slots),
the state aliased onto its output, read 0.54 ms beside it and was not
kept: PERF.md section 6, PR 37). So the step has no kernel.

``impl="xla"`` of the chunk is the plain form (a ``lax.scan`` over
rows): what runs off a TPU, and what the tests and
``tools/selective_scan_bench.py`` hold the kernel to.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import default_platform

#: the kernel's name: its custom call in the HLO, and the operation a
#: device trace shows inside ``jit_chunk``
CHUNK_KERNEL_NAME = "selective_scan_chunk"

#: rows a grid step of the chunk kernel walks, and channels (lanes) it
#: keeps in registers: 4 lane groups of 128 are 8 registers of state
#: and four independent chains for the scheduler to interleave
_ROW_BLOCK = 128
_CHANNEL_TILE = 512
_LANES = 128
_VMEM_LIMIT = 48 << 20


def silu(x):
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# XLA forms
# ---------------------------------------------------------------------------
def selective_scan_chunk_xla(c, dt, B, C, A, D, h0, chunk_len, z=None):
    """The plain form of :func:`selective_scan_chunk`: a ``lax.scan``
    over the rows."""
    T = c.shape[0]
    live = (jnp.arange(T) < chunk_len)[:, None]
    dt = jnp.where(live, dt, 0.0)
    x = jnp.where(live, dt * c, 0.0)
    B = jnp.where(live, B, 0.0)         # 0 * NaN would reach the state

    def row(h, r):
        dt_t, x_t, b_t, c_t = r
        h = jnp.exp(dt_t[None, :] * A) * h + x_t[None, :] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0)

    h, y = lax.scan(row, h0, (dt, x, B, C))
    y = jnp.where(live, y + D[None] * c, 0.0)
    if z is not None:
        y = jnp.where(live, y * silu(z), 0.0)
    return y, h


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _chunk_kernel(len_ref, dt_ref, c_ref, bb_ref, cb_ref, a_ref, d_ref,
                  h0_ref, *refs, Tb: int, gated: bool):
    """One grid step (row block ``i``, channel tile ``j``).

    Refs: len_ref ``(chunk_len,)`` scalar-prefetched; dt_ref, c_ref
    (and z_ref where ``gated``) ``[Tb, tc]``; bb_ref, cb_ref ``[Tb, N,
    128]`` (a row's B and C on every lane); a_ref ``[N, tc]``; d_ref
    ``[1, tc]``; h0_ref ``[N, tc]``; y_ref ``[Tb, tc]``; hout_ref ``[N,
    tc]``; scratch h_s ``[tiles, N, tc]``, the state of every channel
    tile across row blocks."""
    z_ref, refs = (refs[0], refs[1:]) if gated else (None, refs)
    y_ref, hout_ref, h_s = refs
    i, j = pl.program_id(0), pl.program_id(1)
    N, tc = a_ref.shape
    G = tc // _LANES
    clen = len_ref[0]
    base = i * Tb

    @pl.when(i == 0)
    def _first():
        h_s[j] = h0_ref[...]

    @pl.when(base < clen)
    def _live():
        a = a_ref[...]
        A = [a[:, g * _LANES:(g + 1) * _LANES] for g in range(G)]
        d = d_ref[...]
        h_in = h_s[j]
        r8 = lax.broadcasted_iota(jnp.int32, (8, 1), 0)

        def eight(k, hs):
            r0 = pl.multiple_of(k * 8, 8)
            lv = base + r0 + r8 < clen
            c8 = c_ref[pl.ds(r0, 8), :]
            dt8 = jnp.where(lv, dt_ref[pl.ds(r0, 8), :], 0.0)
            x8 = jnp.where(lv, dt8 * c8, 0.0)
            hs = list(hs)
            ys = [jnp.zeros((8, _LANES), jnp.float32)] * G
            for r in range(8):
                bb, cb = bb_ref[r0 + r], cb_ref[r0 + r]       # [N, 128]
                for g in range(G):
                    sl = slice(g * _LANES, (g + 1) * _LANES)
                    hs[g] = jnp.exp(dt8[r:r + 1, sl] * A[g]) * hs[g] \
                        + x8[r:r + 1, sl] * bb
                    s = jnp.sum(hs[g] * cb, axis=0, keepdims=True)
                    ys[g] = jnp.where(r8 == r, s, ys[g])
            y8 = jnp.concatenate(ys, axis=1) + d * c8
            if gated:
                y8 = y8 * silu(z_ref[pl.ds(r0, 8), :])
            y_ref[pl.ds(r0, 8), :] = jnp.where(lv, y8, 0.0)
            return tuple(hs)

        hs = lax.fori_loop(
            0, Tb // 8, eight,
            tuple(h_in[:, g * _LANES:(g + 1) * _LANES] for g in range(G)))
        h_s[j] = jnp.concatenate(hs, axis=1)

    @pl.when(base >= clen)
    def _dead():
        y_ref[...] = jnp.zeros_like(y_ref)

    # every step hands its tile's state out: the last row block's stays
    hout_ref[...] = h_s[j]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_chunk_pallas(c, dt, B, C, A, D, h0, chunk_len, z=None,
                                interpret: bool = False):
    """The Pallas form of :func:`selective_scan_chunk`. Jitted by its
    shapes: a model's layers share one lowering."""
    T, Di = c.shape
    N = A.shape[0]
    Tb = min(T, _ROW_BLOCK)
    tc = min(Di, _CHANNEL_TILE)
    if T % Tb or Tb % 8 or Di % tc or tc % _LANES:
        raise ValueError(f"a chunk of {T} rows by {Di} channels: rows "
                         f"must be a multiple of {min(T, _ROW_BLOCK)} and "
                         f"8, channels of {tc} and {_LANES}")
    f32 = jnp.float32
    wide = lambda m: jnp.broadcast_to(               # noqa: E731
        m.astype(f32)[:, :, None], (T, N, _LANES))
    # a padded row's B meets a zero in the kernel: 0 * NaN would reach
    # the state, so it is zeroed here, inside the broadcast's fusion
    B = jnp.where((jnp.arange(T) < chunk_len)[:, None], B, 0.0)
    rows = pl.BlockSpec((Tb, tc), lambda i, j, *_: (i, j))
    bcast = pl.BlockSpec((Tb, N, _LANES), lambda i, j, *_: (i, 0, 0))
    state = pl.BlockSpec((N, tc), lambda i, j, *_: (0, j))
    gated = z is not None
    y, h = pl.pallas_call(
        functools.partial(_chunk_kernel, Tb=Tb, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // Tb, Di // tc),
            in_specs=[rows, rows, bcast, bcast, state,
                      pl.BlockSpec((1, tc), lambda i, j, *_: (0, j)),
                      state] + [rows] * gated,
            out_specs=[rows, state],
            scratch_shapes=[pltpu.VMEM((Di // tc, N, tc), f32)]),
        out_shape=[jax.ShapeDtypeStruct((T, Di), f32),
                   jax.ShapeDtypeStruct((N, Di), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=CHUNK_KERNEL_NAME,
    )(jnp.asarray(chunk_len, jnp.int32).reshape(1), dt.astype(f32),
      c.astype(f32), wide(B), wide(C), A.astype(f32),
      D.astype(f32).reshape(1, Di), h0.astype(f32),
      *([z.astype(f32)] if gated else []))
    return y, h


# ---------------------------------------------------------------------------
# What a layer calls
# ---------------------------------------------------------------------------
def selective_scan_chunk(c, dt, B, C, A, D, h0, chunk_len, z=None,
                         impl: str = "auto",
                         interpret: Optional[bool] = None):
    """The recurrence over the rows of one sequence's chunk.

    c, dt: ``[T, Di]`` (``dt`` after its softplus); B, C: ``[T, N]``;
    A, h0: ``[N, Di]``; D: ``[Di]``; chunk_len: scalar, the rows that
    are not padding; z: ``[T, Di]`` or None. Returns ``(y [T, Di], h
    [N, Di])``: ``y`` with ``D * c`` added and, where ``z`` is given,
    gated by ``silu(z)``, zero in the padded rows; ``h`` the state
    after row ``chunk_len - 1`` (``h0`` itself where ``chunk_len`` is
    0). ``auto`` runs the kernel on a TPU and the scan elsewhere."""
    on_tpu = default_platform() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "xla"
    if impl == "xla":
        return selective_scan_chunk_xla(c, dt, B, C, A, D, h0, chunk_len, z)
    if impl != "pallas":
        raise ValueError(f"unknown selective scan impl {impl!r}")
    if interpret is None:
        interpret = not on_tpu
    return selective_scan_chunk_pallas(c, dt, B, C, A, D, h0, chunk_len, z,
                                       interpret=interpret)


def selective_scan_step(c, dt, B, C, A, D, h, live):
    """One row a slot. c, dt: ``[S, Di]``; B, C: ``[S, N]``; A: ``[N,
    Di]``; D: ``[Di]``; h: ``[S, N, Di]``; live: ``[S]`` bool, a lane
    that is not live keeps its state bit for bit. Returns ``(y [S,
    Di], h)`` with ``D * c`` added to ``y``."""
    dt = jnp.where(live[:, None], dt, 0.0)
    x = jnp.where(live[:, None], dt * c, 0.0)
    B = jnp.where(live[:, None], B, 0.0)    # 0 * NaN would reach the state
    h = jnp.exp(dt[:, None, :] * A[None]) * h \
        + x[:, None, :] * B[:, :, None]
    return (h * C[:, :, None]).sum(1) + D[None] * c, h

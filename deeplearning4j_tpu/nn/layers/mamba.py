"""A Mamba-1 mixer (a selective state-space layer), as pure functions
over an explicit parameter dict: the rows of one sequence's prefill
chunk (:func:`mamba_chunk`) and one row a slot of a decode step
(:func:`mamba_step`). Both take the layer's two pieces of state in and
hand them back: the last ``d_conv - 1`` inputs of the causal depthwise
convolution, and the recurrence's ``h`` (``[N, Di]`` float32, states on
sublanes: :mod:`deeplearning4j_tpu.kernels.selective_scan`).

For a normed row ``n`` (``Di`` inner channels, ``N`` states, ``R`` the
rank of the step size's projection):

    [u, z] = split2(n W_in)
    c_t    = silu(conv_b + sum_j conv_w[:, j] * u_{t - (d_conv - 1) + j})
    [dt', B', C'] = split(c W_x; R, N, N)
    dt = softplus(RMSNorm(dt'; dt_norm) W_dt + b_dt)
    B  = RMSNorm(B'; b_norm),  C = RMSNorm(C'; c_norm)
    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * c_t) * B_t,   A = -exp(A_log)
    y_t = h_t C_t + D * c_t
    out = (y * silu(z)) W_out

The three inner norms are Jamba's. ``A`` is held as the program's
``[N, Di]`` float32 ``-exp(A_log)^T``, made once where the weights are
put in place. Matmul operands take the weights' dtype (bfloat16 weights:
bf16 operands, float32 accumulation); the convolution, the norms, ``dt``
and the recurrence are float32.

A row that is not live (a chunk's padding, a step's dead lane) leaves
both pieces of state as they were: its ``dt`` is zero in the recurrence
and the convolution's inputs are taken from before it.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import jax.numpy as jnp

from ...kernels.selective_scan import (selective_scan_chunk,
                                       selective_scan_step, silu)


#: the named scopes of the mixer's two parts in a profile (one served
#: model has this layer: its name)
PROJ_SCOPE, SCAN_SCOPE = "jamba.mamba.proj", "jamba.mamba.scan"


def _mm(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _selection(w: Dict, c, eps: float):
    """``(dt, B, C)`` of rows ``c`` [T, Di] (float32)."""
    R, N = w["dt_norm"].shape[0], w["b_norm"].shape[0]
    dbc = _mm(c, w["W_x"])
    dt = jax.nn.softplus(
        _mm(_rms(dbc[:, :R], w["dt_norm"], eps), w["W_dt"]) + w["b_dt"])
    return (dt, _rms(dbc[:, R:R + N], w["b_norm"], eps),
            _rms(dbc[:, R + N:], w["c_norm"], eps))


def mamba_chunk(w: Dict, x, conv_prev, h_prev, chunk_len, eps: float):
    """x [T, D] normed rows of one sequence; conv_prev [d_conv - 1, Di]
    the convolution's inputs before row 0; h_prev [N, Di]; chunk_len
    the rows that are not padding. Returns (out [T, D] float32, the
    convolution's inputs before row ``chunk_len`` in ``conv_prev``'s
    type, ``h`` after row ``chunk_len - 1``)."""
    T = x.shape[0]
    Di, taps = w["conv_w"].shape
    with jax.named_scope(PROJ_SCOPE):
        uz = _mm(x, w["W_in"])
        u, z = uz[:, :Di], uz[:, Di:]
        ext = jnp.concatenate([conv_prev.astype(jnp.float32), u], 0)
        c = silu(w["conv_b"] + sum(w["conv_w"][:, j][None] * ext[j:j + T]
                                   for j in range(taps)))
        conv_last = jax.lax.dynamic_slice_in_dim(
            ext, chunk_len, taps - 1, 0).astype(conv_prev.dtype)
        dt, B, C = _selection(w, c, eps)
    with jax.named_scope(SCAN_SCOPE):
        y, h = selective_scan_chunk(c, dt, B, C, w["A"], w["D"], h_prev,
                                    chunk_len, z)
    with jax.named_scope(PROJ_SCOPE):
        return _mm(y, w["W_out"]), conv_last, h


def mamba_step(w: Dict, x, conv_st, h_st, live, eps: float):
    """One row a slot: x [S, D] normed; conv_st [S, d_conv - 1, Di];
    h_st [S, N, Di]; live [S] bool. Returns (out [S, D] float32,
    conv_st, h_st after the row; a lane that is not live keeps
    both)."""
    Di = w["conv_w"].shape[0]
    with jax.named_scope(PROJ_SCOPE):
        uz = _mm(x, w["W_in"])
        u, z = uz[:, :Di], uz[:, Di:]
        ext = jnp.concatenate([conv_st.astype(jnp.float32), u[:, None]], 1)
        c = silu(w["conv_b"] + (ext * w["conv_w"].T[None]).sum(1))
        conv_new = jnp.where(live[:, None, None],
                             ext[:, 1:].astype(conv_st.dtype), conv_st)
        dt, B, C = _selection(w, c, eps)
    with jax.named_scope(SCAN_SCOPE):
        y, h = selective_scan_step(c, dt, B, C, w["A"], w["D"], h_st, live)
    with jax.named_scope(PROJ_SCOPE):
        return _mm(y * silu(z), w["W_out"]), conv_new, h


#: the ``ssm`` block of a generator's ``/stats``
SSM_COUNTERS = ("chunk_rows", "decode_rows")


class SsmAccount:
    """What the state-space layers of a served model did: the account a
    model hands the engine (``model.step_account()``), fed the small
    integer vector every decode step and prefill chunk returns beside
    its tokens (one number: live rows x state-space layers) and
    published under :attr:`block`. Written by the scheduler thread when
    a step's or a chunk's results reach the host, with that iteration's
    other counters."""

    #: the account's key in a generator's ``/stats``
    block = "ssm"

    def __init__(self, state_bytes_per_slot: int):
        self._lock = threading.Lock()
        self.state_bytes_per_slot = int(state_bytes_per_slot)
        self.chunk_rows = 0     # live chunk rows x state-space layers
        self.decode_rows = 0    # live lanes x state-space layers

    def decode_step(self, counters) -> None:
        with self._lock:
            self.decode_rows += int(counters[0])

    def chunk(self, counters) -> None:
        with self._lock:
            self.chunk_rows += int(counters[0])

    def snapshot(self) -> Dict:
        with self._lock:
            out = {k: getattr(self, k) for k in SSM_COUNTERS}
        out["state_bytes_per_slot"] = self.state_bytes_per_slot
        return out

"""Plain reference for a Jamba-shaped causal LM (``configs/*.json`` with
``"reference": "jamba"``): weights from a seed, and the full forward
pass in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made:
no cache, no kernel, no chunking. ``cfg`` is the configuration file's
``model`` block, under the published ``config.json`` keys.

Layer ``l`` is attention where ``l % attn_layer_period ==
attn_layer_offset`` and Mamba elsewhere; ``num_experts`` is 1, so every
layer's feed-forward is the dense gated MLP. Block over x [T, D]
(RMSNorm(x; w) = x * rsqrt(mean(x^2) + rms_norm_eps) * w):

    h = x + Mixer_l(RMSNorm(x; input_layernorm))
    y = h + (silu(n W_gate) * (n W_up)) W_down,  n = RMSNorm(h; pre_ff_layernorm)

Attention: ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``hidden_size /
num_attention_heads``, no bias, NO positional encoding, scores scaled
by head^-1/2, causal softmax, then ``Wo``.

Mamba-1 mixer (``Di = mamba_expand * hidden_size`` channels, ``N =
mamba_d_state``, ``R = mamba_dt_rank``, ``K = mamba_d_conv``):

    [u, z] = split2(n W_in)
    c_t    = silu(conv_b + sum_{j<K} conv_w[:, j] * u_{t-(K-1)+j})   # zeros before the sequence
    [dt', B', C'] = split(c W_x; R, N, N)
    dt = softplus(RMSNorm(dt'; dt_norm) W_dt + b_dt)
    B  = RMSNorm(B'; b_norm),  C = RMSNorm(C'; c_norm)
    h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * c_t)[:, None] * B_t[None, :]
    y_t = h_t C_t + D * c_t,        A = -exp(A_log)  [Di, N],  h_{-1} = 0
    out = (y * silu(z)) W_out

the recurrence a ``lax.scan`` over the rows. After the last block:
RMSNorm(.; final_layernorm) and logits against the embedding (the head
is tied).

Weights: matrices N(0, 0.02), norm weights 1, and the Mamba paper's own
initialisation for what shapes the recurrence (N(0, 0.02) there would
make it degenerate): ``A_log = log(1 .. N)`` a channel, ``D`` 1,
``b_dt`` the inverse softplus of ``exp(U(log 0.001, log 0.1))``,
``conv_b`` 0, and the convolution's taps U(-1/sqrt(K), 1/sqrt(K)) (the
default of the depthwise convolution they are published as; at N(0,
0.02) the mixer's output is a fiftieth of the MLP's and drops out of
the logits). Made in float32 and rounded ONCE to the configuration's
``dtype``: what ``make_params`` returns, the program holds, and this
forward reads back as float32, so both sides compute with the same
values. The forward itself is float32 at ``highest`` matmul precision,
unless ``dtype`` asks for the lower-precision control: then every
weight matrix and every input of a weight matmul is rounded to that
type first (each tensor scaled to the type's range, float32
accumulation); the norms, the convolution, the recurrence and the
attention scores stay float32, as in the program.

Weights are made layer by layer and dropped, so the reference never
holds more than one layer.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
DT_MIN, DT_MAX = 1e-3, 0.1
ROWS = 128      # query rows of attention scored at a time (divides PAD)
# a sequence's rows are padded to a multiple of this: eight sequences of
# 4.6k to 15k tokens then come in at most six lengths, each compiled
# once (and found in the compile cache by the next run)
PAD = 2048


def root_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    dh = d // cfg["num_attention_heads"]
    return d, dh, cfg["num_key_value_heads"] * dh


# -- weights ----------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def _embed_weights(cfg, key) -> Dict[str, jnp.ndarray]:
    d, dt = cfg["hidden_size"], jnp.dtype(cfg["dtype"])
    k = jax.random.fold_in(key, 1 << 20)
    # a test at a tiny width narrows the embedding: there the head, tied
    # to it, would otherwise answer every token with itself
    std = cfg.get("embed_std", INIT_STD)
    return {"embed": (jax.random.normal(k, (cfg["vocab_size"], d),
                                        jnp.float32) * std).astype(dt),
            "final_layernorm": jnp.ones((d,), dt),
            "rms_norm_eps": jnp.float32(cfg["rms_norm_eps"])}


def layer_weights(cfg: dict, key, layer: int) -> Dict[str, jnp.ndarray]:
    """One layer's weights, rounded to the configuration's dtype."""
    return _kind_weights(_hashable(cfg), is_attention(cfg, layer),
                         jax.random.fold_in(key, layer))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kind_weights(cfg, attention: bool, layer_key) -> Dict[str, jnp.ndarray]:
    """Compiled once a kind of layer, not once a layer."""
    d, dh, kv = _dims(cfg)
    f = cfg["intermediate_size"]
    dt = jnp.dtype(cfg["dtype"])
    k = iter(jax.random.split(layer_key, 16))

    def n(*shape):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * INIT_STD).astype(dt)

    def u(lo, hi, *shape):
        return jax.random.uniform(next(k), shape, jnp.float32, lo, hi)

    ones = lambda m: jnp.ones((m,), dt)                 # noqa: E731
    w = {"input_layernorm": ones(d), "pre_ff_layernorm": ones(d),
         "W_gate": n(d, f), "W_up": n(d, f), "W_down": n(f, d)}
    if attention:
        w.update(Wq=n(d, d), Wk=n(d, kv), Wv=n(d, kv), Wo=n(d, d))
        return w
    di, ns = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    r, taps = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    step = jnp.exp(u(math.log(DT_MIN), math.log(DT_MAX), di))
    w.update(
        W_in=n(d, 2 * di), W_x=n(di, r + 2 * ns), W_dt=n(r, di),
        W_out=n(di, d),
        conv_w=u(-taps ** -0.5, taps ** -0.5, di, taps).astype(dt),
        conv_b=jnp.zeros((di,), dt),
        b_dt=step + jnp.log(-jnp.expm1(-step)),     # softplus^-1(step)
        dt_norm=ones(r), b_norm=ones(ns), c_norm=ones(ns),
        A_log=jnp.broadcast_to(
            jnp.log(jnp.arange(1, ns + 1, dtype=jnp.float32)), (di, ns)),
        D=jnp.ones((di,), jnp.float32))
    return w


def make_params(cfg: dict, seed: int):
    """(embedding group, list of layers), in the configuration's dtype
    (``b_dt``, ``A_log`` and ``D`` float32). One jitted call a layer,
    so that the float32 draws of one layer are all the device holds
    beside the rounded weights."""
    key = root_key(seed)
    return (_embed_weights(_hashable(cfg), key),
            [layer_weights(cfg, key, i)
             for i in range(cfg["num_hidden_layers"])])


# -- forward ----------------------------------------------------------------
def _round(x, dtype):
    """``x`` as float32, through ``dtype`` first where a control asks:
    scaled so that the tensor's largest magnitude is the type's, as an
    8-bit deployment scales a tensor, rounded, and scaled back."""
    x = x.astype(jnp.float32)
    if dtype is None:
        return x
    top = jnp.max(jnp.abs(x))
    s = jnp.where(top > 0, top / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _mm(x, w, dtype):
    return _round(x, dtype) @ _round(w, dtype)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def attention(cfg, w, a, dtype):
    """a [T, D] normed -> [T, D], ``ROWS`` query rows at a time."""
    T = a.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // hq
    q = _mm(a, w["Wq"], dtype).reshape(T, hq, dh)
    k = _mm(a, w["Wk"], dtype).reshape(T, hkv, dh)
    v = _mm(a, w["Wv"], dtype).reshape(T, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=1)     # query head n: KV head n // g
    v = jnp.repeat(v, hq // hkv, axis=1)
    rows = min(ROWS, T)
    j = jnp.arange(T)[None, None, :]

    def some(r0):
        qi = jax.lax.dynamic_slice_in_dim(q, r0, rows, 0)
        i = (r0 + jnp.arange(rows))[None, :, None]
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(dh)
        s = jnp.where(j <= i, s, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(some, jnp.arange(0, T, rows))
    return _mm(out.reshape(T, hq * dh), w["Wo"], dtype)


def mamba(cfg, w, a, dtype):
    """a [T, D] normed -> [T, D]: the Mamba-1 mixer, the recurrence one
    row a step from a zero state."""
    T = a.shape[0]
    eps = cfg["rms_norm_eps"]
    ns, r, taps = (cfg["mamba_d_state"], cfg["mamba_dt_rank"],
                   cfg["mamba_d_conv"])
    u, z = jnp.split(_mm(a, w["W_in"], dtype), 2, -1)
    up = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    cw = w["conv_w"].astype(jnp.float32)
    c = _silu(w["conv_b"].astype(jnp.float32)
              + sum(cw[:, j] * up[j:j + T] for j in range(taps)))
    dbc = _mm(c, w["W_x"], dtype)
    dt = jax.nn.softplus(_mm(_rms(dbc[:, :r], w["dt_norm"], eps),
                             w["W_dt"], dtype) + w["b_dt"])
    B = _rms(dbc[:, r:r + ns], w["b_norm"], eps)
    C = _rms(dbc[:, r + ns:], w["c_norm"], eps)
    A = -jnp.exp(w["A_log"]).T            # held [N, Di]: h^T, lane-dense

    def row(h, t):
        dt_t, c_t, b_t, c_out = t
        h = jnp.exp(dt_t[None, :] * A) * h \
            + (dt_t * c_t)[None, :] * b_t[:, None]
        return h, (h * c_out[:, None]).sum(0)

    _, y = jax.lax.scan(row, jnp.zeros_like(A), (dt, c, B, C), unroll=8)
    return _mm((y + w["D"] * c) * _silu(z), w["W_out"], dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def block(cfg, attn: bool, w, x, dtype):
    eps = cfg["rms_norm_eps"]
    n = _rms(x, w["input_layernorm"], eps)
    h = x + (attention if attn else mamba)(cfg, w, n, dtype)
    n = _rms(h, w["pre_ff_layernorm"], eps)
    return h + _mm(_silu(_mm(n, w["W_gate"], dtype))
                   * _mm(n, w["W_up"], dtype), w["W_down"], dtype)


def final_hidden(cfg: dict, seed: int, seqs: Sequence[np.ndarray],
                 dtype=None):
    """The last block's output, one ``[T_i, D]`` array a sequence
    (``T_i`` its length rounded up to ``PAD`` rows; causal, so padding
    never reaches a real row), and the embedding group (whose final
    norm and tied head turn rows into logits, see :func:`head_logits`).
    Each layer's weights are made from the seed, used for every
    sequence and dropped."""
    cfg = _hashable(cfg)
    key = root_key(seed)
    with jax.default_matmul_precision(precision_for(dtype)):
        emb = _embed_weights(cfg, key)
        xs = []
        for s in seqs:
            pad = min(PAD, 8)
            while pad < PAD and pad < len(s):
                pad *= 2
            ids = np.zeros(-(-len(s) // pad) * pad, np.int32)
            ids[:len(s)] = s
            xs.append(emb["embed"][jnp.asarray(ids)].astype(jnp.float32))
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, key, layer)
            xs = [block(cfg, is_attention(cfg, layer), w, x, dtype)
                  for x in xs]
        return xs, emb


class _Frozen(dict):
    """The ``model`` block as a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _hashable(cfg: dict) -> "_Frozen":
    return cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)


def precision_for(dtype) -> str:
    """Float32 products at full precision in the reference and in the
    control alike: the control's loss is its rounding, made above."""
    return "highest"


def head_logits(emb, rows, dtype=None):
    """Final RMSNorm and the tied head over rows [R, D] of the last
    block's output: logits [R, V] in float32."""
    h = _rms(rows.astype(jnp.float32), emb["final_layernorm"],
             emb["rms_norm_eps"])
    return jnp.einsum("rd,vd->rv", _round(h, dtype),
                      _round(emb["embed"], dtype))

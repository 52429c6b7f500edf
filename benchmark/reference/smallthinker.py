"""Plain reference for a SmallThinker-shaped causal LM
(``configs/*.json`` with ``"reference": "smallthinker"``): weights from
a seed, and the full forward pass in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made:
no cache, no kernel, no chunking, no batching, no ring. ``cfg`` is the
configuration file's ``model`` block, under the published
``config.json`` keys.

Block ``l`` over x [T, D] (RMSNorm(x; w) = x * rsqrt(mean(x^2) +
rms_norm_eps) * w; no bias anywhere):

    r = x W_r                          # router logits FROM THE BLOCK'S INPUT, not normed
    a = RMSNorm(x; input_layernorm)
    q = a Wq as num_attention_heads heads; k, v = a Wk, a Wv as num_key_value_heads
    q, k = rope(q), rope(k)            where rope_layout[l] == 1: rotate-half over all
                                       head_dim lanes, base rope_theta; else nothing
    s_ij = q_i . k_j / sqrt(head_dim), j <= i, and j > i - sliding_window_size
                                       where sliding_window_layout[l] == 1
    h = x + softmax(s) v Wo            # query head n reads KV head n // g
    m = RMSNorm(h; post_attention_layernorm)
    E = the moe_num_active_primary_experts largest of r;  w = softmax over those logits
    y = h + sum_e w_e (relu(m W1_e) * (m W3_e)) W2_e      # w_e = 0 where e not in E

then ``RMSNorm(y; norm) head`` (the head is not tied). Every expert's
product is formed for every row and weighted by ``w``: no gather, no
capacity.

Weights are N(0, 0.02) (norm weights 1 + N(0, 0.02)), made in float32
and rounded ONCE to the configuration's ``dtype``: what ``make_params``
returns, the program holds, and this forward reads back as float32, so
both sides compute with the same values. The forward itself is float32
at ``highest`` matmul precision, unless ``dtype`` asks for the
lower-precision control: then every weight matrix and every input of a
weight matmul is rounded to that type first (each tensor scaled to the
type's range, float32 accumulation); the router, the norms and the
attention scores stay float32, as in the program.

Weights are made layer by layer and dropped, so the reference never
holds more than one layer (0.8 GB in bfloat16). A sequence goes through
alone, padded to a multiple of ``PAD`` rows (causal: padding never
reaches a real row), its attention over ``ROWS`` query rows at a time
so that a 16k-token sequence's scores fit (28 heads x 512 x 16,384
float32 are 0.9 GB).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
PAD = 2048      # sequences are padded to a multiple of this many rows
ROWS = 512      # query rows whose scores are formed at a time


def root_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


class _Frozen(dict):
    """The ``model`` block as a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _hashable(cfg: dict) -> "_Frozen":
    return cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)


# -- weights ----------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def _embed_weights(cfg, key) -> Dict[str, jnp.ndarray]:
    d, v, dt = cfg["hidden_size"], cfg["vocab_size"], jnp.dtype(cfg["dtype"])
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    n = lambda kk, shape: (  # noqa: E731
        jax.random.normal(kk, shape, jnp.float32) * INIT_STD)
    return {"embed": n(k[0], (v, d)).astype(dt),
            "norm": (1.0 + n(k[1], (d,))).astype(dt),
            "head": n(k[2], (d, v)).astype(dt),
            "rms_norm_eps": jnp.float32(cfg["rms_norm_eps"])}


@functools.partial(jax.jit, static_argnums=0)
def _layer_weights(cfg, layer_key) -> Dict[str, jnp.ndarray]:
    """Every layer has the same shapes: compiled once."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    e, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    dt = jnp.dtype(cfg["dtype"])
    k = iter(jax.random.split(layer_key, 16))

    def n(*shape, mean=0.0):
        return (mean + jax.random.normal(next(k), shape, jnp.float32)
                * INIT_STD).astype(dt)

    return {"input_layernorm": n(d, mean=1.0),
            "post_attention_layernorm": n(d, mean=1.0),
            "W_r": n(d, e), "Wq": n(d, q), "Wk": n(d, kv), "Wv": n(d, kv),
            "Wo": n(q, d), "W1": n(e, d, f), "W3": n(e, d, f),
            "W2": n(e, f, d)}


def layer_weights(cfg: dict, key, layer: int) -> Dict[str, jnp.ndarray]:
    return _layer_weights(_hashable(cfg), jax.random.fold_in(key, layer))


def make_params(cfg: dict, seed: int):
    """(embedding group, list of layers), in the configuration's dtype.
    One jitted call a layer, so that the float32 draws of one layer are
    all the device holds beside the rounded weights."""
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


def _rope(x, theta):
    """x [T, H, Dh] at positions 0..T-1, rotate-half."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention(cfg, w, a, rotary, windowed, dtype):
    """a [T, D] normed -> [T, D]: the block's attention, ``ROWS`` query
    rows at a time. ``rotary`` and ``windowed`` are the layer's two
    flags (traced: one compiled block serves both kinds of layer)."""
    T = a.shape[0]
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = _mm(a, w["Wq"], dtype).reshape(T, hq, dh)
    k = _mm(a, w["Wk"], dtype).reshape(T, hkv, dh)
    v = _mm(a, w["Wv"], dtype).reshape(T, hkv, dh)
    q = jnp.where(rotary, _rope(q, cfg["rope_theta"]), q)
    k = jnp.where(rotary, _rope(k, cfg["rope_theta"]), k)
    k = jnp.repeat(k, hq // hkv, axis=1)     # query head n: KV head n // g
    v = jnp.repeat(v, hq // hkv, axis=1)
    # the last sliding_window_size keys, the query's own included
    reach = jnp.where(windowed, cfg["sliding_window_size"], T + 1)
    rows = min(ROWS, T)
    j = jnp.arange(T)[None, None, :]

    def some(r0):
        qi = jax.lax.dynamic_slice_in_dim(q, r0, rows, 0)
        i = (r0 + jnp.arange(rows))[None, :, None]
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(dh)
        s = jnp.where((j <= i) & (j > i - reach), s, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(some, jnp.arange(0, T, rows))
    return _mm(out.reshape(T, hq * dh), w["Wo"], dtype)


def route(cfg, logits):
    """logits [T, E] -> the weight of every expert [T, E]: softmax over
    the ``moe_num_active_primary_experts`` largest logits, zero
    elsewhere."""
    top, chosen = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    w = jax.nn.softmax(top, -1)
    return jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], chosen].add(w)


def experts_sum(cfg, w, m, weights, dtype):
    """sum_e weights[:, e] * (relu(m W1_e) * (m W3_e)) W2_e, every
    expert's product formed for every row."""

    def one(e, out):
        h = jax.nn.relu(_mm(m, w["W1"][e], dtype)) * _mm(m, w["W3"][e], dtype)
        return out + weights[:, e][:, None] * _mm(h, w["W2"][e], dtype)

    return jax.lax.fori_loop(0, cfg["moe_num_primary_experts"], one,
                             jnp.zeros_like(m))


@functools.partial(jax.jit, static_argnums=(0, 5))
def block(cfg, w, x, rotary, windowed, dtype):
    eps = cfg["rms_norm_eps"]
    logits = x @ w["W_r"].astype(jnp.float32)
    h = x + attention(cfg, w, _rms(x, w["input_layernorm"], eps), rotary,
                      windowed, dtype)
    m = _rms(h, w["post_attention_layernorm"], eps)
    return h + experts_sum(cfg, w, m, route(cfg, logits), dtype)


def final_hidden(cfg: dict, seed: int, seqs: Sequence[np.ndarray],
                 dtype=None):
    """The last block's output, one ``[T_i, D]`` array a sequence
    (``T_i`` its length rounded up to ``PAD`` rows), and the embedding
    group (whose final norm and head turn rows into logits, see
    :func:`head_logits`). Each layer's weights are made from the seed,
    used for every sequence and dropped."""
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
            flags = (jnp.bool_(cfg["rope_layout"][layer] == 1),
                     jnp.bool_(cfg["sliding_window_layout"][layer] == 1))
            xs = [block(cfg, w, x, *flags, dtype) for x in xs]
        return xs, emb


def precision_for(dtype) -> str:
    """Float32 products at full precision in the reference and in the
    control alike: the control's loss is its rounding, made above."""
    return "highest"


def head_logits(emb, rows, dtype=None):
    """Final RMSNorm and the untied head over rows [R, D] of the last
    block's output: logits [R, V] in float32."""
    h = _rms(rows.astype(jnp.float32), emb["norm"], emb["rms_norm_eps"])
    return _mm(h, emb["head"], dtype)

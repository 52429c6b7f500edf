"""Plain reference for an LFM2-MoE-shaped causal LM
(``configs/*.json`` with ``"reference": "lfm2_moe"``): weights from a
seed, and the full forward pass in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made:
no cache, no kernel, no chunking. ``cfg`` is the configuration file's
``model`` block, under the published ``config.json`` keys.

Block ``l`` over x [B, T, D] (RMSNorm(x; w) = x * rsqrt(mean(x^2) +
norm_eps) * w):

    h = x + Op_l(RMSNorm(x; operator_norm))
    y = h + FF_l(RMSNorm(h; ffn_norm))

``Op`` for ``layer_types[l] == "conv"``: ``[B, C, u] = split3(x W_in)``,
``v = B * u``, ``c_t = sum_j w[:, j] * v_{t - (L - 1) + j}`` (depthwise,
causal, zeros before the sequence), ``(C * c) W_out``. For
``"full_attention"``: q as ``num_attention_heads`` heads, k and v as
``num_key_value_heads``, RMSNorm of q and k over each head's lanes,
rotary positions over all lanes (rotate-half, base ``rope_theta``),
causal softmax(q k^T / sqrt(head)) v with query head i reading KV head
``i // g``, then ``W_out``. ``FF`` below ``num_dense_layers``:
``(silu(x W1) * x W3) W2``. Above: ``s = sigmoid(x W_g)``; the
``num_experts_per_tok`` largest of ``s + expert_bias`` are chosen (the
bias selects, it does not weigh); weights ``s[chosen] / (sum + 1e-6)``
times ``routed_scaling_factor``; the weighted sum of the chosen
experts' SwiGLU. After the last block: RMSNorm(.; embedding_norm) and
logits against the embedding (the head is tied).

Weights are N(0, 0.02) (``expert_bias`` N(0, 0.01), norm weights
1 + N(0, 0.02)), made in float32 and rounded ONCE to the
configuration's ``dtype``: what ``make_params`` returns, the program
holds, and this forward reads back as float32, so both sides compute
with the same values. The forward itself is float32 at ``highest``
matmul precision, unless ``dtype`` asks for the lower-precision
control: then every weight matrix and every input of a weight matmul is
rounded to that type first (each tensor scaled to the type's range,
float32 accumulation); the router, the norms and the attention scores
stay float32, as in the program.

Weights are made layer by layer and dropped, so the reference never
holds more than one layer (an expert layer is 0.7 GB in bfloat16, 1.4
as float32). The experts are computed by gathering each expert's
tokens: the routing runs first, the host reads the largest group, and
one loop over the experts multiplies each one's rows by its matrices.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
BIAS_STD = 0.01
ROUTE_EPS = 1e-6


def root_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    dh = d // cfg["num_attention_heads"]
    return d, dh, cfg["num_key_value_heads"] * dh


# -- weights ----------------------------------------------------------------
def embed_weights(cfg: dict, key) -> Dict[str, jnp.ndarray]:
    return _embed_weights(_hashable(cfg), key)


@functools.partial(jax.jit, static_argnums=0)
def _embed_weights(cfg, key) -> Dict[str, jnp.ndarray]:
    d, dt = cfg["hidden_size"], jnp.dtype(cfg["dtype"])
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    n = lambda kk, shape, std: (  # noqa: E731
        jax.random.normal(kk, shape, jnp.float32) * std)
    # a test at a tiny width narrows the embedding: there the head, tied
    # to it, would otherwise answer every token with itself
    std = cfg.get("embed_std", INIT_STD)
    return {"embed": n(k[0], (cfg["vocab_size"], d), std).astype(dt),
            "embedding_norm": (1.0 + n(k[1], (d,), INIT_STD)).astype(dt),
            "norm_eps": jnp.float32(cfg["norm_eps"])}


def layer_kind(cfg: dict, layer: int):
    """(operator, whether the FF is the dense MLP): what a layer's
    shapes follow from."""
    return cfg["layer_types"][layer], layer < cfg["num_dense_layers"]


def layer_weights(cfg: dict, key, layer: int) -> Dict[str, jnp.ndarray]:
    """One layer's weights, rounded to the configuration's dtype
    (``expert_bias`` stays float32)."""
    return _kind_weights(_hashable(cfg), layer_kind(cfg, layer),
                         jax.random.fold_in(key, layer))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kind_weights(cfg, kind, layer_key) -> Dict[str, jnp.ndarray]:
    """Compiled once a kind of layer, not once a layer."""
    op, dense = kind
    d, dh, kv = _dims(cfg)
    dt = jnp.dtype(cfg["dtype"])
    k = iter(jax.random.split(layer_key, 16))

    def n(*shape, std=INIT_STD, mean=0.0, dtype=dt):
        return (mean + jax.random.normal(next(k), shape, jnp.float32)
                * std).astype(dtype)

    w = {"operator_norm": n(d, mean=1.0), "ffn_norm": n(d, mean=1.0)}
    if op == "conv":
        w.update(W_in=n(d, 3 * d), conv_w=n(d, cfg["conv_L_cache"]),
                 W_out=n(d, d))
    else:
        w.update(Wq=n(d, d), Wk=n(d, kv), Wv=n(d, kv), Wo=n(d, d),
                 q_norm=n(dh, mean=1.0), k_norm=n(dh, mean=1.0))
    if dense:
        f = cfg["intermediate_size"]
        w.update(W1=n(d, f), W3=n(d, f), W2=n(f, d))
    else:
        e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        w.update(W_g=n(d, e),
                 expert_bias=n(e, std=BIAS_STD, dtype=jnp.float32),
                 W1=n(e, d, f), W3=n(e, d, f), W2=n(e, f, d))
    return w


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
    """x [B, T, H, Dh] at positions 0..T-1, rotate-half."""
    T, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def conv_op(cfg, w, x, dtype):
    B, T, d = x.shape
    taps = cfg["conv_L_cache"]
    b, c, u = jnp.split(_mm(x, w["W_in"], dtype), 3, -1)
    v = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    cw = w["conv_w"].astype(jnp.float32)
    conv = sum(cw[:, j] * v[:, j:j + T] for j in range(taps))
    return _mm(c * conv, w["W_out"], dtype)


def attention_op(cfg, w, x, dtype):
    B, T, d = x.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    eps = cfg["norm_eps"]
    q = _mm(x, w["Wq"], dtype).reshape(B, T, hq, dh)
    k = _mm(x, w["Wk"], dtype).reshape(B, T, hkv, dh)
    v = _mm(x, w["Wv"], dtype).reshape(B, T, hkv, dh)
    q = _rope(_rms(q, w["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, w["k_norm"], eps), cfg["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)     # query head i: KV head i // g
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return _mm(a.reshape(B, T, d), w["Wo"], dtype)


def route(cfg, w, x):
    """x [N, D] -> (chosen [N, k], weights [N, k]), float32."""
    s = jax.nn.sigmoid(x @ w["W_g"].astype(jnp.float32))
    sel = s + w["expert_bias"] if cfg["use_expert_bias"] else s
    _, chosen = jax.lax.top_k(sel, cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, chosen, 1)
    if cfg["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + ROUTE_EPS)
    return chosen, g * cfg["routed_scaling_factor"]


def swiglu(x, w1, w3, w2, dtype):
    return _mm(jax.nn.silu(_mm(x, w1, dtype)) * _mm(x, w3, dtype), w2, dtype)


def experts_sum(cfg, w, x, chosen, g, cap: int, dtype):
    """sum_e g_e * swiglu_e(x) over each row's chosen experts: expert
    by expert, its (at most ``cap``) rows gathered, multiplied and added
    back. A row whose ``chosen`` is ``num_experts`` has no expert."""
    N = x.shape[0]

    def one(e, out):
        ge = jnp.where(chosen == e, g, 0.0).sum(-1)             # [N]
        (idx,) = jnp.nonzero((chosen == e).any(-1), size=cap, fill_value=N)
        rows = jnp.minimum(idx, N - 1)
        y = swiglu(x[rows], w["W1"][e], w["W3"][e], w["W2"][e], dtype)
        return out.at[idx].add(y * ge[rows][:, None], mode="drop")

    return jax.lax.fori_loop(0, cfg["num_experts"], one, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _block_top(cfg, kind, w, x, live, dtype):
    """Everything of a block of ``kind`` up to the expert layer's
    routing: (h, (the FF's input, chosen, weights, rows of the fullest
    expert)); for a dense layer the whole block, and nothing to
    route."""
    eps = cfg["norm_eps"]
    op = conv_op if kind[0] == "conv" else attention_op
    h = x + op(cfg, w, _rms(x, w["operator_norm"], eps), dtype)
    f = _rms(h, w["ffn_norm"], eps)
    if kind[1]:
        return h + swiglu(f, w["W1"], w["W3"], w["W2"], dtype), None
    flat = f.reshape(-1, f.shape[-1])
    chosen, g = route(cfg, w, flat)
    chosen = jnp.where(live.reshape(-1, 1), chosen, cfg["num_experts"])
    fullest = jnp.zeros(cfg["num_experts"] + 1, jnp.int32).at[
        chosen.reshape(-1)].add(1)[:-1].max()
    return h, (flat, chosen, g, fullest)


@functools.partial(jax.jit, static_argnums=(0, 6, 7))
def _block_experts(cfg, w, h, flat, chosen, g, cap, dtype):
    return h + experts_sum(cfg, w, flat, chosen, g, cap, dtype
                           ).reshape(h.shape)


def final_hidden(cfg: dict, seed: int, seqs: Sequence[np.ndarray],
                 dtype=None, batch: int = 8):
    """The last block's output [n, T, D] for the given sequences, and
    the embedding group (whose final norm and tied head turn rows of it
    into logits, see :func:`head_logits`).

    Sequences are padded to one length (causal, so padding never
    reaches a real row; padded rows route to no expert) and go through
    each layer in groups of ``batch``; each layer's weights are made
    from the seed, used and dropped."""
    cfg = _hashable(cfg)
    key = root_key(seed)
    T = -(-max(len(s) for s in seqs) // 128) * 128
    ids = np.zeros((len(seqs), T), np.int32)
    live = np.zeros((len(seqs), T), bool)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
        live[i, :len(s)] = True
    with jax.default_matmul_precision(precision_for(dtype)):
        emb = _embed_weights(cfg, key)
        x = emb["embed"][jnp.asarray(ids)].astype(jnp.float32)
        cut = range(0, len(seqs), batch)
        xs = [x[i:i + batch] for i in cut]
        lives = [jnp.asarray(live[i:i + batch]) for i in cut]
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, key, layer)
            tops = [_block_top(cfg, layer_kind(cfg, layer), w, x, lv, dtype)
                    for x, lv in zip(xs, lives)]
            # one compiled loop a power of two of the fullest expert
            xs = [h if r is None else _block_experts(
                      cfg, w, h, r[0], r[1], r[2],
                      1 << max(int(r[3]) - 1, 255).bit_length(), dtype)
                  for h, r in tops]
        return jnp.concatenate(xs, 0), emb


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
    h = _rms(rows.astype(jnp.float32), emb["embedding_norm"],
             emb["norm_eps"])
    return jnp.einsum("rd,vd->rv", _round(h, dtype),
                      _round(emb["embed"], dtype))

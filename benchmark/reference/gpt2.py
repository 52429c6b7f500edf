"""Plain reference for a GPT-2-shaped causal LM: weights from a seed,
and the forward pass in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made.
The forward follows the GPT-2 paper's block (pre-LayerNorm, causal
multi-head attention, tanh-approximated GELU MLP, final LayerNorm,
linear head) with the two departures the served class has, stated in
the configuration file: no bias on the q/k/v projections, and a head
that is not tied to the token embedding.

Weights are made layer by layer, so the reference never holds more than
one block on the device; activations of all checked sequences ride
through the layers together.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


def root_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def embed_weights(cfg: dict, key) -> Dict[str, jnp.ndarray]:
    d, v, t = cfg["d_model"], cfg["vocab_size"], cfg["max_seq_len"]
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 5)
    return {"tok": _normal(k[0], (v, d), INIT_STD),
            "pos": _normal(k[1], (t, d), INIT_STD),
            "lnf_g": 1.0 + _normal(k[2], (d,), INIT_STD),
            "lnf_b": _normal(k[3], (d,), INIT_STD),
            "head": _normal(k[4], (d, v), INIT_STD)}


def block_weights(cfg: dict, key, layer) -> Dict[str, jnp.ndarray]:
    """GPT-2's initialisation: N(0, 0.02), the two projections into
    the residual stream scaled by 1/sqrt(2 * n_layers)."""
    d, f = cfg["d_model"], cfg["d_ff"]
    res = INIT_STD / math.sqrt(2 * cfg["n_layers"])
    k = jax.random.split(jax.random.fold_in(key, layer), 13)
    return {"ln1_g": 1.0 + _normal(k[0], (d,), INIT_STD),
            "ln1_b": _normal(k[1], (d,), INIT_STD),
            "wq": _normal(k[2], (d, d), INIT_STD),
            "wk": _normal(k[3], (d, d), INIT_STD),
            "wv": _normal(k[4], (d, d), INIT_STD),
            "wo": _normal(k[5], (d, d), res),
            "bo": _normal(k[6], (d,), INIT_STD),
            "ln2_g": 1.0 + _normal(k[7], (d,), INIT_STD),
            "ln2_b": _normal(k[8], (d,), INIT_STD),
            "w1": _normal(k[9], (d, f), INIT_STD),
            "b1": _normal(k[10], (f,), INIT_STD),
            "w2": _normal(k[11], (f, d), res),
            "b2": _normal(k[12], (d,), INIT_STD)}


def make_params(cfg: dict, seed: int):
    """All weights in one jitted call on the device, f32: the embedding
    group and a list of blocks."""
    @jax.jit
    def build(key):
        return (embed_weights(cfg, key),
                [block_weights(cfg, key, i) for i in range(cfg["n_layers"])])

    return build(root_key(seed))


def _ln(x, g, b, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def block_forward(n_heads: int, w, x, dtype=None):
    """One block over x [B, T, D]. ``dtype`` (e.g. bfloat16) computes
    the whole block in that type: the lower-precision control."""
    if dtype is not None:
        w = {k: v.astype(dtype) for k, v in w.items()}
        x = x.astype(dtype)
    B, T, D = x.shape
    dh = D // n_heads
    h = _ln(x, w["ln1_g"], w["ln1_b"])
    q = (h @ w["wq"]).reshape(B, T, n_heads, dh)
    k = (h @ w["wk"]).reshape(B, T, n_heads, dh)
    v = (h @ w["wv"]).reshape(B, T, n_heads, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, D)
    x = x + a @ w["wo"] + w["bo"]
    h = _ln(x, w["ln2_g"], w["ln2_b"])
    h = jax.nn.gelu(h @ w["w1"] + w["b1"], approximate=True)
    return x + h @ w["w2"] + w["b2"]


def final_hidden(cfg: dict, seed: int, seqs: Sequence[np.ndarray],
                 dtype=None, batch: int = 4):
    """The last block's output [n, T, D] for the given sequences, and
    the embedding group (whose final LayerNorm and head turn rows of it
    into logits, see :func:`head_logits`).

    float32 at ``highest`` matmul precision unless ``dtype`` asks for
    the lower-precision control, which computes everything in that
    type. Sequences are padded to one length (causal, so padding never
    reaches a real row) and go through each layer in groups of
    ``batch``; each layer's weights are made from the seed, used and
    dropped, so the reference never holds more than one block."""
    key = root_key(seed)
    T = max(len(s) for s in seqs)
    T = min(-(-T // 128) * 128, cfg["max_seq_len"])
    ids = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    with jax.default_matmul_precision(precision_for(dtype)):
        emb = jax.jit(functools.partial(embed_weights, cfg))(key)
        x = emb["tok"][jnp.asarray(ids)] + emb["pos"][:T][None]
        if dtype is not None:
            x = x.astype(dtype)
        groups = [x[i:i + batch] for i in range(0, len(seqs), batch)]
        mk = jax.jit(functools.partial(block_weights, cfg))
        fwd = jax.jit(functools.partial(block_forward, cfg["n_heads"],
                                        dtype=dtype))
        for layer in range(cfg["n_layers"]):
            w = mk(key, layer)
            groups = [fwd(w, g) for g in groups]
        return jnp.concatenate(groups, 0), emb


def precision_for(dtype) -> str:
    return "highest" if dtype is None else "default"


def head_logits(emb, rows, dtype=None):
    """Final LayerNorm and head over rows [R, D] of the last block's
    output: logits [R, V] in float32."""
    h = _ln(rows, emb["lnf_g"], emb["lnf_b"])
    if dtype is not None:
        return (h.astype(dtype) @ emb["head"].astype(dtype)
                ).astype(jnp.float32)
    return h.astype(jnp.float32) @ emb["head"]

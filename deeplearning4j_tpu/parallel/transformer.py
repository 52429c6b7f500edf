"""DistributedTransformer: one train step composing dp x sp x pp x tp.

This is the framework's flagship distributed path — the capability the
reference reaches with Spark + the Aeron parameter server (data parallel
only, SURVEY.md §2.4) extended to the full TPU parallelism menu:

- dp   : batch sharded over "dp", gradients averaged by the shard_map
         transpose (the compiled psum IS the gradient-sharing bus)
- sp   : sequence sharded over "sp", exact attention via ring_attention
         (ppermute ring, LSE accumulation)
- pp   : one transformer block per "pp" rank, GPipe microbatching via
         pipeline_apply (scan + ppermute)
- tp   : attention heads + MLP hidden dim sharded over "tp"
         (Megatron column/row-parallel, one psum per block half)

Everything is ONE shard_map'ed jitted function — XLA schedules every
collective over ICI; there is no user-space transport.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .longseq import ring_attention
from .pipeline import pipeline_apply
from .tensor import tp_mlp

AXES = ("dp", "sp", "pp", "tp")


def make_4d_mesh(n_devices: Optional[int] = None, dp: int = 1, sp: int = 1,
                 pp: int = 1, tp: int = 1, devices=None) -> Mesh:
    """Mesh with the canonical ("dp", "sp", "pp", "tp") axes. Size-1 axes
    are legal and compile the same collective program shape."""
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if dp * sp * pp * tp != n:
        raise ValueError(f"dp*sp*pp*tp = {dp*sp*pp*tp} != {n} devices")
    arr = np.asarray(devices).reshape(dp, sp, pp, tp)
    return Mesh(arr, AXES)


from ..nn.functional import layer_norm as _ln


class DistributedTransformer:
    """Causal-LM transformer with 4D-parallel training step.

    n_layers must equal the pp axis size (one block per stage). Heads and
    d_ff must divide the tp axis size; seq_len the sp size; batch the
    dp size * n_microbatches.
    """

    def __init__(self, mesh: Mesh, vocab: int = 256, d_model: int = 64,
                 n_heads: int = 4, d_ff: int = 128, seq_len: int = 128,
                 n_microbatches: Optional[int] = None,
                 dtype=jnp.float32, seed: int = 0):
        self.mesh = mesh
        self.vocab, self.d_model = vocab, d_model
        self.n_heads, self.d_ff = n_heads, d_ff
        self.seq_len = seq_len
        self.dtype = dtype
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.S_pp = shape["pp"]
        self.S_tp = shape["tp"]
        self.S_sp = shape["sp"]
        self.S_dp = shape["dp"]
        self.n_micro = n_microbatches or max(2, self.S_pp)
        if n_heads % self.S_tp or d_ff % self.S_tp:
            raise ValueError("n_heads and d_ff must divide tp size")
        if seq_len % self.S_sp:
            raise ValueError("seq_len must divide sp size")
        self.d_head = d_model // n_heads
        self.params, self.specs = self._init(seed)
        self._step_fn = None

    # ------------------------------------------------------------------
    def _init(self, seed):
        k = jax.random.PRNGKey(seed)
        ks = jax.random.split(k, 12)
        d, H, Dh, f, V, S = (self.d_model, self.n_heads, self.d_head,
                             self.d_ff, self.vocab, self.S_pp)

        def init(key, *shape, scale=None):
            scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
            return (jax.random.normal(key, shape) * scale).astype(self.dtype)

        stages = {
            # stacked [n_stages, ...]; stage axis sharded over pp
            "wqkv": init(ks[0], S, d, 3, H, Dh, scale=1 / np.sqrt(d)),
            "wo": init(ks[1], S, H, Dh, d, scale=1 / np.sqrt(d)),
            "w1": init(ks[2], S, d, f, scale=1 / np.sqrt(d)),
            "b1": jnp.zeros((S, f), self.dtype),
            "w2": init(ks[3], S, f, d, scale=1 / np.sqrt(f)),
            "b2": jnp.zeros((S, d), self.dtype),
            "ln1_g": jnp.ones((S, d), self.dtype),
            "ln1_b": jnp.zeros((S, d), self.dtype),
            "ln2_g": jnp.ones((S, d), self.dtype),
            "ln2_b": jnp.zeros((S, d), self.dtype),
        }
        params = {
            "embed": init(ks[4], V, d, scale=0.02),
            "pos": init(ks[5], self.seq_len, d, scale=0.02),
            "lnf_g": jnp.ones((d,), self.dtype),
            "lnf_b": jnp.zeros((d,), self.dtype),
            "stages": stages,
        }
        specs = {
            "embed": P(), "pos": P("sp", None),
            "lnf_g": P(), "lnf_b": P(),
            "stages": {
                "wqkv": P("pp", None, None, "tp", None),
                "wo": P("pp", "tp", None, None),
                "w1": P("pp", None, "tp"),
                "b1": P("pp", "tp"),
                "w2": P("pp", "tp", None),
                "b2": P("pp", None),
                "ln1_g": P("pp", None), "ln1_b": P("pp", None),
                "ln2_g": P("pp", None), "ln2_b": P("pp", None),
            },
        }
        with self.mesh:
            params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self.mesh, s)), params, specs,
                is_leaf=lambda x: isinstance(x, jnp.ndarray))
        return params, specs

    # ------------------------------------------------------------------
    def _block(self, sp_params, x):
        """One transformer block on a [mb, T_local, d] activation.
        sp_params: this pp-rank's stage params with the stage axis
        squeezed and tp shards local."""
        h = _ln(x, sp_params["ln1_g"], sp_params["ln1_b"])
        qkv = jnp.einsum("btd,dchk->btchk", h, sp_params["wqkv"])
        q, kk, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        att = ring_attention(q, kk, v, "sp", causal=True)
        # row-parallel output projection: heads are tp-sharded
        proj = jnp.einsum("bthk,hkd->btd", att, sp_params["wo"])
        x = x + lax.psum(proj, "tp")
        h = _ln(x, sp_params["ln2_g"], sp_params["ln2_b"])
        x = x + tp_mlp(h, sp_params["w1"], sp_params["b1"],
                       sp_params["w2"], sp_params["b2"], "tp")
        return x

    def _local_loss(self, params, tokens, targets):
        """Per-device loss; runs INSIDE shard_map over the 4D mesh.
        tokens/targets: [B_local, T_local] int32."""
        B_l, T_l = tokens.shape
        mb = B_l // self.n_micro
        x = jnp.take(params["embed"], tokens, axis=0) + \
            params["pos"][None, :T_l, :]
        x = x.reshape(self.n_micro, mb, T_l, self.d_model)

        def stage_fn(sp, act):
            return self._block(sp, act)

        # squeeze the (local, length-1) stage axis off each stage param
        local_stage = jax.tree_util.tree_map(
            lambda a: a[0], params["stages"])
        y = pipeline_apply(stage_fn, local_stage, x, "pp")
        y = y.reshape(B_l, T_l, self.d_model)
        y = _ln(y, params["lnf_g"], params["lnf_b"])
        logits = jnp.einsum("btd,vd->btv", y, params["embed"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1).squeeze(-1)
        loss = nll.mean()
        # identical scalar on every device: average over dp and sp shards
        return lax.pmean(lax.pmean(loss, "dp"), "sp")

    # ------------------------------------------------------------------
    def _build_step(self):
        mesh = self.mesh
        pspec_tree = self.specs

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(pspec_tree, P("dp", "sp"), P("dp", "sp")),
            out_specs=(P(),))
        def loss_sm(params, tokens, targets):
            return (self._local_loss(params, tokens, targets),)

        def step(params, tokens, targets, lr):
            loss, grads = jax.value_and_grad(
                lambda p: loss_sm(p, tokens, targets)[0])(params)
            params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g.astype(p.dtype), params, grads)
            return params, loss

        return jax.jit(step, donate_argnums=(0,))

    def train_step(self, tokens, targets, lr: float = 1e-2):
        """One jitted 4D-parallel SGD step. tokens/targets:
        [batch, seq_len] int32 host arrays; batch must divide
        dp * n_microbatches. lr is a traced argument — varying it per
        call (schedules) does not retrace."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        with self.mesh:
            tok = jax.device_put(
                jnp.asarray(tokens, jnp.int32),
                NamedSharding(self.mesh, P("dp", "sp")))
            tgt = jax.device_put(
                jnp.asarray(targets, jnp.int32),
                NamedSharding(self.mesh, P("dp", "sp")))
            self.params, loss = self._step_fn(
                self.params, tok, tgt, jnp.float32(lr))
        return float(loss)

"""4D-parallelism tests: ring attention (sp), pipeline (pp), tensor
parallel (tp), MoE (ep), gradient compression, and the composed
DistributedTransformer — all on the virtual 8-device CPU mesh
(SURVEY.md §4.2 loopback-test philosophy).

The load-bearing checks are PARITY tests: every distributed path must
produce the same numbers as a plain single-device implementation of the
same math.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.longseq import (blockwise_attention,
                                                 dot_product_attention,
                                                 ring_attention)
from deeplearning4j_tpu.parallel.pipeline import (pipeline_apply,
                                                  stack_stage_params)
from deeplearning4j_tpu.parallel.moe import moe_ffn
from deeplearning4j_tpu.parallel import compression as comp
from deeplearning4j_tpu.parallel.transformer import (DistributedTransformer,
                                                     make_4d_mesh)


def _qkv(np_rng, B=2, T=32, H=4, D=8):
    return tuple(np_rng.randn(B, T, H, D).astype(np.float32) * 0.5
                 for _ in range(3))


class TestBlockwiseAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_plain(self, np_rng, causal):
        q, k, v = _qkv(np_rng)
        want = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal)
        got = blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), block_size=8,
                                  causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_ragged_block(self, np_rng):
        q, k, v = _qkv(np_rng, T=21)  # not a multiple of block_size
        want = dot_product_attention(*map(jnp.asarray, (q, k, v)))
        got = blockwise_attention(*map(jnp.asarray, (q, k, v)),
                                  block_size=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


class TestRingAttention:
    def _mesh_sp(self, n=4):
        return Mesh(np.asarray(jax.devices()[:n]), ("sp",))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_plain(self, np_rng, causal):
        q, k, v = _qkv(np_rng, T=32)
        mesh = self._mesh_sp(4)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(None, "sp"),) * 3,
                           out_specs=P(None, "sp"))
        def f(q, k, v):
            return ring_attention(q, k, v, "sp", causal=causal)

        want = dot_product_attention(*map(jnp.asarray, (q, k, v)),
                                     causal=causal)
        got = f(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_gradients_match_plain(self, np_rng):
        q, k, v = _qkv(np_rng, B=1, T=16, H=2, D=4)
        mesh = self._mesh_sp(4)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(None, "sp"),) * 3,
                           out_specs=P())
        def loss_ring(q, k, v):
            o = ring_attention(q, k, v, "sp", causal=True)
            return lax.psum(jnp.sum(o ** 2), "sp")

        def loss_plain(q, k, v):
            o = dot_product_attention(q, k, v, causal=True)
            return jnp.sum(o ** 2)

        args = tuple(map(jnp.asarray, (q, k, v)))
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(*args)
        g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(*args)
        for gr, gp in zip(g_ring, g_plain):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gp),
                                       rtol=5e-4, atol=5e-5)


class TestPipeline:
    def test_matches_sequential(self, np_rng):
        S, n_micro, mb, d = 4, 6, 2, 8
        mesh = Mesh(np.asarray(jax.devices()[:S]), ("pp",))
        ws = [np_rng.randn(d, d).astype(np.float32) * 0.3 for _ in range(S)]
        stacked = stack_stage_params(
            [{"w": jnp.asarray(w)} for w in ws])
        x = np_rng.randn(n_micro, mb, d).astype(np.float32)

        def stage(p, a):
            return jnp.tanh(a @ p["w"])

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=({"w": P("pp", None, None)}, P()),
                           out_specs=P())
        def run(params, x):
            local = jax.tree_util.tree_map(lambda a: a[0], params)
            return pipeline_apply(stage, local, x, "pp")

        got = run(stacked, jnp.asarray(x))
        want = jnp.asarray(x)
        for w in ws:
            want = jnp.tanh(want @ jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_differentiable(self, np_rng):
        S, n_micro, mb, d = 2, 4, 2, 4
        mesh = Mesh(np.asarray(jax.devices()[:S]), ("pp",))
        ws = [np_rng.randn(d, d).astype(np.float32) * 0.3 for _ in range(S)]
        stacked = stack_stage_params([{"w": jnp.asarray(w)} for w in ws])
        x = jnp.asarray(np_rng.randn(n_micro, mb, d).astype(np.float32))

        def stage(p, a):
            return jnp.tanh(a @ p["w"])

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=({"w": P("pp", None, None)}, P()),
                           out_specs=P())
        def loss_sm(params, x):
            local = jax.tree_util.tree_map(lambda a: a[0], params)
            y = pipeline_apply(stage, local, x, "pp")
            return jnp.sum(y ** 2)

        def loss_seq(params, x):
            y = x
            for i in range(S):
                y = jnp.tanh(y @ params["w"][i])
            return jnp.sum(y ** 2)

        g_pp = jax.grad(loss_sm)(stacked, x)
        g_seq = jax.grad(loss_seq)(stacked, x)
        np.testing.assert_allclose(np.asarray(g_pp["w"]),
                                   np.asarray(g_seq["w"]),
                                   rtol=1e-4, atol=1e-5)


class TestMoE:
    def test_routing_and_shapes(self, np_rng):
        S, E_local, d, f, N_local = 4, 2, 8, 16, 32
        E = S * E_local
        mesh = Mesh(np.asarray(jax.devices()[:S]), ("ep",))
        wg = jnp.asarray(np_rng.randn(d, E).astype(np.float32) * 0.3)
        w1 = jnp.asarray(np_rng.randn(E, d, f).astype(np.float32) * 0.3)
        b1 = jnp.zeros((E, f), jnp.float32)
        w2 = jnp.asarray(np_rng.randn(E, f, d).astype(np.float32) * 0.3)
        b2 = jnp.zeros((E, d), jnp.float32)
        x = jnp.asarray(np_rng.randn(S * N_local, d).astype(np.float32))

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P("ep", None), P(), P("ep", None, None), P("ep", None),
                      P("ep", None, None), P("ep", None)),
            out_specs=(P("ep", None), P()))
        def f_moe(x, wg, w1, b1, w2, b2):
            y, aux = moe_ffn(x, wg, w1, b1, w2, b2, "ep",
                             capacity_factor=4.0)
            return y, lax.pmean(aux, "ep")

        y, aux = f_moe(x, wg, w1, b1, w2, b2)
        assert y.shape == x.shape
        assert np.isfinite(float(aux))
        # with generous capacity, nearly all tokens routed -> output != 0
        nonzero = np.mean(np.abs(np.asarray(y)).sum(-1) > 1e-6)
        assert nonzero > 0.9

    def test_matches_dense_reference(self, np_rng):
        # capacity large enough that nothing is dropped -> must equal the
        # dense per-token expert evaluation
        S, E_local, d, f, N_local = 2, 2, 4, 8, 8
        E = S * E_local
        mesh = Mesh(np.asarray(jax.devices()[:S]), ("ep",))
        wg = jnp.asarray(np_rng.randn(d, E).astype(np.float32))
        w1 = jnp.asarray(np_rng.randn(E, d, f).astype(np.float32) * 0.3)
        b1 = jnp.zeros((E, f), jnp.float32)
        w2 = jnp.asarray(np_rng.randn(E, f, d).astype(np.float32) * 0.3)
        b2 = jnp.zeros((E, d), jnp.float32)
        x = jnp.asarray(np_rng.randn(S * N_local, d).astype(np.float32))

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P("ep", None), P(), P("ep", None, None), P("ep", None),
                      P("ep", None, None), P("ep", None)),
            out_specs=(P("ep", None), P()))
        def f_moe(x, wg, w1, b1, w2, b2):
            y, aux = moe_ffn(x, wg, w1, b1, w2, b2, "ep",
                             capacity_factor=float(E))
            return y, lax.pmean(aux, "ep")

        y, _ = f_moe(x, wg, w1, b1, w2, b2)
        gates = jax.nn.softmax(x @ wg, axis=-1)
        expert = jnp.argmax(gates, axis=-1)
        h = jax.nn.gelu(jnp.einsum("nd,edf->enf", x, w1) + b1[:, None])
        dense = jnp.einsum("enf,efd->end", h, w2) + b2[:, None]
        want = (dense[expert, jnp.arange(x.shape[0])]
                * jnp.take_along_axis(gates, expert[:, None], 1))
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


class TestCompression:
    def test_encode_decode_round_trip(self, np_rng):
        u = np_rng.randn(100).astype(np.float32) * 0.01
        enc, residual = comp.threshold_encode(u, 0.01)
        dec = comp.threshold_decode(enc, u.shape, 0.01)
        # decode + residual reconstructs the update exactly
        np.testing.assert_allclose(dec + residual, u, atol=1e-7)

    def test_topk_round_trip(self, np_rng):
        u = jnp.asarray(np_rng.randn(64).astype(np.float32))
        idx, vals, residual = comp.topk_encode(u, 8)
        dec = comp.topk_decode(idx, vals, u.shape)
        np.testing.assert_allclose(np.asarray(dec + residual),
                                   np.asarray(u), atol=1e-7)
        assert np.count_nonzero(np.asarray(dec)) == 8

    def test_adaptive_threshold(self, np_rng):
        h = comp.EncodingHandler(threshold=1e-6, min_sparsity=1e-3,
                                 max_sparsity=1e-2)
        for _ in range(10):
            h.encode(np_rng.randn(1000).astype(np.float32))
        assert h.threshold > 1e-6  # adapted upward (too dense initially)
        assert h.last_sparsity <= 0.2

    def test_accumulator_bus(self, np_rng):
        shapes = {"w": (50,)}
        bus = comp.LoopbackBus()
        acc = [comp.EncodedGradientsAccumulator(
            i, bus, shapes, threshold=0.1,
            min_sparsity=0.0, max_sparsity=1.0)  # fixed threshold
            for i in range(3)]
        g0 = np_rng.randn(50).astype(np.float32) * 0.3
        g1 = np_rng.randn(50).astype(np.float32) * 0.3
        zero = np.zeros(50, np.float32)
        total = np.zeros(50, np.float32)
        # Strom encoding sends +-threshold QUANTA per round; the remainder
        # rides the residual and drains over subsequent rounds
        for r in range(30):
            acc[0].store_update({"w": g0 if r == 0 else zero})
            acc[1].store_update({"w": g1 if r == 0 else zero})
            total = acc[2].apply_update({"w": total})["w"]
        err = np.abs(total - (g0 + g1)).max()
        assert err <= 0.2 + 1e-6  # within one quantum per sender
        # exactly-once: draining an empty queue adds nothing
        again = acc[2].apply_update({"w": total})["w"]
        np.testing.assert_array_equal(again, total)

    def test_residual_carry_recovers_small_updates(self):
        h = comp.EncodingHandler(threshold=0.5, min_sparsity=0.0,
                                 max_sparsity=1.0)
        total_sent = np.zeros(4, np.float32)
        u = np.array([0.2, 0.0, 0.0, 0.0], np.float32)
        for _ in range(5):
            enc = h.encode(u)
            total_sent += comp.threshold_decode(enc, (4,), 0.5)
        # 5 * 0.2 = 1.0 -> two threshold-sized quanta eventually sent
        assert total_sent[0] == pytest.approx(1.0, abs=0.51)


class TestDistributedTransformer:
    def _ref_loss(self, model, tokens, targets):
        """Single-device reference of the same math."""
        p = jax.tree_util.tree_map(np.asarray, model.params)
        x = p["embed"][tokens] + p["pos"][None]
        S = model.S_pp

        def ln(x, g, b):
            m = x.mean(-1, keepdims=True)
            v = ((x - m) ** 2).mean(-1, keepdims=True)
            return (x - m) / np.sqrt(v + 1e-5) * g + b

        for s in range(S):
            st = {k: v[s] for k, v in p["stages"].items()}
            h = ln(x, st["ln1_g"], st["ln1_b"])
            qkv = np.einsum("btd,dchk->btchk", h, st["wqkv"])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            att = np.asarray(dot_product_attention(
                *map(jnp.asarray, (q, k, v)), causal=True))
            x = x + np.einsum("bthk,hkd->btd", att, st["wo"])
            h = ln(x, st["ln2_g"], st["ln2_b"])
            hid = np.asarray(jax.nn.gelu(jnp.asarray(
                h @ st["w1"] + st["b1"])))
            x = x + hid @ st["w2"] + st["b2"]
        x = ln(x, p["lnf_g"], p["lnf_b"])
        logits = np.einsum("btd,vd->btv", x, p["embed"])
        logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
        return float(-np.take_along_axis(
            logp, targets[..., None], axis=-1).mean())

    def test_loss_matches_single_device_reference(self, np_rng):
        mesh = make_4d_mesh(8, dp=1, sp=2, pp=2, tp=2)
        model = DistributedTransformer(mesh, vocab=32, d_model=16,
                                       n_heads=2, d_ff=32, seq_len=8,
                                       n_microbatches=2)
        tokens = np_rng.randint(0, 32, (4, 8))
        targets = np_rng.randint(0, 32, (4, 8))
        want = self._ref_loss(model, tokens, targets)
        # train_step with lr=0 leaves params intact and returns the loss
        got = model.train_step(tokens, targets, lr=0.0)
        assert got == pytest.approx(want, rel=2e-4)

    def test_training_descends(self, np_rng):
        mesh = make_4d_mesh(8, dp=2, sp=1, pp=2, tp=2)
        model = DistributedTransformer(mesh, vocab=32, d_model=16,
                                       n_heads=2, d_ff=32, seq_len=8,
                                       n_microbatches=2)
        tokens = np_rng.randint(0, 32, (8, 8))
        targets = np.roll(tokens, -1, axis=1)
        losses = [model.train_step(tokens, targets, lr=0.1)
                  for _ in range(15)]
        assert losses[-1] < losses[0] * 0.9

    def test_all_axes_meshes_build(self):
        # every axis >1 somewhere; size-1 axes compile the same program
        for dims in [(8, 1, 1, 1), (1, 8, 1, 1), (2, 2, 2, 1), (1, 2, 2, 2)]:
            make_4d_mesh(8, *dims)
        with pytest.raises(ValueError):
            make_4d_mesh(8, dp=3, sp=1, pp=1, tp=1)

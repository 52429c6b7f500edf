"""Quantized KV-cache tests (ISSUE 15): quantize-on-write primitives
and NaN transparency, decode/paged kernel parity (fused-XLA vs Pallas
interpret) on bf16/int8 pools, quantized stale-tail poison invariance,
engine token parity across kv_dtypes on both backends, engine-level
quarantine THROUGH a quantized cache (poison must travel the int8
sidecar, never be laundered to finite garbage), COW copying scale rows
with blocks, recompute-recovery rebuilding quantized pools
token-identically, int8 weight-only MLP accuracy, and /stats //metrics
exposition parity for the new quantization observability leaves."""
import collections
import re
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels.decode_attention import (
    decode_attention_pallas, decode_attention_xla)
from deeplearning4j_tpu.kernels.kv_quant import (QuantArray, QuantWeight,
                                                 dequantize, is_quantized,
                                                 kv_bytes_per_token,
                                                 kv_copy_row, kv_nbytes,
                                                 kv_set, kv_update_slice,
                                                 kv_zeros, mm,
                                                 quantize_rows,
                                                 quantize_weight)
from deeplearning4j_tpu.kernels.paged_attention import (
    fuse_kv, gather_blocks, paged_attention_pallas, paged_attention_xla)
from deeplearning4j_tpu.serving import (FaultInjector, GenerationEngine,
                                        InferenceServer,
                                        PoisonRequestError)
from deeplearning4j_tpu.zoo.transformer_lm import (CausalTransformerLM,
                                                   quantize_mlp_weights)

VOCAB = 64
# poison rig token (kept out of every clean prompt, see _CachePoisonLM)
NAN_TRIGGER = VOCAB - 3


def _lm(seed=0, cls=CausalTransformerLM):
    return cls(vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4,
               max_seq_len=32, seed=seed, implementation="plain").init()


def _ref_greedy(lm, prompt, n):
    """Uncached full-prefix greedy decode — the f32 correctness oracle."""
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = np.asarray(lm.logits(np.asarray(toks)[None]))[0, -1]
        t = int(logits.argmax())
        out.append(t)
        toks.append(t)
    return out


def _quant_cache(x, kv_dtype):
    """f32 cache array -> what the pool stores for ``kv_dtype``."""
    if kv_dtype == "int8":
        return quantize_rows(x)
    if kv_dtype == "bf16":
        return x.astype(jnp.bfloat16)
    return x


def _quant_pool(kp, vp, kv_dtype):
    """f32 K and V blocks [N, H, Bs, D] -> the paged pool that stores
    them at ``kv_dtype``: side by side, each half by its own scales."""
    return fuse_kv(_quant_cache(kp, kv_dtype), _quant_cache(vp, kv_dtype))


def _cache_arrays(eng):
    """A layer's cache arrays, either backend."""
    return getattr(eng._cache, "pools", None) or eng._cache.ks


def _run_all(eng, reqs, seed0=0):
    """Submit all requests concurrently (greedy); returns token lists
    (None for a failed request) and the raised errors."""
    results = [None] * len(reqs)
    errors = [None] * len(reqs)

    def go(i):
        p, n = reqs[i]
        try:
            results[i] = eng.generate(p, max_tokens=n, seed=seed0 + i,
                                      timeout_ms=120_000)["tokens"]
        except Exception as e:  # noqa: BLE001 — recorded for asserts
            errors[i] = e
    ts = [threading.Thread(target=go, args=(i,)) for i in range(len(reqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return results, errors


_REQS = [(np.random.RandomState(i).randint(0, 32, 3 + 2 * i).tolist(),
          5 + i) for i in range(3)]


# ---------------------------------------------------------------------------
# quantization primitives
# ---------------------------------------------------------------------------
class TestQuantPrimitives:
    def test_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16))
        qa = quantize_rows(x)
        assert qa.q.dtype == jnp.int8
        assert qa.scale.shape == x.shape[:-1]
        err = np.abs(np.asarray(dequantize(qa)) - np.asarray(x))
        # symmetric int8: per-row error <= scale/2 = amax/254
        amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
        assert (err <= amax / 254 + 1e-7).all()

    def test_nan_row_stays_nan(self):
        """NaN transparency: a poisoned row must dequantize back to
        non-finite — quantization never launders poison into finite
        garbage (the quarantine invariant, see TestQuarantine)."""
        x = jnp.ones((3, 4)).at[1].set(jnp.nan)
        qa = quantize_rows(x)
        assert not np.isfinite(np.asarray(qa.scale)[1])
        back = np.asarray(dequantize(qa))
        assert not np.isfinite(back[1]).any()
        assert np.isfinite(back[0]).all() and np.isfinite(back[2]).all()

    def test_zero_row_scale_one_not_zero(self):
        qa = quantize_rows(jnp.zeros((2, 8)))
        np.testing.assert_array_equal(np.asarray(qa.scale), 1.0)
        np.testing.assert_array_equal(np.asarray(dequantize(qa)), 0.0)

    def test_nbytes_accounting(self):
        shape = (4, 2, 8, 16)                  # [S, H, T, D]
        n = int(np.prod(shape))
        assert kv_nbytes(shape, "f32") == 4 * n
        assert kv_nbytes(shape, "bf16") == 2 * n
        assert kv_nbytes(shape, "int8") == n + int(np.prod(shape[:-1])) * 4
        # per-token bytes across layers: K+V, sidecar included for int8
        shapes = [(2, 8, 16)] * 3              # (H, T, D) x layers
        assert kv_bytes_per_token(shapes, "f32") == 3 * 2 * 2 * 16 * 4
        assert kv_bytes_per_token(shapes, "int8") == 3 * 2 * (32 + 8)

    def test_kv_set_quantizes_on_write(self):
        pool = kv_zeros((4, 2, 8, 16), "int8")
        assert is_quantized(pool)
        val = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        out = kv_set(pool, 2, val)
        back = np.asarray(dequantize(out))
        np.testing.assert_allclose(back[2], np.asarray(val), atol=2e-2)
        # untouched rows still zero
        assert np.abs(back[0]).max() == 0 and np.abs(back[3]).max() == 0

    def test_update_slice_aligns_sidecar(self):
        pool = kv_zeros((2, 2, 8, 4), "int8")
        slab = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 6, 4))
        out = kv_update_slice(pool, slab, (1, 0, 0, 0))
        back = np.asarray(dequantize(out))
        np.testing.assert_allclose(back[1, :, :6], np.asarray(slab)[0],
                                   atol=2e-2)
        assert np.abs(back[0]).max() == 0 and np.abs(back[1, :, 6:]).max() == 0

    def test_copy_row_copies_scales(self):
        pool = kv_zeros((3, 2, 4, 8), "int8")
        slab = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (1, 2, 4, 8))
        pool = kv_update_slice(pool, slab, (0, 0, 0, 0))
        out = kv_copy_row(pool, 0, 2)
        np.testing.assert_array_equal(np.asarray(out.q[2]),
                                      np.asarray(out.q[0]))
        np.testing.assert_array_equal(np.asarray(out.scale[2]),
                                      np.asarray(out.scale[0]))


# ---------------------------------------------------------------------------
# kernel parity on quantized pools (Pallas interpret vs fused XLA)
# ---------------------------------------------------------------------------
class TestDecodeKernelQuant:
    def _inputs(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        S, T, H, D = 3, 16, 4, 8
        q = jax.random.normal(ks[0], (S, H, D))
        k = jax.random.normal(ks[1], (S, H, T, D))
        v = jax.random.normal(ks[2], (S, H, T, D))
        lens = jnp.array([1, 7, 16], jnp.int32)
        return q, k, v, lens

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_pallas_matches_xla_quantized(self, dt):
        q, k, v, lens = self._inputs()
        kq, vq = _quant_cache(k, dt), _quant_cache(v, dt)
        a = np.asarray(decode_attention_xla(q, kq, vq, lens))
        b = np.asarray(decode_attention_pallas(q, kq, vq, lens,
                                               interpret=True))
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
        # and both stay close to the f32 reference
        ref = np.asarray(decode_attention_xla(q, k, v, lens))
        np.testing.assert_allclose(a, ref, rtol=6e-2, atol=6e-2)

    def test_mixed_quant_raises(self):
        q, k, v, lens = self._inputs()
        with pytest.raises(ValueError, match="quantized together"):
            decode_attention_pallas(q, quantize_rows(k), v, lens,
                                    interpret=True)

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_stale_tail_poison_ignored_quantized(self, dt):
        """NaN past the live length in a QUANTIZED pool (a quarantined
        request's quantized leavings — for int8 the poison lives in the
        scale sidecar) must not influence successors: the V-side
        where-guard has to fire before the scale multiply, because
        0 * NaN = NaN."""
        q, k, v, lens = self._inputs()
        lens = jnp.array([1, 7, 9], jnp.int32)
        base_k, base_v = _quant_cache(k, dt), _quant_cache(v, dt)
        k2 = k.at[:, :, 9:].set(jnp.nan)
        v2 = v.at[:, :, 9:].set(jnp.nan)
        pois_k, pois_v = _quant_cache(k2, dt), _quant_cache(v2, dt)
        if dt == "int8":    # the poison really is scale-carried
            assert not np.isfinite(np.asarray(pois_k.scale)[:, :, 9:]).any()
        for impl in (decode_attention_xla,
                     lambda *a: decode_attention_pallas(*a,
                                                        interpret=True)):
            base = np.asarray(impl(q, base_k, base_v, lens))
            poisoned = np.asarray(impl(q, pois_k, pois_v, lens))
            assert np.isfinite(poisoned).all()
            np.testing.assert_allclose(base, poisoned, rtol=1e-5,
                                       atol=1e-6)

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_empty_lane_zero_quantized(self, dt):
        S, T, H, D = 2, 8, 2, 4
        q = jnp.ones((S, H, D))
        k = _quant_cache(jnp.ones((S, H, T, D)), dt)
        v = _quant_cache(jnp.ones((S, H, T, D)), dt)
        lens = jnp.array([0, 8], jnp.int32)
        for impl in (decode_attention_xla,
                     lambda *a: decode_attention_pallas(*a,
                                                        interpret=True)):
            out = np.asarray(impl(q, k, v, lens))
            assert np.isfinite(out).all()
            assert np.abs(out[0]).max() == 0.0


class TestPagedKernelQuant:
    def _inputs(self):
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        S, N, H, Bs, D, B = 3, 8, 4, 4, 8, 4
        q = jax.random.normal(ks[0], (S, H, D))
        kp = jax.random.normal(ks[1], (N, H, Bs, D))
        vp = jax.random.normal(ks[2], (N, H, Bs, D))
        tables = jnp.array([[1, 2, 0, 0], [3, 4, 5, 0], [6, 7, 1, 2]],
                           jnp.int32)
        lens = jnp.array([3, 8, 14], jnp.int32)
        return q, kp, vp, tables, lens

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_pallas_matches_xla_quantized(self, dt):
        q, kp, vp, tables, lens = self._inputs()
        pool = _quant_pool(kp, vp, dt)
        a = np.asarray(paged_attention_xla(q, pool, tables, lens))
        b = np.asarray(paged_attention_pallas(q, pool, tables, lens,
                                              interpret=True))
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
        ref = np.asarray(paged_attention_xla(q, fuse_kv(kp, vp), tables,
                                             lens))
        np.testing.assert_allclose(a, ref, rtol=6e-2, atol=6e-2)

    def test_gather_blocks_carries_scales(self):
        q, kp, vp, tables, lens = self._inputs()
        kq, vq = quantize_rows(kp), quantize_rows(vp)
        pool = fuse_kv(kq, vq)
        assert pool.scale.shape == (8, 2, 4, 4)     # K's, then V's
        plain = gather_blocks(fuse_kv(dequantize(kq), dequantize(vq)),
                              tables)
        for g, want in zip(gather_blocks(pool, tables), plain):
            assert is_quantized(g)
            assert g.scale.shape == g.q.shape[:-1]
            np.testing.assert_allclose(np.asarray(dequantize(g)),
                                       np.asarray(want), rtol=1e-6)

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_stale_block_poison_ignored_quantized(self, dt):
        """A freed block full of quantized NaN re-enters a table past
        the live length (or padded as NULL) — successors must not see
        it."""
        q, kp, vp, tables, lens = self._inputs()
        kp2 = kp.at[2].set(jnp.nan)    # seq 0 reads block 2 past len 3
        vp2 = vp.at[2].set(jnp.nan)
        lens2 = jnp.array([3, 8, 8], jnp.int32)   # nobody reads blk 2 live
        for impl in (paged_attention_xla,
                     lambda *a: paged_attention_pallas(*a,
                                                       interpret=True)):
            base = np.asarray(impl(q, _quant_pool(kp, vp, dt), tables,
                                   lens2))
            # the keys' half of the block poisoned, the values', both
            for k2, v2 in ((kp2, vp2), (kp2, vp), (kp, vp2)):
                poisoned = np.asarray(impl(q, _quant_pool(k2, v2, dt),
                                           tables, lens2))
                assert np.isfinite(poisoned).all()
                np.testing.assert_allclose(base, poisoned, rtol=1e-5,
                                           atol=1e-6)

    @pytest.mark.parametrize("H,Bs,G", [(4, 4, 2), (4, 8, 2), (25, 16, 4)])
    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_every_grid_edge_matches_xla_quantized(self, monkeypatch, dt,
                                                   H, Bs, G):
        """Lengths on every block and chunk edge, tables out of pool
        order, NaN in every dead place (in an int8 pool the poison is
        carried by the scales)."""
        from test_paged_generation import chunks_of, ragged_paged_case
        chunks_of(monkeypatch, G)
        q, kp, vp, tables, lens = ragged_paged_case(H, Bs, 8, G, seed=3)
        pool = _quant_pool(kp, vp, dt)
        a = np.asarray(paged_attention_xla(q, pool, tables, lens))
        b = np.asarray(paged_attention_pallas(q, pool, tables, lens,
                                              interpret=True))
        assert np.isfinite(b).all()
        assert np.abs(b[0]).max() == 0.0            # the empty lane
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)

    def test_mixed_quant_raises(self):
        q, kp, vp, tables, lens = self._inputs()
        with pytest.raises(ValueError, match="quantized together"):
            fuse_kv(quantize_rows(kp), vp)


# ---------------------------------------------------------------------------
# engine token parity across kv_dtypes, both backends
# ---------------------------------------------------------------------------
class TestEngineKVDtypes:
    PROMPT = [1, 5, 2, 9, 3, 7, 4, 6]

    @pytest.fixture(scope="class")
    def lm(self):
        return _lm()

    @pytest.fixture(scope="class")
    def oracle(self, lm):
        return _ref_greedy(lm, self.PROMPT, 8)

    def _engine(self, lm, backend, dt):
        kw = dict(num_slots=2, max_queue=16, min_prompt_bucket=8,
                  kv_dtype=dt)
        if backend == "paged":
            kw.update(cache="paged", block_size=8, prompt_buckets=[8],
                      prefill_chunk_tokens=8)
        eng = GenerationEngine(lm, **kw)
        eng.warmup()
        return eng

    @pytest.mark.parametrize("backend", ["slots", "paged"])
    @pytest.mark.parametrize("dt", ["f32", "bf16", "int8"])
    def test_tokens_match_f32_oracle(self, lm, oracle, backend, dt):
        """f32 is bit-identical by construction; on this model the
        bf16/int8 legs land the same greedy argmaxes (the bench tracks
        the logit rel-err that backs this up)."""
        eng = self._engine(lm, backend, dt)
        try:
            out = eng.generate(self.PROMPT, max_tokens=8,
                               timeout_ms=120_000)
            assert out["tokens"] == oracle
            st = eng.stats()
            assert st["kv_dtype"] == dt
            assert st["kv_bits"] == {"f32": 32, "bf16": 16, "int8": 8}[dt]
            T_or_Bs = _cache_arrays(eng)[0].shape[2]
            assert st["kv_bytes_per_token"] == kv_bytes_per_token(
                lm.cache_shapes(T_or_Bs), dt)
            if dt == "int8":
                assert is_quantized(_cache_arrays(eng)[0])
                assert st["quant"]["scale_bytes"] > 0
            else:
                assert st["quant"]["scale_bytes"] == 0
        finally:
            eng.stop()

    def test_bytes_shrink_with_dtype(self, lm):
        """The whole point: same capacity, fewer bytes. (No warmup —
        pool sizing is decided at construction.)"""
        sizes = {}
        for dt in ("f32", "bf16", "int8"):
            eng = GenerationEngine(lm, num_slots=2, max_queue=16,
                                   cache="paged", block_size=8,
                                   prompt_buckets=[8],
                                   prefill_chunk_tokens=8, kv_dtype=dt)
            try:
                sizes[dt] = eng._cache.nbytes()
            finally:
                eng.stop()
        assert sizes["bf16"] == sizes["f32"] // 2
        assert sizes["f32"] // 4 < sizes["int8"] < sizes["f32"] // 2


# ---------------------------------------------------------------------------
# quarantine THROUGH the quantized cache
# ---------------------------------------------------------------------------
class TestDotOperandAudit:
    """What the MXU streams is a dot's OPERAND dtype (the result is f32
    by design: ``preferred_element_type``). On a bf16 or int8 pool the
    two cache-side attention dots of every layer (QK and PV) must run
    on bf16 operands and NOTHING else may change dtype: an f32-operand
    dot there would mean the dequantised cache was materialised in f32.
    Read from the StableHLO of the programs the engine itself lowers
    (backend-independent: the CPU backend upcasts bf16 only later)."""

    N_LAYERS = 2

    @staticmethod
    def _dot_operands(text):
        pairs = re.findall(
            r"stablehlo\.dot(?:_general)?\b[^\n]*:\s*"
            r"\(tensor<[^>]*x(\w+)>,\s*tensor<[^>]*x(\w+)>\)", text)
        return collections.Counter(pairs)

    def _lowered(self, monkeypatch, program, dt):
        """StableHLO of one of the engine's programs, lowered with the
        arguments and donation the engine compiles it with."""
        from deeplearning4j_tpu.serving import generation
        texts = []
        real = generation.compile_memoized

        def capture(fn, args, donate):
            texts.append(jax.jit(fn, donate_argnums=tuple(donate))
                         .lower(*args).as_text())
            return real(fn, args, donate)

        kw = dict(num_slots=4, max_queue=16, prompt_buckets=[16],
                  kv_dtype=dt)
        if program != "slot_decode":
            kw.update(cache="paged", block_size=8,
                      prefill_chunk_tokens=16)
        eng = GenerationEngine(_lm(), **kw)
        try:
            monkeypatch.setattr(generation, "compile_memoized", capture)
            if program == "chunk":
                eng._get_chunk_exe(16, 8)
            else:
                eng._get_decode_exe()
        finally:
            eng.stop()
        assert len(texts) == 1
        return self._dot_operands(texts[0])

    @pytest.mark.parametrize("program",
                             ["paged_decode", "slot_decode", "chunk"])
    def test_only_the_attention_dots_move_to_bf16(self, monkeypatch,
                                                  program):
        base = self._lowered(monkeypatch, program, "f32")
        assert set(base) == {("f32", "f32")}
        moved = 2 * self.N_LAYERS            # QK and PV, every layer
        for dt in ("bf16", "int8"):
            dots = self._lowered(monkeypatch, program, dt)
            assert dots == {("f32", "f32"): base["f32", "f32"] - moved,
                            ("bf16", "bf16"): moved}, (program, dt, dots)


class _CachePoisonLM(CausalTransformerLM):
    """Poison rig that NaNs the prefill K/V SLABS (never the prefill
    logits) for prompts containing NAN_TRIGGER. The NaN therefore
    enters the pool through quantize-on-write, and the FIRST DECODE
    step only goes non-finite if the quantized cache faithfully carries
    the poison back out (int8: via the scale sidecar). If quantization
    laundered the NaN into finite garbage, no quarantine would fire and
    the test would fail — the NaN-transparency invariant, end to end."""

    def forward_prefill(self, params, tokens, key_mask=None):
        logits, ks, vs = super().forward_prefill(params, tokens, key_mask)
        bad = jnp.any(tokens == NAN_TRIGGER, axis=-1)[:, None, None, None]
        ks = [jnp.where(bad, jnp.nan, k) for k in ks]
        vs = [jnp.where(bad, jnp.nan, v) for v in vs]
        return logits, ks, vs

    def forward_prefill_chunk(self, params, tokens, p0, chunk_len,
                              pools, block_table, state=(),
                              last_only=False):
        logits, pools, state = super().forward_prefill_chunk(
            params, tokens, p0, chunk_len, pools, block_table, state,
            last_only)
        bad = jnp.any(tokens == NAN_TRIGGER)
        C = tokens.shape[1] if tokens.ndim > 1 else tokens.shape[0]
        Bs = pools[0].shape[2]
        gpos = p0 + jnp.arange(C)
        blk = block_table[gpos // Bs]
        off = gpos % Bs
        add = jnp.where(bad, jnp.nan, 0.0)

        def poison(pool):
            if is_quantized(pool):
                # int8 pools carry poison in the f32 scale sidecar
                # ([N, 2, H, Bs]: the keys' scales and the values')
                s = pool.scale
                s = s.at[blk, :, :, off].set(s[blk, :, :, off] + add)
                return QuantArray(pool.q, s)
            return pool.at[blk, :, off].set(pool[blk, :, off] + add)

        return logits, [poison(p) for p in pools], state


class TestQuarantine:
    @pytest.fixture(scope="class")
    def plm(self):
        return _lm(cls=_CachePoisonLM)

    @pytest.fixture(scope="class")
    def eng_int8(self, plm):
        eng = GenerationEngine(plm, num_slots=3, max_queue=64,
                               min_prompt_bucket=4, kv_dtype="int8")
        eng.warmup()
        yield eng
        eng.stop()

    @pytest.fixture(scope="class")
    def base_int8(self, eng_int8):
        out, errs = _run_all(eng_int8, _REQS)
        assert all(e is None for e in errs)
        return out

    def test_nan_travels_quantized_cache_and_quarantines(self, eng_int8,
                                                         base_int8):
        eng = eng_int8
        q0 = eng.metrics.quarantined
        reqs = list(_REQS) + [([1, NAN_TRIGGER, 2], 6)]
        out, errs = _run_all(eng, reqs)
        assert isinstance(errs[3], PoisonRequestError)
        assert "quarantined" in str(errs[3])
        assert [errs[i] for i in range(3)] == [None] * 3
        assert out[:3] == base_int8        # batchmates unharmed
        assert eng.metrics.quarantined == q0 + 1
        assert eng.metrics.recoveries == 0  # per-lane, no global rebuild

    def test_slot_reuse_after_quantized_nan_is_clean(self, eng_int8,
                                                     base_int8):
        """Fill every slot with quantized NaN leavings, free them
        WITHOUT zeroing, rerun clean: the kernels' quantized stale-tail
        masking keeps successors bit-identical."""
        eng = eng_int8
        nan_prompt = [NAN_TRIGGER] + list(range(1, 17))
        _, errs = _run_all(eng, [(nan_prompt, 4)] * 3)
        # every quarantine here proves the NaN crossed the int8 pool:
        # the rig NaNs only the K/V slabs, never the logits, so the
        # poison had to survive quantize-on-write to be seen at all
        # (pool buffers are donated every step, so we can't inspect
        # them directly without racing the scheduler)
        assert all(isinstance(e, PoisonRequestError) for e in errs)
        out2, errs2 = _run_all(eng, _REQS)
        assert all(e is None for e in errs2)
        assert out2 == base_int8

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_paged_quarantine_frees_quantized_blocks(self, plm, dt):
        eng = GenerationEngine(plm, num_slots=3, max_queue=64,
                               cache="paged", block_size=4,
                               prompt_buckets=[8],
                               prefill_chunk_tokens=8, kv_dtype=dt)
        eng.warmup()
        try:
            base, errs0 = _run_all(eng, _REQS)
            assert all(e is None for e in errs0)
            reqs = list(_REQS) + [([1, NAN_TRIGGER, 2], 6)]
            out, errs = _run_all(eng, reqs)
            assert isinstance(errs[3], PoisonRequestError)
            assert out[:3] == base
            # quarantine released the poisoned blocks; the NaN'd
            # quantized blocks get reused without zeroing
            eng.clear_prefix_cache()
            assert eng._allocator.free_count == eng._allocator.capacity
            out2, errs2 = _run_all(eng, _REQS)
            assert all(e is None for e in errs2)
            assert out2 == base
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# COW copies scales (referenced from generation.py _cow_fn)
# ---------------------------------------------------------------------------
class TestCOWScales:
    _P16 = [1, 5, 2, 9, 3, 7, 4, 6, 8, 10, 1, 5, 2, 9, 3, 7]

    def _mkeng(self, lm, sharing, dt):
        eng = GenerationEngine(lm, num_slots=3, max_queue=64,
                               min_prompt_bucket=4, cache="paged",
                               block_size=8, prefill_chunk_tokens=8,
                               enable_prefix_sharing=sharing,
                               kv_dtype=dt)
        eng.warmup()
        return eng

    def test_cow_divergent_suffix_int8_matches_unshared(self):
        """Two requests share a 16-token int8 prefix then diverge; the
        writable copy must carry the blocks AND their scale rows — a
        value-only copy would dequantize the suffix with stale scales
        and the shared leg would drift from the unshared one."""
        lm = _lm()
        p_a = self._P16 + [11, 12, 13, 14]
        p_b = self._P16 + [21, 22, 23, 24]
        outs = {}
        for sharing in (True, False):
            eng = self._mkeng(lm, sharing, "int8")
            try:
                ra = eng.generate(p_a, max_tokens=5, timeout_ms=120_000)
                rb = eng.generate(p_b, max_tokens=5, timeout_ms=120_000)
                # an exact-duplicate block-aligned prompt COWs its
                # final matched block (the L-1 cap lands inside a
                # shared block) — the path kv_copy_row serves
                rc1 = eng.generate(self._P16, max_tokens=5,
                                   timeout_ms=120_000)
                rc2 = eng.generate(self._P16, max_tokens=5,
                                   timeout_ms=120_000)
                assert rc2["tokens"] == rc1["tokens"]
                outs[sharing] = (ra["tokens"], rb["tokens"],
                                 rc1["tokens"])
                if sharing:
                    assert eng.metrics.prefix_hits >= 1
                    assert eng.metrics.cow_copies >= 1
            finally:
                eng.stop()
        assert outs[True] == outs[False]

    def test_session_turns_int8(self):
        """Session KV pinning on an int8 pool: turn N re-prefills only
        its new suffix over quantized pinned blocks."""
        lm = _lm()
        eng = self._mkeng(lm, True, "int8")
        try:
            r1 = eng.generate(self._P16, max_tokens=4,
                              session_id="alice", timeout_ms=120_000)
            turn2 = self._P16 + r1["tokens"] + [12, 13]
            r2 = eng.generate(turn2, max_tokens=4, session_id="alice",
                              timeout_ms=120_000)
            assert r2["tokens"] == _ref_greedy(lm, turn2, 4)
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# recompute-recovery rebuilds the quantized pool
# ---------------------------------------------------------------------------
class TestRecoveryQuantized:
    @pytest.mark.parametrize("backend", ["slots", "paged"])
    def test_corrupting_fault_recovers_quantized_token_identical(
            self, backend):
        lm = _lm()
        kw = dict(num_slots=3, max_queue=64, min_prompt_bucket=4,
                  kv_dtype="int8", retry_backoff_ms=0.2,
                  retry_backoff_max_ms=2.0)
        if backend == "paged":
            kw.update(cache="paged", block_size=4, prompt_buckets=[8],
                      prefill_chunk_tokens=8)
        eng = GenerationEngine(lm, **kw)
        eng.warmup()
        try:
            base, errs0 = _run_all(eng, _REQS)
            assert all(e is None for e in errs0)
            inj = FaultInjector(plan={"device_step": [3]},
                                corrupting=("device_step",))
            v0, c0 = eng.metrics.recoveries, eng.metrics.compiles
            eng.set_fault_injector(inj)
            try:
                out, errs = _run_all(eng, _REQS)
            finally:
                eng.set_fault_injector(None)
            assert all(e is None for e in errs)
            assert out == base                       # token-identical
            assert eng.metrics.recoveries - v0 >= 1
            assert eng.metrics.compiles - c0 == 0    # same exe, new pool
            # the rebuilt pool is still an int8 QuantArray (type check
            # only — the buffers themselves are donated every step)
            assert is_quantized(_cache_arrays(eng)[0])
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# int8 weight-only MLP
# ---------------------------------------------------------------------------
class TestWeightOnlyMLP:
    def test_quantize_weight_per_output_channel(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (16, 8)) * \
            jnp.arange(1, 9)[None, :]        # wildly different columns
        qw = quantize_weight(w)
        assert qw.q.dtype == jnp.int8 and qw.scale.shape == (8,)
        err = np.abs(np.asarray(qw.q.astype(jnp.float32) *
                                qw.scale[None, :]) - np.asarray(w))
        # per-output-channel scales: error <= scale/2 per column, so a
        # single shared scale's worst-case bound would fail here
        assert (err <= np.asarray(qw.scale)[None, :] / 2 + 1e-6).all()

    def test_mm_matches_dense(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        w = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
        ref = np.asarray(x @ w)
        got = np.asarray(mm(x, quantize_weight(w)))
        np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)
        # plain arrays fall through exactly
        np.testing.assert_array_equal(np.asarray(mm(x, w)), ref)

    def test_quantize_mlp_weights_idempotent_and_accurate(self):
        lm = _lm()
        prompt = np.asarray([[1, 5, 2, 9, 3, 7, 4, 6]])
        ref = np.asarray(lm.logits(prompt))[0, -1]
        qlm = quantize_mlp_weights(lm)
        assert qlm is lm                     # in-place on params
        for bp in lm._params["blocks"]:
            assert isinstance(bp["W1"], QuantWeight)
            assert isinstance(bp["W2"], QuantWeight)
        quantize_mlp_weights(lm)             # second call is a no-op
        got = np.asarray(lm.logits(prompt))[0, -1]
        rel = np.abs(got - ref).max() / np.abs(ref).max()
        assert rel < 0.02

    def test_engine_runs_quantized_mlp_with_int8_kv(self):
        lm = _lm()
        oracle = _ref_greedy(lm, [1, 5, 2, 9, 3, 7, 4, 6], 6)
        quantize_mlp_weights(lm)
        eng = GenerationEngine(lm, num_slots=2, max_queue=16,
                               min_prompt_bucket=8, kv_dtype="int8")
        eng.warmup()
        try:
            out = eng.generate([1, 5, 2, 9, 3, 7, 4, 6], max_tokens=6,
                               timeout_ms=120_000)
            assert out["tokens"] == oracle
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# observability: quant leaves on /stats and /metrics
# ---------------------------------------------------------------------------
import sys  # noqa: E402
import os  # noqa: E402
sys.path.insert(0, os.path.dirname(__file__))
from _obs_util import assert_exposition_parity  # noqa: E402
from _obs_util import parse_prometheus as _parse_prometheus  # noqa: E402


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        import json
        return json.loads(r.read().decode())


class TestQuantObservability:
    def test_quant_leaves_export_with_parity(self):
        lm = _lm()
        srv = InferenceServer(port=0)
        g = srv.register_generator(
            "lm", lm, num_slots=2, max_seq_len=32, prompt_buckets=[8],
            cache="paged", block_size=8, prefill_chunk_tokens=8,
            kv_dtype="int8")
        g.warmup()
        try:
            g.generate([1, 5, 2, 9, 3, 7, 4, 6], max_tokens=4,
                       timeout_ms=120_000)
            base = f"http://{srv.host}:{srv.port}"
            stats = _get_json(base + "/stats")
            m = stats["models"]["lm"]
            assert m["kv_dtype"] == "int8"
            assert m["kv_bits"] == 8
            assert m["kv_bytes_per_token"] > 0
            assert m["quant"]["scale_bytes"] > 0
            assert m["quant"]["blocks_quantized"] >= 0
            resp = urllib.request.urlopen(base + "/metrics", timeout=30)
            samples, types = _parse_prometheus(resp.read().decode())
            # every numeric leaf (kv_bits, kv_bytes_per_token, the
            # quant block) must round-trip; kv_dtype is a string and
            # deliberately /stats-only
            assert_exposition_parity(stats, samples, types)
            lab = '{model="lm"}'
            assert samples[("dl4j_model_kv_bits", lab)] == 8
            assert types["dl4j_model_kv_bits"] == "gauge"
            assert samples[("dl4j_model_quant_scale_bytes", lab)] == \
                m["quant"]["scale_bytes"]
            assert not any("kv_dtype" in n for n, _ in samples)
        finally:
            srv.stop()

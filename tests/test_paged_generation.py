"""Paged KV-cache subsystem tests (ISSUE 3): the block allocator and
tables, the paged-attention kernel (Pallas interpret parity + gather
equivalence with the dense slot kernel), chunked prefill at the layer
and model level, and the GenerationEngine's paged backend — token
identity with the slot backend over a 32-request mixed-length workload
(including block free/reuse cycles and mid-stream chunked prefill),
>=2x concurrency at equal pool bytes, block admission control, zero
post-warmup recompiles, the no-zeroing-on-reuse invariant, and the
paged stats surface."""
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels.decode_attention import decode_attention_xla
from deeplearning4j_tpu.kernels.kv_quant import (is_quantized, kv_copy_row,
                                                 kv_pack_host,
                                                 kv_unpack_host,
                                                 quantize_rows)
from deeplearning4j_tpu.kernels.paged_attention import (
    fuse_kv, gather_blocks, kv_pool_set, paged_attention_pallas,
    paged_attention_xla, split_kv)
from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderLayer
from deeplearning4j_tpu.serving import (BlockAllocator, BlockTable,
                                        ClientError, GenerationEngine,
                                        InferenceServer, PagedKVCache)
from deeplearning4j_tpu.serving.paging import (NULL_BLOCK, blocks_for,
                                               export_block_run,
                                               import_block_run,
                                               pow2_bucket)
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM


def _lm(vocab=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=32,
        seed=0):
    return CausalTransformerLM(vocab_size=vocab, d_model=d_model,
                               n_layers=n_layers, n_heads=n_heads,
                               max_seq_len=max_seq_len, seed=seed,
                               implementation="plain").init()


def _ref_greedy(lm, prompt, n):
    """Uncached full-prefix greedy decode — the oracle both cache
    backends must reproduce exactly."""
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = np.asarray(lm.logits(np.asarray(toks)[None]))[0, -1]
        t = int(logits.argmax())
        out.append(t)
        toks.append(t)
    return out


def ragged_paged_case(H, Bs, D, G, seed=0, g=1, lens="edges"):
    """Inputs that put every edge of the paged kernel's grid in one
    call, for chunks of ``G`` blocks of ``Bs`` keys: lengths 0, 1, one
    short of / at / one past a block edge and a chunk edge, two whole
    chunks, and the full table (``2 G + 1`` blocks wide, so the last
    chunk is ragged); tables that are a random permutation of the pool,
    not in pool order; and NaN wherever no key lives: in a pool block
    no table names, in the block every table entry past the length
    names, and in the tail of each lane's last live block. ``lens="ones"``
    puts every lane at length 1 instead (one pair a lane, the rest of
    the table dead); ``g`` query heads to each of the ``H`` KV heads.
    Returns (q, k_pool, v_pool, tables, lengths), the pools f32 and
    apart ([N, H, Bs, D] each): ``fuse_kv`` lays them side by side as
    the kernel reads them."""
    B = 2 * G + 1
    lens = sorted({0, 1, Bs - 1, Bs, Bs + 1, G * Bs - 1, G * Bs,
                   G * Bs + 1, 2 * G * Bs, B * Bs}) if lens == "edges" \
        else [1] * 8
    S = len(lens)
    N = S * B + 3                   # + null block, 2 poisoned blocks
    rs = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (S, g * H, D))
    kp = np.array(jax.random.normal(ks[1], (N, H, Bs, D)))
    vp = np.array(jax.random.normal(ks[2], (N, H, Bs, D)))
    unnamed, past = N - 1, N - 2
    tables = rs.permutation(np.arange(1, N - 2)).reshape(S, B)
    for pool in (kp, vp):
        pool[[unnamed, past]] = np.nan
        for s, n in enumerate(lens):
            if n % Bs:
                pool[tables[s, n // Bs], :, n % Bs:] = np.nan
    for s, n in enumerate(lens):
        tables[s, -(-n // Bs):] = past
    return (q, jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32))


def chunks_of(monkeypatch, G):
    """Make the paged kernel walk its table ``G`` blocks a grid step,
    as a larger shape's VMEM budget would."""
    mod = importlib.import_module(
        "deeplearning4j_tpu.kernels.paged_attention")
    monkeypatch.setattr(mod, "blocks_per_chunk", lambda *a: G)


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.fixture(scope="module")
def paged_engine(lm):
    eng = GenerationEngine(lm, num_slots=4, max_queue=64,
                           min_prompt_bucket=4, cache="paged",
                           block_size=8, prefill_chunk_tokens=8)
    eng.warmup()
    yield eng
    eng.stop()


# ---------------------------------------------------------------------------
# allocator / tables / pool
# ---------------------------------------------------------------------------
class TestBlockAllocator:
    def test_null_block_reserved(self):
        a = BlockAllocator(5)
        assert a.capacity == 4
        got = a.alloc(4)
        assert sorted(got) == [1, 2, 3, 4]       # block 0 never leaves
        assert NULL_BLOCK not in got

    def test_all_or_nothing(self):
        a = BlockAllocator(5)
        assert a.alloc(5) is None                # over capacity
        assert a.free_count == 4                 # NOTHING was claimed
        got = a.alloc(3)
        assert a.alloc(2) is None                # 1 free < 2 wanted
        assert a.free_count == 1
        a.free(got)
        assert a.free_count == 4

    def test_reuse_and_double_free_guard(self):
        a = BlockAllocator(4)
        g1 = a.alloc(3)
        a.free(g1[:1])
        assert a.alloc(1) == g1[:1]              # LIFO: warm block first
        a.free(g1)                               # release everything
        with pytest.raises(ValueError):
            a.free(g1[1:])                       # double free
        with pytest.raises(ValueError):
            a.free([NULL_BLOCK])                 # never allocatable

    def test_peak_tracking(self):
        a = BlockAllocator(9)
        g = a.alloc(5)
        a.free(g)
        a.alloc(2)
        assert a.peak_used == 5
        assert a.stats()["peak_used"] == 5

    def test_helpers(self):
        assert blocks_for(1, 8) == 1
        assert blocks_for(8, 8) == 1
        assert blocks_for(9, 8) == 2
        assert pow2_bucket(1) == 1
        assert pow2_bucket(5) == 8
        assert pow2_bucket(9, cap=8) == 8

    def test_block_table_padding(self):
        t = BlockTable([4, 2, 9], block_size=8)
        assert len(t) == 3 and t.capacity_tokens == 24
        padded = t.padded(8)
        assert padded.dtype == np.int32
        assert padded[:3].tolist() == [4, 2, 9]
        assert (padded[3:] == NULL_BLOCK).all()
        with pytest.raises(ValueError):
            t.padded(2)

    def test_pool_bytes(self):
        pool = PagedKVCache([(2, 8, 4), (2, 8, 4)], num_blocks=10)
        # 2 layers * K+V * 10 blocks * 2*8*4 f32
        assert pool.nbytes() == 2 * 2 * 10 * 2 * 8 * 4 * 4
        assert pool.block_nbytes() * 10 == pool.nbytes()
        # one array a layer, K and V side by side on the lanes: the
        # bytes counted are the bytes held
        assert [p.shape for p in pool.pools] == [(10, 2, 8, 8)] * 2
        assert sum(p.nbytes for p in pool.pools) == pool.nbytes()


# ---------------------------------------------------------------------------
# the pool's layout: K and V side by side in one row
# ---------------------------------------------------------------------------
def _halves(pool):
    """The f32 K and V blocks [N, H, Bs, D] a pool of any dtype holds."""
    if is_quantized(pool):
        k, v = split_kv(pool.q.astype(jnp.float32))
        return (np.asarray(k * pool.scale[:, 0, ..., None]),
                np.asarray(v * pool.scale[:, 1, ..., None]))
    return tuple(np.asarray(x, np.float32) for x in split_kv(pool))


class TestPoolLayout:
    TOL = {"f32": 0.0, "bf16": 2e-2, "int8": 3e-2}

    @pytest.mark.parametrize("dt", ["f32", "bf16", "int8"])
    @pytest.mark.parametrize("rows", ["step", "chunk"])
    def test_written_rows_land_in_their_lanes(self, rows, dt):
        """A decode step's rows (one a lane, at its position) and a
        chunk's (consecutive positions of one table): the key in lanes
        0..D-1 of (block, head, offset), the value in D..2D-1, and
        nothing else touched."""
        N, H, Bs, D = 7, 3, 4, 8
        (pool,) = PagedKVCache([(H, Bs, D)], N, kv_dtype=dt).pools
        if rows == "step":
            tables = np.array([[5, 2], [3, 0], [6, 1]], np.int32)
            pos = np.array([6, 1, 3], np.int32)
            blk, off = tables[np.arange(3), pos // Bs], pos % Bs
        else:
            table = np.array([4, 1, 6], np.int32)
            gpos = 3 + np.arange(6)             # positions 3..8
            blk, off = table[gpos // Bs], gpos % Bs
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        k = jax.random.normal(ks[0], (len(blk), H, D))
        v = jax.random.normal(ks[1], (len(blk), H, D))
        pool = kv_pool_set(pool, (jnp.asarray(blk)[:, None],
                                  jnp.arange(H)[None, :],
                                  jnp.asarray(off)[:, None]), k, v)
        assert pool.shape == (N, H, Bs, 2 * D)
        got_k, got_v = _halves(pool)
        np.testing.assert_allclose(got_k[blk, :, off], np.asarray(k),
                                   atol=self.TOL[dt])
        np.testing.assert_allclose(got_v[blk, :, off], np.asarray(v),
                                   atol=self.TOL[dt])
        dark = np.ones((N, Bs), bool)
        dark[blk, off] = False
        assert not got_k.transpose(0, 2, 1, 3)[dark].any()
        assert not got_v.transpose(0, 2, 1, 3)[dark].any()

    @pytest.mark.parametrize("dt", ["f32", "bf16", "int8"])
    def test_block_copy_and_host_round_trip_carry_both_halves(self, dt):
        """Copy-on-write's block copy, and a demotion's gather ->
        packed host arrays -> a restore's scatter into other blocks:
        keys, values and (int8) both halves' scales move together."""
        N, H, Bs, D = 9, 2, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(2), 2)
        k = jax.random.normal(ks[0], (N, H, Bs, D))
        v = jax.random.normal(ks[1], (N, H, Bs, D))
        cast = {"f32": lambda x: x, "bf16": lambda x: x.astype(
            jnp.bfloat16), "int8": quantize_rows}[dt]
        pool = fuse_kv(cast(k), cast(v))
        want_k, want_v = _halves(pool)
        got_k, got_v = _halves(kv_copy_row(pool, 3, 7))
        np.testing.assert_array_equal(got_k[7], want_k[3])
        np.testing.assert_array_equal(got_v[7], want_v[3])
        np.testing.assert_array_equal(got_k[:7], want_k[:7])
        # blocks 5, 2, 8 out (a bucket of 4: one NULL-padded entry) ...
        src = jnp.asarray([5, 2, 8, NULL_BLOCK], jnp.int32)
        (rows,) = export_block_run([pool], src)
        host = kv_pack_host(rows, 3)
        assert host[0].shape == (3, H, Bs, 2 * D)
        assert len(host) == (2 if dt == "int8" else 1)
        # ... and back into blocks 1, 6, 4 of an empty pool
        (fresh,) = PagedKVCache([(H, Bs, D)], N, kv_dtype=dt).pools
        dst = jnp.asarray([1, 6, 4, NULL_BLOCK], jnp.int32)
        (fresh,) = import_block_run([fresh], [kv_unpack_host(host, 4)],
                                    dst)
        got_k, got_v = _halves(fresh)
        np.testing.assert_array_equal(got_k[[1, 6, 4]], want_k[[5, 2, 8]])
        np.testing.assert_array_equal(got_v[[1, 6, 4]], want_v[[5, 2, 8]])
        assert not got_k[[2, 3, 5, 7, 8]].any()


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------
class TestPagedAttentionKernel:
    def _setup(self, S=3, H=4, D=8, N=10, Bs=4, B=4):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (S, H, D))
        kp = jax.random.normal(ks[1], (N, H, Bs, D))
        vp = jax.random.normal(ks[2], (N, H, Bs, D))
        tbl = jnp.array([[3, 1, 0, 0], [2, 5, 7, 0], [9, 8, 6, 4]],
                        jnp.int32)
        lens = jnp.array([5, 12, 16], jnp.int32)
        return q, kp, vp, tbl, lens

    def test_pallas_matches_xla(self):
        q, kp, vp, tbl, lens = self._setup()
        pool = fuse_kv(kp, vp)
        a = np.asarray(paged_attention_xla(q, pool, tbl, lens))
        b = np.asarray(paged_attention_pallas(q, pool, tbl, lens,
                                              interpret=True))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_matches_dense_slot_kernel_on_gathered_blocks(self):
        """The gathered pool view IS the slot layout — the two kernels
        must agree exactly (this equivalence is what makes
        paged-vs-slot token identity hold at the engine level)."""
        q, kp, vp, tbl, lens = self._setup()
        pool = fuse_kv(kp, vp)
        a = np.asarray(paged_attention_xla(q, pool, tbl, lens))
        kd, vd = gather_blocks(pool, tbl)
        dense = np.asarray(decode_attention_xla(q, kd, vd, lens))
        np.testing.assert_allclose(a, dense, rtol=0, atol=0)
        # and the panels are the blocks' own rows, in table order
        np.testing.assert_array_equal(
            np.asarray(kd[1, :, :12]),
            np.asarray(kp)[[2, 5, 7]].transpose(1, 0, 2, 3).reshape(
                4, 12, 8))
        np.testing.assert_array_equal(
            np.asarray(vd[2]),
            np.asarray(vp)[[9, 8, 6, 4]].transpose(1, 0, 2, 3).reshape(
                4, 16, 8))

    def test_empty_lane_is_zero_not_nan(self):
        q, kp, vp, tbl, lens = self._setup()
        lens = jnp.array([0, 12, 16], jnp.int32)
        for impl in (paged_attention_xla,
                     lambda *a: paged_attention_pallas(*a,
                                                      interpret=True)):
            out = np.asarray(impl(q, fuse_kv(kp, vp), tbl, lens))
            assert np.isfinite(out).all()
            assert np.abs(out[0]).max() == 0.0

    def test_stale_block_tail_ignored(self):
        """Positions >= length — the stale tail of a recycled block —
        must not influence the output (the no-zeroing invariant's
        kernel-level half)."""
        q, kp, vp, tbl, lens = self._setup()
        lens = jnp.array([5, 12, 16], jnp.int32)
        # poison row 0's second block beyond position 5 (block 1 of its
        # table holds positions 4..7 -> offsets 1..3 are dead); NaN is
        # the hard case — a quarantined request's freed blocks keep
        # their non-finite K/V, and 0 * NaN = NaN would leak through
        # (either half of a row alone, and both: the halves share a
        # row, and neither may reach the other's sum)
        for tail in (99.0, jnp.nan):
            for impl in (paged_attention_xla,
                         lambda *a: paged_attention_pallas(
                             *a, interpret=True)):
                base = np.asarray(impl(q, fuse_kv(kp, vp), tbl, lens))
                kp2 = kp.at[1, :, 2:].set(tail)
                vp2 = vp.at[1, :, 2:].set(-tail)
                for pool in (fuse_kv(kp2, vp2), fuse_kv(kp2, vp),
                             fuse_kv(kp, vp2)):
                    poisoned = np.asarray(impl(q, pool, tbl, lens))
                    np.testing.assert_allclose(base[0], poisoned[0],
                                               rtol=1e-6)

    @pytest.mark.parametrize("H,Bs,D,G,g,lens", [
        (4, 4, 8, 2, 1, "edges"), (4, 8, 8, 2, 1, "edges"),
        (4, 16, 8, 4, 1, "edges"), (25, 4, 8, 2, 1, "edges"),
        (25, 8, 8, 4, 1, "edges"), (25, 16, 64, 8, 1, "edges"),
        # every lane one pair long, the rest of its table dead
        (4, 4, 8, 2, 1, "ones"), (25, 16, 64, 4, 1, "ones"),
        # grouped-query heads, on the VPU and (128 lanes) the MXU form
        (2, 4, 8, 2, 4, "edges"), (1, 8, 64, 2, 20, "edges"),
        (2, 4, 128, 2, 4, "edges"), (1, 4, 128, 2, 20, "ones")])
    def test_every_grid_edge_matches_xla_and_nan_stays_dark(
            self, monkeypatch, H, Bs, D, G, g, lens):
        """The grid visits the live (slot, chunk) pairs only: a free
        lane's one pair writes zeros, a lane filling the table visits
        its ragged last chunk, and no NaN past a length reaches a
        row."""
        chunks_of(monkeypatch, G)
        q, kp, vp, tbl, lens = ragged_paged_case(H, Bs, D, G, g=g,
                                                 lens=lens)
        pool = fuse_kv(kp, vp)
        a = np.asarray(paged_attention_xla(q, pool, tbl, lens))
        b = np.asarray(paged_attention_pallas(q, pool, tbl, lens,
                                              interpret=True))
        assert np.isfinite(b).all()
        if lens[0] == 0:
            assert np.abs(b[0]).max() == 0.0        # the empty lane
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("G", [1, 2, 4])
    def test_live_pairs_list_the_live_chunks_slot_major(self, G, window):
        """The kernel's grid (``live_pairs``): ``max(1, n_s)`` pairs a
        slot, slot-major and in chunk order, ``n`` their count, and
        every live pair's operands aimed at the table entries the static
        grid's chunk fetched: entry ``c * G + b`` while it holds live
        keys, else the last live one that operand had (from the window's
        first block on a ring, wrapping)."""
        from deeplearning4j_tpu.kernels.paged_attention import live_pairs
        Bs, B = 4, 9
        lens = np.array([0, 1, 4, 5, 13, 33, 36, 40, 77, 3], np.int32)
        if window is None:
            lens = np.minimum(lens, B * Bs)
        S = len(lens)
        span = B if window is None else min(B, -(-window // Bs) + 1)
        C = -(-span // G)
        tbl = np.random.RandomState(G).randint(1, 99, (S, B)).astype(
            np.int32)
        p = live_pairs(jnp.asarray(tbl), jnp.asarray(lens), Bs, G, C,
                       window)
        lo = np.zeros(S, int) if window is None \
            else np.maximum(lens - window, 0)
        want = []                       # (slot, chunk, G entries)
        for s, n in enumerate(lens):
            first, last = lo[s] // Bs, (max(n, 1) - 1) // Bs
            n_s = (last - first) // G + 1 if n else 0
            for c in range(max(1, n_s)):
                ent = []
                for b in range(G):
                    rel = c * G + b
                    while rel > last - first and rel >= G:
                        rel -= G        # the operand's last live block
                    blk = first + rel
                    ent.append(tbl[s, blk % B if window else min(blk, B - 1)])
                want.append((s, c, ent))
        n = int(p.n)
        assert n == len(want) == int(np.asarray(p.n_chunks).sum())
        assert p.slot.shape == (S * C,) and p.entry.shape == (S * C * G,)
        got = [(int(s), int(c), list(e)) for s, c, e in zip(
            np.asarray(p.slot)[:n], np.asarray(p.chunk)[:n],
            np.asarray(p.entry).reshape(-1, G)[:n])]
        live = [(s, c, e) for s, c, e in want if lens[s] > 0]
        assert [w[:2] for w in got] == [w[:2] for w in want]
        assert [w for w in got if lens[w[0]] > 0] == live
        # past n the list repeats its last pair: never visited, but
        # every index map still reads a block the pool has
        assert (np.asarray(p.slot)[n:] == S - 1).all()

    @pytest.mark.parametrize("shape,itemsize,B,want", [
        ((25, 16, 64), 4, 64, 4),       # the benchmark's cell
        ((25, 16, 64), 2, 64, 8), ((25, 16, 64), 1, 64, 8),
        ((12, 16, 64), 4, 64, 8),       # chip_smoke's GPT-2 small
        ((4, 8, 16), 4, 4, 4),          # no more than the table holds
        ((4, 8, 16), 4, 24, 8),         # nor than the body unrolls (PR 30)
        ((8, 16, 64), 2, 64, 8),        # lfm2-8b-a1b: reckoned from H_kv
        ((4, 4, 8), 4, 1, 1), ((64, 128, 128), 4, 64, 1)])
    def test_blocks_per_chunk_follows_the_vmem_budget(self, shape,
                                                      itemsize, B, want):
        from deeplearning4j_tpu.kernels.paged_attention import \
            blocks_per_chunk
        assert blocks_per_chunk(*shape, itemsize, B) == want


# ---------------------------------------------------------------------------
# layer / model
# ---------------------------------------------------------------------------
class TestPagedLayerParity:
    def test_block_chunked_prefill_and_paged_decode_match_dense(self):
        """TransformerEncoderLayer: chunked paged prefill + paged
        decode must reproduce apply_seq exactly (same construction as
        the slot test, one granularity finer)."""
        B, T, C, Bs = 1, 8, 16, 4
        lay = TransformerEncoderLayer(n_heads=4, causal=True,
                                      implementation="plain")
        lay.build((T, C))
        p = lay.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, C))
        y_full, _, _ = lay.apply_seq(p, x, None, False, None, (), None)
        (pool,) = PagedKVCache([lay.cache_shape(Bs)], 6).pools
        tbl = jnp.asarray(BlockTable([2, 4, 1], Bs).padded(4))
        # prefill positions 0..3 in two chunks of 2
        for p0 in (0, 2):
            y_c, pool = lay.apply_prefill_paged(
                p, x[:, p0:p0 + 2], pool, tbl, np.int32(p0),
                np.int32(2))
            np.testing.assert_allclose(np.asarray(y_c[0]),
                                       np.asarray(y_full[0, p0:p0 + 2]),
                                       atol=1e-5)
        # decode positions 4..7 one at a time
        for t in range(4, T):
            o, pool = lay.apply_decode_paged(
                p, x[:, t], pool, tbl[None], jnp.array([t], jnp.int32))
            np.testing.assert_allclose(np.asarray(o),
                                       np.asarray(y_full[:, t]),
                                       atol=1e-5)

    def test_model_chunked_prefill_matches_full_prefill(self, lm):
        rs = np.random.RandomState(0)
        prompt = rs.randint(0, 64, 13).astype(np.int32)
        L, bucket, Bs, C = 13, 16, 8, 8
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :L] = prompt
        mask = (jnp.arange(bucket)[None] < L).astype(jnp.float32)
        logits_d, _, _ = lm.forward_prefill(lm._params, toks, mask)
        last_dense = np.asarray(logits_d[0, L - 1])
        pools = PagedKVCache(lm.cache_shapes(Bs), num_blocks=8).pools
        tbl = jnp.asarray(BlockTable([3, 1, 5], Bs).padded(4))
        last_chunk = None
        for p0 in range(0, L, C):
            clen = min(C, L - p0)
            ct = np.zeros((1, C), np.int32)
            ct[0, :clen] = prompt[p0:p0 + clen]
            logits_c, pools, _ = lm.forward_prefill_chunk(
                lm._params, ct, np.int32(p0), np.int32(clen), pools,
                tbl)
            last_chunk = np.asarray(logits_c[clen - 1])
        np.testing.assert_allclose(last_chunk, last_dense, atol=1e-5)
        assert int(last_chunk.argmax()) == int(last_dense.argmax())


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class TestPagedEngine:
    def test_greedy_matches_uncached_reference(self, lm, paged_engine):
        r = paged_engine.generate([1, 2, 3], max_tokens=6)
        assert r["tokens"] == _ref_greedy(lm, [1, 2, 3], 6)
        assert r["finish_reason"] == "length"

    def test_32_request_mixed_lengths_identical_to_slot_backend(self, lm):
        """ISSUE 3 acceptance: a 32-request mixed-length workload
        through BOTH backends produces token-identical outputs —
        including block free/reuse cycles (32 requests through a pool
        that holds ~6 concurrently) and mid-stream chunked prefill
        (prompts up to 20 tokens, chunk cap 8)."""
        slots = GenerationEngine(lm, num_slots=4, max_queue=64,
                                 min_prompt_bucket=4)
        slots.warmup()
        paged = GenerationEngine(lm, num_slots=4, max_queue=64,
                                 min_prompt_bucket=4, cache="paged",
                                 block_size=8, num_blocks=25,
                                 prefill_chunk_tokens=8)
        paged.warmup()
        rs = np.random.RandomState(7)
        cases = []
        for i in range(32):
            plen = int(rs.choice([1, 3, 6, 12, 20]))
            n = int(rs.choice([2, 5, 9]))
            cases.append((rs.randint(0, 64, plen).tolist(), n,
                          float(rs.choice([0.0, 0.8]))))

        def run(eng):
            out = [None] * len(cases)

            def go(i):
                p, n, temp = cases[i]
                out[i] = eng.generate(p, max_tokens=n, temperature=temp,
                                      top_k=8, seed=i,
                                      timeout_ms=120_000)
            ts = [threading.Thread(target=go, args=(i,))
                  for i in range(len(cases))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return out

        rs_out = run(slots)
        rp_out = run(paged)
        for i, (a, b) in enumerate(zip(rs_out, rp_out)):
            assert a["tokens"] == b["tokens"], (
                f"request {i} diverged: {a['tokens']} vs {b['tokens']}")
        # reuse really happened: 32 requests > pool concurrency
        assert paged.metrics.blocks_peak_used <= 24
        # after drain the only live blocks are prefix-index pins —
        # releasing them must reclaim the pool exactly (a leaked block
        # would survive the clear)
        paged.clear_prefix_cache()
        assert paged.stats()["paged"]["blocks_free"] == 24
        # mid-stream chunking really happened
        assert paged.metrics.chunked_prefills >= 1
        slots.stop()
        paged.stop()

    def test_2x_concurrency_at_equal_pool_bytes(self, lm):
        """ISSUE 3 acceptance: a request mix whose summed T_max would
        NOT fit the dense cache runs concurrently on the paged pool of
        equal bytes. Dense: 2 slots x 32 = 64 positions. Paged: the
        same 64 positions as 8 blocks serve >= 4 concurrent sequences
        (>= 2x the dense slot ceiling)."""
        dense = GenerationEngine(lm, num_slots=2, max_queue=64,
                                 min_prompt_bucket=4)
        dense_bytes = dense.metrics.cache_bytes
        dense.stop()
        paged = GenerationEngine(lm, num_slots=8, max_queue=64,
                                 min_prompt_bucket=4, cache="paged",
                                 block_size=8, num_blocks=9)
        # equal pool bytes up to the reserved null block
        assert paged.metrics.cache_bytes == dense_bytes * 9 // 8
        paged.warmup()
        results = [None] * 16

        def go(i):
            results[i] = paged.generate([1 + i % 8, 2], max_tokens=6,
                                        seed=i, timeout_ms=120_000)
        ts = [threading.Thread(target=go, args=(i,)) for i in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for i, r in enumerate(results):
            assert r is not None and len(r["tokens"]) == 6, (i, r)
        occ = paged.metrics.occupancy_hist.snapshot()
        assert any(int(k) >= 4 for k in occ), \
            f"never >= 4 concurrent (2x dense ceiling): {occ}"
        paged.stop()

    def test_zero_recompiles_after_warmup(self, paged_engine):
        before = paged_engine.metrics.compiles
        threads = [threading.Thread(
            target=lambda i=i: paged_engine.generate(
                [1 + i, 2] * (i + 1), max_tokens=4, temperature=0.5,
                seed=i))
            for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert paged_engine.metrics.compiles == before

    def test_seeded_sampling_matches_slot_backend(self, lm, paged_engine):
        slots = GenerationEngine(lm, num_slots=2, max_queue=16,
                                 min_prompt_bucket=4)
        slots.warmup()
        kw = dict(max_tokens=8, temperature=0.9, top_k=8, seed=42)
        a = slots.generate([5, 6], **kw)
        b = paged_engine.generate([5, 6], **kw)
        assert a["tokens"] == b["tokens"]
        slots.stop()

    def test_admission_waits_for_blocks_not_failure(self, lm):
        """When the pool is exhausted, later requests WAIT (FIFO at
        the queue head) and complete once blocks free — no 5xx, no
        over-commit."""
        eng = GenerationEngine(lm, num_slots=4, max_queue=32,
                               min_prompt_bucket=4, cache="paged",
                               block_size=8, num_blocks=5)  # 4 usable
        eng.warmup()
        # each request: prompt 9 + 7 gen = 16 tokens = 2 blocks;
        # 4 usable blocks -> only 2 run concurrently, 6 submitted
        results = [None] * 6

        def go(i):
            results[i] = eng.generate(list(range(1, 10)), max_tokens=7,
                                      seed=i, timeout_ms=120_000)
        ts = [threading.Thread(target=go, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for r in results:
            assert r is not None and len(r["tokens"]) == 7
        assert eng.metrics.server_errors == 0
        eng.clear_prefix_cache()              # release index pins
        assert eng.metrics.blocks_free == 4   # all reclaimed
        eng.stop()

    def test_oversized_request_rejected_up_front(self, lm):
        eng = GenerationEngine(lm, num_slots=2, max_queue=8,
                               min_prompt_bucket=4, cache="paged",
                               block_size=8, num_blocks=3)  # 16 tokens
        with pytest.raises(ClientError, match="blocks"):
            eng.generate(list(range(1, 20)), max_tokens=8)
        eng.stop()

    def test_misconfiguration_rejected(self, lm):
        with pytest.raises(ValueError, match="cache"):
            GenerationEngine(lm, num_slots=1, cache="virtual")
        with pytest.raises(ValueError, match="block_size"):
            GenerationEngine(lm, num_slots=1, cache="paged",
                             block_size=0)
        with pytest.raises(ValueError, match="num_blocks"):
            GenerationEngine(lm, num_slots=1, cache="paged",
                             num_blocks=1)  # only the null block

    def test_streaming_and_eos_on_paged(self, lm, paged_engine):
        kw = dict(max_tokens=5, temperature=0.7, top_k=4, seed=11)
        blocking = paged_engine.generate([3, 4], **kw)
        chunks = list(paged_engine.stream([3, 4], **kw))
        tokens = [c["token"] for c in chunks if "token" in c]
        assert tokens == blocking["tokens"]
        assert chunks[-1]["done"] is True
        probe = paged_engine.generate([5, 6], max_tokens=8,
                                      temperature=0.9, top_k=8, seed=42)
        eos = probe["tokens"][2]
        r = paged_engine.generate([5, 6], max_tokens=8, temperature=0.9,
                                  top_k=8, seed=42, eos_id=eos)
        assert r["finish_reason"] == "eos"
        assert r["tokens"] == probe["tokens"][:3]

    def test_paged_stats_surface(self, paged_engine):
        paged_engine.generate(list(range(1, 15)), max_tokens=4)
        s = paged_engine.stats()
        assert s["cache_backend"] == "paged"
        p = s["paged"]
        assert p["block_size"] == 8
        assert p["blocks_total"] > 0
        # idle engine: everything still held belongs to the prefix
        # index (the 14-token prompt spans one full 8-token block)
        assert p["blocks_free"] + p["prefix_cache"]["prefix_blocks"] \
            == p["blocks_total"]
        paged_engine.clear_prefix_cache()
        assert paged_engine.stats()["paged"]["blocks_free"] \
            == p["blocks_total"]
        assert p["blocks_peak_used"] >= 2             # 14+4 tokens
        assert p["prefill_chunks"] >= 2               # 14 tokens, cap 8
        assert p["chunked_prefills"] >= 1
        assert 0.0 <= p["fragmentation"] <= 1.0
        assert s["kv_cache_bytes"] > 0

    def test_stats_over_http(self, lm):
        srv = InferenceServer(port=0)
        g = srv.register_generator("plm", _lm(), num_slots=2,
                                   cache="paged", block_size=8,
                                   prefill_chunk_tokens=8,
                                   min_prompt_bucket=4)
        g.warmup()
        import json
        import urllib.request
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/models/plm/generate",
            data=json.dumps({"prompt": list(range(1, 12)),
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        r = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert len(r["tokens"]) == 4
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/stats", timeout=10).read())
        m = stats["models"]["plm"]
        assert m["cache_backend"] == "paged"
        assert m["paged"]["blocks_total"] > 0
        assert m["paged"]["prefill_chunks"] >= 2
        srv.stop()


class TestNoZeroingInvariant:
    """The no-zeroing-on-reuse contract (`serving/kvcache.py`
    docstring), asserted end-to-end for BOTH cache granularities: a
    new occupant of a slot/block must be unaffected by the previous
    occupant's stale K/V beyond its own length."""

    def test_slot_reuse_long_then_short(self, lm):
        eng = GenerationEngine(lm, num_slots=1, max_queue=8,
                               min_prompt_bucket=4)
        eng.warmup()
        # long occupant writes deep into the single slot...
        eng.generate(list(range(1, 12)), max_tokens=18, seed=0)
        # ...then a SHORT occupant reuses it; its tokens must match the
        # oracle exactly even though positions 3.. hold stale K/V
        r = eng.generate([7, 8], max_tokens=5)
        assert r["tokens"] == _ref_greedy(lm, [7, 8], 5)
        eng.stop()

    def test_block_reuse_long_then_short(self, lm):
        eng = GenerationEngine(lm, num_slots=2, max_queue=8,
                               min_prompt_bucket=4, cache="paged",
                               block_size=8, num_blocks=5,  # 4 usable
                               prefill_chunk_tokens=8)
        eng.warmup()
        # occupy (nearly) every block with a long sequence...
        eng.generate(list(range(1, 12)), max_tokens=18, seed=0)
        assert eng.metrics.blocks_peak_used >= 4
        # ...then short sequences cycle through the recycled blocks
        for start in (3, 9, 15):
            prompt = [start, start + 1]
            r = eng.generate(prompt, max_tokens=5)
            assert r["tokens"] == _ref_greedy(lm, prompt, 5)
        eng.stop()

    def test_fresh_occupant_unaffected_by_poisoned_stale_tail(self):
        """Kernel-level half for the slot cache (the paged sibling
        lives in TestPagedAttentionKernel): poison everything beyond
        the live length, output must not move."""
        S, H, T, D = 1, 2, 16, 4
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (S, H, D))
        k = jax.random.normal(ks[1], (S, H, T, D))
        v = jax.random.normal(ks[2], (S, H, T, D))
        lens = jnp.array([6], jnp.int32)
        base = np.asarray(decode_attention_xla(q, k, v, lens))
        for tail in (1e6, jnp.nan):
            k2 = k.at[:, :, 6:].set(tail)
            v2 = v.at[:, :, 6:].set(-tail)
            poisoned = np.asarray(decode_attention_xla(q, k2, v2, lens))
            np.testing.assert_allclose(base, poisoned, rtol=1e-6)


class TestChunkedPrefillScheduling:
    def test_long_prompt_interleaves_with_decode(self, lm):
        """While a long prompt chunk-prefills, already-running requests
        must keep producing tokens — the decode loop is never starved
        for the whole prefill (the Sarathi property, asserted
        structurally: chunks and decode steps interleave)."""
        eng = GenerationEngine(lm, num_slots=2, max_queue=16,
                               min_prompt_bucket=4, cache="paged",
                               block_size=4, prefill_chunk_tokens=4)
        eng.warmup()
        stamps = []

        def short_client():
            for item in eng.stream([1, 2], max_tokens=20,
                                   temperature=0.0, seed=1,
                                   timeout_ms=120_000):
                if "token" in item:
                    stamps.append(time.perf_counter())
        t = threading.Thread(target=short_client)
        t.start()
        while len(stamps) < 3:          # decode loop is rolling
            time.sleep(0.001)
        # 24-token prompt -> 6 chunks of 4, interleaved with decode
        r = eng.generate(list(range(1, 25)), max_tokens=3,
                         timeout_ms=120_000)
        t.join()
        assert r["tokens"] == _ref_greedy(lm, list(range(1, 25)), 3)
        assert eng.metrics.chunked_prefills >= 1
        assert eng.metrics.prefill_chunks >= 6
        # the short stream kept emitting while the long prompt was
        # being absorbed (strictly more tokens than could have arrived
        # before the long submit)
        assert len(stamps) == 20
        eng.stop()

    def test_chunk_plan_shapes(self, lm):
        eng = GenerationEngine(lm, num_slots=1, max_queue=4,
                               min_prompt_bucket=4, cache="paged",
                               block_size=8, prefill_chunk_tokens=8)
        assert eng._chunk_plan(3) == [(0, 4, 3)]
        assert eng._chunk_plan(8) == [(0, 8, 8)]
        assert eng._chunk_plan(20) == [(0, 8, 8), (8, 8, 8),
                                       (16, 4, 4)]
        # every chunk fits its request's table bucket by construction
        plan = eng._chunk_plan(31)
        span = max(31 + 1, plan[-1][0] + plan[-1][1])
        assert pow2_bucket(blocks_for(span, 8)) <= eng._tbl_top
        eng.stop()


class TestPagedStreamDisconnect:
    """Mid-stream client disconnect on the PAGED backend (ISSUE 4
    satellite — the slot backend's coverage lives in
    test_generation.py): closing a stream() iterator must free the
    request's BLOCKS promptly, not just its slot. Reuses the shared
    warmed module engine; each test starts and ends with an idle
    engine and a full pool."""

    def test_dropped_stream_frees_blocks(self, lm, paged_engine):
        eng = paged_engine
        eng.clear_prefix_cache()    # drop pins left by earlier tests
        cap = eng._allocator.capacity
        errs0 = eng.metrics.server_errors
        it = eng.stream([1, 2, 3], max_tokens=25, temperature=0.5)
        next(it)            # stream is live, blocks are claimed...
        assert eng._allocator.free_count < cap
        it.close()          # ...then the client hangs up
        deadline = time.time() + 5.0
        while eng._allocator.free_count < cap and time.time() < deadline:
            time.sleep(0.01)
        # the scheduler released slot AND blocks at the next step —
        # long before the abandoned request's max_tokens would have
        assert eng._allocator.free_count == cap
        assert eng._slots.active_count == 0
        # pool fully reusable afterwards
        r = eng.generate([1, 2, 3], max_tokens=3)
        assert r["tokens"] == _ref_greedy(lm, [1, 2, 3], 3)
        assert eng.metrics.server_errors == errs0

    def test_never_started_paged_stream_releases_blocks(
            self, paged_engine):
        eng = paged_engine
        eng.clear_prefix_cache()    # drop pins left by earlier tests
        cap = eng._allocator.capacity
        it = eng.stream([1, 2], max_tokens=25, temperature=0.5)
        it.close()          # consumer never called next()
        deadline = time.time() + 5.0
        while (eng._allocator.free_count < cap
               or eng._slots.active_count) and time.time() < deadline:
            time.sleep(0.01)
        assert eng._allocator.free_count == cap
        assert eng._slots.active_count == 0


# -- cache groups: a ring for the window layers beside the plain table (PR 35) ---------
def _grouped_lm(seed=0):
    from deeplearning4j_tpu.zoo.smallthinker import SmallThinkerLM
    return SmallThinkerLM(
        vocab_size=64, hidden_size=32, head_dim=8, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, moe_ffn_hidden_size=16,
        moe_num_primary_experts=4, moe_num_active_primary_experts=2,
        sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
        sliding_window_size=8, max_position_embeddings=64, dtype="float32",
        seed=seed).init()


def _grouped_engine(**kw):
    opts = dict(num_slots=3, max_seq_len=64, prompt_buckets=[8],
                cache="paged", block_size=4, prefill_chunk_tokens=8)
    opts.update(kw)
    return GenerationEngine(_grouped_lm(), **opts)


def _wait(pred, timeout=30.0):
    t = time.time() + timeout
    while time.time() < t:
        if pred():
            return True
        time.sleep(0.01)
    return False


class TestCacheGroups:
    RING = 5        # blocks_for(window 8 + chunk 8, 4) + 1

    def test_a_group_a_pool_an_allocator_and_a_table(self):
        eng = _grouped_engine(num_blocks=40)
        try:
            g, w = eng._groups
            assert (g.name, g.window, g.ring, g.num_blocks) == (
                "global", None, None, 40)
            assert (w.name, w.window, w.ring) == ("window", 8, self.RING)
            assert w.num_blocks == 3 * self.RING + 1     # dense-equivalent
            assert g.allocator is eng._allocator and g.tables is eng._tables
            assert [p.shape[0] for p in eng._pools] == [40, 16, 16, 16]
            assert w.tables.shape == (3, self.RING)
            st = eng.stats()["paged"]
            assert st["blocks_total"] == 39        # the first group's
            assert st["groups"]["window"]["blocks_total"] == 15
        finally:
            eng.stop()

    def test_a_request_never_holds_more_than_the_ring_and_returns_both(self):
        eng = _grouped_engine()
        try:
            eng.warmup()
            seen = []
            orig = eng._chunk_landed

            def spy(st, *a, **k):
                seen.append((len(eng._slot_blocks[st.slot]),
                             len(eng._groups[1].slot_blocks[st.slot])))
                return orig(st, *a, **k)
            eng._chunk_landed = spy
            out = eng.generate(list(range(1, 41)), max_tokens=9,
                               temperature=0.0)
            assert len(out["tokens"]) == 9
            # 49 positions: 13 blocks of 4 in the global group, the
            # ring's 5 in the window group, through all five chunks
            assert seen == [(13, self.RING)] * 5
            out = eng.generate([1, 2, 3], max_tokens=4, temperature=0.0)
            assert seen[-1] == (2, 2)           # shorter than the ring
            g = eng.stats()["paged"]["groups"]
            for name in ("global", "window"):
                assert g[name]["blocks_free"] == g[name]["blocks_total"]
            assert g["window"]["blocks_peak_used"] == self.RING
        finally:
            eng.stop()

    @pytest.mark.parametrize("short", ["global", "window"])
    def test_admission_reserves_both_groups_or_neither(self, short):
        """Two long requests: the second cannot be covered by the group
        ``short``; it holds nothing of either group while it waits,
        names that group, and is admitted when the first retires."""
        blocks = {"global": {"global": 14, "window": 16},
                  "window": {"global": 40, "window": 8}}[short]
        eng = _grouped_engine(num_blocks=blocks)
        try:
            eng.warmup()
            prompt = list(range(1, 41))
            outs = [None, None]

            def go(i):
                outs[i] = eng.generate(prompt, max_tokens=12,
                                       temperature=0.0)
            first = threading.Thread(target=go, args=(0,))
            first.start()
            assert _wait(lambda: eng._slots.active_count == 1)
            second = threading.Thread(target=go, args=(1,))
            second.start()
            assert _wait(lambda: eng._held is not None
                         or outs[0] is not None)
            if outs[0] is None:
                assert eng._held_group == short
                used = [g.allocator.used_count for g in eng._groups]
                assert used == [13, self.RING]    # the first's alone
            first.join(60)
            second.join(60)
            assert outs[0]["tokens"] == outs[1]["tokens"]
            sch = eng.stats()["scheduler"]
            assert sch["admit_blocked_on"][short] > 0
            other = "window" if short == "global" else "global"
            assert sch["admit_blocked_on"][other] == 0
            assert sch["head_blocked_s"]["blocks"] > 0
            for g in eng._groups:
                assert g.allocator.used_count == 0
        finally:
            eng.stop()

    def test_recovery_re_prefills_both_groups(self):
        from deeplearning4j_tpu.faults import FaultInjector
        want_eng = _grouped_engine()
        try:
            want = want_eng.generate(list(range(1, 31)), max_tokens=10,
                                     temperature=0.0)["tokens"]
        finally:
            want_eng.stop()
        inj = FaultInjector(plan={"device_step": [4]},
                            corrupting=("device_step",))
        eng = _grouped_engine(fault_injector=inj)
        try:
            got = eng.generate(list(range(1, 31)), max_tokens=10,
                               temperature=0.0)["tokens"]
            assert got == want
            st = eng.stats()
            assert st["faults"]["recoveries"] >= 1
            for g in eng._groups:
                assert g.allocator.used_count == 0
                assert (g.tables == NULL_BLOCK).all()
        finally:
            eng.stop()

    @pytest.mark.parametrize("kw,why", [
        (dict(cache="slots"), "paged backend only"),
        (dict(speculation_k=2), "speculation_k"),
        (dict(offload_host_bytes=1 << 20), "offload_host_bytes"),
        (dict(num_blocks={"window": 9, "nobody": 4}), "names no cache group"),
    ])
    def test_what_cannot_carry_a_ring_is_refused_at_construction(
            self, kw, why):
        with pytest.raises(ValueError, match=why):
            _grouped_engine(**kw)

    def test_prefix_sharing_is_off_and_a_session_is_a_client_error(self):
        from deeplearning4j_tpu.serving.engine import ClientError
        eng = _grouped_engine(enable_prefix_sharing=True)
        try:
            assert not eng.enable_prefix_sharing
            with pytest.raises(ClientError, match="cache groups"):
                eng.generate([1, 2, 3], max_tokens=2, session_id="s")
            a = eng.generate(list(range(1, 20)), max_tokens=3,
                             temperature=0.0)
            b = eng.generate(list(range(1, 20)), max_tokens=3,
                             temperature=0.0)
            assert a["tokens"] == b["tokens"]
            assert eng.stats()["paged"]["prefix_cache"]["prefix_hits"] == 0
        finally:
            eng.stop()

    def test_a_one_group_models_stats_keys_are_unchanged(self, paged_engine):
        st = paged_engine.stats()
        assert "groups" not in st["paged"]
        assert set(st["scheduler"]) == {
            "loop_s", "iterations", "phase_s", "phase_n", "head_blocked_s",
            "kv_live_token_steps", "kv_blocks_attended",
            "kv_blocks_spanned", "slowest"}
        assert paged_engine._groups == []
        with pytest.raises(ValueError, match="declares no cache groups"):
            GenerationEngine(_lm(), cache="paged", block_size=8,
                             num_blocks={"global": 9})

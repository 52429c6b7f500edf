"""The prefill chunk against the pool (PR 33): its write by blocks
(``kv_pool_set_span``, held to the row-by-row ``kv_pool_set``) and its
attention (``paged_prefill_attention``: the table's span gathered and
attended densely, held to a plain float64 sum over the sequence's own
keys): every head grouping, pool type, start and table bucket the engine
meets, stale memory that is not finite, and greedy tokens through the
engine with the chunk written by rows instead.
``tests/test_chip_bringup.py`` compiles both for a v5e at both cells'
widths."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels.kv_quant import QuantArray, quantize_rows
from deeplearning4j_tpu.kernels.paged_attention import (
    fuse_kv, kv_pool_set, kv_pool_set_span, paged_prefill_attention,
    split_kv)
from deeplearning4j_tpu.serving import GenerationEngine, PagedKVCache
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

PA = importlib.import_module("deeplearning4j_tpu.kernels.paged_attention")
_DT = {"f32": jnp.float32, "bf16": jnp.bfloat16}


# -- the chunk's write ---------------------------------------------------------
#: name -> (N, H, Bs, D, C, table entries, of them allocated, p0)
SPANS = {
    "whole_blocks": (9, 3, 4, 8, 8, 2, 2, 0),
    "mid_block_start": (9, 3, 4, 8, 6, 3, 3, 3),
    "mid_block_start_and_end": (9, 3, 4, 8, 8, 4, 4, 5),
    "a_verify_span_inside_one_block": (9, 3, 4, 8, 2, 4, 2, 5),
    "a_verify_span_across_two_blocks": (9, 3, 4, 8, 2, 4, 2, 3),
    "padding_onto_null_padded_entries": (9, 3, 4, 8, 8, 4, 2, 4),
    "padding_past_the_table": (9, 3, 4, 8, 8, 2, 2, 4),
    "second_chunk_block_16": (40, 2, 16, 8, 32, 8, 5, 37),
}


@pytest.mark.parametrize("dt", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("case", list(SPANS))
def test_a_spans_write_by_blocks_equals_the_write_by_rows(case, dt):
    """Every block but the null one holds, bit for bit, what
    ``kv_pool_set`` leaves at ``(table[j // Bs], :, j % Bs)``: the rows
    written, everything else as it was (an int8 pool's scales with its
    values). Rows on NULL-padded entries and past the table go to the
    null block."""
    N, H, Bs, D, C, B, owned, p0 = SPANS[case]
    (pool,) = PagedKVCache([(H, Bs, D)], N, kv_dtype=dt).pools
    rs = np.random.RandomState(3)
    if dt == "int8":        # what a previous occupant left
        pool = QuantArray(
            jnp.asarray(rs.randint(-99, 99, pool.q.shape), jnp.int8),
            jnp.asarray(rs.rand(*pool.scale.shape), jnp.float32))
    else:
        pool = jnp.asarray(rs.randn(*pool.shape), pool.dtype)
    tbl = np.zeros(B, np.int32)
    tbl[:owned] = rs.permutation(np.arange(1, N))[:owned]
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    k = jax.random.normal(ks[0], (C, H, D))
    v = jax.random.normal(ks[1], (C, H, D))
    g = p0 + np.arange(C)
    blk = np.where(g // Bs < B, tbl[np.minimum(g // Bs, B - 1)], 0)
    want = jax.jit(kv_pool_set)(
        pool, (jnp.asarray(blk)[:, None], jnp.arange(H)[None, :],
               jnp.asarray(g % Bs)[:, None]), k, v)
    got = jax.jit(kv_pool_set_span)(pool, jnp.asarray(tbl), jnp.int32(p0),
                                    k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32)[1:],
                                      np.asarray(b, np.float32)[1:])


def test_a_spans_write_is_one_update_a_block_not_one_a_row():
    """The lowered scatter takes ``C / Bs + 1`` indices whose updates
    are whole blocks: a scatter a row costs a v5e ~70 ns for each of
    ``C * H`` rows (PERF.md section 6, PR 33)."""
    N, H, Bs, D, C, B = 40, 5, 16, 64, 256, 32
    pool = jnp.zeros((N, H, Bs, 2 * D), jnp.float32)
    rows = jnp.zeros((C, H, D), jnp.float32)
    jaxpr = jax.make_jaxpr(kv_pool_set_span)(
        pool, jnp.zeros(B, jnp.int32), jnp.int32(5), rows, rows)
    (scatter,) = [e for e in jaxpr.jaxpr.eqns
                  if e.primitive.name.startswith("scatter")]
    assert scatter.invars[2].aval.shape == (C // Bs + 1, H, Bs, 2 * D)


# -- the chunk's attention -------------------------------------------------------
def _case(Hq, Hkv, D, Bs, C, B, p0, dt, N=48, seed=0):
    """Queries, a pool and a table bucket ``B`` for a chunk of ``C``
    rows at ``p0``: the sequence owns the blocks its ``p0 + C``
    positions need, scattered over the pool; the rest of the bucket is
    NULL-padded. NaN is planted where nothing may be read: the null
    block, and the value rows past ``p0 + C`` of the last block (a
    previous occupant's leavings)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (C, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (N, Hkv, Bs, D), jnp.float32)
    v = jax.random.normal(ks[2], (N, Hkv, Bs, D), jnp.float32)
    need = -(-(p0 + C) // Bs)
    tbl = np.zeros(B, np.int32)
    tbl[:need] = np.random.RandomState(seed).permutation(
        np.arange(1, N))[:need]
    k, v = k.at[0].set(jnp.nan), v.at[0].set(jnp.nan)
    tail = (p0 + C) % Bs
    if tail:
        v = v.at[tbl[need - 1], :, tail:].set(jnp.nan)
    cast = quantize_rows if dt == "int8" else \
        (lambda x: x.astype(_DT[dt]))
    return q, fuse_kv(cast(k), cast(v)), tbl


def _plain(q, pool, tbl, p0):
    """Row ``c`` over the keys ``j <= p0 + c`` of its own sequence, a
    position at a time out of the pool as stored, in float64."""
    if isinstance(pool, QuantArray):     # sidecar [N, 2, H, Bs]
        k, v = split_kv(np.asarray(pool.q, np.float64))
        k = k * np.asarray(pool.scale)[:, 0, :, :, None]
        v = v * np.asarray(pool.scale)[:, 1, :, :, None]
    else:
        k, v = split_kv(np.asarray(pool, np.float64))
    q = np.asarray(q, np.float64)
    C, Hq, D = q.shape
    Bs, g = k.shape[2], Hq // k.shape[1]
    out = np.zeros((C, Hq, D))
    for c in range(C):
        js = np.arange(p0 + c + 1)
        kk = k[tbl[js // Bs], :, js % Bs]                 # [T, Hkv, D]
        vv = v[tbl[js // Bs], :, js % Bs]
        for h in range(Hq):
            s = kk[:, h // g] @ q[c, h] / np.sqrt(D)
            w = np.exp(s - s.max())
            out[c, h] = (w / w.sum()) @ vv[:, h // g]
    return out


#: name -> (Hq, Hkv, D, Bs, C, table bucket, p0)
CASES = {
    "first_chunk": (4, 4, 16, 8, 16, 2, 0),
    "first_chunk_in_a_wider_bucket": (4, 4, 16, 8, 16, 4, 0),
    "second_chunk": (4, 4, 16, 8, 16, 4, 16),
    "second_chunk_in_a_wider_bucket": (4, 4, 16, 8, 16, 8, 16),
    "shared_prefix_start_mid_block": (4, 4, 16, 8, 16, 8, 11),
    "a_verify_span": (4, 4, 16, 8, 3, 4, 13),
    "grouped_32_over_8": (32, 8, 16, 8, 16, 4, 0),
    "grouped_32_over_8_second_chunk_mid_block": (32, 8, 16, 8, 16, 8, 19),
    "the_cells_block_and_head_size": (2, 2, 64, 16, 32, 16, 27),
}
#: against float64: f32's sums; bf16 rounds the queries, the stored rows
#: and the probabilities; int8 besides rounds the queries against rows
#: the oracle reads dequantized
TOL = {"f32": 1e-5, "bf16": 3e-2, "int8": 3e-2}


@pytest.mark.parametrize("case,dt", [
    (c, dt) for c in CASES for dt in ("f32", "bf16", "int8")
    # XLA's CPU backend has no bf16 x bf16 = f32 dot for the form a
    # query group is mapped over its panel in
    if dt == "f32" or CASES[c][0] == CASES[c][1]])
def test_a_chunks_attention_is_the_plain_sum_over_its_own_keys(case, dt):
    """Every row equals the float64 sum over the keys at or below it,
    read through the table, whatever lies elsewhere in the bucket (NaN
    in the null block and in the stale tail of the last block)."""
    Hq, Hkv, D, Bs, C, B, p0 = CASES[case]
    q, pool, tbl = _case(Hq, Hkv, D, Bs, C, B, p0, dt)
    got = np.asarray(jax.jit(paged_prefill_attention)(
        q, pool, jnp.asarray(tbl), jnp.int32(p0)))
    assert got.shape == (C, Hq, D) and got.dtype == np.float32
    np.testing.assert_allclose(got, _plain(q, pool, tbl, p0),
                               atol=TOL[dt], rtol=0)


# -- through the engine ------------------------------------------------------
def by_rows(pool, block_table, p0, k, v):
    """``kv_pool_set_span``'s contract through the row-by-row scatter a
    chunk used before PR 33."""
    C, H = k.shape[:2]
    Bs = (pool.q if isinstance(pool, QuantArray) else pool).shape[2]
    g = p0 + jnp.arange(C)
    return kv_pool_set(pool, (block_table[g // Bs][:, None],
                              jnp.arange(H)[None, :], (g % Bs)[:, None]),
                       k, v)


@pytest.fixture
def chunk_written_by_rows(monkeypatch):
    """Every chunk program traced from here on writes its K and V a row
    at a time."""
    calls = []

    def forced(pool, block_table, p0, k, v):
        calls.append(k.shape)
        return by_rows(pool, block_table, p0, k, v)
    monkeypatch.setattr(PA, "kv_pool_set_span", forced)
    return calls


def _engine(lm, **kw):
    eng = GenerationEngine(lm, num_slots=2, max_queue=16,
                           min_prompt_bucket=4, cache="paged",
                           block_size=8, prefill_chunk_tokens=8, **kw)
    eng.warmup()
    return eng


#: prompts of one chunk, of two (the second cut short), and of two
#: whole chunks
PROMPTS = {"one_chunk": [5, 9, 2], "two_chunks": list(range(3, 16)),
           "two_whole_chunks": list(range(20, 36))}


@pytest.fixture(scope="module")
def tiny_lm():
    return CausalTransformerLM(vocab_size=64, d_model=32, n_layers=2,
                               n_heads=4, max_seq_len=48, seed=0,
                               implementation="plain").init()


@pytest.fixture(scope="module")
def tokens(tiny_lm):
    eng = _engine(tiny_lm)
    try:
        out = {k: eng.generate(p, max_tokens=6)["tokens"]
               for k, p in PROMPTS.items()}
        out["shared"] = eng.generate(PROMPTS["two_whole_chunks"] + [7, 1],
                                     max_tokens=6)["tokens"]
    finally:
        eng.stop()
    return out


@pytest.mark.parametrize("prompt", list(PROMPTS))
def test_greedy_tokens_equal_those_of_a_chunk_written_by_rows(
        tiny_lm, tokens, chunk_written_by_rows, prompt):
    eng = _engine(tiny_lm)
    try:
        got = eng.generate(PROMPTS[prompt], max_tokens=6)["tokens"]
    finally:
        eng.stop()
    assert chunk_written_by_rows, "no chunk went through the entry point"
    assert got == tokens[prompt]


def test_a_prefix_shared_second_request_starts_past_the_shared_blocks(
        tiny_lm, tokens, chunk_written_by_rows):
    """The second request finds the first's two whole blocks in the
    prefix index and prefills only its tail, at ``p0`` 16: the write by
    blocks leaves the shared blocks as the first request left them."""
    eng = _engine(tiny_lm)
    try:
        first = eng.generate(PROMPTS["two_whole_chunks"],
                             max_tokens=6)["tokens"]
        hits = eng.stats()["paged"]["prefix_cache"]["prefix_hits"]
        got = eng.generate(PROMPTS["two_whole_chunks"] + [7, 1],
                           max_tokens=6)["tokens"]
        assert eng.stats()["paged"]["prefix_cache"]["prefix_hits"] > hits
    finally:
        eng.stop()
    assert first == tokens["two_whole_chunks"]
    assert got == tokens["shared"]


# -- a window, a ring and the tiled kernels (PR 35) -----------------------------
def _ring_case(Hq, Hkv, D, Bs, R, total, dt, seed=0, N=40):
    """A sequence of ``total`` positions written through a ring of ``R``
    entries (each position where ``(p // Bs) % R`` puts it, later laps
    over earlier ones), every other row of the pool NaN: the null block,
    blocks of nobody, and what the ring's entries hold past the newest
    position. Returns (pool, table, k [total, Hkv, D], v)."""
    rs = np.random.RandomState(seed)
    k = rs.randn(total, Hkv, D).astype(np.float32)
    v = rs.randn(total, Hkv, D).astype(np.float32)
    tbl = rs.permutation(np.arange(1, N))[:R].astype(np.int32)
    pool = np.full((N, Hkv, Bs, 2 * D), np.nan, np.float32)
    for p in range(total):
        pool[tbl[(p // Bs) % R], :, p % Bs] = np.concatenate(
            [k[p], v[p]], -1)
    pool = jnp.asarray(pool, _DT[dt])
    back = np.asarray(pool.astype(jnp.float32))
    for p in range(max(0, total - R * Bs), total):   # as stored
        row = back[tbl[(p // Bs) % R], :, p % Bs]
        k[p], v[p] = row[:, :D], row[:, D:]
    return pool, tbl, k, v


def _plain_rows(q, k, v, positions, window):
    """float64 attention of query rows ``q [n, Hq, D]`` at
    ``positions`` over the sequence's own keys ``j <= pos`` (``j > pos
    - window``)."""
    n, Hq, D = q.shape
    g = Hq // k.shape[1]
    out = np.zeros((n, Hq, D))
    for i, pos in enumerate(positions):
        lo = 0 if window is None else max(0, pos - window + 1)
        for h in range(Hq):
            kk = k[lo:pos + 1, h // g].astype(np.float64)
            s = kk @ q[i, h].astype(np.float64) / np.sqrt(D)
            w = np.exp(s - s.max())
            out[i, h] = (w / w.sum()) @ v[lo:pos + 1, h // g]
    return out


#: name -> (Hq, Hkv, D, Bs, ring entries, window)
RINGS = {
    "g1_head64": (2, 2, 64, 4, 5, 8),
    "g4_head64": (8, 2, 64, 4, 5, 8),
    "g7_head128": (14, 2, 128, 4, 5, 8),
    "g7_head128_block8": (7, 1, 128, 8, 4, 16),
    "window_longer_than_any_context": (4, 2, 128, 4, 12, 40),
}
_TOL = {"f32": 2e-5, "bf16": 3e-2}


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("dt", ["f32", "bf16"])
@pytest.mark.parametrize("case", list(RINGS))
def test_windowed_decode_reads_the_last_window_through_the_ring(
        case, dt, impl):
    """Lanes at lengths below, at and several laps past the window,
    each with a ring of its own; every row of the pool outside a lane's
    window is NaN. f32: 2e-5 (f32 products and softmax against float64);
    bf16: 3e-2 (the pool's rounding is in both sides' keys; the
    kernel's bf16 probabilities and query rows are what is left)."""
    Hq, Hkv, D, Bs, R, W = RINGS[case]
    lengths = [1, W - 1, W, W + 3, 2 * R * Bs + 1, 37]
    rs = np.random.RandomState(1)
    pools, tbls, want = [], [], []
    q = rs.randn(len(lengths), Hq, D).astype(np.float32)
    N = 1 + R * len(lengths)
    pool = np.full((N, Hkv, Bs, 2 * D), np.nan, np.float32)
    pool = jnp.asarray(pool, _DT[dt])
    for s, n in enumerate(lengths):
        p1, t1, k, v = _ring_case(Hq, Hkv, D, Bs, R, n, dt, seed=s, N=R + 1)
        t1 = t1 + s * R             # this lane's own blocks
        pool = pool.at[t1].set(p1[t1 - s * R])
        tbls.append(t1)
        want.append(_plain_rows(q[s:s + 1], k, v, [n - 1], W)[0])
    got = PA.paged_attention(
        jnp.asarray(q), pool, jnp.asarray(np.stack(tbls)),
        jnp.asarray(lengths, jnp.int32), impl=impl, window=W,
        **({"interpret": True} if impl == "pallas" else {}))
    np.testing.assert_allclose(np.asarray(got), np.stack(want),
                               atol=_TOL[dt], rtol=_TOL[dt])


@pytest.mark.parametrize("dt", ["f32", "bf16"])
@pytest.mark.parametrize("g,D", [(1, 128), (4, 128), (7, 128)])
def test_wide_heads_decode_on_the_mxu_form_without_a_window(g, D, dt):
    """Heads of 128 lanes take ``_paged_kernel_wide`` with or without a
    window: the plain table, lengths bounded, NaN past them."""
    Hkv, Bs, B = 2, 4, 6
    lengths = [1, 4, 9, 23]
    rs = np.random.RandomState(2)
    q = rs.randn(len(lengths), g * Hkv, D).astype(np.float32)
    pool = jnp.full((1 + B * len(lengths), Hkv, Bs, 2 * D), np.nan,
                    _DT[dt])
    tbls, want = [], []
    for s, n in enumerate(lengths):
        p1, t1, k, v = _ring_case(g * Hkv, Hkv, D, Bs, B, n, dt, seed=s,
                                  N=B + 1)
        t1 = t1 + s * B
        pool = pool.at[t1].set(p1[t1 - s * B])
        tbls.append(t1)
        want.append(_plain_rows(q[s:s + 1], k, v, [n - 1], None)[0])
    got = PA.paged_attention_pallas(
        jnp.asarray(q), pool, jnp.asarray(np.stack(tbls)),
        jnp.asarray(lengths, jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.stack(want),
                               atol=_TOL[dt], rtol=_TOL[dt])


#: name -> (Hq, Hkv, D, Bs, table or ring entries, window, C, p0, chunk_len)
CHUNKS = {
    "first_chunk": (4, 2, 64, 4, 8, None, 8, 0, 8),
    "g7_third_chunk_padded": (7, 1, 128, 4, 8, None, 8, 16, 5),
    "g4_unaligned_start": (8, 2, 128, 4, 16, None, 16, 21, 16),
    "ring_first_chunk": (4, 2, 64, 4, 5, 8, 8, 0, 8),
    "ring_wraps_g7": (14, 2, 128, 4, 5, 8, 8, 40, 8),
    "ring_wraps_padded_unaligned": (7, 1, 128, 4, 5, 8, 8, 27, 3),
    "ring_window_inside_the_chunk": (2, 2, 64, 4, 9, 4, 16, 32, 11),
    "ring_window_longer_than_the_context": (4, 2, 128, 4, 13, 40, 8, 8, 8),
}


@pytest.mark.parametrize("case,dt,impl", [
    (c, dt, impl) for c in CHUNKS for dt in ("f32", "bf16")
    for impl in ("pallas", "xla")
    # XLA's CPU backend has no bf16 x bf16 = f32 dot for the form a
    # query group is mapped over its panel in
    if dt == "f32" or impl == "pallas" or CHUNKS[c][0] == CHUNKS[c][1]])
def test_a_chunk_attends_its_own_keys_tiled_or_dense(case, dt, impl):
    """The tiled kernel (interpret mode) and XLA's span path against
    the float64 sum, with and without a window: every row of the pool
    that is not one of the sequence's first ``p0 + chunk_len``
    positions (as the ring holds them) is NaN. Rows of padding are not
    compared. Tolerances as for decode."""
    Hq, Hkv, D, Bs, B, W, C, p0, clen = CHUNKS[case]
    total = p0 + clen
    R = B if W is not None else total // Bs + 2
    pool, tbl, k, v = _ring_case(Hq, Hkv, D, Bs, R, total, dt, seed=5)
    if W is None:               # a plain table, NULL-padded to B entries
        tbl = np.concatenate([tbl[:-(-total // Bs)],
                              np.zeros(B, np.int32)])[:B]
    q = np.random.RandomState(6).randn(C, Hq, D).astype(np.float32)
    want = _plain_rows(q[:clen], k, v, p0 + np.arange(clen), W)
    if impl == "pallas":
        got = PA.paged_prefill_attention_pallas(
            jnp.asarray(q), pool, jnp.asarray(tbl), p0, clen, window=W,
            interpret=True)
    else:
        # the chunk's padded rows were written too (finite junk)
        pad = np.arange(total, p0 + C)
        ent = (pad // Bs) % R if W is not None else pad // Bs
        pool = pool.at[tbl[ent], :, pad % Bs].set(0.5)
        got = PA.paged_prefill_attention(
            jnp.asarray(q), pool, jnp.asarray(tbl), p0, clen, window=W)
    np.testing.assert_allclose(np.asarray(got)[:clen], want,
                               atol=_TOL[dt], rtol=_TOL[dt])


def test_a_rings_span_write_lands_where_the_ring_puts_each_position():
    """``kv_pool_set_span(ring=True)``: position p in entry ``(p // Bs)
    % R``, other rows as they were."""
    H, Bs, D, R, C, p0 = 2, 4, 8, 5, 8, 14
    rs = np.random.RandomState(8)
    pool = jnp.asarray(rs.randn(9, H, Bs, 2 * D), jnp.float32)
    tbl = np.array([3, 7, 1, 5, 8], np.int32)
    k = jnp.asarray(rs.randn(C, H, D), jnp.float32)
    v = jnp.asarray(rs.randn(C, H, D), jnp.float32)
    got = np.asarray(kv_pool_set_span(pool, jnp.asarray(tbl), p0, k, v,
                                      ring=True))
    want = np.asarray(pool).copy()
    for c in range(C):
        p = p0 + c
        want[tbl[(p // Bs) % R], :, p % Bs] = np.concatenate(
            [np.asarray(k[c]), np.asarray(v[c])], -1)
    np.testing.assert_array_equal(got, want)

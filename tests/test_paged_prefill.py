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

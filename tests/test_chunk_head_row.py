"""A prefill chunk's head runs over the one row it samples (PR 36), for
the three served classes at tiny sizes, float32:

- ``forward_prefill_chunk(..., last_only=True)`` returns ``[1, V]``
  logits equal to row ``chunk_len - 1`` of the all-rows form, and the
  pools, the slot state and the counters of the two forms are the same
  arrays (a full chunk and a partial one, both past the first chunk);
- the engine's chunk program, lowered with the arguments the engine
  compiles it with, holds no ``[C, V]`` value; the all-rows form does;
- the poison guard keeps its reach: a non-finite value in a live row of
  the final hidden state that is NOT the sampled row quarantines that
  request alone, the same value in a row past ``chunk_len`` nobody.

The chunk bucket is wider than ``HEAD_ROWS``, the rows the sampled
row's head is computed over (``nn/functional.py``).
"""
import importlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.faults import PoisonRequestError
from deeplearning4j_tpu.nn.functional import HEAD_ROWS
from deeplearning4j_tpu.serving import PagedKVCache, generation
from deeplearning4j_tpu.serving.generation import GenerationEngine

CLASSES = ["transformer_lm", "lfm2_moe", "smallthinker"]
BS, C = 4, 16                   # block size, chunk bucket
#: the vocabularies are chosen so that no other value of a chunk
#: program is ``[C, V]``-shaped
VOCAB = {"transformer_lm": 61, "lfm2_moe": 67, "smallthinker": 97}
# float32 on both sides; one row's product against the same row of the
# chunk's (a matrix-vector against a matrix-matrix sum order)
LOGIT_TOL = 2e-6


def build(kind):
    """A tiny served model of the class in ``zoo/<kind>.py`` and the
    engine options it is served with."""
    engine = dict(num_slots=3, max_seq_len=64, prompt_buckets=[C],
                  cache="paged", block_size=BS, prefill_chunk_tokens=C)
    if kind == "transformer_lm":
        from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM
        lm = CausalTransformerLM(
            vocab_size=VOCAB[kind], d_model=32, n_layers=2, n_heads=4,
            max_seq_len=64, seed=0, implementation="plain")
    elif kind == "lfm2_moe":
        from deeplearning4j_tpu.zoo.lfm2_moe import Lfm2MoeLM
        lm = Lfm2MoeLM(
            vocab_size=VOCAB[kind], hidden_size=64, intermediate_size=160,
            moe_intermediate_size=48, num_hidden_layers=5,
            num_dense_layers=1,
            layer_types=["conv", "full_attention", "conv", "conv", "conv"],
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, conv_L_cache=3, norm_eps=1e-5,
            rope_theta=1e6, norm_topk_prob=True, use_expert_bias=True,
            routed_scaling_factor=1, max_position_embeddings=128,
            conv_bias=False, dtype="float32")
        engine["num_blocks"] = 49
    else:
        from deeplearning4j_tpu.zoo.smallthinker import SmallThinkerLM
        lm = SmallThinkerLM(
            vocab_size=VOCAB[kind], hidden_size=32, head_dim=8,
            num_hidden_layers=4, num_attention_heads=28,
            num_key_value_heads=4, moe_ffn_hidden_size=16,
            moe_num_primary_experts=8, moe_num_active_primary_experts=3,
            sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
            sliding_window_size=8, rope_theta=1.5e6, rms_norm_eps=1e-6,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            tie_word_embeddings=False, max_position_embeddings=64,
            dtype="float32")
    return lm.init(), engine


# -- the two forms of the model's method -----------------------------------
def by_hand(kind, lm):
    """Pools, tables and the keywords (state, slot) of one request's
    chunks, as the engine's chunk program hands them to the class."""
    need = 3 * C // BS                  # three chunks of positions
    table = np.zeros(need + 4, np.int32)
    table[:need] = 1 + np.random.RandomState(0).permutation(need)
    if kind == "smallthinker":
        ring = (8 + C) // BS + 1        # blocks_for(window + C) + 1
        n = [0] * lm.n_layers
        for g in lm.cache_groups():
            for i in g["layers"]:
                n[i] = need + 2 if g["window"] is None else ring + 2
        pools = PagedKVCache(lm.cache_shapes(BS), n).pools
        tables = (jnp.asarray(table),
                  jnp.asarray(1 + np.arange(ring)[::-1], jnp.int32))
        return pools, tables, {"state": [], "slot": jnp.int32(1)}
    pools = PagedKVCache(lm.cache_shapes(BS), need + 2).pools
    if kind == "lfm2_moe":
        state = [jnp.zeros(s, d) for s, d in lm.slot_state_shapes(3)]
        return pools, jnp.asarray(table), {"state": state,
                                           "slot": jnp.int32(1)}
    return pools, jnp.asarray(table), {"state": ()}


@pytest.mark.parametrize("chunk_len", [C, 11, 3],
                         ids=["full", "partial", "under_head_rows"])
@pytest.mark.parametrize("kind", CLASSES)
def test_the_one_row_form_is_the_all_rows_forms_sampled_row(kind, chunk_len):
    lm, _ = build(kind)
    pools, table, kw = by_hand(kind, lm)
    toks = np.random.default_rng(5).integers(0, VOCAB[kind], (3, 1, C))
    p0 = 0
    for t in toks[:2]:                  # the request's earlier chunks
        out = lm.forward_prefill_chunk(
            lm._params, jnp.asarray(t, jnp.int32), jnp.int32(p0),
            jnp.int32(C), pools, table, **kw)
        pools, p0 = out[1], p0 + C
        if "slot" in kw:
            kw["state"] = out[2]
    assert p0 > 0
    rows, one = [lm.forward_prefill_chunk(
        lm._params, jnp.asarray(toks[2], jnp.int32), jnp.int32(p0),
        jnp.int32(chunk_len), pools, table, **kw, **form)
        for form in ({}, {"last_only": True})]
    assert rows[0].shape == (C, VOCAB[kind])
    assert one[0].shape == (1, VOCAB[kind])
    want = np.asarray(rows[0])[chunk_len - 1]
    assert np.isfinite(want).all() and np.ptp(want) > 0
    assert np.abs(np.asarray(one[0])[0] - want).max() <= LOGIT_TOL
    assert int(np.argmax(one[0])) == int(np.argmax(want))
    # what the chunk leaves behind does not know which form ran: pools,
    # the slot state, the counters
    a, b = (jax.tree_util.tree_leaves(o[1:]) for o in (rows, one))
    assert len(a) == len(b) >= len(pools)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)


# -- the engine's program ----------------------------------------------------
def _lowered_chunk(monkeypatch, eng):
    """StableHLO of the engine's chunk program for (chunk bucket C,
    table bucket 8) with the arguments and donation the engine compiles
    it with, and those arguments."""
    seen = []
    real = generation.compile_memoized

    def capture(fn, args, donate):
        seen.append((jax.jit(fn, donate_argnums=tuple(donate))
                     .lower(*args).as_text(), args))
        return real(fn, args, donate)

    monkeypatch.setattr(generation, "compile_memoized", capture)
    eng._get_chunk_exe(C, 8)
    (got,) = seen
    return got


@pytest.mark.parametrize("kind", CLASSES)
def test_the_engines_chunk_program_holds_no_chunk_by_vocab_value(
        monkeypatch, kind):
    lm, engine = build(kind)
    eng = GenerationEngine(lm, **engine)
    try:
        text, args = _lowered_chunk(monkeypatch, eng)
    finally:
        eng.stop()
    shape = "tensor<%dx%dx" % (C, VOCAB[kind])
    assert HEAD_ROWS < C
    assert "tensor<1x%dx" % VOCAB[kind] in text
    assert shape not in text
    # the same arguments through the form that keeps every row
    def all_rows(params, pools, state, tokens, p0, clen, table, slot):
        kw = {"slot": slot} if eng._extended else {}
        return lm.forward_prefill_chunk(params, tokens, p0, clen, pools,
                                        table, state=state, **kw)

    rows = jax.jit(all_rows).lower(*args[:8]).as_text()
    assert shape in rows


# -- the guard ---------------------------------------------------------------
MARK = 5        # the prompt length whose chunk gets the planted value
PROMPTS = [np.random.default_rng([36, n]).integers(1, 60, n).tolist()
           for n in (3, MARK, 11, 26)]
assert [n % C for n in map(len, PROMPTS)].count(MARK) == 1


def _run_all(eng):
    out, errs = [None] * len(PROMPTS), [None] * len(PROMPTS)

    def go(i):
        try:
            out[i] = eng.generate(PROMPTS[i], max_tokens=6, temperature=0.0,
                                  timeout_ms=120_000)["tokens"]
        except Exception as e:  # noqa: BLE001 — recorded for asserts
            errs[i] = e
    ts = [threading.Thread(target=go, args=(i,)) for i in range(len(PROMPTS))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return out, errs


def _served(kind):
    lm, engine = build(kind)
    eng = GenerationEngine(lm, **engine)
    try:
        eng.warmup()
        out, errs = _run_all(eng)
        return out, errs, eng.metrics.quarantined
    finally:
        eng.stop()


@pytest.fixture(scope="module", params=CLASSES)
def healthy(request):
    out, errs, quarantined = _served(request.param)
    assert errs == [None] * len(PROMPTS) and quarantined == 0
    return request.param, out


@pytest.mark.parametrize("row,caught", [(1, True), (MARK + 1, False)],
                         ids=["live_row", "row_past_chunk_len"])
def test_a_non_finite_hidden_row_is_caught_only_where_it_is_live(
        monkeypatch, healthy, row, caught):
    """The value is planted in the final hidden state, after the last
    layer: it reaches no other row, and the sampled row (``MARK - 1``)
    and its logits stay finite. Both planted rows lie among the
    ``HEAD_ROWS`` rows the head runs over."""
    kind, base = healthy
    module = importlib.import_module("deeplearning4j_tpu.zoo." + kind)
    real = module.sampled_row_logits

    def planted(x, n_live, head):
        hit = (jnp.arange(x.shape[0]) == row) & (n_live == MARK)
        return real(jnp.where(hit[:, None], jnp.inf, x), n_live, head)

    monkeypatch.setattr(module, "sampled_row_logits", planted)
    out, errs, quarantined = _served(kind)
    marked = [len(p) for p in PROMPTS].index(MARK)
    if caught:
        assert isinstance(errs[marked], PoisonRequestError)
        assert quarantined == 1
        out[marked] = base[marked]
    else:
        assert errs[marked] is None and quarantined == 0
    assert [e for i, e in enumerate(errs) if i != marked] == [None] * 3
    assert out == base

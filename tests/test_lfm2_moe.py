"""The LFM2-MoE-shaped served class at a tiny size of the same pattern
(one dense conv layer, then attention, conv, conv, conv; 8 experts, 2 a
token; 2 KV heads for 4 query heads), float32, seeded random weights:

- the class against the benchmark's plain reference on logits, prefill
  in chunks of unequal length and then decode through the pool and the
  slot state, against the reference's full forward pass;
- the router's equations alone;
- the short convolution's state across a chunk boundary, across slot
  reuse with a NaN-poisoned slot, and after recompute-recovery;
- the grouped-query paged kernel (interpret mode) against XLA, and with
  one query head a KV head bit-equal to what PR 27's kernel gave;
- the expert kernel (interpret mode) against the plain sum;
- what the engine refuses for a model with slot state;
- zero compiles after warm-up, the ``moe.*`` and ``state.*`` counters
  in ``/stats`` and ``/metrics``;
- ``run.py --control 1`` on the tiny configuration ends
  ``correct: false``.
"""
import http.client
import json
import os
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (REPO, HERE, os.path.join(HERE, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmark import run  # noqa: E402
from deeplearning4j_tpu.faults import FaultInjector, TransientFault  # noqa: E402
from deeplearning4j_tpu.kernels.moe_experts import expert_ffn  # noqa: E402
from deeplearning4j_tpu.kernels.paged_attention import (  # noqa: E402
    fuse_kv, paged_attention_pallas, paged_attention_xla)
from deeplearning4j_tpu.nn.layers.moe import NORM_EPS, moe_ffn, route  # noqa: E402
from deeplearning4j_tpu.serving import InferenceServer, PagedKVCache  # noqa: E402
from deeplearning4j_tpu.serving.generation import GenerationEngine  # noqa: E402
from _obs_util import assert_exposition_parity, parse_prometheus  # noqa: E402

TINY = dict(
    vocab_size=257, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=48, num_hidden_layers=5, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_attention_heads=4, num_key_value_heads=2, num_experts=8,
    num_experts_per_tok=2, conv_L_cache=3, norm_eps=1e-5, rope_theta=1e6,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    max_position_embeddings=128, conv_bias=False, dtype="float32",
    embed_std=0.002)
SEED = 3
ENGINE = dict(num_slots=3, max_seq_len=64, prompt_buckets=[16],
              cache="paged", block_size=8, num_blocks=33,
              prefill_chunk_tokens=16)
# float32 on both sides, the same rounded weights; what differs is the
# order of the sums (a chunk's gathered panel against the full pass, the
# experts' sorted rows against gathered ones): a few float32 ulps of
# logits of magnitude ~0.1, after ~10 layers of ~100-term sums
LOGIT_TOL = 2e-6


@pytest.fixture(scope="module")
def ref():
    return run.load_module(REPO, "reference", "lfm2_moe")


@pytest.fixture(scope="module")
def lm(ref):
    served = run.load_module(REPO, "served", "lfm2_moe")
    return served.build({"model": TINY, "eos_id": None}, SEED, ref)


def reference_logits(ref, seq):
    with jax.default_matmul_precision("highest"):
        hid, emb = ref.final_hidden(TINY, SEED, [np.asarray(seq, np.int32)])
        return np.asarray(ref.head_logits(emb, hid[0][:len(seq)]))


def smallest_route_margin(ref, seq):
    """The smallest gap, over tokens and expert layers, between the
    last chosen and the first unchosen expert's selection score in the
    reference's pass: a choice that flips on a near tie is reported by
    this, not covered by a wider tolerance."""
    margins = []
    real_route = ref.route

    def spy(cfg, w, x):
        s = jax.nn.sigmoid(x @ w["W_g"].astype(jnp.float32)) \
            + w["expert_bias"]
        top = jnp.sort(s, -1)[:, ::-1]
        k = cfg["num_experts_per_tok"]
        margins.append(float((top[:, k - 1] - top[:, k])[:len(seq)].min()))
        return real_route(cfg, w, x)

    ref.route = spy
    try:
        with jax.disable_jit(), jax.default_matmul_precision("highest"):
            ref.final_hidden(TINY, SEED, [np.asarray(seq, np.int32)])
    finally:
        ref.route = real_route
    return min(margins)


def serve_by_hand(lm, seq, chunks, slot=1, slots=3, poison=True):
    """Logits of every position of ``seq``: prefill in ``chunks``
    ((valid, bucket) pairs) then one decode step a token, through the
    paged pools and the slot state, as the engine's programs call the
    two forwards."""
    Bs, N = 8, 20
    pools = PagedKVCache(lm.cache_shapes(Bs), N).pools
    fill = jnp.nan if poison else 0.0       # the slot's last occupant
    state = [jnp.full(shape, fill, dt)
             for shape, dt in lm.slot_state_shapes(slots)]
    table = np.zeros(8, np.int32)
    table[:6] = [3, 5, 7, 9, 11, 13]
    got, counters, p0 = [], [], 0
    for clen, bucket in chunks:
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :clen] = seq[p0:p0 + clen]
        lg, pools, state, cnt = lm.forward_prefill_chunk(
            lm._params, jnp.asarray(toks), jnp.int32(p0), jnp.int32(clen),
            pools, jnp.asarray(table), state=state, slot=jnp.int32(slot))
        got.append(np.asarray(lg)[:clen])
        counters.append(np.asarray(cnt))
        p0 += clen
    tables = np.zeros((slots, 8), np.int32)
    tables[slot] = table
    live = np.zeros(slots, bool)
    live[slot] = True
    for t in range(p0, len(seq)):
        toks = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        toks[slot], pos[slot] = seq[t], t
        lg, pools, state, cnt = lm.forward_decode_paged(
            lm._params, jnp.asarray(toks), jnp.asarray(pos), pools,
            jnp.asarray(tables), "xla", state=state, live=jnp.asarray(live))
        got.append(np.asarray(lg)[slot][None])
        counters.append(np.asarray(cnt))
    return np.concatenate(got, 0), counters, state


# -- the class against the reference ----------------------------------------
def test_chunks_then_decode_match_the_references_full_pass(ref, lm):
    seq = np.random.default_rng(0).integers(0, 257, 37).astype(np.int32)
    want = reference_logits(ref, seq)
    # chunks of 10, 5 and 13 tokens (buckets 16, 8, 16), 9 decode steps;
    # the slot's state starts as NaN: its last occupant's
    got, counters, state = serve_by_hand(lm, seq, ((10, 16), (5, 8),
                                                   (13, 16)))
    worst = np.abs(got - want).max()
    assert worst <= LOGIT_TOL, (
        f"logits differ by {worst} (scale {np.abs(want).max()}); the "
        f"smallest routing margin in the reference's pass is "
        f"{smallest_route_margin(ref, seq)}")
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # 4 expert layers, 2 experts a token; a chunk's padded rows route
    # nowhere, and a decode step's two empty lanes neither
    assert [int(c[0]) for c in counters[:3]] == [80, 40, 104]
    assert all(int(c[0]) == 8 and int(c[1]) == 8 for c in counters[3:])
    assert all(int(c[2:].sum()) == int(c[0]) for c in counters)
    # the other slots' state is untouched (still the poison)
    assert all(bool(jnp.isnan(s[0]).all() and jnp.isnan(s[2]).all()
                    and jnp.isfinite(s[1]).all()) for s in state)


def test_conv_state_hands_over_at_any_chunk_boundary(lm):
    """The same prompt cut at other boundaries gives the same logits:
    what a chunk hands the next one is the whole of the state."""
    seq = np.random.default_rng(1).integers(0, 257, 29).astype(np.int32)
    a, _, _ = serve_by_hand(lm, seq, ((16, 16), (13, 16)))
    b, _, _ = serve_by_hand(lm, seq, ((1, 8), (2, 8), (16, 16), (7, 8)))
    c, _, _ = serve_by_hand(lm, seq, ((9, 16),))     # then 20 decode steps
    assert np.abs(a - b).max() <= LOGIT_TOL
    assert np.abs(a - c).max() <= LOGIT_TOL


def test_the_reference_reads_the_weights_the_program_holds(ref, lm):
    emb, layers = ref.make_params(TINY, SEED)
    assert np.array_equal(np.asarray(emb["embed"]),
                          np.asarray(lm._params["embed"]))
    for w, mine in zip(layers, lm._params["layers"]):
        assert set(w) == set(mine)
        for k in w:
            assert np.array_equal(np.asarray(w[k], np.float32),
                                  np.asarray(mine[k], np.float32)), k
    bf = dict(TINY, dtype="bfloat16")
    w = ref.layer_weights(bf, ref.root_key(SEED), 1)
    assert w["Wq"].dtype == jnp.bfloat16 and w["W1"].dtype == jnp.bfloat16
    assert w["expert_bias"].dtype == jnp.float32


# -- the router alone ---------------------------------------------------------
def test_router_bias_selects_and_does_not_weigh():
    x = jnp.eye(4, dtype=jnp.float32)[:2] * 3.0            # two tokens
    w_gate = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0],
                          [0.0, 0.0, 0.0, 0.0, 0.0],
                          [0, 0, 0, 0, 0], [0, 0, 0, 0, 0]], jnp.float32)
    s = jax.nn.sigmoid(np.asarray(x @ w_gate))
    no_bias = jnp.zeros(5)
    e0, g0 = route(x, w_gate, no_bias, 2)
    assert sorted(e0[0].tolist()) == [0, 1]
    # a bias lifts expert 4 over expert 1 for token 0 ...
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 5.0])
    e1, g1 = route(x, w_gate, bias, 2)
    assert sorted(e1[0].tolist()) == [0, 4]
    # ... and the weights are the SCORES of the chosen, not score + bias
    chosen = s[0][np.asarray(e1[0])]
    assert np.allclose(np.asarray(g1[0]), chosen / (chosen.sum() + NORM_EPS),
                       rtol=1e-6)
    # the 1e-6: weights sum to sum / (sum + 1e-6), not to 1
    assert float(g1[0].sum()) == pytest.approx(
        chosen.sum() / (chosen.sum() + 1e-6), rel=1e-7)
    assert float(g1[0].sum()) < 1.0
    # token 1 scores 0.5 everywhere: the bias alone decides
    assert 4 in e1[1].tolist()
    # without renormalisation the weights are the scores themselves
    _, raw = route(x, w_gate, bias, 2, norm_topk_prob=False, scaling=2.0)
    assert np.allclose(np.asarray(raw[0]), 2.0 * chosen, rtol=1e-6)


def test_dead_lanes_route_nowhere_and_count_nowhere():
    rng = np.random.default_rng(2)
    E, D, F, T, k = 8, 32, 16, 6, 2
    p = {"W_g": jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
         "expert_bias": jnp.zeros(E),
         "W1": jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, jnp.float32),
         "W3": jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, jnp.float32),
         "W2": jnp.asarray(rng.normal(size=(E, F, D)) * 0.1, jnp.float32)}
    x = rng.normal(size=(T, D)).astype(np.float32)
    live = np.array([True, False, True, True, False, True])
    poisoned = x.copy()
    poisoned[~live] = np.nan          # what a dead lane holds is anything
    experts, g = route(jnp.asarray(poisoned), p["W_g"], p["expert_bias"], k,
                       jnp.asarray(live))
    assert (np.asarray(experts)[~live] == E).all()
    assert (np.asarray(g)[~live] == 0).all()
    y, counts = moe_ffn(p, jnp.asarray(poisoned), k, jnp.asarray(live))
    y_all, _ = moe_ffn(p, jnp.asarray(x), k)
    assert int(counts["pairs"]) == k * live.sum()
    assert int(counts["expert_tokens"].sum()) == k * live.sum()
    assert int(counts["experts_touched"]) == \
        int((np.asarray(counts["expert_tokens"]) > 0).sum())
    assert (np.asarray(y)[~live] == 0).all()
    assert np.allclose(np.asarray(y)[live], np.asarray(y_all)[live],
                       atol=1e-6)


# -- kernels --------------------------------------------------------------------
def _wave(shape, f):
    n = int(np.prod(shape))
    return np.sin(np.arange(n, dtype=np.float64) * f).reshape(shape).astype(
        np.float32)


def test_paged_kernel_with_one_query_head_a_kv_head_is_bit_equal_to_pr27s():
    """``tests/fixtures/paged_kernel_pr27_output.npy`` is what the
    kernel of PR 27 (commit d10e9b6) returned for these inputs in
    interpret mode; the grouped body with g = 1 returns the same bits."""
    S, H, D, N, Bs = 3, 3, 64, 7, 8
    q, k, v = (_wave((S, H, D), 0.37), _wave((N, H, Bs, D), 0.11),
               _wave((N, H, Bs, D), 0.23))
    tbl = np.array([[1, 4, 2, 0, 0], [3, 5, 6, 1, 2], [2, 0, 0, 0, 0]],
                   np.int32)
    lens = np.array([19, 40, 0], np.int32)
    out = np.asarray(paged_attention_pallas(
        jnp.asarray(q), fuse_kv(jnp.asarray(k), jnp.asarray(v)),
        jnp.asarray(tbl), jnp.asarray(lens), interpret=True))
    want = np.load(os.path.join(HERE, "fixtures",
                                "paged_kernel_pr27_output.npy"))
    assert np.array_equal(out, want)


@pytest.mark.parametrize("dt,tol", [(jnp.float32, 2e-6),
                                    (jnp.bfloat16, 5e-3)])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (32, 8), (6, 1)])
def test_grouped_query_paged_kernel_matches_xla(hq, hkv, dt, tol):
    rng = np.random.default_rng(hq)
    S, D, N, Bs, B = 5, 64, 24, 16, 6
    q = jnp.asarray(rng.normal(size=(S, hq, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, hkv, Bs, D)), dt)
    vp = jnp.asarray(rng.normal(size=(N, hkv, Bs, D)), dt)
    tbl = jnp.asarray(rng.integers(1, N, (S, B)), jnp.int32)
    lens = jnp.asarray([0, 1, 17, 64, 96], jnp.int32)
    pool = fuse_kv(kp, vp)
    got = paged_attention_pallas(q, pool, tbl, lens, interpret=True)
    want = paged_attention_xla(q, pool, tbl, lens)
    assert float(jnp.abs(got - want).max()) <= tol
    # query head i reads KV head i // g: the same call with the KV
    # heads repeated and no grouping gives the same numbers (not the
    # same bits: the blocks a chunk holds follow from the KV heads)
    g = hq // hkv
    flat = paged_attention_pallas(q, jnp.repeat(pool, g, 1), tbl, lens,
                                  interpret=True)
    assert float(jnp.abs(got - flat).max()) <= 2e-6


@pytest.mark.parametrize("M,sizes", [
    (64, [0, 5, 0, 17, 3, 0, 20, 9]),       # a decode step: dead pairs last
    (256, [40, 0, 60, 1, 0, 100, 30, 5]),   # a chunk: two row tiles
    (64, [64, 0, 0, 0, 0, 0, 0, 0]),
    (64, [0, 0, 0, 0, 0, 0, 0, 0])])        # no live pair at all
def test_expert_kernel_matches_the_plain_sum(M, sizes):
    rng = np.random.default_rng(M)
    E, D, F = 8, 128, 256
    x = rng.normal(size=(M, D)).astype(np.float32)
    w1, w3 = ((rng.normal(size=(E, D, F)) * 0.1).astype(np.float32)
              for _ in range(2))
    w2 = (rng.normal(size=(E, F, D)) * 0.1).astype(np.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    # the plain sum: every row by its own expert's matrices, one expert
    # at a time, in float64
    plain, r = np.zeros((n, D)), 0
    for e, m in enumerate(sizes):
        xe = x[r:r + m].astype(np.float64)
        a = xe @ w1[e]
        plain[r:r + m] = (a / (1 + np.exp(-a)) * (xe @ w3[e])) @ w2[e]
        r += m
    with jax.default_matmul_precision("highest"):
        got = expert_ffn(x, w1, w3, w2, gs, impl="pallas", interpret=True)
        ragged = expert_ffn(x, w1, w3, w2, gs, impl="ragged")
    assert got.shape == (M, D)
    if n:
        assert float(np.abs(got[:n] - plain).max()) <= 1e-5
        assert float(np.abs(ragged[:n] - plain).max()) <= 1e-5


# -- the engine -------------------------------------------------------------------
@pytest.fixture(scope="module")
def server(lm):
    srv = InferenceServer(port=0)
    gen = srv.register_generator("lm", lm, **ENGINE)
    gen.warmup()
    yield srv, gen
    srv.stop()


def _post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/v1/models/lm/generate", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


PROMPTS = [np.random.default_rng([7, i]).integers(0, 257, n).tolist()
           for i, n in enumerate((21, 9, 40, 17, 33))]
NEW = 7


def _generate_all(port):
    outs = {}

    def go(i):
        outs[i] = _post(port, {"prompt": PROMPTS[i], "max_tokens": NEW,
                               "temperature": 0.0})
    threads = [threading.Thread(target=go, args=(i,)) for i in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(outs[i][0] == 200 for i in range(5)), outs
    return [outs[i][1]["tokens"] for i in range(5)]


def _reference_greedy(ref, prompt, tokens):
    """The reference's first choice at every served position, and how
    far the served token's logit lies below it."""
    seq = np.asarray(prompt + tokens, np.int32)
    lg = reference_logits(ref, seq)[len(prompt) - 1:len(seq) - 1]
    gap = lg.max(-1) - lg[np.arange(len(tokens)), tokens]
    return lg.argmax(-1).tolist(), gap


@pytest.fixture(scope="module")
def baseline(server, ref):
    srv, gen = server
    c0 = gen.metrics.compiles
    s0 = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/stats").read())["models"]["lm"]
    tokens = _generate_all(srv.port)
    # the pipeline's last step (every lane past its end) is collected
    # after the last answer has left
    deadline = time.time() + 30
    while gen.engine._pending and time.time() < deadline:
        time.sleep(0.01)
    s1 = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/stats").read())["models"]["lm"]
    return tokens, gen.metrics.compiles - c0, s0, s1


def test_served_tokens_are_the_references_greedy_tokens(baseline, ref):
    tokens = baseline[0]
    for prompt, toks in zip(PROMPTS, tokens):
        assert len(toks) == NEW
        first, gap = _reference_greedy(ref, prompt, toks)
        # float32 both sides: a served token is the reference's first
        # choice, or lies within the logits' tolerance of it (a tie)
        assert (gap <= 2 * LOGIT_TOL).all(), (toks, first, gap)


@pytest.fixture(scope="module")
def tokens_written_by_rows(lm):
    """The same five prompts through an engine whose chunk programs
    write their K and V a row at a time (``kv_pool_set``, as before
    PR 33) where the model writes them by blocks."""
    import importlib
    zoo = importlib.import_module("deeplearning4j_tpu.zoo.lfm2_moe")
    shapes = []

    def by_rows(pool, table, p0, k, v):
        shapes.append((k.shape, pool.shape))
        g = p0 + jnp.arange(k.shape[0])
        Bs = pool.shape[2]
        return zoo.kv_pool_set(
            pool, (table[g // Bs][:, None], jnp.arange(k.shape[1])[None, :],
                   (g % Bs)[:, None]), k, v)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(zoo, "kv_pool_set_span", by_rows)
        eng = GenerationEngine(lm, **ENGINE)
        try:
            eng.warmup()
            tokens = [eng.generate(p, max_tokens=NEW)["tokens"]
                      for p in PROMPTS]
        finally:
            eng.stop()
    # 2 KV heads of 16, a chunk of 16 rows
    assert shapes and set(shapes) == {((16, 2, 16), (33, 2, 8, 32))}
    return tokens


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_greedy_tokens_equal_those_of_a_chunk_written_by_rows(
        baseline, tokens_written_by_rows, i):
    """Prompts of 1, 2 and 3 chunks, grouped-query heads, the conv
    state beside the pool: token for token."""
    assert tokens_written_by_rows[i] == baseline[0][i]


def test_zero_compiles_after_warmup_and_the_counters_add_up(baseline, lm):
    _, compiles, s0, s1 = baseline
    assert compiles == 0
    moe0, moe1 = s0["moe"], s1["moe"]
    decode_tokens = 5 * (NEW - 1)       # token 0 comes out of the prefill
    per_token = lm.top_k * lm.n_moe_layers
    assert moe1["decode_pairs"] - moe0["decode_pairs"] == \
        per_token * decode_tokens
    assert moe1["chunk_pairs"] - moe0["chunk_pairs"] == \
        per_token * sum(len(p) for p in PROMPTS)
    steps = s1["decode_steps"] - s0["decode_steps"]
    assert moe1["decode_expert_slots"] - moe0["decode_expert_slots"] == \
        steps * lm.n_moe_layers * lm.n_experts
    touched = moe1["decode_experts_touched"] - moe0["decode_experts_touched"]
    assert 0 < touched <= moe1["decode_pairs"] - moe0["decode_pairs"]
    assert sum(moe1["expert_tokens"].values()) == \
        moe1["decode_pairs"] + moe1["chunk_pairs"]
    assert moe1["expert_tokens_max_over_mean"] >= 1.0
    # 4 conv layers x 3 slots x 2 inputs x 64 floats
    assert s1["state"]["slot_bytes"] == 4 * 3 * 2 * 64 * 4
    assert s1["paged"]["prefix_cache"]["prefix_hits"] == 0


def test_metrics_expose_the_moe_and_state_counters(server, baseline):
    srv, _ = server
    base = f"http://127.0.0.1:{srv.port}"
    stats = json.loads(urllib.request.urlopen(base + "/stats").read())
    samples, types = parse_prometheus(
        urllib.request.urlopen(base + "/metrics").read().decode())
    assert assert_exposition_parity(stats, samples, types) > 20
    lab = '{model="lm"}'
    moe = stats["models"]["lm"]["moe"]
    assert samples[("dl4j_model_moe_decode_pairs_total", lab)] == \
        moe["decode_pairs"]
    assert types["dl4j_model_moe_decode_experts_touched_total"] == "counter"
    assert types["dl4j_model_moe_decode_expert_slots_total"] == "counter"
    assert types["dl4j_model_moe_chunk_pairs_total"] == "counter"
    assert samples[("dl4j_model_state_slot_bytes", lab)] == \
        stats["models"]["lm"]["state"]["slot_bytes"]
    assert any(n == "dl4j_model_moe_expert_tokens" and "bucket=" in lb
               for n, lb in samples)


def test_a_poisoned_slot_is_not_inherited(server, baseline):
    """No zeroing between occupants: the engine never clears a slot's
    state, and the next request's first chunk never reads it."""
    srv, gen = server
    eng = gen.engine
    assert eng._idle()
    eng._state = [jnp.full_like(s, jnp.nan) for s in eng._state]
    assert _generate_all(srv.port) == baseline[0]
    assert gen.metrics.quarantined == 0


def test_recompute_recovery_rebuilds_the_state(server, baseline):
    srv, gen = server
    eng = gen.engine
    r0, c0 = gen.metrics.recoveries, gen.metrics.compiles
    eng.set_fault_injector(FaultInjector(
        plan={"device_step": [3], "prefill": [2, 6]},
        corrupting=("device_step", "prefill")))
    try:
        assert _generate_all(srv.port) == baseline[0]
    finally:
        eng.set_fault_injector(None)
    assert gen.metrics.recoveries - r0 == 3
    assert gen.metrics.compiles == c0


class _FaultBehindAChunk:
    """An injector that raises one TransientFault at the decode step's
    seam in an iteration whose chunk (dispatched just before, still in
    flight) continues a request: ``p0 > 0``, so the chunk has read the
    slot state its request's earlier chunk wrote and has overwritten
    it."""

    def __init__(self, engine):
        self.engine, self.p0, self.fired = engine, None, []
        self.chunks = 0                 # chunks sent to the device

    def fire(self, seam, worker=None):
        if seam == "latency":           # an iteration starts
            self.p0 = None
        elif seam == "prefill":         # its chunk is about to go out
            st = self.engine._prefilling[0]
            self.p0 = st.plan[st.idx][0]
            self.chunks += 1
        elif seam == "device_step" and self.p0 and not self.fired:
            self.fired.append(self.p0)
            raise TransientFault("injected behind a chunk in flight")
        return False


def test_a_fault_at_the_step_behind_a_chunk_in_flight_lands_the_chunk(
        server, baseline):
    """The loop queues the next step behind a chunk and collects the
    chunk last. A transient fault at the step's seam retries the
    iteration; the chunk that ran must land first, or the retry would
    run it again over the state it has itself written."""
    srv, gen = server
    eng = gen.engine
    inj = _FaultBehindAChunk(eng)
    r0, rec0 = gen.metrics.retries, gen.metrics.recoveries
    eng.set_fault_injector(inj)
    try:
        assert _generate_all(srv.port) == baseline[0]
    finally:
        eng.set_fault_injector(None)
    assert len(inj.fired) == 1 and inj.fired[0] > 0
    assert gen.metrics.retries - r0 == 1
    assert gen.metrics.recoveries == rec0
    # no chunk ran twice: one went out for every 16 tokens of a prompt
    assert inj.chunks == sum(
        -(-len(p) // ENGINE["prefill_chunk_tokens"]) for p in PROMPTS)


def test_session_id_is_a_400(server):
    srv, _ = server
    status, body = _post(srv.port, {"prompt": [1, 2, 3], "max_tokens": 2,
                                    "session_id": "conv-1"})
    assert status == 400 and "slot state" in body["error"]


@pytest.mark.parametrize("kw,why", [
    (dict(speculation_k=2), "speculation_k"),
    (dict(offload_host_bytes=1 << 20), "offload_host_bytes"),
    (dict(cache="slots"), "paged")])
def test_the_engine_refuses_what_cannot_carry_slot_state(lm, kw, why):
    with pytest.raises(ValueError, match=why):
        GenerationEngine(lm, **dict(ENGINE, **kw))


def test_a_model_without_slot_state_declares_none(server):
    from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM
    plain = CausalTransformerLM(vocab_size=50, d_model=16, n_layers=1,
                                n_heads=2, max_seq_len=32).init()
    eng = GenerationEngine(plain, num_slots=2, max_seq_len=32, cache="paged",
                           block_size=8, prefill_chunk_tokens=8)
    try:
        assert eng._state == [] and not eng._stateful
        assert eng.enable_prefix_sharing
        assert eng.metrics.slot_state_bytes == 0
        assert eng.generate([1, 2, 3], max_tokens=3)["tokens"]
        assert "moe" not in eng.stats()
    finally:
        eng.stop()
    assert not server[1].engine.enable_prefix_sharing


# -- the benchmark's comparison on the tiny configuration -----------------------------
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    import benchmark_testlib as lib
    root = lib.make_root(tmp_path_factory.mktemp("lfm2"))
    b = os.path.join(root, "benchmark")
    real = lib.load(lib.BENCH, "configs", "lfm2-8b-a1b.json")
    cfg = dict(real, name="tiny-lfm2", model=TINY,
               engine=dict(real["engine"], num_slots=4, max_seq_len=64,
                           prompt_buckets=[16], block_size=8, num_blocks=33,
                           prefill_chunk_tokens=16, kv_dtype="f32"),
               warmup={"buckets": [16]})
    lib.dump(cfg, b, "configs", "tiny-lfm2.json")
    spec = lib.load(root, "BENCHMARK.json")
    spec["configs"].append({"name": "tiny-lfm2", "source": real["source"],
                            "file": "benchmark/configs/tiny-lfm2.json",
                            "reduced": real["reduced"], "why": "tiny"})
    # enough served tokens for the 8-bit control to put another first
    traffic = lib.load(b, "traffic", "tiny_decode.json")
    traffic["check_requests"] = 200
    lib.dump(traffic, b, "traffic", "tiny_lfm2_decode.json")
    cell = {"name": "tiny-lfm2.decode", "config": "tiny-lfm2",
            "traffic": "tiny_lfm2_decode", "chips": 1, "why": "tiny"}
    spec["workloads"].append(cell)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-lm.decode" in m.get("workloads", []) \
                and not m["name"].startswith("paged_att"):
            m["workloads"].append(cell["name"])
    lib.dump(spec, root, "BENCHMARK.json")
    return root


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "control"])
def test_run_py_judges_the_tiny_configuration(tiny_root, control):
    """The program ends ``correct: true``; with the 8-bit control in
    its place (``run.py --control 1``) the same run ends
    ``correct: false``, by the limit the real cell's traffic file
    holds."""
    out, obs = run.run_cell("tiny-lfm2.decode", 11, 4.0, False,
                            require_chip=False, control=control,
                            root=tiny_root)
    assert out["failed"] == 0, out["failures"]
    assert out["failures"]["compiles_after_warmup"] == 0
    got = out["compared"]["served_gap_over_control"]
    assert got["limit"] is not None
    assert out["compared"]["control_logit_gap_mean"]["value"] > 0
    if control:
        assert out["correct"] is False and got["value"] == 1.0
        assert out["control"] == "float8_e4m3fn"
    else:
        assert out["correct"] is True and got["value"] < got["limit"]


# -- the expert layer's new arguments leave this model's programs alone (PR 35) -------
#: SHA-256 (first 16 hex digits) of the StableHLO that the tiny model's
#: decode step and prefill chunk lower to, locations stripped, taken on
#: the commit before ``moe_ffn`` gained ``router_logits`` / ``scoring``
#: / ``gate`` and ``span_attend`` its ``kpos`` / ``window``, with this
#: container's JAX (0.9.0). A JAX upgrade changes them: take them anew
#: from the parent commit then, never from the tree under test.
LOWERED_BEFORE_PR35 = {"step": "181459453d855f41", "chunk": "5ab16d6137fa402a"}


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_the_programs_lower_to_the_operations_they_did_before_pr35(program):
    import hashlib
    import re
    from deeplearning4j_tpu.zoo.lfm2_moe import Lfm2MoeLM
    lm = Lfm2MoeLM(**{k: v for k, v in TINY.items() if k != "embed_std"}
                   ).init()
    pools = PagedKVCache(lm.cache_shapes(8), 20).pools
    state = [jnp.zeros(s, d) for s, d in lm.slot_state_shapes(3)]
    if program == "step":
        text = jax.jit(
            lambda p, pl, st, t, pos, tb, lv: lm.forward_decode_paged(
                p, t, pos, pl, tb, "xla", state=st, live=lv)).lower(
            lm._params, pools, state, jnp.zeros(3, jnp.int32),
            jnp.zeros(3, jnp.int32), jnp.zeros((3, 8), jnp.int32),
            jnp.ones(3, bool)).as_text()
    else:
        text = jax.jit(
            lambda p, pl, st, t, p0, cl, tb, sl: lm.forward_prefill_chunk(
                p, t, p0, cl, pl, tb, state=st, slot=sl)).lower(
            lm._params, pools, state, jnp.zeros((1, 16), jnp.int32),
            jnp.int32(0), jnp.int32(16), jnp.zeros(8, jnp.int32),
            jnp.int32(1)).as_text()
    text = re.sub(r"loc\(.*?\)|#loc\d*( = .*)?", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        LOWERED_BEFORE_PR35[program]

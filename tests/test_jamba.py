"""The Jamba-shaped served class at a tiny size of the same pattern
(Mamba, Mamba, attention, Mamba; 4 query heads over ONE KV head; 4
states a channel, a convolution of 4 taps, three inner norms), float32,
seeded random weights:

- the class against the benchmark's plain reference on logits: prefill
  in chunks (an uneven last chunk), then decode, through the paged pool
  and the two slot arrays a Mamba layer, every pool row and every state
  row that was never written NaN; a bfloat16 run fails the tolerance;
- what a model with slot state owes the engine: a chunk's padded rows
  and a step's dead lanes leave the state bit for bit, a reused slot
  starts from zeros;
- the paged kernels (interpret mode) at 20 query heads over 1 KV head
  of 128 against ``paged_attention_xla`` / ``span_attend``;
- the engine: greedy tokens against the reference, two requests
  interleaved equal each alone, zero compiles after warm-up, the
  ``ssm`` block and ``state.slot_bytes`` of ``/stats``, what it refuses;
- ``run.py`` on the tiny configuration, and with ``--control 1`` ending
  ``correct: false``.
"""
import os
import sys
import threading

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
from deeplearning4j_tpu.kernels.paged_attention import (  # noqa: E402
    fuse_kv, gather_span, paged_attention_pallas, paged_attention_xla,
    paged_prefill_attention_pallas, span_attend)
from deeplearning4j_tpu.serving import PagedKVCache  # noqa: E402
from deeplearning4j_tpu.serving.generation import GenerationEngine  # noqa: E402

TINY = dict(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
    attn_layer_period=4, attn_layer_offset=2, mamba_d_state=4,
    mamba_d_conv=4, mamba_dt_rank=4, mamba_expand=2, mamba_conv_bias=True,
    mamba_proj_bias=False, num_experts=1, num_experts_per_tok=1,
    rms_norm_eps=1e-6, tie_word_embeddings=True, sliding_window=None,
    max_position_embeddings=64, dtype="float32",
    # the head is tied: at this width a narrow embedding would answer
    # every token with itself
    embed_std=0.002)
SEED = 3
BS, CHUNK = 4, 8
ENGINE = dict(num_slots=3, max_seq_len=64, prompt_buckets=[CHUNK],
              cache="paged", block_size=BS, prefill_chunk_tokens=CHUNK)
# float32 on both sides, the same weights; what differs is the order of
# the sums (a chunk's gathered panel against the full pass, the state
# carried [N, Di] against [Di, N]): 8e-9 on logits of magnitude ~0.06
# after 4 layers, a few float32 ulps. A bfloat16 run reads 1.5e-4
LOGIT_TOL = 2e-7


@pytest.fixture(scope="module")
def ref():
    return run.load_module(REPO, "reference", "jamba")


def build(ref, **changes):
    served = run.load_module(REPO, "served", "jamba")
    return served.build({"model": dict(TINY, **changes)}, SEED, ref)


@pytest.fixture(scope="module")
def lm(ref):
    return build(ref)


def reference_logits(ref, seq, cfg=TINY):
    hid, emb = ref.final_hidden(cfg, SEED, [np.asarray(seq, np.int32)])
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.head_logits(emb, hid[0][:len(seq)]))


class Hand:
    """The two forwards as the engine's programs call them, over one
    pool and one set of slot arrays that live across sequences. Every
    pool row and every state row starts as NaN: the null block, blocks
    of nobody, slots nobody has used."""

    def __init__(self, lm, slots=3, room=64):
        self.lm, self.slots = lm, slots
        self.n_blocks = room // BS + 2
        self.pools = [jnp.full_like(p, jnp.nan) for p in PagedKVCache(
            lm.cache_shapes(BS), slots * self.n_blocks + 1).pools]
        self.state = [jnp.full(shape, jnp.nan, dtype)
                      for shape, dtype in lm.slot_state_shapes(slots)]
        self.counters = None

    def table(self, slot):
        t = np.zeros(self.n_blocks, np.int32)
        t[:-2] = 1 + slot * self.n_blocks + np.arange(self.n_blocks - 2)
        return t

    def chunk(self, slot, tokens, p0, pad_with=0):
        toks = np.full((1, CHUNK), pad_with, np.int32)
        toks[0, :len(tokens)] = tokens
        lg, self.pools, self.state, self.counters = \
            self.lm.forward_prefill_chunk(
                self.lm._params, jnp.asarray(toks), jnp.int32(p0),
                jnp.int32(len(tokens)), self.pools,
                jnp.asarray(self.table(slot)), state=self.state,
                slot=jnp.int32(slot))
        return np.asarray(lg)[:len(tokens)]

    def step(self, lanes, impl="xla"):
        """``lanes``: {slot: (token, position)} of the live lanes."""
        toks, pos = (np.zeros(self.slots, np.int32) for _ in range(2))
        tables = np.zeros((self.slots, self.n_blocks), np.int32)
        live = np.zeros(self.slots, bool)
        for s, (t, p) in lanes.items():
            toks[s], pos[s], live[s], tables[s] = t, p, True, self.table(s)
        lg, self.pools, self.state, self.counters = \
            self.lm.forward_decode_paged(
                self.lm._params, jnp.asarray(toks), jnp.asarray(pos),
                self.pools, jnp.asarray(tables), impl, state=self.state,
                live=jnp.asarray(live))
        return np.asarray(lg)

    def serve(self, seq, prefill, slot=1, impl="xla"):
        """Logits of every position of ``seq``: ``prefill`` positions
        in chunks of ``CHUNK`` rows, then one decode step a token."""
        got = [self.chunk(slot, seq[p0:min(p0 + CHUNK, prefill)], p0)
               for p0 in range(0, prefill, CHUNK)]
        got += [self.step({slot: (seq[t], t)}, impl)[slot][None]
                for t in range(prefill, len(seq))]
        return np.concatenate(got, 0)


# -- the class against the reference ----------------------------------------
#: (positions in all, of them prefilled): one short chunk, whole chunks,
#: an uneven last chunk, a prompt that is all of the sequence
CONTEXTS = [(5, 3), (11, 8), (24, 13), (33, 32), (40, 21), (40, 40)]


@pytest.mark.parametrize("total,prefill", CONTEXTS)
def test_chunks_then_decode_match_the_references_full_pass(ref, lm, total,
                                                           prefill):
    seq = np.random.default_rng(total).integers(0, 97, total)
    hand = Hand(lm)
    got = hand.serve(seq, prefill)
    want = reference_logits(ref, seq)
    assert np.isfinite(got).all()
    worst = np.abs(got - want).max()
    assert worst <= LOGIT_TOL, (worst, np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # live rows x the three Mamba layers
    rows = 1 if prefill < total else (prefill - 1) % CHUNK + 1
    assert hand.counters.tolist() == [3 * rows]


def test_a_bfloat16_run_fails_the_tolerance(ref):
    """The same pass with bfloat16 weights and matmul operands against
    the float32 reference of those weights: hundreds of tolerances."""
    low = build(ref, dtype="bfloat16")
    seq = np.random.default_rng(24).integers(0, 97, 24)
    got = Hand(low).serve(seq, 13)
    want = reference_logits(ref, seq, dict(TINY, dtype="bfloat16"))
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_the_decode_kernel_serves_the_same_logits(ref, lm):
    """The same pass with the Pallas paged kernel (interpret mode) at
    one KV head."""
    seq = np.random.default_rng(1).integers(0, 97, 30)
    got = Hand(lm).serve(seq, 19, impl="pallas")
    assert np.abs(got - reference_logits(ref, seq)).max() <= LOGIT_TOL


def test_the_reference_reads_the_weights_the_program_holds(ref, lm):
    emb, layers = ref.make_params(TINY, SEED)
    assert [ref.is_attention(TINY, i) for i in range(4)] == [
        False, False, True, False]
    assert lm.attn_layers == [2] and lm.mamba_layers == [0, 1, 3]
    for i, names in ((2, ("Wq", "Wk", "W_down")),
                     (3, ("W_in", "W_x", "W_dt", "W_out", "conv_w", "b_dt"))):
        for name in names:
            np.testing.assert_array_equal(
                np.asarray(layers[i][name], np.float32),
                np.asarray(lm._params["layers"][i][name], np.float32))
    np.testing.assert_array_equal(
        np.asarray(lm._params["layers"][0]["A"]),
        -np.exp(np.asarray(layers[0]["A_log"])).T)
    assert lm._params["layers"][0]["A"].shape == (4, 64)        # [N, Di]
    assert "head" not in lm._params                             # tied
    assert lm.cache_shapes(8) == [(1, 8, 8)]                    # one layer
    assert [(s, str(jnp.dtype(d))) for s, d in lm.slot_state_shapes(3)] == [
        ((3, 4, 64), "float32"), ((3, 3, 64), "float32")] * 3


def test_the_reference_is_causal_past_its_row_blocks(ref):
    """600 positions (padded to 640: five of the reference's attention
    row blocks, which 640 is a multiple of and 600 is not): the first 40
    rows' logits are those of the 40-token prefix alone."""
    seq = np.random.default_rng(8).integers(0, 97, 600)
    long = reference_logits(ref, seq)
    assert long.shape == (600, 97) and np.isfinite(long).all()
    assert np.abs(long[:40] - reference_logits(ref, seq[:40])).max() <= \
        LOGIT_TOL


def test_the_published_layer_order_puts_attention_at_7_and_21(ref):
    import benchmark_testlib as lib
    m = lib.load(lib.BENCH, "configs", "jamba2-3b.json")["model"]
    assert [i for i in range(28) if ref.is_attention(m, i)] == [7, 21]
    from deeplearning4j_tpu.zoo.jamba import JambaLM
    big = JambaLM(**m)
    assert big.attn_layers == [7, 21] and len(big.mamba_layers) == 26
    assert big.cache_shapes(16384) == [(1, 16384, 128)] * 2
    shapes = big.slot_state_shapes(16)
    assert len(shapes) == 52
    assert shapes[0] == ((16, 16, 5120), jnp.float32)
    assert shapes[1] == ((16, 3, 5120), jnp.bfloat16)
    assert big.step_account().snapshot()["state_bytes_per_slot"] == 9_318_400


# -- what a model with slot state owes the engine ------------------------------
def test_a_chunks_padded_rows_leave_the_state_bit_for_bit(lm):
    """The last chunk of a prompt is 5 live rows of 8. Whatever tokens
    the padded rows hold, the state after it is the same bits, and the
    bits a chunk of exactly those rows' state hands to decode."""
    seq = np.random.default_rng(2).integers(0, 97, 13)
    states = []
    for pad in (0, 55):
        hand = Hand(lm)
        hand.chunk(1, seq[:8], 0)
        hand.chunk(1, seq[8:], 8, pad_with=pad)
        states.append([np.asarray(a) for a in hand.state])
    for a, b in zip(*states):
        np.testing.assert_array_equal(a[1], b[1])
        assert np.isfinite(a[1]).all()
        assert np.isnan(a[0]).all() and np.isnan(a[2]).all()  # not its slot


def test_a_steps_dead_lanes_leave_the_state_bit_for_bit(lm):
    """Two prompts in slots 0 and 2; a step with only slot 2 live
    changes slot 2's state and not one bit of slot 0's."""
    rs = np.random.default_rng(4)
    a, b = rs.integers(0, 97, 11), rs.integers(0, 97, 6)
    hand = Hand(lm)
    hand.chunk(0, a[:8], 0)
    hand.chunk(0, a[8:], 8)
    hand.chunk(2, b, 0)
    before = [np.asarray(s) for s in hand.state]
    hand.step({2: (5, 6)})
    assert hand.counters.tolist() == [3]
    for old, new in zip(before, hand.state):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[0], old[0])
        assert np.isnan(new[1]).all()
        assert (new[2] != old[2]).any()


def test_a_reused_slot_starts_from_zeros(ref, lm):
    """A second request in the slot a first one left: its first chunk
    starts from zeros whatever the slot holds, so its logits are those
    of the request alone."""
    rs = np.random.default_rng(6)
    first, second = rs.integers(0, 97, 19), rs.integers(0, 97, 14)
    hand = Hand(lm)
    hand.serve(first, 12)
    got = hand.serve(second, 9)
    assert np.abs(got - reference_logits(ref, second)).max() <= LOGIT_TOL


# -- the paged kernels at 20 query heads over 1 KV head ------------------------
def _pool(rs, n_blocks, bs, d, dt):
    k = rs.randn(n_blocks, 1, bs, d).astype(np.float32)
    v = rs.randn(n_blocks, 1, bs, d).astype(np.float32)
    return fuse_kv(jnp.asarray(k, dt), jnp.asarray(v, dt))


@pytest.mark.parametrize("dt,tol", [(jnp.float32, 2e-5),
                                    (jnp.bfloat16, 2e-2)])
def test_the_decode_kernel_takes_a_group_of_20_at_one_kv_head(dt, tol):
    """The MXU body (heads of 128 lanes): 20 query rows of the one KV
    head, padded to the sublane tile, against XLA's gather path."""
    rs = np.random.RandomState(0)
    S, Hq, D, Bs, B = 3, 20, 128, 8, 6
    pool = _pool(rs, 1 + S * B, Bs, D, dt)
    tables = 1 + np.arange(S * B, dtype=np.int32).reshape(S, B)
    lengths = np.array([1, 29, 48], np.int32)
    q = jnp.asarray(rs.randn(S, Hq, D), jnp.float32)
    got = paged_attention_pallas(q, pool, jnp.asarray(tables),
                                 jnp.asarray(lengths), interpret=True)
    want = paged_attention_xla(q, pool, jnp.asarray(tables),
                               jnp.asarray(lengths))
    assert got.shape == (S, Hq, D)
    assert float(jnp.abs(got - want).max()) <= tol


@pytest.mark.parametrize("p0,clen", [(0, 32), (32, 32), (64, 17)])
def test_the_chunk_kernel_takes_a_group_of_20_at_one_kv_head(p0, clen):
    rs = np.random.RandomState(1)
    C, Hq, D, Bs, B = 32, 20, 128, 8, 14
    pool = _pool(rs, 1 + B, Bs, D, jnp.float32)
    table = jnp.asarray(1 + rs.permutation(B).astype(np.int32))
    q = jnp.asarray(rs.randn(C, Hq, D), jnp.float32)
    got = paged_prefill_attention_pallas(q, pool, table, p0, clen,
                                         interpret=True)
    kk, vv = gather_span(pool, table)
    want = span_attend(q, kk, vv, p0 + jnp.arange(C), p0 + C, jnp.float32)
    assert float(jnp.abs(got[:clen] - want[:clen]).max()) <= 2e-5
    assert np.isfinite(np.asarray(got)).all()


# -- through the engine -----------------------------------------------------------
@pytest.fixture(scope="module")
def engine(lm):
    eng = GenerationEngine(lm, **ENGINE)
    eng.warmup()
    yield eng
    eng.stop()


PROMPTS = [np.random.default_rng([5, i]).integers(0, 97, n).tolist()
           for i, n in enumerate((3, 9, 17, 30, 44))]
NEW = 11


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_served_tokens_are_the_references_greedy_tokens(engine, ref, i):
    c0 = engine.metrics.compiles
    toks = engine.generate(PROMPTS[i], max_tokens=NEW,
                           temperature=0.0)["tokens"]
    want = reference_logits(ref, PROMPTS[i] + toks)
    rows = want[len(PROMPTS[i]) - 1:-1]
    gap = rows.max(-1) - rows[np.arange(NEW), toks]
    assert gap.max() <= LOGIT_TOL
    assert engine.metrics.compiles == c0


def test_two_requests_interleaved_equal_each_alone(engine):
    """Their chunks and steps share the engine's iterations, three
    slots and one set of state arrays; each answer is the one the
    request gets alone."""
    pair = [PROMPTS[3], PROMPTS[4]]
    alone = [engine.generate(p, max_tokens=NEW, temperature=0.0)["tokens"]
             for p in pair]
    outs = {}

    def go(i):
        outs[i] = engine.generate(pair[i], max_tokens=NEW,
                                  temperature=0.0)["tokens"]
    threads = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert [outs[0], outs[1]] == alone


def test_stats_hold_the_ssm_block_and_the_states_bytes(engine, lm):
    """One request of 17 prompt tokens and 6 new ones: 17 chunk rows
    and 5 steps of one live lane, each times three Mamba layers."""
    a = engine.stats()
    engine.generate(PROMPTS[2], max_tokens=6, temperature=0.0)
    b = engine.stats()
    assert b["ssm"]["chunk_rows"] - a["ssm"]["chunk_rows"] == 3 * 17
    assert b["ssm"]["decode_rows"] - a["ssm"]["decode_rows"] == 3 * 5
    per_slot = 3 * (4 * 64 * 4 + 3 * 64 * 4)       # h and the conv inputs
    assert b["ssm"]["state_bytes_per_slot"] == per_slot
    assert b["state"]["slot_bytes"] == 3 * per_slot
    assert "moe" not in b


@pytest.mark.parametrize("kw,why", [
    (dict(speculation_k=2), "speculation_k"),
    (dict(offload_host_bytes=1 << 20), "offload_host_bytes"),
    (dict(cache="slots"), "paged")])
def test_the_engine_refuses_what_cannot_carry_slot_state(lm, kw, why):
    with pytest.raises(ValueError, match=why):
        GenerationEngine(lm, **dict(ENGINE, **kw))


def test_prefix_sharing_is_off_and_what_the_class_cannot_be_is_refused(
        engine):
    from deeplearning4j_tpu.zoo.jamba import JambaLM
    assert not engine.enable_prefix_sharing
    for bad in (dict(num_experts=2), dict(mamba_proj_bias=True),
                dict(tie_word_embeddings=False), dict(sliding_window=64)):
        with pytest.raises(ValueError):
            JambaLM(**dict(TINY, **bad))


# -- the benchmark's comparison on the tiny configuration -----------------------------
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    import benchmark_testlib as lib
    root = lib.make_root(tmp_path_factory.mktemp("jamba"))
    b = os.path.join(root, "benchmark")
    real = lib.load(lib.BENCH, "configs", "jamba2-3b.json")
    cfg = dict(real, name="tiny-jamba", model=TINY,
               engine=dict(real["engine"], num_slots=4, max_seq_len=64,
                           prompt_buckets=[CHUNK], block_size=BS,
                           num_blocks=65, prefill_chunk_tokens=CHUNK,
                           kv_dtype="f32"),
               warmup={"buckets": [CHUNK]})
    lib.dump(cfg, b, "configs", "tiny-jamba.json")
    spec = lib.load(root, "BENCHMARK.json")
    spec["configs"].append({"name": "tiny-jamba", "source": real["source"],
                            "file": "benchmark/configs/tiny-jamba.json",
                            "reduced": real["reduced"], "why": "tiny"})
    traffic = lib.load(b, "traffic", "tiny_decode.json")
    traffic["check_requests"] = 200
    traffic["limits"] = lib.load(
        lib.BENCH, "traffic", "long_context_backlog.json")["limits"]
    lib.dump(traffic, b, "traffic", "tiny_jamba_decode.json")
    cell = {"name": "tiny-jamba.decode", "config": "tiny-jamba",
            "traffic": "tiny_jamba_decode", "chips": 1, "why": "tiny"}
    spec["workloads"].append(cell)
    real_cell = "jamba2-3b.long_context_backlog"
    real_spec = lib.load(lib.REPO, "BENCHMARK.json")
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in real_spec[g] if real_cell in m.get("workloads", [])}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in listed:
            m["workloads"] = m.get("workloads", []) + [cell["name"]]
    lib.dump(spec, root, "BENCHMARK.json")
    return root


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "control"])
def test_run_py_judges_the_tiny_configuration(tiny_root, control):
    """The program ends ``correct: true``; with the 8-bit control in
    its place (``run.py --control 1``) the same run ends
    ``correct: false``, by the limit the real cell's traffic file
    holds."""
    out, obs = run.run_cell("tiny-jamba.decode", 11, 4.0, False,
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
        # the counts that read /stats find the ssm block's counters
        counts = obs["counts"]
        span = obs["window"]["span"]
        for cost in ("prefill_chunks_scan_cost",
                     "decode_steps_attention_cost",
                     "prefill_chunks_attention_cost"):
            fl, by = getattr(counts, cost)(obs, span)
            assert fl > 0 and by > 0, cost
        for name in ("kv_pool_live_share", "kv_blocks_peak_share",
                     "slot_occupancy"):
            v = run.read_metric(name, obs, os.path.join(tiny_root,
                                                        "benchmark"))
            assert v is not None and 0 < v <= 100, (name, v)

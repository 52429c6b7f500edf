"""The SmallThinker-shaped served class at a tiny size of the same
pattern (global, window, window, window; 28 query heads over 4 KV
heads; 8 ReLU-gated experts, 3 a token; a window of 8), float32, seeded
random weights:

- the class against the benchmark's plain reference on logits: prefill
  in chunks, then decode, through the two cache groups (a ring of 5
  blocks of 4 for the window layers beside a plain table), contexts
  several windows long, every pool row that was never written NaN;
- a NoPE layer, a rotary layer and a window layer each against what
  their equations say about the order and the reach of the keys;
- the router reads the block's input; softmax over the chosen logits;
  the ReLU gate in both forms of the expert matmuls;
- the engine over the two groups: greedy tokens against the reference,
  zero compiles after warm-up, the ``paged.groups.*`` and
  ``scheduler.kv_rows_*`` counters;
- ``run.py`` on the tiny configuration, and with ``--control 1`` ending
  ``correct: false``.
"""
import os
import sys

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
from deeplearning4j_tpu.kernels.moe_experts import expert_ffn  # noqa: E402
from deeplearning4j_tpu.nn.layers.moe import route_softmax  # noqa: E402
from deeplearning4j_tpu.serving import PagedKVCache  # noqa: E402
from deeplearning4j_tpu.serving.generation import GenerationEngine  # noqa: E402
from deeplearning4j_tpu.zoo.smallthinker import SmallThinkerLM  # noqa: E402

TINY = dict(
    vocab_size=97, hidden_size=32, head_dim=8, num_hidden_layers=4,
    num_attention_heads=28, num_key_value_heads=4, moe_ffn_hidden_size=16,
    moe_num_primary_experts=8, moe_num_active_primary_experts=3,
    sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
    sliding_window_size=8, rope_theta=1.5e6, rms_norm_eps=1e-6,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    tie_word_embeddings=False, max_position_embeddings=64,
    dtype="float32")
SEED = 3
BS, CHUNK, RING = 4, 8, 5       # ring = blocks_for(8 + 8, 4) + 1
ENGINE = dict(num_slots=3, max_seq_len=64, prompt_buckets=[CHUNK],
              cache="paged", block_size=BS, prefill_chunk_tokens=CHUNK)
# float32 on both sides, the same weights; what differs is the order of
# the sums (a chunk's gathered panel and ring against the full pass, the
# experts' sorted rows against every expert's product): a few float32
# ulps of logits of magnitude ~0.1 after 4 layers of ~30-term sums
LOGIT_TOL = 2e-6


@pytest.fixture(scope="module")
def ref():
    return run.load_module(REPO, "reference", "smallthinker")


def build(ref, **changes):
    served = run.load_module(REPO, "served", "smallthinker")
    return served.build({"model": dict(TINY, **changes)}, SEED, ref)


@pytest.fixture(scope="module")
def lm(ref):
    return build(ref)


def reference_logits(ref, seq, cfg=TINY):
    hid, emb = ref.final_hidden(cfg, SEED, [np.asarray(seq, np.int32)])
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.head_logits(emb, hid[0][:len(seq)]))


def serve_by_hand(lm, seq, prefill, slot=1, slots=3, impl="xla"):
    """Logits of every position of ``seq``: ``prefill`` positions in
    chunks of ``CHUNK`` rows, then one decode step a token, through
    both cache groups as the engine's programs call the two forwards.
    Every pool starts as NaN: the null block, blocks of nobody, and
    whatever a ring entry or a block's tail holds that the sequence has
    not written."""
    groups = lm.cache_groups()
    need = -(-(len(seq) + CHUNK) // BS)
    blocks = {"global": 1 + need, "window": 1 + RING}
    n = [0] * lm.n_layers
    for g in groups:
        for i in g["layers"]:
            n[i] = blocks[g["name"]] + 2
    pools = [jnp.full_like(p, jnp.nan)
             for p in PagedKVCache(lm.cache_shapes(BS), n).pools]
    rs = np.random.RandomState(0)
    tbl_g = np.zeros(need + 2, np.int32)
    tbl_g[:need] = 1 + rs.permutation(need + 1)[:need]
    tbl_w = (1 + rs.permutation(RING + 1)[:RING]).astype(np.int32)
    got, p0, cnt = [], 0, None
    while p0 < prefill:
        clen = min(CHUNK, prefill - p0)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :clen] = seq[p0:p0 + clen]
        lg, pools, _, cnt = lm.forward_prefill_chunk(
            lm._params, jnp.asarray(toks), jnp.int32(p0), jnp.int32(clen),
            pools, tuple(jnp.asarray({"global": tbl_g, "window": tbl_w}[
                g["name"]]) for g in groups))
        got.append(np.asarray(lg)[:clen])
        p0 += clen
    tables = []
    for g in groups:
        own = {"global": tbl_g, "window": tbl_w}[g["name"]]
        tables.append(np.zeros((slots, len(own)), np.int32))
        tables[-1][slot] = own
    live = np.zeros(slots, bool)
    live[slot] = True
    for t in range(prefill, len(seq)):
        toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        toks[slot], pos[slot] = seq[t], t
        lg, pools, _, cnt = lm.forward_decode_paged(
            lm._params, jnp.asarray(toks), jnp.asarray(pos), pools,
            tuple(jnp.asarray(t) for t in tables), impl,
            live=jnp.asarray(live))
        got.append(np.asarray(lg)[slot][None])
    return np.concatenate(got, 0), np.asarray(cnt)


# -- the class against the reference ----------------------------------------
#: (positions in all, of them prefilled): contexts below the window,
#: a chunk boundary inside it, and rings that wrap once to three times
CONTEXTS = [(5, 3), (11, 8), (24, 13), (33, 32), (40, 21), (40, 40)]


@pytest.mark.parametrize("total,prefill", CONTEXTS)
def test_chunks_then_decode_match_the_references_full_pass(ref, lm, total,
                                                           prefill):
    seq = np.random.default_rng(total).integers(0, 97, total)
    got, cnt = serve_by_hand(lm, seq, prefill)
    want = reference_logits(ref, seq)
    assert np.isfinite(got).all()
    worst = np.abs(got - want).max()
    assert worst <= LOGIT_TOL, (worst, np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).all()
    if prefill < total:     # a step: one live lane, 3 experts a layer
        assert int(cnt[0]) == 12 and int(cnt[2:].sum()) == 12


def test_the_decode_kernels_serve_the_same_logits(ref, lm):
    """The same pass with the Pallas decode kernels (interpret mode):
    the windowed call through the ring and the plain one."""
    seq = np.random.default_rng(1).integers(0, 97, 30)
    got, _ = serve_by_hand(lm, seq, 19, impl="pallas")
    assert np.abs(got - reference_logits(ref, seq)).max() <= LOGIT_TOL


def test_the_reference_reads_the_weights_the_program_holds(ref, lm):
    emb, layers = ref.make_params(TINY, SEED)
    assert len(layers) == 4
    for name in ("Wq", "W1", "W_r", "input_layernorm"):
        np.testing.assert_array_equal(
            np.asarray(layers[2][name], np.float32),
            np.asarray(lm._params["layers"][2][name], np.float32))
    np.testing.assert_array_equal(np.asarray(emb["head"]),
                                  np.asarray(lm._params["head"]))
    assert lm._params["head"].shape == (32, 97)         # not tied
    assert lm.cache_groups() == [
        {"name": "global", "window": None, "layers": [0]},
        {"name": "window", "window": 8, "layers": [1, 2, 3]}]


# -- one layer of each kind against its equations ---------------------------------
def _last_logits(model, seq):
    got, _ = serve_by_hand(model, np.asarray(seq), len(seq))
    return got[-1]


#: name -> (window flag, rope flag, what moving an early key does)
KINDS = {"global_nope": (0, 0), "global_rotary": (0, 1),
         "window_rotary": (1, 1)}


@pytest.mark.parametrize("kind", list(KINDS))
def test_one_layer_of_each_kind_follows_its_equations(ref, kind):
    """A one-layer model, the last row's logits. Without positions the
    order of the earlier keys cannot matter and with rotary positions
    it does; a window layer does not see a key ``window`` or more
    positions back, a global layer does."""
    win, rope = KINDS[kind]
    one = build(ref, num_hidden_layers=1, sliding_window_layout=[win],
                rope_layout=[rope])
    seq = np.random.default_rng(9).integers(0, 97, 21)
    base = _last_logits(one, seq)
    swapped = seq.copy()
    swapped[[15, 17]] = seq[[17, 15]]           # both inside any window
    moved = np.abs(_last_logits(one, swapped) - base).max()
    far = seq.copy()
    far[20 - 8] = (seq[20 - 8] + 1) % 97        # the first key out of reach
    reach = np.abs(_last_logits(one, far) - base).max()
    near = seq.copy()
    near[20 - 7] = (seq[20 - 7] + 1) % 97       # the last key in reach
    assert np.abs(_last_logits(one, near) - base).max() > 1e-5
    if rope:
        assert moved > 1e-5
    else:
        assert moved <= LOGIT_TOL
    if win:
        assert reach == 0.0
    else:
        assert reach > 1e-5


def test_the_router_reads_the_blocks_input(ref):
    """Layer 0's router sees the embedding row itself: not the normed
    row (the norm's weights here change signs, so its choice differs)
    and not the stream after attention."""
    one = build(ref, num_hidden_layers=1, sliding_window_layout=[0],
                rope_layout=[0])
    w = one._params["layers"][0]
    flip = jnp.asarray(np.random.default_rng(2).choice([-1.0, 1.0], 32),
                       jnp.float32)
    one._params["layers"][0] = dict(w, input_layernorm=flip)
    tok = 11
    x = np.asarray(one._params["embed"][tok], np.float64)
    w_r = np.asarray(w["W_r"], np.float64)
    top = lambda v: set(np.argsort(v @ w_r)[-3:].tolist())  # noqa: E731
    normed = x / np.sqrt((x * x).mean() + 1e-6) * np.asarray(flip)
    assert top(x) != top(normed)
    _, cnt = serve_by_hand(one, np.asarray([tok, tok]), 1)
    chosen = set(np.nonzero(cnt[2:])[0].tolist())
    # the second token's K and V are the first's: attention returns v,
    # so the stream after attention is x + v Wo, and the choice from it
    # is not x's either
    assert chosen == top(x)


def test_weights_are_the_softmax_over_the_chosen_logits():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0, 0.0],
                          [0.1, 0.2, 0.3, 0.4, 0.5]])
    experts, g = route_softmax(logits, 2, jnp.asarray([True, False]))
    assert experts.tolist() == [[3, 0], [5, 5]]     # a dead row: no expert
    e = np.exp([3.0, 2.0])
    np.testing.assert_allclose(np.asarray(g[0]), e / e.sum(), rtol=1e-6)
    assert g[1].tolist() == [0.0, 0.0]


@pytest.mark.parametrize("impl", ["ragged", "pallas"])
def test_the_experts_gate_is_relu(impl):
    rs = np.random.RandomState(4)
    sizes = np.array([3, 0, 9, 4], np.int32)
    x = rs.randn(16, 32).astype(np.float32)
    w1, w3 = rs.randn(2, 4, 32, 128).astype(np.float32) * 0.2
    w2 = rs.randn(4, 128, 32).astype(np.float32) * 0.2
    kw = {"interpret": True} if impl == "pallas" else {}
    got = np.asarray(expert_ffn(jnp.asarray(x), jnp.asarray(w1),
                                jnp.asarray(w3), jnp.asarray(w2),
                                jnp.asarray(sizes), impl=impl, gate="relu",
                                **kw))
    e = np.repeat(np.arange(4), sizes)
    want = np.stack([(np.maximum(x[i] @ w1[e[i]], 0) * (x[i] @ w3[e[i]]))
                     @ w2[e[i]] for i in range(16)])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    silu = np.asarray(expert_ffn(jnp.asarray(x), jnp.asarray(w1),
                                 jnp.asarray(w3), jnp.asarray(w2),
                                 jnp.asarray(sizes), impl=impl, **kw))
    assert np.abs(silu - want).max() > 1e-2


# -- through the engine -----------------------------------------------------------
@pytest.fixture(scope="module")
def engine(lm):
    eng = GenerationEngine(lm, **ENGINE)
    eng.warmup()
    yield eng
    eng.stop()


PROMPTS = [np.random.default_rng([5, i]).integers(0, 97, n).tolist()
           for i, n in enumerate((3, 9, 17, 30, 44))]


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_served_tokens_are_the_references_greedy_tokens(engine, ref, i):
    c0 = engine.metrics.compiles
    toks = engine.generate(PROMPTS[i], max_tokens=11,
                           temperature=0.0)["tokens"]
    want = reference_logits(ref, PROMPTS[i] + toks)
    rows = want[len(PROMPTS[i]) - 1:-1]
    gap = rows.max(-1) - rows[np.arange(11), toks]
    assert gap.max() <= LOGIT_TOL
    assert engine.metrics.compiles == c0


def test_group_counters_follow_the_lengths(engine):
    """One lane decoding from 20 to 25 positions: a global layer reads
    its length, the three window layers 8 each; one lifetime would read
    the length in all four."""
    a = engine.stats()["scheduler"]
    engine.generate(PROMPTS[2] + [1, 2, 3], max_tokens=6, temperature=0.0)
    b = engine.stats()["scheduler"]
    lens = np.arange(21, 26)        # five steps after the chunk's token
    d = lambda k, g=None: (b[k][g] - a[k][g]) if g else b[k] - a[k]  # noqa: E731
    assert d("kv_rows_attended", "global") == lens.sum()
    assert d("kv_rows_attended", "window") == 3 * 8 * len(lens)
    assert d("kv_rows_full") == 4 * lens.sum()
    g = engine.stats()["paged"]["groups"]
    assert g["window"]["ring_blocks"] == RING and g["window"]["window"] == 8
    assert g["window"]["blocks_peak_used"] <= RING
    assert g["window"]["blocks_free"] == g["window"]["blocks_total"]
    assert g["global"]["blocks_free"] == g["global"]["blocks_total"]
    assert g["window"]["block_bytes"] == 3 * g["global"]["block_bytes"]


# -- the benchmark's comparison on the tiny configuration -----------------------------
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    import benchmark_testlib as lib
    root = lib.make_root(tmp_path_factory.mktemp("smallthinker"))
    b = os.path.join(root, "benchmark")
    real = lib.load(lib.BENCH, "configs", "smallthinker-21b-a3b.json")
    cfg = dict(real, name="tiny-st", model=TINY,
               engine=dict(real["engine"], num_slots=4, max_seq_len=64,
                           prompt_buckets=[CHUNK], block_size=BS,
                           num_blocks=65, prefill_chunk_tokens=CHUNK,
                           kv_dtype="f32"),
               warmup={"buckets": [CHUNK]})
    lib.dump(cfg, b, "configs", "tiny-st.json")
    spec = lib.load(root, "BENCHMARK.json")
    spec["configs"].append({"name": "tiny-st", "source": real["source"],
                            "file": "benchmark/configs/tiny-st.json",
                            "reduced": real["reduced"], "why": "tiny"})
    traffic = lib.load(b, "traffic", "tiny_decode.json")
    traffic["check_requests"] = 200
    traffic["limits"] = lib.load(
        lib.BENCH, "traffic", "long_context_backlog.json")["limits"]
    lib.dump(traffic, b, "traffic", "tiny_st_decode.json")
    cell = {"name": "tiny-st.decode", "config": "tiny-st",
            "traffic": "tiny_st_decode", "chips": 1, "why": "tiny"}
    spec["workloads"].append(cell)
    real_cell = "smallthinker-21b-a3b.long_context_backlog"
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
    out, obs = run.run_cell("tiny-st.decode", 11, 4.0, False,
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
        # the readers that take /stats find the groups' counters
        for name in ("window_kv_rows_read_share",
                     "kv_window_ring_live_share",
                     "kv_global_pool_live_share"):
            v = run.read_metric(name, obs, os.path.join(tiny_root,
                                                        "benchmark"))
            assert v is not None and 0 < v <= 100, (name, v)

"""Block-level prefix sharing + persistent session KV cache (ISSUE
11): refcounted allocator semantics, chained block hashing, the LRU
prefix index and session store, engine-level sharing with
copy-on-write (token identity against the uncached greedy oracle),
adversarial interactions (NaN quarantine must leave shared blocks
bit-unchanged, recompute-recovery must rebuild refcounts with zero
leaked blocks), persistent sessions (turn N+1 prefills only the
unseen tail, eviction reclaims every block), and the session_id
plumbing through the HTTP surface and the fleet router's
session-affinity routing."""
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.serving import (BlockAllocator, ClientError,
                                        FaultInjector, FleetRouter,
                                        GenerationEngine,
                                        InferenceServer, ReplicaFleet)
from deeplearning4j_tpu.serving.paging import (PrefixIndex, Session,
                                               SessionStore,
                                               chain_hashes)
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

from test_fault_tolerance import NAN_TRIGGER, VOCAB, _PoisonLM


def _lm(seed=0):
    return CausalTransformerLM(vocab_size=VOCAB, d_model=32, n_layers=2,
                               n_heads=4, max_seq_len=32, seed=seed,
                               implementation="plain").init()


def _ref_greedy(lm, prompt, n):
    """Uncached full-prefix greedy decode — the oracle every shared,
    COW'd, or session-resumed path must reproduce exactly."""
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = np.asarray(lm.logits(np.asarray(toks)[None]))[0, -1]
        t = int(logits.argmax())
        out.append(t)
        toks.append(t)
    return out


@pytest.fixture(scope="module")
def lm():
    return _lm()


def _mkeng(lm, sharing=True, **kw):
    opts = dict(num_slots=3, max_queue=64, min_prompt_bucket=4,
                cache="paged", block_size=8, prefill_chunk_tokens=8,
                enable_prefix_sharing=sharing)
    opts.update(kw)
    eng = GenerationEngine(lm, **opts)
    eng.warmup()
    return eng


# a 16-token prompt = exactly two full 8-token blocks, so both blocks
# land in the prefix index when it completes
_P16 = [1, 5, 2, 9, 3, 7, 4, 6, 8, 10, 1, 5, 2, 9, 3, 7]


# ---------------------------------------------------------------------------
# allocator refcounts
# ---------------------------------------------------------------------------
class TestAllocatorRefcounts:
    def test_share_defers_release_until_last_free(self):
        a = BlockAllocator(5)
        g = a.alloc(2)
        a.share(g)                       # refcount 2
        a.free(g)                        # 2 -> 1: still owned
        assert a.free_count == 2
        a.free(g)                        # 1 -> 0: released
        assert a.free_count == 4

    def test_share_unallocated_raises(self):
        a = BlockAllocator(5)
        g = a.alloc(1)
        a.free(g)
        with pytest.raises(ValueError, match="unallocated"):
            a.share(g)                   # freed block can't be pinned

    def test_free_batch_over_refcount_is_double_free(self):
        a = BlockAllocator(5)
        g = a.alloc(1)
        with pytest.raises(ValueError, match="double free"):
            a.free(g + g)                # one ref, two frees in batch
        # the failed batch must not have decremented anything
        assert a.free_count == 3
        a.free(g)
        assert a.free_count == 4

    def test_shared_stat_counts_multi_ref_blocks(self):
        a = BlockAllocator(6)
        g = a.alloc(3)
        a.share(g[:2])
        assert a.stats()["shared"] == 2
        assert a.shared_count == 2
        a.free(g[:2])
        assert a.stats()["shared"] == 0


# ---------------------------------------------------------------------------
# chained hashing / prefix index / session store
# ---------------------------------------------------------------------------
class TestChainHashes:
    def test_full_blocks_only(self):
        t = np.arange(20, dtype=np.int32)
        assert len(chain_hashes(t, 8)) == 2          # 20 // 8
        assert len(chain_hashes(t[:7], 8)) == 0

    def test_chained_not_positional(self):
        """A block's digest encodes its whole prefix: two sequences
        sharing block 1's tokens but differing in block 0 must NOT
        collide — matching block 1 alone would splice the wrong
        prefix."""
        a = np.arange(16, dtype=np.int32)
        b = a.copy()
        b[0] += 1
        ha, hb = chain_hashes(a, 8), chain_hashes(b, 8)
        assert ha[0] != hb[0]
        assert ha[1] != hb[1]            # diverges despite equal tokens

    def test_deterministic(self):
        t = np.arange(16, dtype=np.int32)
        assert chain_hashes(t, 8) == chain_hashes(t.copy(), 8)


class TestPrefixIndex:
    def test_longest_chain_match(self):
        idx = PrefixIndex()
        h = chain_hashes(np.arange(24, dtype=np.int32), 8)
        idx.register(h[0], 11)
        idx.register(h[1], 12)
        assert idx.match(h) == [11, 12]  # h[2] unknown: chain stops
        assert idx.match(chain_hashes(
            np.arange(1, 25, dtype=np.int32), 8)) == []

    def test_register_dedups(self):
        idx = PrefixIndex()
        h = chain_hashes(np.arange(8, dtype=np.int32), 8)
        assert idx.register(h[0], 7) is True
        assert idx.register(h[0], 8) is False        # digest already held

    def test_lru_eviction_order(self):
        idx = PrefixIndex(capacity=2)
        hs = [chain_hashes(np.full(8, i, np.int32), 8)[0]
              for i in range(3)]
        idx.register(hs[0], 1)
        idx.register(hs[1], 2)
        idx.match([hs[0]])               # touch 0: now 1 is LRU
        idx.register(hs[2], 3)
        assert idx.evict_over_capacity() == [2]
        assert sorted(idx.clear()) == [1, 3]
        assert len(idx) == 0


class TestSessionStore:
    def test_put_get_and_same_id_replacement(self):
        st = SessionStore(capacity=4)
        displaced = st.put("a", [1, 2, 3], [10])
        assert displaced == []
        old = st.get("a")
        assert isinstance(old, Session) and old.blocks == [10]
        displaced = st.put("a", [1, 2, 3, 4], [10, 11])
        assert [s.blocks for s in displaced] == [[10]]
        assert st.get("a").blocks == [10, 11]

    def test_capacity_lru(self):
        st = SessionStore(capacity=2)
        st.put("a", [1], [1])
        st.put("b", [2], [2])
        st.get("a")                      # touch: b is now LRU
        displaced = st.put("c", [3], [3])
        assert [s.blocks for s in displaced] == [[2]]
        assert "a" in st and "c" in st and "b" not in st
        assert sorted(b for s in st.clear() for b in s.blocks) == [1, 3]


# ---------------------------------------------------------------------------
# engine-level sharing: identity, COW, accounting
# ---------------------------------------------------------------------------
class TestEngineSharing:
    def test_identical_prompts_share_and_match_oracle(self, lm):
        eng = _mkeng(lm)
        try:
            want = _ref_greedy(lm, _P16, 6)
            r1 = eng.generate(_P16, max_tokens=6, timeout_ms=60_000)
            hits0 = eng.metrics.prefix_hits
            r2 = eng.generate(_P16, max_tokens=6, timeout_ms=60_000)
            assert r1["tokens"] == want
            assert r2["tokens"] == want
            assert eng.metrics.prefix_hits == hits0 + 1
            assert eng.metrics.prefix_tokens_matched >= 15
            # an exact-duplicate prompt COWs its final matched block
            # (the L-1 cap) rather than writing into a shared one
            assert eng.metrics.cow_copies >= 1
        finally:
            eng.stop()

    def test_shared_prefix_uses_fewer_blocks(self, lm):
        """Same three-request workload with a common 16-token prefix,
        the last two requests LIVE at the same time: the sharing
        engine's peak block footprint must be strictly below the
        unshared engine's (the shared prefix is resident once, not
        once per request)."""
        p_a = _P16 + [11, 12, 13, 14]
        p_b = _P16 + [21, 22, 23, 24]
        p_c = _P16 + [31, 32, 33, 34]
        peaks = {}
        outs = {}
        for sharing in (True, False):
            eng = _mkeng(lm, sharing=sharing)
            try:
                eng.generate(p_a, max_tokens=4, timeout_ms=60_000)
                s_b = eng.stream(p_b, max_tokens=4, timeout_ms=60_000)
                toks_b = [next(s_b)["token"]]
                s_c = eng.stream(p_c, max_tokens=4, timeout_ms=60_000)
                next(s_c)                # both requests now hold blocks
                peaks[sharing] = eng.metrics.blocks_peak_used
                toks_b += [c["token"] for c in s_b if "token" in c]
                list(s_c)
                outs[sharing] = toks_b
            finally:
                eng.stop()
        assert outs[True] == outs[False] == _ref_greedy(lm, p_b, 4)
        assert peaks[True] < peaks[False]

    def test_cow_on_divergent_suffix_matches_oracle(self, lm):
        """Request B shares A's first block but diverges inside the
        second: only the common chain is matched, and B's outputs are
        bitwise the unshared oracle's."""
        p_b = _P16[:12] + [30, 31, 32, 33]
        eng = _mkeng(lm)
        try:
            eng.generate(_P16, max_tokens=4, timeout_ms=60_000)
            r = eng.generate(p_b, max_tokens=4, timeout_ms=60_000)
            assert r["tokens"] == _ref_greedy(lm, p_b, 4)
            # only block 0's chain matched (block 1's digest diverged)
            assert eng.metrics.prefix_tokens_matched >= 8
        finally:
            eng.stop()

    def test_zero_recompiles_with_sharing(self, lm):
        eng = _mkeng(lm)
        try:
            eng.generate(_P16, max_tokens=4, timeout_ms=60_000)
            before = eng.metrics.compiles
            eng.generate(_P16, max_tokens=4, timeout_ms=60_000)  # COW hit
            eng.generate(_P16ALT, max_tokens=4, timeout_ms=60_000)
            eng.generate(_P16 + [17, 18], max_tokens=4,
                         timeout_ms=60_000)                      # partial
            assert eng.metrics.compiles == before
        finally:
            eng.stop()

    def test_stats_and_gauges_surface(self, lm):
        eng = _mkeng(lm)
        try:
            eng.generate(_P16, max_tokens=4, timeout_ms=60_000)
            eng.generate(_P16, max_tokens=4, timeout_ms=60_000)
            p = eng.stats()["paged"]
            pc = p["prefix_cache"]
            assert pc["enabled"] is True
            assert pc["prefix_hits"] >= 1
            assert pc["prefix_blocks"] == 2          # _P16 = 2 blocks
            assert pc["cow_copies"] >= 1
            assert 0.0 <= p["fragmentation"] <= 1.0
            assert eng.clear_prefix_cache() == 2
            assert eng.stats()["paged"]["prefix_cache"]["prefix_blocks"] \
                == 0
        finally:
            eng.stop()


_P16ALT = [2, 6, 3, 10, 4, 8, 5, 7, 9, 11, 2, 6, 3, 10, 4, 8]


# ---------------------------------------------------------------------------
# adversarial interactions: quarantine + recovery
# ---------------------------------------------------------------------------
class TestSharingUnderFaults:
    def test_quarantined_nan_leaves_shared_blocks_bit_unchanged(self):
        """A poisoned request that SHARES a healthy prefix writes its
        NaN K/V only into its own (fresh or COW'd) blocks: the shared
        blocks' pool rows are bitwise identical before and after, and
        a healthy re-reader's tokens don't move."""
        from deeplearning4j_tpu.serving import PoisonRequestError
        plm = _PoisonLM(vocab_size=VOCAB, d_model=32, n_layers=2,
                        n_heads=4, max_seq_len=32, seed=0,
                        implementation="plain").init()
        eng = _mkeng(plm)
        try:
            base = eng.generate(_P16, max_tokens=4,
                                timeout_ms=60_000)["tokens"]
            shared_blocks = sorted(eng._prefix_index.blocks())
            assert shared_blocks
            before = [np.asarray(k)[shared_blocks] for k in eng._pools]
            with pytest.raises(PoisonRequestError):
                eng.generate(_P16 + [NAN_TRIGGER], max_tokens=4,
                             timeout_ms=60_000)
            assert eng.metrics.quarantined == 1
            after = [np.asarray(k)[shared_blocks] for k in eng._pools]
            for b, a in zip(before, after):
                np.testing.assert_array_equal(b, a)
            again = eng.generate(_P16, max_tokens=4,
                                 timeout_ms=60_000)["tokens"]
            assert again == base
        finally:
            eng.stop()

    def test_recovery_rebuilds_refcounts_zero_leaks(self, lm):
        """A corrupting fault mid-storm forces recompute-recovery
        while shared blocks are live: outputs stay identical to the
        fault-free run, and after drain + cache clears every block is
        back in the pool — the wholesale allocator reset rebuilt the
        refcounts without leaking a single pin."""
        reqs = [(_P16, 5), (_P16, 5), (_P16ALT, 5), (_P16 + [17], 4)]

        def run_all(eng):
            out = [None] * len(reqs)

            def go(i):
                p, n = reqs[i]
                out[i] = eng.generate(p, max_tokens=n,
                                      timeout_ms=120_000)["tokens"]
            ts = [threading.Thread(target=go, args=(i,))
                  for i in range(len(reqs))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return out

        clean = _mkeng(lm)
        try:
            baseline = run_all(clean)
        finally:
            clean.stop()
        eng = _mkeng(lm)
        try:
            run_all(eng)                 # registers the shared prefix
            inj = FaultInjector(plan={"prefill": [2]},
                                corrupting=("prefill",))
            eng.set_fault_injector(inj)
            out = run_all(eng)
            assert out == baseline
            assert eng.metrics.recoveries >= 1
            eng.set_fault_injector(None)
            eng.evict_sessions()
            eng.clear_prefix_cache()
            assert eng._allocator.free_count == eng._allocator.capacity
            assert eng._allocator.shared_count == 0
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# persistent sessions
# ---------------------------------------------------------------------------
class TestSessions:
    def test_turn2_prefills_only_the_tail(self, lm):
        eng = _mkeng(lm)
        try:
            r1 = eng.generate(_P16, max_tokens=5, session_id="alice",
                              timeout_ms=60_000)
            assert r1["tokens"] == _ref_greedy(lm, _P16, 5)
            assert eng.metrics.sessions_live == 1
            turn2 = _P16 + r1["tokens"] + [12, 13, 14]
            hits0 = eng.metrics.session_hits
            pf0 = eng.metrics.prefill_tokens
            r2 = eng.generate(turn2, max_tokens=4, session_id="alice",
                              timeout_ms=60_000)
            assert r2["tokens"] == _ref_greedy(lm, turn2, 4)
            assert eng.metrics.session_hits == hits0 + 1
            # the session pinned prompt+gen[:-1] = 20 tokens of the
            # 24-token turn-2 prompt: well under half re-prefilled
            assert eng.metrics.prefill_tokens - pf0 < len(turn2) // 2
        finally:
            eng.stop()

    def test_eviction_reclaims_every_block(self, lm):
        eng = _mkeng(lm, session_capacity=2)
        try:
            for i, sid in enumerate(("a", "b", "c")):
                eng.generate([1 + i] * 9, max_tokens=4, session_id=sid,
                             timeout_ms=60_000)
            # capacity 2: "a" was LRU-displaced at "c"'s pin
            assert eng.metrics.sessions_live == 2
            assert eng.metrics.session_evictions >= 1
            assert eng.evict_sessions() == 2
            assert eng.metrics.sessions_live == 0
            eng.clear_prefix_cache()
            assert eng._allocator.free_count == eng._allocator.capacity
        finally:
            eng.stop()

    def test_session_requires_paged_sharing(self, lm):
        slots = GenerationEngine(lm, num_slots=2, max_queue=8,
                                 min_prompt_bucket=4)
        try:
            with pytest.raises(ClientError, match="paged"):
                slots.generate([1, 2], max_tokens=2, session_id="x")
        finally:
            slots.stop()
        off = _mkeng(lm, sharing=False)
        try:
            with pytest.raises(ClientError, match="prefix sharing"):
                off.generate([1, 2], max_tokens=2, session_id="x")
        finally:
            off.stop()

    def test_session_id_validation(self, lm):
        eng = _mkeng(lm)
        try:
            with pytest.raises(ClientError, match="session_id"):
                eng.generate([1, 2], max_tokens=2, session_id="")
            with pytest.raises(ClientError, match="session_id"):
                eng.generate([1, 2], max_tokens=2, session_id="s" * 300)
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# HTTP + fleet plumbing
# ---------------------------------------------------------------------------
class TestHTTPAndFleet:
    def _post(self, port, path, payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_generate_route_session_id(self, lm):
        server = InferenceServer(port=0)
        g = server.register_generator(
            "lm", lm, num_slots=2, max_seq_len=32, prompt_buckets=[8],
            cache="paged", block_size=8, prefill_chunk_tokens=8)
        g.warmup()
        try:
            st, r1 = self._post(server.port, "/v1/models/lm/generate",
                                {"prompt": _P16, "max_tokens": 4,
                                 "session_id": "s1"})
            assert st == 200
            turn2 = _P16 + r1["tokens"] + [3, 4]
            st, r2 = self._post(server.port, "/v1/models/lm/generate",
                                {"prompt": turn2, "max_tokens": 3,
                                 "session_id": "s1"})
            assert st == 200
            assert g.metrics.session_hits >= 1
            assert r2["tokens"] == _ref_greedy(lm, turn2, 3)
            st, body = self._post(server.port, "/v1/models/lm/generate",
                                  {"prompt": [1, 2], "max_tokens": 2,
                                   "session_id": 42})
            assert st == 400 and "session_id" in body["error"]
        finally:
            server.stop()

    def test_fleet_session_affinity(self, lm):
        """Turns of one session land on ONE replica — the one holding
        its pinned blocks — instead of rotating across the fleet."""
        def factory():
            server = InferenceServer(port=0)
            g = server.register_generator(
                "lm", lm, num_slots=2, max_seq_len=32,
                prompt_buckets=[8], cache="paged", block_size=8,
                prefill_chunk_tokens=8)
            g.warmup()
            return server
        fleet = ReplicaFleet(poll_interval_s=None)
        for _ in range(2):
            f = factory()
            fleet.add(f, factory=None)
        router = FleetRouter(fleet)
        try:
            hist = list(_P16)
            for _ in range(4):
                st, body = router.post(
                    "/v1/models/lm/generate",
                    {"prompt": hist, "max_tokens": 2,
                     "session_id": "conv-1"})
                assert st == 200
                hist = hist + body["tokens"] + [3]
            routed = sorted(r.routed for r in fleet.replicas())
            assert routed == [0, 4]      # every turn on one replica
            assert fleet.metrics.session_affinity_hits >= 3
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

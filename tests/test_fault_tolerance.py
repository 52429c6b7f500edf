"""Fault-tolerant serving tests (ISSUE 4): deterministic fault
injection, supervised engine loops (transient retry with backoff,
recompute-recovery after cache-corrupting failures — zero accepted
requests lost, token-identical outputs, zero post-warmup recompiles),
poison-request quarantine (per-lane finite-logits guard), graceful
drain + /healthz//readyz + SIGTERM wiring, micro-batcher supervision
and deadline-drop-at-dequeue, and crash-safe elastic checkpointing."""
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.serving import (CorruptedStateFault,
                                        DeadlineExceededError,
                                        DrainingError, FaultInjector,
                                        GenerationEngine,
                                        InferenceEngine, InferenceServer,
                                        MicroBatcher, PoisonRequestError,
                                        TransientFault)
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

VOCAB = 64
# poison rig token ids (see _PoisonLM); kept out of every test prompt
POISON = VOCAB - 1
TRIGGER = VOCAB - 2
NAN_TRIGGER = VOCAB - 3


def _lm(seed=0):
    return CausalTransformerLM(vocab_size=VOCAB, d_model=32, n_layers=2,
                               n_heads=4, max_seq_len=32, seed=seed,
                               implementation="plain").init()


class _PoisonLM(CausalTransformerLM):
    """NaN rig for quarantine tests. Prompts containing NAN_TRIGGER
    make the prefill logits non-finite; prompts containing TRIGGER
    force the first sampled token to POISON, whose decode step then
    produces NaN logits — a request that goes poisonous MID-DECODE,
    with healthy batchmates in the same device call. POISON is
    suppressed everywhere else so no clean request can ever sample it
    organically.

    Like a real activation blow-up, a poisoned call also writes NaN
    into the K/V rows the request owns (its slot lane / block
    positions) — the slot or blocks are then freed WITHOUT zeroing, so
    reuse tests prove the kernels' stale-tail V-masking keeps
    successors clean (0 * NaN = NaN otherwise)."""

    def _rig(self, logits):
        supp = jnp.where(jnp.arange(self.vocab_size) == POISON,
                         -1e9, 0.0)
        return logits + supp

    def forward_prefill(self, params, tokens, key_mask=None):
        logits, ks, vs = super().forward_prefill(params, tokens, key_mask)
        logits = self._rig(logits)
        trig = jnp.any(tokens == TRIGGER, axis=-1)
        hot = jnp.where(jnp.arange(self.vocab_size) == POISON,
                        50.0, -50.0)
        logits = jnp.where(trig[:, None, None], hot[None, None, :],
                           logits)
        nan_trig = jnp.any(tokens == NAN_TRIGGER, axis=-1)
        logits = jnp.where(nan_trig[:, None, None], jnp.nan, logits)
        bad = nan_trig[:, None, None, None]
        ks = [jnp.where(bad, jnp.nan, k) for k in ks]
        vs = [jnp.where(bad, jnp.nan, v) for v in vs]
        return logits, ks, vs

    def forward_decode(self, params, tokens, pos, k_caches, v_caches,
                       impl="auto"):
        logits, kcs, vcs = super().forward_decode(
            params, tokens, pos, k_caches, v_caches, impl)
        logits = self._rig(logits)
        bad = (tokens == POISON)
        # poison the K/V this step wrote at `pos` for the bad rows
        rows = jnp.arange(tokens.shape[0])
        nan3 = jnp.where(bad[:, None, None], jnp.nan, 0.0)
        kcs = [k.at[rows, :, pos].set(k[rows, :, pos] + nan3)
               for k in kcs]
        vcs = [v.at[rows, :, pos].set(v[rows, :, pos] + nan3)
               for v in vcs]
        return jnp.where(bad[:, None], jnp.nan, logits), kcs, vcs

    def forward_decode_paged(self, params, tokens, pos, pools,
                             block_tables, impl="auto", state=()):
        logits, pools, state = super().forward_decode_paged(
            params, tokens, pos, pools, block_tables, impl, state)
        logits = self._rig(logits)
        bad = (tokens == POISON)
        # poison the pool row (K and V) this step wrote for the bad rows
        Bs = pools[0].shape[2]
        blk = jnp.take_along_axis(block_tables, (pos // Bs)[:, None],
                                  axis=1)[:, 0]
        off = pos % Bs
        nan3 = jnp.where(bad[:, None, None], jnp.nan, 0.0)
        pools = [p.at[blk, :, off].set(p[blk, :, off] + nan3)
                 for p in pools]
        return jnp.where(bad[:, None], jnp.nan, logits), pools, state

    def forward_prefill_chunk(self, params, tokens, p0, chunk_len,
                              pools, block_table, state=(),
                              last_only=False):
        # same rig for the paged chunked-prefill path: logits [C, V],
        # or [1, V] as the engine asks (every rig broadcasts over rows)
        logits, pools, state = super().forward_prefill_chunk(
            params, tokens, p0, chunk_len, pools, block_table, state,
            last_only)
        logits = self._rig(logits)
        trig = jnp.any(tokens == TRIGGER)
        hot = jnp.where(jnp.arange(self.vocab_size) == POISON,
                        50.0, -50.0)
        logits = jnp.where(trig, hot[None, :], logits)
        nan_trig = jnp.any(tokens == NAN_TRIGGER)
        logits = jnp.where(nan_trig, jnp.nan, logits)
        # poison every pool position this chunk wrote (its own blocks)
        C = tokens.shape[1]
        Bs = pools[0].shape[2]
        gpos = p0 + jnp.arange(C)
        blk = block_table[gpos // Bs]
        off = gpos % Bs
        nan3 = jnp.where(nan_trig, jnp.nan, 0.0)
        pools = [p.at[blk, :, off].set(p[blk, :, off] + nan3)
                 for p in pools]
        return logits, pools, state


#: mixed-length workload; prompts avoid the poison-rig token ids
_REQS = [(np.random.RandomState(i).randint(0, 32, 3 + 2 * i).tolist(),
          5 + i) for i in range(6)]


def _run_all(eng, reqs=_REQS, seed0=0):
    """Submit all requests concurrently; returns token lists (None for
    a failed request) and the raised errors."""
    results = [None] * len(reqs)
    errors = [None] * len(reqs)

    def go(i):
        p, n = reqs[i]
        try:
            results[i] = eng.generate(
                p, max_tokens=n, temperature=0.8, top_k=8,
                seed=seed0 + i, timeout_ms=120_000)["tokens"]
        except Exception as e:  # noqa: BLE001 — recorded for asserts
            errors[i] = e
    ts = [threading.Thread(target=go, args=(i,)) for i in range(len(reqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return results, errors


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.fixture(scope="module")
def slot_eng(lm):
    """ONE warmed slot-backend engine shared by every chaos scenario
    (via set_fault_injector) — per-test engines would pay the compile
    set over and over."""
    eng = GenerationEngine(lm, num_slots=3, max_queue=64,
                           min_prompt_bucket=4, retry_backoff_ms=0.2,
                           retry_backoff_max_ms=2.0)
    eng.warmup()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def slot_baseline(slot_eng):
    """Fault-free slot-backend outputs — the oracle every chaos run
    must reproduce token-for-token."""
    out, errs = _run_all(slot_eng)
    assert all(e is None for e in errs)
    return out


_PAGED_KW = dict(num_slots=3, max_queue=64, cache="paged", block_size=4,
                 prompt_buckets=[8], prefill_chunk_tokens=8)


@pytest.fixture(scope="module")
def paged_eng(lm):
    eng = GenerationEngine(lm, retry_backoff_ms=0.2,
                           retry_backoff_max_ms=2.0, **_PAGED_KW)
    eng.warmup()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def paged_baseline(paged_eng, slot_baseline):
    out, errs = _run_all(paged_eng)
    assert all(e is None for e in errs)
    assert out == slot_baseline  # backends agree fault-free (PR 3)
    return out


def _chaos_run(eng, inj):
    """Run the workload under an injector on a SHARED warmed engine;
    returns (outputs, errors, Δretries, Δrecoveries, Δcompiles)."""
    m = eng.metrics
    r0, v0, c0 = m.retries, m.recoveries, m.compiles
    eng.set_fault_injector(inj)
    try:
        out, errs = _run_all(eng)
    finally:
        eng.set_fault_injector(None)
    return out, errs, m.retries - r0, m.recoveries - v0, m.compiles - c0


class TestFaultInjector:
    def test_plan_fires_exact_indices(self):
        inj = FaultInjector(plan={"device_step": [2, 4]})
        fired = []
        for _ in range(5):
            try:
                inj.fire("device_step")
                fired.append(False)
            except TransientFault:
                fired.append(True)
        assert fired == [False, True, False, True, False]
        snap = inj.snapshot()
        assert snap["calls"]["device_step"] == 5
        assert snap["fired"]["device_step"] == 2

    def test_rate_stream_is_seed_deterministic(self):
        def pattern(seed):
            inj = FaultInjector(seed=seed, rates={"prefill": 0.3})
            out = []
            for _ in range(50):
                try:
                    inj.fire("prefill")
                    out.append(0)
                except TransientFault:
                    out.append(1)
            return out
        assert pattern(7) == pattern(7)
        assert sum(pattern(7)) > 0  # actually fires at 30%

    def test_seam_independence(self):
        """Interleaving calls at OTHER seams must not shift a seam's
        decision stream (per-seam counters + per-seam RNG)."""
        def pattern(interleave):
            inj = FaultInjector(seed=3, rates={"device_step": 0.5})
            out = []
            for _ in range(30):
                if interleave:
                    inj.fire("client_disconnect")  # separate stream
                try:
                    inj.fire("device_step")
                    out.append(0)
                except TransientFault:
                    out.append(1)
            return out
        assert pattern(False) == pattern(True)

    def test_corrupting_seam_raises_corrupted(self):
        inj = FaultInjector(plan={"device_step": [1]},
                            corrupting=("device_step",))
        with pytest.raises(CorruptedStateFault):
            inj.fire("device_step")

    def test_client_disconnect_returns_instead_of_raising(self):
        inj = FaultInjector(plan={"client_disconnect": [1]})
        assert inj.fire("client_disconnect") is True
        assert inj.fire("client_disconnect") is False

    def test_latency_seam_sleeps(self):
        inj = FaultInjector(plan={"latency": [1]}, latency_ms=30.0)
        t0 = time.perf_counter()
        assert inj.fire("latency") is True
        assert time.perf_counter() - t0 >= 0.025

    def test_config_validation(self):
        with pytest.raises(ValueError):
            FaultInjector(rates={"no_such_seam": 0.1})
        with pytest.raises(ValueError):
            FaultInjector(rates={"prefill": 1.5})
        with pytest.raises(ValueError):
            FaultInjector().fire("no_such_seam")


class TestChaosSlots:
    """Acceptance: injected transient + corrupting faults on the slot
    backend lose zero accepted requests, reproduce the fault-free
    outputs token-for-token, and never recompile post-warmup."""

    def test_transient_faults_retried_token_identical(self, slot_eng,
                                                      slot_baseline):
        inj = FaultInjector(plan={"device_step": [2, 5, 9],
                                  "prefill": [3]})
        out, errs, retries, recoveries, compiles = _chaos_run(
            slot_eng, inj)
        assert all(e is None for e in errs)   # zero requests lost
        assert out == slot_baseline           # token-identical
        assert retries == 4
        assert recoveries == 0
        assert compiles == 0

    def test_corrupting_fault_recovers_token_identical(self, slot_eng,
                                                       slot_baseline):
        inj = FaultInjector(plan={"device_step": [6], "prefill": [2]},
                            corrupting=("device_step", "prefill"))
        out, errs, _, recoveries, compiles = _chaos_run(slot_eng, inj)
        assert all(e is None for e in errs)
        assert out == slot_baseline
        assert recoveries == 2
        assert compiles == 0

    def test_retries_exhausted_falls_back_to_recovery(self, slot_eng,
                                                      slot_baseline):
        # 5 consecutive transient faults vs max_step_retries=2: the
        # loop must give up retrying and rebuild instead of spinning
        inj = FaultInjector(plan={"device_step": [1, 2, 3, 4, 5]})
        slot_eng._max_step_retries = 2
        try:
            out, errs, retries, recoveries, compiles = _chaos_run(
                slot_eng, inj)
        finally:
            slot_eng._max_step_retries = 3
        assert all(e is None for e in errs)
        assert out == slot_baseline
        assert retries >= 2
        assert recoveries >= 1
        assert compiles == 0

    def test_random_rate_chaos_is_lossless(self, slot_eng,
                                           slot_baseline):
        inj = FaultInjector(seed=11, rates={"device_step": 0.05,
                                            "prefill": 0.05})
        out, errs, _, _, compiles = _chaos_run(slot_eng, inj)
        assert all(e is None for e in errs)
        assert out == slot_baseline
        assert compiles == 0

    def test_faults_surface_in_stats(self, slot_eng):
        before = slot_eng.stats()["faults"]["retries"]
        inj = FaultInjector(plan={"device_step": [1]})
        _chaos_run(slot_eng, inj)
        f = slot_eng.stats()["faults"]
        assert f["retries"] == before + 1
        assert set(f) == {"retries", "recoveries", "quarantined",
                          "drains"}


class TestChaosPaged:
    """Same acceptance bar on the paged backend — recovery must also
    rebuild the block allocator (freed blocks reclaimed, re-admission
    re-claims from a fresh pool)."""

    def test_transient_chunk_and_alloc_faults(self, paged_eng,
                                              paged_baseline):
        inj = FaultInjector(plan={"prefill": [2, 6], "alloc": [2],
                                  "device_step": [4]})
        out, errs, retries, _, compiles = _chaos_run(paged_eng, inj)
        assert all(e is None for e in errs)
        assert out == paged_baseline
        assert retries == 4
        assert compiles == 0

    def test_corrupting_faults_recover_and_reclaim_blocks(
            self, paged_eng, paged_baseline):
        inj = FaultInjector(plan={"device_step": [4], "prefill": [2, 9]},
                            corrupting=("device_step", "prefill"))
        out, errs, _, recoveries, compiles = _chaos_run(paged_eng, inj)
        assert all(e is None for e in errs)   # zero requests lost
        assert out == paged_baseline          # token-identical
        assert recoveries == 3
        assert compiles == 0
        # every block returned to the pool after the storm — the only
        # live blocks left are prefix-index pins from post-recovery
        # registrations; releasing them must reclaim the pool exactly
        paged_eng.clear_prefix_cache()
        assert paged_eng._allocator.free_count == \
            paged_eng._allocator.capacity

    def test_mid_prefill_requests_survive_recovery(self, paged_eng,
                                                   paged_baseline):
        # a long prompt is mid-chunked-prefill when the corruption
        # lands (prefill seam call #3 is a chunk of a multi-chunk
        # prompt in this workload); it must restart cleanly
        inj = FaultInjector(plan={"prefill": [3]},
                            corrupting=("prefill",))
        out, errs, _, recoveries, compiles = _chaos_run(paged_eng, inj)
        assert all(e is None for e in errs)
        assert out == paged_baseline
        assert recoveries == 1
        assert compiles == 0


class _NaNDraftLM(CausalTransformerLM):
    """Draft-side NaN rig (ISSUE 12): prefill is clean — lanes prime
    and become speculation-eligible — but every decode step's logits
    are non-finite, so each round's per-lane finite guard trips. The
    target model is untouched; a correct engine turns this into
    plain decode for the tripped lanes, never a failed request."""

    def forward_decode(self, params, tokens, pos, k_caches, v_caches,
                       impl="auto"):
        logits, kcs, vcs = super().forward_decode(
            params, tokens, pos, k_caches, v_caches, impl)
        return jnp.full_like(logits, jnp.nan), kcs, vcs


_SPEC_KW = dict(num_slots=3, max_queue=64, min_prompt_bucket=4,
                retry_backoff_ms=0.2, retry_backoff_max_ms=2.0,
                speculation_k=2)


@pytest.fixture(scope="module")
def spec_eng(lm):
    """Warmed SPECULATING slot-backend engine (same-weights draft so
    rounds actually accept) shared by the spec chaos scenarios."""
    eng = GenerationEngine(lm, draft_model=_lm(), **_SPEC_KW)
    eng.warmup()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def spec_baseline(spec_eng, slot_baseline):
    """Fault-free speculating outputs — the bit-identity contract
    makes the k=0 workload outputs the oracle here too."""
    out, errs = _run_all(spec_eng)
    assert all(e is None for e in errs)
    assert out == slot_baseline
    return out


class TestChaosSpeculative:
    """ISSUE 12 acceptance: faults in the SPECULATIVE plane degrade
    along the documented ladder — draft-side trouble (NaN logits or a
    died/injected draft call) costs speculation only, while a
    corrupting fault at the verify seam forces the same
    recompute-recovery as any target-cache corruption — and every
    surviving request replays token-identical with zero post-warmup
    recompiles."""

    def test_draft_nan_falls_back_lane_only(self, lm, slot_baseline):
        eng = GenerationEngine(lm, draft_model=_NaNDraftLM(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4,
            max_seq_len=32, seed=1,
            implementation="plain").init(), **_SPEC_KW)
        eng.warmup()
        try:
            out, errs = _run_all(eng)
            assert all(e is None for e in errs)    # never the request
            assert out == slot_baseline            # plain-decode result
            sp = eng.stats()["spec"]
            assert sp["draft_fallbacks"] >= 1      # every lane tripped
            assert sp["draft_tokens_accepted"] == 0
        finally:
            eng.stop()

    def test_transient_verify_fault_retried_token_identical(
            self, spec_eng, spec_baseline):
        inj = FaultInjector(plan={"verify": [2]})
        out, errs, retries, recoveries, compiles = _chaos_run(
            spec_eng, inj)
        assert all(e is None for e in errs)
        assert out == spec_baseline
        assert retries == 1
        assert recoveries == 0
        assert compiles == 0

    def test_corrupting_verify_fault_recovers_token_identical(
            self, spec_eng, spec_baseline):
        # the verify call owns the TARGET's donated caches: a
        # corrupting fire there has device_step blast radius —
        # recompute-recovery replays every in-flight request
        inj = FaultInjector(plan={"verify": [3]},
                            corrupting=("verify",))
        out, errs, _, recoveries, compiles = _chaos_run(spec_eng, inj)
        assert all(e is None for e in errs)
        assert out == spec_baseline
        assert recoveries == 1
        assert compiles == 0

    def test_corrupting_draft_fault_costs_speculation_only(
            self, spec_eng, spec_baseline):
        # the draft call only ever donates the DRAFT's own caches, so
        # even a corrupting fire at that seam must degrade to plain
        # decode (fallback counter) with NO retry and NO recovery
        f0 = spec_eng.stats()["spec"]["draft_fallbacks"]
        inj = FaultInjector(plan={"draft": [1, 2]},
                            corrupting=("draft",))
        out, errs, retries, recoveries, compiles = _chaos_run(
            spec_eng, inj)
        assert all(e is None for e in errs)
        assert out == spec_baseline
        assert retries == 0
        assert recoveries == 0
        assert compiles == 0
        assert spec_eng.stats()["spec"]["draft_fallbacks"] > f0


class TestPoisonQuarantine:
    """A request whose logits go non-finite fails ALONE with 500
    while its batchmates keep decoding to unchanged outputs."""

    @pytest.fixture(scope="class")
    def plm(self):
        return _PoisonLM(vocab_size=VOCAB, d_model=32, n_layers=2,
                         n_heads=4, max_seq_len=32, seed=0,
                         implementation="plain").init()

    @pytest.fixture(scope="class")
    def plm_eng(self, plm):
        eng = GenerationEngine(plm, num_slots=3, max_queue=64,
                               min_prompt_bucket=4)
        eng.warmup()
        yield eng
        eng.stop()

    @pytest.fixture(scope="class")
    def plm_base(self, plm_eng):
        out, errs = _run_all(plm_eng, _REQS[:3])
        assert all(e is None for e in errs)
        return out

    def test_decode_poison_fails_alone_slots(self, plm_eng, plm_base):
        eng = plm_eng
        q0 = eng.metrics.quarantined
        reqs = list(_REQS[:3]) + [([1, TRIGGER], 8)]  # poisons mid-decode
        out, errs = _run_all(eng, reqs)
        assert isinstance(errs[3], PoisonRequestError)
        # the shared-faults hierarchy (FaultError, no longer a
        # ServingError subclass) still maps to HTTP 500 via the
        # front-end's default branch
        from deeplearning4j_tpu.serving.aio import _status_for
        assert _status_for(errs[3]) == 500
        assert "quarantined" in str(errs[3])
        assert [errs[i] for i in range(3)] == [None] * 3
        assert out[:3] == plm_base            # batchmates unchanged
        assert eng.metrics.quarantined == q0 + 1
        assert eng.metrics.recoveries == 0    # no global rebuild
        assert eng._slots.active_count == 0   # slot freed
        # the slot that held the poisoned lane is reusable: rerun clean
        out2, errs2 = _run_all(eng, _REQS[:3])
        assert all(e is None for e in errs2) and out2 == plm_base

    def test_prefill_poison_fails_alone_slots(self, plm_eng, plm_base):
        q0 = plm_eng.metrics.quarantined
        reqs = list(_REQS[:3]) + [([NAN_TRIGGER, 2, 3], 8)]
        out, errs = _run_all(plm_eng, reqs)
        assert isinstance(errs[3], PoisonRequestError)
        assert out[:3] == plm_base
        assert plm_eng.metrics.quarantined == q0 + 1

    def test_slot_reuse_after_nan_cache_is_clean(self, plm_eng,
                                                 plm_base):
        """A NaN request leaves non-finite K/V across every cache row
        its prefill slab covered; the freed slots are reused WITHOUT
        zeroing, so successors only stay clean if the kernels mask V
        (not just p) past the live length — 0 * NaN = NaN."""
        eng = plm_eng
        nan_prompt = [NAN_TRIGGER] + list(range(1, 17))  # 32-row slab
        out, errs = _run_all(eng, [(nan_prompt, 4)] * 3)  # all 3 slots
        assert all(isinstance(e, PoisonRequestError) for e in errs)
        out2, errs2 = _run_all(eng, _REQS[:3])
        assert all(e is None for e in errs2)
        assert out2 == plm_base

    def test_poison_frees_blocks_on_paged(self, plm):
        eng = GenerationEngine(plm, num_slots=3, max_queue=64,
                               cache="paged", block_size=4,
                               prompt_buckets=[8],
                               prefill_chunk_tokens=8)
        eng.warmup()
        base_out, base_errs = _run_all(eng, _REQS[:3])
        assert all(e is None for e in base_errs)
        reqs = list(_REQS[:3]) + [([1, TRIGGER], 8),
                                  ([NAN_TRIGGER, 2], 8)]
        out, errs = _run_all(eng, reqs)
        try:
            assert isinstance(errs[3], PoisonRequestError)
            assert isinstance(errs[4], PoisonRequestError)
            assert out[:3] == base_out
            assert eng.metrics.quarantined == 2
            # quarantine released the poisoned requests' blocks (the
            # healthy requests' full prompt blocks stay pinned in the
            # prefix index until cleared)
            eng.clear_prefix_cache()
            assert eng._allocator.free_count == eng._allocator.capacity
            # ...and those blocks still hold the poison's NaN K/V —
            # reusing them must not contaminate fresh requests
            out3, errs3 = _run_all(eng, _REQS[:3])
            assert all(e is None for e in errs3)
            assert out3 == base_out
        finally:
            eng.stop()


class TestGracefulDrain:
    def test_engine_drain_finishes_in_flight_and_rejects_new(self, lm):
        eng = GenerationEngine(lm, num_slots=2, max_queue=64,
                               min_prompt_bucket=4)
        eng.warmup([4])  # every drain-test prompt fits bucket 4
        results = [None] * 4
        threads = []

        def go(i):
            results[i] = eng.generate([1 + i, 2, 3], max_tokens=12,
                                      temperature=0.8, seed=i,
                                      timeout_ms=60_000)
        for i in range(4):
            t = threading.Thread(target=go, args=(i,))
            t.start()
            threads.append(t)
        time.sleep(0.05)  # some in slots, some queued
        assert eng.drain(timeout_s=60.0) is True
        for t in threads:
            t.join()
        # every accepted request finished (none failed by the drain)
        assert all(r is not None and r["finish_reason"] is not None
                   for r in results)
        with pytest.raises(DrainingError):
            eng.generate([1, 2], max_tokens=2)
        assert eng.metrics.drains == 1

    def test_streaming_requests_complete_through_drain(self, lm):
        eng = GenerationEngine(lm, num_slots=2, max_queue=64,
                               min_prompt_bucket=4)
        eng.warmup([4])
        got = {}

        def consume(i):
            toks = []
            for item in eng.stream([1 + i, 2], max_tokens=10,
                                   temperature=0.8, seed=i,
                                   timeout_ms=60_000):
                if "token" in item:
                    toks.append(item["token"])
                else:
                    got[i] = (toks, item.get("finish_reason"))
        ts = [threading.Thread(target=consume, args=(i,))
              for i in range(2)]
        for t in ts:
            t.start()
        time.sleep(0.03)
        assert eng.drain(timeout_s=60.0) is True
        for t in ts:
            t.join()
        assert len(got) == 2
        assert all(len(toks) == 10 and reason == "length"
                   for toks, reason in got.values())

    def test_server_readyz_and_post_shed_during_drain(self, lm):
        srv = InferenceServer(port=0)
        srv.register_generator("gen", lm, num_slots=2,
                               min_prompt_bucket=4)
        base = f"http://{srv.host}:{srv.port}"
        try:
            with urllib.request.urlopen(base + "/readyz", timeout=10) as r:
                assert r.status == 200
                assert json.loads(r.read())["ready"] is True
            with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
                assert r.status == 200
                body = json.loads(r.read())
                assert body["status"] == "ok"
                assert body["models"] == {"gen": True}
            assert srv.drain(timeout_s=30.0) is True
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/readyz", timeout=10)
            assert ei.value.code == 503
            assert ei.value.headers["Retry-After"]
            # new work is shed with 503 + Retry-After, registry intact
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    base + "/v1/models/gen/generate",
                    data=json.dumps({"prompt": [1, 2],
                                     "max_tokens": 2}).encode(),
                    headers={"Content-Type": "application/json"}),
                    timeout=10)
            assert ei.value.code == 503
            assert ei.value.headers["Retry-After"]
            # observability endpoints stay up after the drain
            with urllib.request.urlopen(base + "/stats", timeout=10) as r:
                stats = json.loads(r.read())
            assert stats["models"]["gen"]["faults"]["drains"] == 1
            with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
                assert r.status == 200  # drained != wedged
        finally:
            srv.stop()

    def test_sigterm_wiring_drains(self, lm):
        if threading.current_thread() is not threading.main_thread():
            pytest.skip("signal handlers require the main thread")
        srv = InferenceServer(port=0)
        eng = srv.register_generator("gen", lm, num_slots=2,
                                     min_prompt_bucket=4).engine
        prev = signal.getsignal(signal.SIGTERM)
        try:
            assert srv.install_signal_handlers(
                signals=(signal.SIGTERM,), drain_timeout_s=30.0,
                reraise=False) is True
            os.kill(os.getpid(), signal.SIGTERM)
            # the handler only flips readiness and hands the blocking
            # drain to a worker thread (so it can never deadlock on a
            # lock the interrupted main thread holds) — wait for both
            deadline = time.monotonic() + 10.0
            while (srv.ready() or eng._running) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not srv.ready()
            assert not eng._running          # drained + joined
            drainer = srv._signal_drain
            if drainer is not None:
                drainer.join(timeout=10.0)
            assert eng.metrics.drains == 1
        finally:
            signal.signal(signal.SIGTERM, prev)
            srv.stop()

    def test_sigterm_chains_previous_handler_on_main_thread(self, lm):
        """Chaining works by restoring the previous disposition and
        re-delivering after the drain — the chained handler must run
        on the MAIN thread (handlers like PreemptionHandler call
        signal.signal, which is main-thread-only)."""
        if threading.current_thread() is not threading.main_thread():
            pytest.skip("signal handlers require the main thread")
        srv = InferenceServer(port=0)
        srv.register_generator("gen", lm, num_slots=2,
                               min_prompt_bucket=4)
        seen = []

        def prev_handler(signum, frame):
            seen.append(threading.current_thread())

        old = signal.signal(signal.SIGTERM, prev_handler)
        try:
            assert srv.install_signal_handlers(
                signals=(signal.SIGTERM,), drain_timeout_s=30.0,
                reraise=True) is True
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 10.0
            while not seen and time.monotonic() < deadline:
                time.sleep(0.01)  # keep running bytecodes: re-delivery
                                  # executes on THIS (main) thread
            assert seen and seen[0] is threading.main_thread()
            assert not srv.ready()
        finally:
            signal.signal(signal.SIGTERM, old)
            srv.stop()

    def test_healthz_flags_stalled_loop(self, lm, monkeypatch):
        from deeplearning4j_tpu.serving import generation
        srv = InferenceServer(port=0)
        eng = srv.register_generator("gen", lm, num_slots=2,
                                     min_prompt_bucket=4).engine
        base = f"http://{srv.host}:{srv.port}"
        jam = threading.Event()

        class _Jam:
            """Injector stand-in that wedges the scheduler loop once:
            exactly what a hung device call looks like to the
            watchdog."""

            def fire(self, seam):
                if seam == "latency" and not jam.is_set():
                    jam.wait(3.0)
                return False
        try:
            monkeypatch.setattr(generation, "STALL_TIMEOUT_S", 0.5)
            eng._faults = _Jam()
            time.sleep(2.2)  # loop is stuck inside the iteration; the
            # heartbeat has gone stale past the watchdog. The settle
            # time covers one full idle submit-wake park (up to 1 s,
            # started before STALL_TIMEOUT_S shrank) plus comfortably
            # more than the 0.5 s watchdog after the wedge engages.
            assert not eng.alive()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/healthz", timeout=10)
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "stalled"
            jam.set()  # unwedge: liveness recovers
            time.sleep(0.3)
            assert eng.alive()
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as r:
                assert r.status == 200
        finally:
            jam.set()
            eng._faults = None
            srv.stop()


class _CountingModel:
    """Duck-typed predict model that counts device calls."""

    def __init__(self, delay=0.0):
        self.calls = 0
        self.delay = delay

    def output(self, x):
        self.calls += 1
        if self.delay:
            time.sleep(self.delay)
        return np.asarray(x, np.float32) * 2.0


class TestBatcherFaultTolerance:
    def test_transient_device_fault_is_retried(self):
        inj = FaultInjector(plan={"device_step": [1]})
        engine = InferenceEngine(_CountingModel(), max_batch_size=8,
                                 fault_injector=inj)
        mb = MicroBatcher(engine, max_latency_ms=1.0,
                          retry_backoff_ms=0.2)
        try:
            res = mb.submit(np.ones((2, 3), np.float32))
            np.testing.assert_allclose(res, 2.0 * np.ones((2, 3)))
            assert engine.metrics.retries == 1
            assert engine.metrics.responses == 1
        finally:
            mb.stop()

    def test_retries_exhausted_fails_batch(self):
        inj = FaultInjector(plan={"device_step": list(range(1, 20))})
        engine = InferenceEngine(_CountingModel(), max_batch_size=8,
                                 fault_injector=inj)
        mb = MicroBatcher(engine, max_latency_ms=1.0, max_retries=2,
                          retry_backoff_ms=0.2)
        try:
            with pytest.raises(TransientFault):
                mb.submit(np.ones((1, 3), np.float32))
            assert engine.metrics.retries == 2
        finally:
            mb.stop()

    def test_queued_expiry_dropped_at_dequeue_counted_once(self):
        """A request that dies in the queue is dropped WITHOUT a
        device call and its timeout is counted exactly once, even
        though the waiter and the scheduler both observe the expiry."""
        model = _CountingModel(delay=0.4)
        engine = InferenceEngine(model, max_batch_size=1)
        mb = MicroBatcher(engine, max_batch_size=1, max_latency_ms=1.0)
        try:
            errs = {}

            def slow_head():
                try:
                    mb.submit(np.ones((1, 2), np.float32),
                              timeout_ms=5_000)
                except Exception as e:  # noqa: BLE001
                    errs["head"] = e

            def doomed():
                try:
                    mb.submit(np.ones((1, 2), np.float32),
                              timeout_ms=50)
                except Exception as e:  # noqa: BLE001
                    errs["doomed"] = e
            t1 = threading.Thread(target=slow_head)
            t1.start()
            time.sleep(0.1)           # head occupies the device call
            t2 = threading.Thread(target=doomed)
            t2.start()                # expires while queued behind it
            t1.join()
            t2.join()
            assert "head" not in errs
            assert isinstance(errs["doomed"], DeadlineExceededError)
            time.sleep(0.2)           # let the scheduler pass the queue
            assert model.calls == 1   # no device step for the dead req
            assert engine.metrics.timeouts == 1  # once, not twice
        finally:
            mb.stop()

    def test_drain_rejects_new_and_finishes_queue(self):
        engine = InferenceEngine(_CountingModel(delay=0.05),
                                 max_batch_size=4)
        mb = MicroBatcher(engine, max_latency_ms=1.0)
        try:
            results = []

            def go():
                results.append(mb.submit(np.ones((1, 2), np.float32)))
            ts = [threading.Thread(target=go) for _ in range(3)]
            for t in ts:
                t.start()
            time.sleep(0.05)  # all three are enqueued/in flight
            assert mb.drain(timeout_s=30.0) is True
            for t in ts:
                t.join()
            assert len(results) == 3
            with pytest.raises(DrainingError):
                mb.submit(np.ones((1, 2), np.float32))
            assert engine.metrics.drains == 1
            assert mb.alive()  # drained is stopped, not wedged
        finally:
            mb.stop()


class TestElasticCrashSafety:
    """Satellite: FaultTolerantTrainer._save must be crash-safe — a
    writer dying mid-checkpoint can never corrupt what resume() loads,
    and temp files are invisible to listing/pruning."""

    def _trainer(self, tmp_path):
        from deeplearning4j_tpu.learning import Adam
        from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.parallel.elastic import \
            FaultTolerantTrainer
        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Adam(1e-2)).list()
                .layer(DenseLayer(n_out=4, activation="relu"))
                .layer(OutputLayer(n_out=2, loss="mcxent",
                                   activation="softmax"))
                .input_type_feed_forward(3).build())
        net = MultiLayerNetwork(conf).init()
        return FaultTolerantTrainer(net, str(tmp_path))

    def test_crash_mid_write_preserves_previous_checkpoint(
            self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.parallel.elastic import \
            FaultTolerantTrainer
        from deeplearning4j_tpu.util.serializer import ModelSerializer
        tr = self._trainer(tmp_path)
        tr._save(1)
        good = FaultTolerantTrainer.list_checkpoints(str(tmp_path))
        assert len(good) == 1
        before = open(good[0], "rb").read()

        real = ModelSerializer.write_snapshot

        def dying(snap, path, **kw):
            with open(path, "wb") as f:
                f.write(b"partial garbage")   # truncated write...
            raise OSError("disk full")        # ...then the crash

        # _save snapshots first, then writes via write_snapshot (the
        # async-checkpoint split) — dying at the write layer exercises
        # the same crash the old write_model patch did
        monkeypatch.setattr(ModelSerializer, "write_snapshot",
                            staticmethod(dying))
        with pytest.raises(OSError):
            tr._save(2)
        monkeypatch.setattr(ModelSerializer, "write_snapshot",
                            staticmethod(real))
        # the completed checkpoint is untouched, no temp corpse left,
        # and resume() still loads cleanly
        assert FaultTolerantTrainer.list_checkpoints(
            str(tmp_path)) == good
        assert open(good[0], "rb").read() == before
        assert not [p for p in os.listdir(str(tmp_path)) if ".tmp" in p]
        resumed = FaultTolerantTrainer.resume(str(tmp_path))
        assert resumed._epoch == tr.model._epoch

    def test_listing_and_pruning_skip_temp_and_stray_files(
            self, tmp_path):
        from deeplearning4j_tpu.parallel.elastic import \
            FaultTolerantTrainer
        import subprocess
        tr = self._trainer(tmp_path)
        # a stale temp from a CRASHED previous run (pid provably dead:
        # a reaped child), one from a LIVE concurrent writer (our own
        # pid — preemption-handover overlap), and a stray file
        child = subprocess.Popen(["/bin/true"])
        child.wait()
        stale = os.path.join(
            str(tmp_path), f"checkpoint_epoch9.zip.tmp.{child.pid}")
        open(stale, "wb").write(b"half a checkpoint")
        live = os.path.join(
            str(tmp_path), f"checkpoint_epoch8.zip.tmp.{os.getpid()}")
        open(live, "wb").write(b"another writer, mid-write")
        stray = os.path.join(str(tmp_path), "checkpoint_epochX.zip")
        open(stray, "wb").write(b"not a checkpoint")
        assert FaultTolerantTrainer.list_checkpoints(
            str(tmp_path)) == []
        for e in (1, 2, 3, 4, 5):
            tr._save(e)
        ckpts = FaultTolerantTrainer.list_checkpoints(str(tmp_path))
        # keep_last=3 pruned the oldest REAL checkpoints only
        assert [os.path.basename(p) for p in ckpts] == [
            "checkpoint_epoch3.zip", "checkpoint_epoch4.zip",
            "checkpoint_epoch5.zip"]
        assert os.path.exists(stray)      # never deleted as "oldest"
        assert not os.path.exists(stale)  # dead-pid corpse swept
        assert os.path.exists(live)       # live writer's temp spared

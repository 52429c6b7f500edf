"""Speculative decoding: draft-model propose, chunk-verified accept.

Decode is latency-bound, not compute-bound: every generated token costs
one full device round-trip whose matmuls barely occupy the chip. The
classic fix (Leviathan et al. 2023; Chen et al. 2023 — PAPERS.md) is to
let a cheap DRAFT model guess k tokens ahead and have the target model
score all k guesses in ONE batched forward — sequential target calls
collapse into one call whenever the draft guesses right, and the
machinery degrades to plain decode (one committed token per round)
whenever it guesses wrong.

This module builds the three pure device functions the engine
(:mod:`.generation`) compiles and schedules; the engine owns all
bookkeeping (eligibility, cursor commit, COW, fault ladder):

- **prime**: a draft prefill — write the draft's K/V for a lane's whole
  committed prefix into its slim dense cache. Runs once per admission
  (and per recovery re-admission) at decode-entry, because with prefix
  sharing the TARGET may have skipped prefill entirely while the draft,
  which shares nothing, still needs its own state.
- **propose**: k greedy draft decode steps, unrolled IN-GRAPH over the
  full slot batch — one device call proposes for every lane at once,
  which is what keeps the per-round dispatch overhead at (1 draft +
  per-lane verify) instead of (k drafts + ...).
- **verify**: the target scores ``[current_token, d_1..d_k]`` — k+1
  rows — in one causal pass, samples a target token at EVERY row with
  the engine's exact decode sampling math (same
  ``fold_in(PRNGKey(seed), step)`` uniforms, same top-k/temperature
  core), and computes the accepted run length in-graph.

**The identity contract.** Row ``i`` of a verify span sees exactly the
keys a plain decode step ``i`` would see, and samples with exactly the
fold a plain decode step ``i`` would fold — so the target sample
``tgt_i`` at each row IS the token non-speculative decode would have
emitted. Acceptance is exact-match: draft token ``d_{i+1}`` is accepted
iff it EQUALS ``tgt_i``; the first mismatching row's own target sample
is the correction token, and an all-accepted round's last row yields a
bonus token for free. Every emitted token is therefore a target sample
from the request's own PRNG stream — output is bit-identical to
non-speculative decode at EVERY temperature, not merely
distribution-exact (which a min(1, p/q) acceptance rule would give; an
exact-match rule trades a little accept rate for replayable streams,
which the recompute-recovery contract already relies on).

**Rollback is cursor-only.** A rejected tail's K/V was already written
past the accepted length, and stays there: the engine commits
``pos``/``step`` forward by the accepted run only, and the
no-zeroing-on-reuse invariant (:mod:`.kvcache`, :mod:`.paging`) masks
everything beyond the cursor until a later accepted write overwrites
it. No device work is spent undoing anything.

The paged verify is literally the chunked-prefill runtime-offset
kernel (``forward_prefill_chunk``) with sampling bolted on — it rides
the same (bucket, table-bucket) executable grid the chunk ladder
warms. The slots verify uses the dense sibling ``forward_verify``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .paging import pow2_bucket


def verify_bucket(k: int) -> int:
    """Device width of a verify span: the current token plus k draft
    proposals, padded to the pow2 bucket ladder so verify executables
    stay warmable. Padded rows write masked junk — same contract as a
    prefill chunk's padded tail."""
    return pow2_bucket(int(k) + 1)


def make_prime_fn(draft):
    """Draft prefill into the draft's dense slot cache: the engine's
    ``_prefill_fn`` minus sampling (the draft never emits — it only
    holds state to propose from). Returns ``prime(params, kcs, vcs,
    tokens [1, B], length, slot) -> (ok, kcs, vcs)`` where ``ok`` is
    the finite-logits guard over the valid rows."""

    def prime(params, kcs, vcs, tokens, length, slot):
        bucket = tokens.shape[1]
        key_mask = (jnp.arange(bucket)[None] < length).astype(
            jnp.float32)
        logits, ks, vs = draft.forward_prefill(params, tokens, key_mask)
        ok = jnp.all(jnp.where(
            (jnp.arange(bucket) < length)[None, :, None],
            jnp.isfinite(logits), True))
        kcs = [jax.lax.dynamic_update_slice(kc, k, (slot, 0, 0, 0))
               for kc, k in zip(kcs, ks)]
        vcs = [jax.lax.dynamic_update_slice(vc, v, (slot, 0, 0, 0))
               for vc, v in zip(vcs, vs)]
        return ok, kcs, vcs
    return prime


def make_propose_fn(draft, k: int, impl: str = "auto"):
    """k greedy draft decode steps unrolled in-graph over the slot
    batch. Greedy on purpose: proposals only SEED verification — the
    target's own sampling decides what is emitted, so the draft's job
    is to maximize the chance of matching the target's choice, and at
    the temperatures where speculation pays (low), argmax is that
    maximizer. Returns ``propose(params, kcs, vcs, tokens [S],
    pos [S]) -> (proposals [S, k], ok [S], kcs, vcs)`` with ``ok``
    the per-lane finite-logits guard ANDed across all k steps (a NaN
    anywhere in a lane's draft chain disqualifies that lane's round —
    the engine then falls back to plain decode for it, never failing
    the request)."""
    k = int(k)

    def propose(params, kcs, vcs, tokens, pos):
        t, p = tokens, pos
        ok = jnp.ones(tokens.shape[0], bool)
        props = []
        for _ in range(k):
            logits, kcs, vcs = draft.forward_decode(params, t, p, kcs,
                                                    vcs, impl)
            ok = ok & jnp.all(jnp.isfinite(logits), axis=-1)
            t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = p + 1
            props.append(t)
        return jnp.stack(props, axis=1), ok, kcs, vcs
    return propose


def _verify_tail(logits, tokens, vlen, seed, step0, temp, top_k):
    """Shared in-graph accept/sample tail: target-sample every row
    with the engine's decode sampling math, then count the leading
    run of draft rows that MATCH the target's choice.

    Row ``i`` samples with ``fold_in(PRNGKey(seed), step0 + i)`` — the
    exact uniforms plain decode steps would burn — via the engine's
    ``_sample_batch``. Accept mask: draft token ``tokens[0, i+1]``
    matches target sample ``tgt_i``, limited to the ``vlen - 1`` real
    draft rows; the accepted length is the cumprod-sum of the leading
    run. Returns (tgt [C], n_accepted, ok)."""
    from .generation import _sample_batch
    C = tokens.shape[1]
    rows = jnp.arange(C)
    ok = jnp.all(jnp.where((rows < vlen)[:, None],
                           jnp.isfinite(logits), True))
    tgt = _sample_batch(
        logits,
        jnp.broadcast_to(jnp.asarray(temp, jnp.float32), (C,)),
        jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (C,)),
        jnp.broadcast_to(jnp.asarray(seed, jnp.uint32), (C,)),
        step0 + rows.astype(jnp.int32))
    match = (tgt[:-1] == tokens[0, 1:]) & (rows[:-1] < vlen - 1)
    n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
    return tgt, n_acc, ok


def make_verify_paged_fn(model):
    """Paged verification: ``forward_prefill_chunk`` — the warmed
    runtime-offset chunk kernel, unchanged — plus the shared
    accept/sample tail. Returns ``verify(params, pools,
    tokens [1, C], p0, vlen, table, seed, step0, temp, top_k) ->
    (tgt [C], n_accepted, ok, pools)``."""

    def verify(params, pools, tokens, p0, vlen, table, seed, step0,
               temp, top_k):
        logits, pools, _ = model.forward_prefill_chunk(
            params, tokens, p0, vlen, pools, table)
        tgt, n_acc, ok = _verify_tail(logits, tokens, vlen, seed,
                                      step0, temp, top_k)
        return tgt, n_acc, ok, pools
    return verify


def make_verify_slots_fn(model):
    """Dense-backend verification: ``forward_verify`` (the slot-cache
    sibling of the chunk kernel) plus the shared accept/sample tail.
    Returns ``verify(params, kcs, vcs, tokens [1, C], p0, vlen, slot,
    seed, step0, temp, top_k) -> (tgt [C], n_accepted, ok, kcs,
    vcs)``."""

    def verify(params, kcs, vcs, tokens, p0, vlen, slot, seed, step0,
               temp, top_k):
        logits, kcs, vcs = model.forward_verify(
            params, tokens, p0, vlen, kcs, vcs, slot)
        tgt, n_acc, ok = _verify_tail(logits, tokens, vlen, seed,
                                      step0, temp, top_k)
        return tgt, n_acc, ok, kcs, vcs
    return verify

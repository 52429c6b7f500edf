"""Serving metrics: queue depth, batch-size histogram, latency
percentiles, compile-cache hits/misses.

Built on the profiler's section machinery (`OpProfiler.record` names
``serving.*`` sections) plus the :class:`Reservoir` /
:class:`CountHistogram` aggregates it exposes; `GET /stats` on the
server returns :meth:`ServingMetrics.snapshot` per model.
"""
from __future__ import annotations

import heapq
import re
import threading
import time
from typing import Dict, List

from jax.profiler import TraceAnnotation

from ..profiler import (RESERVOIR_SNAPSHOT_KEYS, CountHistogram,
                        OpProfiler, RateMeter, Reservoir)

#: The phases of one iteration of ``GenerationEngine._loop``. They
#: partition it: at any instant the scheduler thread is in exactly one,
#: and what no site claims is ``other``. Each is a cumulative counter
#: under ``scheduler.phase_s`` in ``/stats`` and, while a
#: ``jax.profiler`` session runs, a ``gen.<phase>`` span on the
#: profiler's clock (docs/observability.md, "The scheduler's time
#: account").
SCHED_PHASES = ("admit", "chunk_dispatch", "chunk_wait",
                "decode_dispatch", "decode_wait", "emit", "idle",
                "fault", "other")
SCHED_SPAN_PREFIX = "gen."
#: the HTTP front-ends' write of one streamed token to the socket
HTTP_WRITE_SPAN = "http.stream_write"
#: why a request waits at the head of the queue
HEAD_BLOCKED_CAUSES = ("blocks", "slots")


class ServingMetrics:
    """Always-on counters for one served model (the reference's
    PerformanceListener role, serving-side). Scalar counters are
    mutated from many HTTP handler threads — use :meth:`inc`, not
    ``+=`` (attribute += is load/add/store and loses updates under
    preemption)."""

    def __init__(self, latency_window: int = 8192):
        self._lock = threading.Lock()
        self.requests = 0          # accepted into the queue/engine
        self.responses = 0         # successful results returned
        self.client_errors = 0     # 4xx-class failures
        self.server_errors = 0     # 5xx-class failures
        self.shed = 0              # rejected, queue full (503)
        self.shed_batch = 0        # batch-priority work shed first (503)
        self.shed_deadline = 0     # deadline budget blown before the
        #                            device call: rejected at dequeue-
        #                            admission, zero device work spent
        self.timeouts = 0          # request deadline exceeded (504)
        # fault-tolerance counters (deeplearning4j_tpu/faults.py)
        self.retries = 0           # transient step failures retried
        self.recoveries = 0        # state rebuilds (n/a for batcher)
        self.quarantined = 0       # poison requests failed alone
        self.drains = 0            # graceful drains initiated
        self.batches = 0           # device calls issued
        self.batch_hist = CountHistogram()   # rows per device call
        self.bucket_hist = CountHistogram()  # padded bucket per call
        self.latency_ms = Reservoir(latency_window)    # request e2e
        self.device_ms = Reservoir(latency_window)     # device call
        self.queue_depth = 0       # gauge, updated by the batcher
        self.queue_max = 0
        self.inflight = 0          # gauge: rows in the device call NOW
        # engine compile cache
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_evictions = 0
        self.warmed_buckets: List[int] = []

    def inc(self, field: str, n: int = 1):
        """Thread-safe counter increment."""
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def mean_batch(self) -> float:
        """Mean number of real rows per device call — the coalescing
        factor (1.0 means the batcher never merged anything)."""
        return self.batch_hist.mean()

    def snapshot(self) -> Dict:
        return {
            "requests": self.requests,
            "responses": self.responses,
            "client_errors": self.client_errors,
            "server_errors": self.server_errors,
            "shed": self.shed,
            "shed_batch": self.shed_batch,
            "shed_deadline": self.shed_deadline,
            "timeouts": self.timeouts,
            "faults": {
                "retries": self.retries,
                "recoveries": self.recoveries,
                "quarantined": self.quarantined,
                "drains": self.drains,
            },
            "queue_depth": self.queue_depth,
            "queue_max": self.queue_max,
            "inflight": self.inflight,
            "batches": self.batches,
            "mean_batch": round(self.mean_batch(), 3),
            "batch_hist": self.batch_hist.snapshot(),
            "bucket_hist": self.bucket_hist.snapshot(),
            "latency_ms": {k: round(v, 3) for k, v in
                           self.latency_ms.snapshot().items()},
            "device_ms": {k: round(v, 3) for k, v in
                          self.device_ms.snapshot().items()},
            "compile_cache": {
                "compiles": self.compiles,
                "hits": self.cache_hits,
                "misses": self.cache_misses,
                "evictions": self.cache_evictions,
                "warmed_buckets": list(self.warmed_buckets),
            },
        }


class _Phase:
    """``with account.phase(name, **attrs) as t0``: one stamp going in
    (returned), one coming out (left in ``account.t``)."""

    __slots__ = ("_acct", "_name", "_attrs")

    def __init__(self, acct, name, attrs):
        self._acct, self._name, self._attrs = acct, name, attrs

    def __enter__(self) -> float:
        return self._acct._enter(self._name, self._attrs)

    def __exit__(self, *exc) -> None:
        self._acct._exit()


class SchedulerAccount:
    """Where the generation scheduler's wall time goes, cut so that it
    adds up: ``sum(phase_s.values()) == loop_s``.

    One mechanism, two outputs. Each phase boundary takes one
    ``time.perf_counter()`` stamp; the interval since the last stamp
    is charged to the phase that was open (``other`` when none), and
    the same interval is a ``jax.profiler.TraceAnnotation`` named
    ``gen.<phase>`` that costs under a microsecond unless a profiler
    session is recording. A phase opened inside another (the idle park
    inside ``admit``, the slot backend's prefill inside ``admit``)
    pauses its parent, counter and span alike, so the partition stays
    exact.

    Written by the scheduler thread only. Totals are committed once an
    iteration under a lock, so a ``/stats`` reader always sees phases
    that sum to ``loop_s`` and step counts of whole iterations: ratios
    of two deltas of this block are exact averages over iterations.
    """

    def __init__(self, clock=time.perf_counter):
        self._clock = clock     # tests put a clock they step by hand
        self._lock = threading.Lock()
        self.loop_s = 0.0
        self.iterations = 0
        self.phase_s = dict.fromkeys(SCHED_PHASES, 0.0)
        self.phase_n = dict.fromkeys(SCHED_PHASES, 0)
        self.head_blocked_s = dict.fromkeys(HEAD_BLOCKED_CAUSES, 0.0)
        self.kv_live_token_steps = 0
        self.kv_blocks_attended = 0
        self.kv_blocks_spanned = 0
        # a model with cache groups (serving/paging.py, CacheGroup):
        # keys the decode steps read by group, what one lifetime for
        # every layer would have read, live positions by group summed
        # over steps, and the head's wait for blocks by the group that
        # could not cover it. Empty, and not in the snapshot, otherwise
        self.kv_rows_attended: Dict[str, int] = {}
        self.kv_rows_full = 0
        self.kv_group_live_token_steps: Dict[str, int] = {}
        self.admit_blocked_on: Dict[str, float] = {}
        #: how many of the longest non-idle iterations are kept
        self.keep_slowest = 8
        self._slowest: List[tuple] = []      # min-heap on seconds
        # the open iteration (scheduler thread only)
        self.t = clock()                     # the last stamp taken
        self._t_iter = self.t
        self._stack: List[list] = []         # [name, attrs, annotation]
        self._it_s: Dict[str, float] = {}
        self._it_n: Dict[str, int] = {}
        self._it_kv = 0
        self._it_attended = self._it_spanned = 0
        self._it_blocked: Dict[str, float] = {}
        self._blocked = None        # (cause, since, group) or None
        self._it_groups: Dict[str, Dict] = {
            "rows": {}, "live": {}, "blocked": {}}
        self._it_rows_full = 0

    # -- scheduler thread ----------------------------------------------
    def start(self) -> None:
        """The scheduler thread is up: the account's clock starts."""
        self.t = self._t_iter = self._clock()

    def phase(self, name: str, **attrs) -> _Phase:
        return _Phase(self, name, attrs)

    def _charge(self, now: float) -> None:
        name = self._stack[-1][0] if self._stack else "other"
        self._it_s[name] = self._it_s.get(name, 0.0) + (now - self.t)
        self.t = now

    def _enter(self, name: str, attrs: dict) -> float:
        now = self._clock()
        self._charge(now)
        if self._stack:
            self._stack[-1][2].__exit__(None, None, None)
        self._it_n[name] = self._it_n.get(name, 0) + 1
        ann = TraceAnnotation(SCHED_SPAN_PREFIX + name, **attrs)
        ann.__enter__()
        self._stack.append([name, attrs, ann])
        return now

    def _exit(self) -> None:
        self._charge(self._clock())
        self._stack.pop()[2].__exit__(None, None, None)
        if self._stack:
            parent = self._stack[-1]
            parent[2] = TraceAnnotation(SCHED_SPAN_PREFIX + parent[0],
                                        **parent[1])
            parent[2].__enter__()

    def head_blocked(self, cause, group=None) -> None:
        """Called once an admission pass: ``cause`` is why the request
        at the head of the queue could not be admitted ("blocks": the
        pool cannot cover it though a slot is free; "slots": no slot
        is free), or None when nothing waits; ``group`` names the cache
        group whose pool it was. The time until the next call is
        charged to that cause (and group)."""
        now = self._clock()
        if self._blocked is not None:
            was, since, grp = self._blocked
            self._it_blocked[was] = self._it_blocked.get(was, 0.0) \
                + (now - since)
            if grp is not None:
                self._add("blocked", {grp: now - since})
        self._blocked = None if cause is None else (cause, now, group)

    def declare_groups(self, names) -> None:
        """The model has cache groups: their counters show from the
        start, at zero."""
        names = list(names)
        for kept in (self.kv_rows_attended, self.kv_group_live_token_steps,
                     self.admit_blocked_on):
            for n in names:
                kept.setdefault(n, 0)

    def _add(self, what: str, by_group: Dict) -> None:
        it = self._it_groups[what]
        for k, v in by_group.items():
            it[k] = it.get(k, 0) + v

    def step_collected(self, kv_tokens_live: int, by_group=None) -> None:
        """One decode step's results are on the host: add the live KV
        tokens it ran over (memory in use, integrated over steps), and
        those of each cache group."""
        self._it_kv += int(kv_tokens_live)
        if by_group:
            self._add("live", by_group)

    def step_rows(self, attended: Dict[str, int], full: int) -> None:
        """One decode step of a model with cache groups is dispatched:
        the keys its lanes read a group (a window group's lanes their
        window at most, times the group's layers), and what every
        layer keeping every position would read."""
        self._add("rows", attended)
        self._it_rows_full += int(full)

    def step_dispatched(self, blocks_attended: int,
                        blocks_spanned: int) -> None:
        """One paged decode step is dispatched: the pool blocks its
        attention has to read (every live lane's ``ceil(length /
        block_size)``) and the block-table entries it spans (slots x
        table width). Their ratio is how far a length-bounded kernel
        undercuts one that walks the table."""
        self._it_attended += int(blocks_attended)
        self._it_spanned += int(blocks_spanned)

    def tick(self, step: int) -> None:
        """End of one iteration of the loop, start of the next."""
        self._charge(self._clock())
        it_s, t0 = self._it_s, self._t_iter
        total = sum(it_s.values())
        with self._lock:
            self.loop_s += total
            self.iterations += 1
            for k, v in it_s.items():
                self.phase_s[k] += v
            for k, n in self._it_n.items():
                self.phase_n[k] += n
            for k, v in self._it_blocked.items():
                self.head_blocked_s[k] += v
            self.kv_live_token_steps += self._it_kv
            self.kv_blocks_attended += self._it_attended
            self.kv_blocks_spanned += self._it_spanned
            self.kv_rows_full += self._it_rows_full
            for what, kept in (
                    ("rows", self.kv_rows_attended),
                    ("live", self.kv_group_live_token_steps),
                    ("blocked", self.admit_blocked_on)):
                for k, v in self._it_groups[what].items():
                    kept[k] = kept.get(k, 0) + v
            if not it_s.get("idle") and self.keep_slowest > 0:
                entry = (total, t0, int(step), it_s)
                if len(self._slowest) < self.keep_slowest:
                    heapq.heappush(self._slowest, entry)
                elif total > self._slowest[0][0]:
                    heapq.heapreplace(self._slowest, entry)
        self._t_iter = self.t
        self._it_s, self._it_n, self._it_blocked = {}, {}, {}
        self._it_kv = 0
        self._it_attended = self._it_spanned = 0
        self._it_groups = {"rows": {}, "live": {}, "blocked": {}}
        self._it_rows_full = 0

    # -- readers ---------------------------------------------------------
    def busy_and_blocked(self):
        """(seconds the loop was not idle, seconds of those it was
        blocked in a fetch), as of the last whole iteration."""
        with self._lock:
            p = self.phase_s
            return (self.loop_s - p["idle"],
                    p["decode_wait"] + p["chunk_wait"])

    def snapshot(self) -> Dict:
        with self._lock:
            slowest = sorted(self._slowest, key=lambda e: -e[0])
            groups = {} if not self.kv_rows_attended else {
                "kv_rows_attended": dict(self.kv_rows_attended),
                "kv_rows_full": self.kv_rows_full,
                "kv_group_live_token_steps":
                    dict(self.kv_group_live_token_steps),
                "admit_blocked_on": dict(self.admit_blocked_on)}
            return {
                **groups,
                "loop_s": self.loop_s,
                "iterations": self.iterations,
                "phase_s": dict(self.phase_s),
                "phase_n": dict(self.phase_n),
                "head_blocked_s": dict(self.head_blocked_s),
                "kv_live_token_steps": self.kv_live_token_steps,
                "kv_blocks_attended": self.kv_blocks_attended,
                "kv_blocks_spanned": self.kv_blocks_spanned,
                # [t_start (perf_counter), seconds, decode step
                # ordinal, {phase: seconds}], longest first
                "slowest": [[t0, s, step, dict(ph)]
                            for s, t0, step, ph in slowest],
            }


class GenerationMetrics:
    """Always-on counters for one continuous-batching generation
    engine. Same threading discipline as :class:`ServingMetrics`
    (scalar counters via :meth:`inc`, never ``+=``): the HTTP handler
    threads and the scheduler thread both write here."""

    def __init__(self, latency_window: int = 8192,
                 rate_window_s: float = 30.0):
        self._lock = threading.Lock()
        self.requests = 0          # accepted into the queue
        self.responses = 0         # finished generations returned
        self.client_errors = 0     # 4xx-class failures
        self.server_errors = 0     # 5xx-class failures
        self.shed = 0              # rejected, queue full (503)
        self.shed_batch = 0        # batch-priority work shed first (503)
        self.shed_deadline = 0     # deadline budget blown before any
        #                            prefill/decode step: rejected at
        #                            admission, zero device work spent
        self.timeouts = 0          # deadline exceeded (504)
        # fault-tolerance counters (deeplearning4j_tpu/faults.py): transient step
        # retries, recompute-recoveries (every in-flight request
        # re-prefilled from prompt + emitted tokens), poison requests
        # quarantined (non-finite logits -> 500, batchmates unharmed),
        # graceful drains
        self.retries = 0
        self.recoveries = 0
        self.quarantined = 0
        self.drains = 0
        self.prefills = 0          # prefill device calls
        self.decode_steps = 0      # decode device calls (all slots)
        self.tokens = RateMeter(rate_window_s)   # generated tokens
        self.occupancy_hist = CountHistogram()   # active slots per step
        self.prompt_bucket_hist = CountHistogram()  # padded prefill len
        self.ttft_ms = Reservoir(latency_window)    # submit -> 1st token
        self.itl_ms = Reservoir(latency_window)     # inter-token gap
        self.prefill_ms = Reservoir(latency_window)
        # a step's dispatch-to-results-on-host span, over the last
        # ``latency_window`` steps since process start. With the
        # pipeline on, neighbouring spans overlap (a step is
        # dispatched before the one ahead of it is collected), so
        # this is NOT the time a step takes and its samples do not
        # add up to wall time: ``scheduler`` below is the account
        # that does
        self.decode_step_ms = Reservoir(latency_window)
        # pipelined decode (ISSUE 14): how long the scheduler actually
        # BLOCKED at the step-t sync after dispatching step t+1 — near
        # zero when host bookkeeping fully overlaps device compute,
        # approaching decode_step_ms when the device is the bottleneck
        self.decode_sync_wait_ms = Reservoir(latency_window)
        # the scheduler's time account (phases, head-of-queue waits by
        # cause, live KV integrated over steps): scheduler thread only
        self.scheduler = SchedulerAccount()
        # the account a model keeps of the counters its forwards return
        # beside their logits (``model.step_account()``; None for a
        # model that returns none): published under its own ``block``
        self.model_account = None
        # bytes of the state a model keeps a slot beside the pools
        # (a short convolution's last inputs): gauge, 0 for a model
        # that declares none
        self.slot_state_bytes = 0
        # HTTP tier: engine's emit stamp -> the token's chunk handed to
        # the socket (written by the front-end's threads, under _lock)
        self.stream_chunks = 0
        self.stream_delay_s = 0.0
        self.stream_delay_max_s = 0.0
        self.queue_depth = 0       # gauge, updated by the scheduler
        self.queue_max = 0
        self.active_slots = 0      # gauge
        self.num_slots = 0
        self.cache_bytes = 0
        # quantized KV pool (ISSUE 15; kernels/kv_quant.py). kv_dtype
        # is a STRING (exposition walker skips strings — identity in
        # labels), so kv_bits carries the precision into /metrics as a
        # numeric gauge (32 / 16 / 8)
        self.kv_dtype = "f32"
        self.kv_bits = 32
        self.kv_bytes_per_token = 0    # K+V bytes per position, all
        #                                layers, sidecar included
        self.quant_blocks_quantized = 0  # gauge: allocated int8 blocks
        self.quant_scale_bytes = 0       # f32 sidecar bytes (0 unless
        #                                  int8)
        # paged-cache gauges/counters (serving/paging.py; all zero
        # when the engine runs the dense slot backend)
        self.cache_backend = "slots"
        self.block_size = 0
        self.blocks_total = 0          # allocatable blocks (excl. null)
        self.blocks_free = 0           # gauge
        self.blocks_peak_used = 0      # high-water mark
        self.prefill_chunks = 0        # chunk device calls
        self.chunked_prefills = 0      # prompts that spanned >1 chunk
        self.kv_tokens_live = 0        # written positions, live seqs
        self.kv_tokens_allocated = 0   # blocks_used * block_size
        # a model with cache groups: allocator and liveness gauges a
        # group, by name (empty, and not in the snapshot, otherwise;
        # the fields above are then its first group's)
        self.groups: Dict[str, Dict] = {}
        # prefix sharing + persistent sessions (paged backend only;
        # docs/generation.md "Prefix sharing")
        self.prefix_sharing = False    # config flag
        self.prefix_hits = 0           # admissions that matched a prefix
        self.session_hits = 0          # ...matched via the session store
        self.session_misses = 0        # session_id sent, nothing pinned
        self.prefix_tokens_matched = 0  # prompt tokens served from cache
        self.prefill_tokens = 0        # prompt tokens actually computed
        self.cow_copies = 0            # copy-on-write block duplications
        self.prefix_evictions = 0      # index entries evicted
        self.session_evictions = 0     # sessions evicted (LRU/pressure)
        self.shared_blocks = 0         # gauge: blocks with refcount > 1
        self.prefix_blocks = 0         # gauge: blocks the index pins
        self.sessions_live = 0         # gauge
        # hierarchical KV tier (PR 16; serving/offload.py): demote-on-
        # evict to host RAM (+ optional disk ring), restore-on-resume.
        # All zero unless offload_host_bytes > 0
        self.offload_enabled = False   # config flag
        self.offload_demotions = 0     # device->host block-run copies
        self.offload_restores = 0      # host->device restores (each one
        #                                is a re-prefill avoided)
        self.offload_prefetch_hits = 0  # restores served from staged
        #                                 prefetch (overlapped IO)
        self.offload_demote_failures = 0   # torn demotions -> discard
        self.offload_restore_failures = 0  # torn restores -> re-prefill
        self.offload_spills = 0        # gauge: RAM -> disk-ring spills
        self.offload_drops = 0         # gauge: runs lost off the bottom
        self.offload_host_runs = 0     # gauge: runs in host RAM
        self.offload_host_blocks = 0   # gauge: blocks in host RAM
        self.offload_host_bytes = 0    # gauge
        self.offload_disk_blocks = 0   # gauge: blocks in the disk ring
        self.offload_disk_bytes = 0    # gauge
        self.offload_restore_ms = Reservoir(latency_window)  # host->
        #                              device restore wall time
        self.offload_demote_ms = Reservoir(latency_window)
        # speculative decoding (serving/speculative.py; both backends;
        # all zero with speculation_k=0)
        self.speculation_k = 0            # config knob (0 = off)
        self.spec_draft_tokens_proposed = 0  # k per verify round
        self.spec_draft_tokens_accepted = 0  # target-matched prefix
        self.spec_verify_batches = 0      # verify device calls
        self.spec_rollbacks = 0           # rounds with a rejected tail
        self.spec_draft_fallbacks = 0     # draft failures -> plain
        #                                   decode (lane never failed)
        # compile cache: decode + one prefill executable per bucket
        self.compiles = 0
        self.warmed_buckets: List[int] = []

    def inc(self, field: str, n: int = 1):
        """Thread-safe counter increment."""
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def note_stream_write(self, delay_s: float):
        """One streamed token reached the socket ``delay_s`` after the
        scheduler emitted it."""
        with self._lock:
            self.stream_chunks += 1
            self.stream_delay_s += delay_s
            if delay_s > self.stream_delay_max_s:
                self.stream_delay_max_s = delay_s

    def snapshot(self) -> Dict:
        occ = self.occupancy_hist
        steps = occ.total()
        with self._lock:    # one write's three fields, never torn
            stream = {"chunks": self.stream_chunks,
                      "delay_s": self.stream_delay_s,
                      "delay_max_s": self.stream_delay_max_s}
        paged = None
        if self.cache_backend == "paged":
            used = self.blocks_total - self.blocks_free
            alloc = self.kv_tokens_allocated
            paged = {
                "block_size": self.block_size,
                "blocks_total": self.blocks_total,
                "blocks_free": self.blocks_free,
                "blocks_used": used,
                "blocks_peak_used": self.blocks_peak_used,
                "utilization": round(used / self.blocks_total, 4)
                if self.blocks_total else 0.0,
                # internal fragmentation: the share of ALLOCATED token
                # capacity not (yet) holding live K/V — bounded by
                # block_size-1 tokens per sequence, vs up to
                # max_seq_len-1 per slot on the dense backend
                "fragmentation": round(
                    1.0 - self.kv_tokens_live / alloc, 4)
                if alloc else 0.0,
                "kv_tokens_live": self.kv_tokens_live,
                "kv_tokens_allocated": alloc,
                "prefill_chunks": self.prefill_chunks,
                "chunked_prefills": self.chunked_prefills,
                **({"groups": dict(self.groups)} if self.groups else {}),
                "prefix_cache": {
                    "enabled": self.prefix_sharing,
                    "prefix_hits": self.prefix_hits,
                    "session_hits": self.session_hits,
                    "session_misses": self.session_misses,
                    "prefix_tokens_matched": self.prefix_tokens_matched,
                    "prefill_tokens": self.prefill_tokens,
                    "cow_copies": self.cow_copies,
                    "prefix_evictions": self.prefix_evictions,
                    "session_evictions": self.session_evictions,
                    "shared_blocks": self.shared_blocks,
                    "prefix_blocks": self.prefix_blocks,
                    "sessions_live": self.sessions_live,
                },
                "offload": {
                    "enabled": self.offload_enabled,
                    "demotions": self.offload_demotions,
                    "restores": self.offload_restores,
                    "prefetch_hits": self.offload_prefetch_hits,
                    "demote_failures": self.offload_demote_failures,
                    "restore_failures": self.offload_restore_failures,
                    "spills": self.offload_spills,
                    "drops": self.offload_drops,
                    "host_runs": self.offload_host_runs,
                    "host_blocks": self.offload_host_blocks,
                    "host_bytes": self.offload_host_bytes,
                    "disk_blocks": self.offload_disk_blocks,
                    "disk_bytes": self.offload_disk_bytes,
                    "restore_ms": {
                        k: round(v, 3) for k, v in
                        self.offload_restore_ms.snapshot().items()},
                    "demote_ms": {
                        k: round(v, 3) for k, v in
                        self.offload_demote_ms.snapshot().items()},
                },
            }
        return {
            "cache_backend": self.cache_backend,
            "paged": paged,
            "requests": self.requests,
            "responses": self.responses,
            "client_errors": self.client_errors,
            "server_errors": self.server_errors,
            "shed": self.shed,
            "shed_batch": self.shed_batch,
            "shed_deadline": self.shed_deadline,
            "timeouts": self.timeouts,
            "faults": {
                "retries": self.retries,
                "recoveries": self.recoveries,
                "quarantined": self.quarantined,
                "drains": self.drains,
            },
            "queue_depth": self.queue_depth,
            "queue_max": self.queue_max,
            "prefills": self.prefills,
            "decode_steps": self.decode_steps,
            "tokens_generated": self.tokens.total(),
            "tokens_per_sec": round(self.tokens.rate(), 3),
            "slots": {
                "num_slots": self.num_slots,
                "active": self.active_slots,
                "mean_occupancy": round(occ.mean(), 3),
                "utilization": round(
                    occ.mean() / self.num_slots, 4) if (
                        self.num_slots and steps) else 0.0,
                "occupancy_hist": occ.snapshot(),
            },
            "spec": {
                "enabled": self.speculation_k > 0,
                "speculation_k": self.speculation_k,
                "draft_tokens_proposed": self.spec_draft_tokens_proposed,
                "draft_tokens_accepted": self.spec_draft_tokens_accepted,
                "accept_rate": round(
                    self.spec_draft_tokens_accepted
                    / self.spec_draft_tokens_proposed, 4)
                if self.spec_draft_tokens_proposed else 0.0,
                "verify_batches": self.spec_verify_batches,
                "rollbacks": self.spec_rollbacks,
                "draft_fallbacks": self.spec_draft_fallbacks,
            },
            "prompt_bucket_hist": self.prompt_bucket_hist.snapshot(),
            "ttft_ms": {k: round(v, 3) for k, v in
                        self.ttft_ms.snapshot().items()},
            "itl_ms": {k: round(v, 3) for k, v in
                       self.itl_ms.snapshot().items()},
            "prefill_ms": {k: round(v, 3) for k, v in
                           self.prefill_ms.snapshot().items()},
            "decode_step_ms": {k: round(v, 3) for k, v in
                               self.decode_step_ms.snapshot().items()},
            "decode_sync_wait_ms": {
                k: round(v, 3) for k, v in
                self.decode_sync_wait_ms.snapshot().items()},
            "scheduler": self.scheduler.snapshot(),
            **({} if self.model_account is None else {
                self.model_account.block: self.model_account.snapshot()}),
            "state": {"slot_bytes": self.slot_state_bytes},
            "stream": stream,
            "kv_cache_bytes": self.cache_bytes,
            "kv_dtype": self.kv_dtype,
            "kv_bits": self.kv_bits,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "quant": {
                "blocks_quantized": self.quant_blocks_quantized,
                "scale_bytes": self.quant_scale_bytes,
            },
            "compile_cache": {
                "compiles": self.compiles,
                "warmed_buckets": list(self.warmed_buckets),
            },
        }


def profiler_sections() -> Dict:
    """The profiler's own `serving.*` and `generation.*` section
    timings (populated when ProfilingMode is OPERATIONS/ALL), merged
    into `GET /stats`."""
    return {name: stats for name, stats in
            OpProfiler.get_instance().timings().items()
            if name.startswith(("serving.", "generation."))}


# -- Prometheus text exposition ----------------------------------------
# `GET /metrics` on both the replica server and the fleet front-end is
# generated here from the SAME snapshot dicts `GET /stats` serves, so
# the two views cannot drift: one source of truth, two encodings.
# Output follows the text exposition format version 0.0.4 (`# TYPE`
# lines, label escaping, one family per metric name).

#: monotonically increasing snapshot fields -> emitted as counters with
#: the conventional ``_total`` suffix; every other numeric leaf is a
#: gauge. Keyed by the LEAF name, so ``faults.retries`` matches
#: ``retries`` here.
_PROM_COUNTERS = frozenset({
    "requests", "responses", "client_errors", "server_errors",
    "shed", "shed_batch", "shed_deadline", "timeouts",
    "retries", "recoveries", "quarantined", "drains",
    "batches", "prefills", "decode_steps", "tokens_generated",
    "prefill_chunks", "chunked_prefills",
    "prefix_hits", "session_hits", "session_misses",
    "prefix_tokens_matched", "prefill_tokens", "cow_copies",
    "prefix_evictions", "session_evictions",
    # speculative decoding (the `spec` snapshot block; leaf names —
    # `spec_verify_batches` also matches the `batches` rule, the rest
    # are matched here)
    "draft_tokens_proposed", "draft_tokens_accepted", "verify_batches",
    "rollbacks", "draft_fallbacks",
    # hierarchical KV tier (the `paged.offload` snapshot block)
    "demotions", "restores", "prefetch_hits", "demote_failures",
    "restore_failures",
    "compiles", "hits", "misses", "evictions",
    "client_disconnects",
    # the scheduler's time account and the HTTP tier's stream writes
    "iterations", "kv_live_token_steps", "kv_blocks_attended",
    "kv_blocks_spanned", "chunks",
    # routing counters of a served model with experts (the `moe` block)
    "decode_pairs", "decode_experts_touched", "decode_expert_slots",
    "chunk_pairs",
    # rows the state-space layers of a served model walked (`ssm`)
    "chunk_rows", "decode_rows",
    # fleet-side counters
    "routed", "hedges", "hedges_won", "hedge_budget_denied",
    "requests_lost", "ejections", "readmissions", "restarts",
    "streams", "sheds", "cooldowns", "breaker_trips",
    "breaker_probes", "breaker_recoveries", "fleet_shed",
    "session_affinity_hits",
    # training-side counters (supervisor / async writer / per-worker
    # fleet telemetry / event-timeline rollups / stats router)
    "anomalies_skipped", "async_checkpoints", "sync_checkpoints",
    "sharded_checkpoints", "preemptions", "preempts_broadcast",
    "preempts_received", "writes", "steps", "preempts",
    "anomaly_skips", "dropped",
    "preempt_broadcast", "preempt_received", "anomaly_skip",
    "rollback", "checkpoint_commit", "re_mesh", "resume",
})

_RESERVOIR_KEYS = frozenset(RESERVOIR_SNAPSHOT_KEYS)


def _prom_name(*parts: str) -> str:
    name = "_".join(p for p in parts if p)
    name = re.sub(r"[^a-zA-Z0-9_]", "_", name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def _prom_escape(value) -> str:
    return (str(value).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _prom_value(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


class _PromWriter:
    """Accumulates samples grouped per metric family (the exposition
    format requires all lines of one name to be contiguous, with the
    `# TYPE` line first)."""

    def __init__(self):
        self._families: "Dict[str, Dict]" = {}

    def sample(self, name: str, mtype: str, labels: Dict, value):
        fam = self._families.get(name)
        if fam is None:
            fam = self._families[name] = {"type": mtype, "lines": []}
        lab = ",".join(f'{k}="{_prom_escape(v)}"'
                       for k, v in labels.items() if v is not None)
        fam["lines"].append(
            f"{name}{{{lab}}} {_prom_value(value)}" if lab
            else f"{name} {_prom_value(value)}")

    def render(self) -> str:
        out = []
        for name, fam in self._families.items():
            out.append(f"# TYPE {name} {fam['type']}")
            out.extend(fam["lines"])
        return "\n".join(out) + "\n" if out else "\n"


def _walk(w: _PromWriter, base: str, labels: Dict, obj) -> None:
    """Recursively flatten a stats snapshot into exposition samples.
    Reservoir-shaped dicts become summaries (quantile-labelled, plus
    `_count`); integer-keyed dicts (CountHistograms) become one
    labelled series; strings are skipped (identity lives in labels)."""
    if isinstance(obj, bool) or isinstance(obj, (int, float)):
        if any(base.endswith("_" + c) or base == c
               for c in _PROM_COUNTERS):
            w.sample(base + "_total", "counter", labels, obj)
        else:
            w.sample(base, "gauge", labels, obj)
        return
    if isinstance(obj, dict):
        if obj and set(obj) == _RESERVOIR_KEYS:
            for q, key in (("0.5", "p50"), ("0.9", "p90"),
                           ("0.99", "p99")):
                w.sample(base, "summary",
                         {**labels, "quantile": q}, obj[key])
            w.sample(base + "_count", "summary", labels, obj["count"])
            w.sample(base + "_mean", "gauge", labels, obj["mean"])
            w.sample(base + "_max", "gauge", labels, obj["max"])
            return
        if obj and all(_is_int_key(k) for k in obj) and \
                all(isinstance(v, (int, float)) for v in obj.values()):
            # CountHistogram shape: int keys, numeric values -> one
            # bucket-labelled series. Int-keyed dicts of DICTS (e.g.
            # per-worker fleet telemetry) fall through to nested paths
            for k, v in obj.items():
                w.sample(base, "gauge", {**labels, "bucket": k}, v)
            return
        for k, v in obj.items():
            _walk(w, _prom_name(base, str(k)), labels, v)
        return
    if isinstance(obj, (list, tuple)):
        w.sample(base + "_count", "gauge", labels, len(obj))
        return
    # strings / None: identity belongs in labels, not sample values


def _is_int_key(k) -> bool:
    try:
        int(k)
        return True
    except (TypeError, ValueError):
        return False


def prometheus_text(stats: Dict, prefix: str = "dl4j") -> str:
    """Render a `/stats`-shaped snapshot (replica server or fleet
    router) as Prometheus text exposition. Replica server snapshots
    (``{"summary", "models", "profiler"}``) emit per-model families
    labelled ``{model=...}``; fleet snapshots (``{"fleet": ...}``)
    emit fleet counters plus per-replica gauges labelled
    ``{replica=...}``."""
    w = _PromWriter()
    if "models" in stats:
        summary = dict(stats.get("summary") or {})
        summary.pop("models", None)      # covered by the models block
        _walk(w, _prom_name(prefix, "server"), {}, summary)
        for mname, snap in (stats.get("models") or {}).items():
            _walk(w, _prom_name(prefix, "model"), {"model": mname}, snap)
        for section, timing in (stats.get("profiler") or {}).items():
            _walk(w, _prom_name(prefix, "profiler"),
                  {"section": section}, timing)
    elif "fleet" in stats:
        fl = dict(stats["fleet"])
        replicas = fl.pop("replicas", [])
        _walk(w, _prom_name(prefix, "fleet"), {}, fl)
        for rep in replicas:
            rid = rep.get("id") if isinstance(rep, dict) else None
            _walk(w, _prom_name(prefix, "replica"),
                  {"replica": rid}, rep)
    else:
        _walk(w, prefix, {}, stats)
    return w.render()

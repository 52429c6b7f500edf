"""Continuous-batching autoregressive generation runtime.

Iteration-level scheduling (Orca, OSDI '22) over a slot-managed
static-shape KV cache (the vLLM/PagedAttention regime at slot
granularity, PAPERS.md): instead of batching whole generate() calls —
where the fastest request waits for the slowest — the scheduler
re-forms the device batch EVERY DECODE STEP. Each iteration it

1. admits queued requests into free cache slots (one compiled prefill
   per admission, at the request's power-of-two prompt bucket),
2. decodes ONE token for every active slot in a single device call
   (the same compiled executable every step — shapes never change),
3. samples per-slot (greedy / temperature / top-k, per-request seeded
   PRNG folded with the step index, so results are reproducible
   regardless of which slot or step a request lands on), and
4. retires sequences on EOS or ``max_tokens``, freeing their slots for
   the next admission — a finishing request never blocks on its batch.

Exactly TWO executable kinds exist: single-token decode over the full
slot batch, and prefill per prompt bucket (a handful of power-of-two
lengths). ``warmup()`` AOT-compiles all of them, so steady-state
traffic — any mix of prompt lengths, generation lengths, and sampling
params — runs with ZERO recompiles.

Overload semantics match the micro-batcher: bounded queue sheds
(:class:`~.batcher.QueueFullError` → 503), per-request deadlines
(:class:`~.batcher.DeadlineExceededError` → 504) are enforced both in
the queue and mid-generation.

Admission control (docs/serving.md "Overload and admission control"):
requests carry a priority class (``interactive`` default, ``batch``
shed first — batch work only gets the front ``BATCH_QUEUE_FRACTION``
of the queue), and admission is cost-aware: the engine keeps measured
EWMAs of per-token prefill time and per-step decode time, rejects a
request up front when its estimated prefill + ``max_tokens`` decode
cost cannot fit its deadline budget (504 — no replica can serve it),
and sheds a queued request at dequeue-admission once its queue wait
has eaten the budget needed to produce even a first token — zero
prefill/decode steps are ever spent on a request that cannot finish.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import (Any, Callable, Dict, Iterator, List, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..faults import (CorruptedStateFault, PoisonRequestError,
                      TransientFault, poll_until_idle)
from ..profiler import OpProfiler
from .batcher import (PRIORITIES, DeadlineExceededError, DrainingError,
                      QueueFullError)
from .engine import ClientError, ServingError, compile_memoized
from ..kernels.kv_quant import (canonical_kv_dtype, kv_bytes_per_token,
                                kv_copy_row, kv_nbytes, kv_pack_host,
                                kv_unpack_host, kv_update_slice)
from ..kernels.paged_attention import kv_pool_zeros
from .kvcache import KVCache, SlotTable
from .metrics import GC_ACCOUNT, GenerationMetrics
from .offload import (DiskRing, HostBlockStore, HostRun,
                      OffloadPrefetcher)
from .paging import (NULL_BLOCK, BlockAllocator, BlockTable, CacheGroup,
                     PagedKVCache, PrefixIndex, SessionStore, blocks_for,
                     chain_hashes, export_block_run, import_block_run,
                     pow2_bucket)
from .speculative import (make_prime_fn, make_propose_fn,
                          make_verify_paged_fn, make_verify_slots_fn,
                          verify_bucket)

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# sampling (pure, jit-traced inside the executables)
# ---------------------------------------------------------------------------
#: static cap on per-request top_k: the filter thresholds via
#: ``lax.top_k(logits, cap)`` — a full per-row sort costs ~10x more on
#: CPU and the cap keeps the executable shape static. Requests asking
#: for top_k >= vocab get exact no-filter sampling.
TOP_K_CAP = 128

#: entries the prefix index holds before it evicts the least recently
#: used (one entry a full prompt block)
PREFIX_INDEX_CAPACITY = 1024

#: recompute-recoveries one request may take part in before it is
#: failed as the likely cause
MAX_RECOVERIES_PER_REQUEST = 3

#: heartbeat age past which ``alive()`` calls the scheduler wedged (the
#: loop beats every iteration; its longest pause is one device call)
STALL_TIMEOUT_S = 30.0

#: share of the queue that batch-class work may fill; interactive work
#: gets all of it
BATCH_QUEUE_FRACTION = 0.5


def _sample_from_logits(logits, temps, top_ks, us):
    """Greedy (temp <= 0) / temperature / top-k sampling, vectorized
    over rows; ``us`` is one pre-drawn uniform per row and ``top_ks <=
    0`` disables the filter per row. The single shared sampling core —
    prefill and decode both route through it, so the first token and
    every later token come from bit-identical math.

    Two deliberate cost choices, both measured against the decode-step
    budget: the top-k threshold comes from a static-cap ``lax.top_k``
    (not a full sort), and sampling is inverse-CDF with ONE uniform per
    sequence rather than categorical-via-Gumbel (Gumbel needs V
    independent draws per slot per step; the threefry bits for
    [num_slots, V] dominate small-model steps)."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    cap = min(TOP_K_CAP, vocab)
    desc = jax.lax.top_k(logits, cap)[0]                   # [S, cap]
    kth = jnp.take_along_axis(
        desc, jnp.clip(top_ks - 1, 0, cap - 1)[:, None], axis=1)
    filt = jnp.where((top_ks[:, None] > 0)
                     & (top_ks[:, None] < vocab)
                     & (logits < kth), _NEG_INF, logits)
    p = jax.nn.softmax(filt / jnp.maximum(temps, 1e-6)[:, None],
                       axis=-1)
    c = jnp.cumsum(p, axis=-1)
    sampled = jnp.argmax(c > (us * c[:, -1])[:, None],  # c[-1]: drift
                         axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def _sample_batch(logits, temps, top_ks, seeds, steps):
    """Decode-step sampling over the slot batch. The per-request PRNG
    stream is fold_in(PRNGKey(seed), step) — slot- and
    schedule-independent, so results are reproducible under any
    admission order."""
    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t))(
        seeds, steps)
    us = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
    return _sample_from_logits(logits, temps, top_ks, us)


def _sample_one(logits, temp, top_k, key):
    """Single-row sampling (prefill). ``key`` is the request's step-0
    fold; the math is the shared core, one-row batched."""
    u = jax.random.uniform(key, ())
    return _sample_from_logits(
        logits[None], jnp.asarray(temp, jnp.float32)[None],
        jnp.asarray(top_k, jnp.int32)[None], u[None])[0]


# ---------------------------------------------------------------------------
# request
# ---------------------------------------------------------------------------
def _recovery_seq(req: "_GenRequest") -> np.ndarray:
    """The K/V prefix a (possibly recovered) request must hold before
    its next decode step: the prompt, plus — after recompute-recovery —
    the already-emitted tokens minus the last one, whose K/V the next
    decode step writes at ``pos`` exactly like a fresh admission's
    first sampled token. Shared by both cache backends so the resume
    math can never diverge between them."""
    if req.tokens:
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
    return req.prompt


class _GenRequest:
    __slots__ = ("prompt", "max_tokens", "temperature", "top_k", "seed",
                 "eos_id", "deadline", "priority", "session_id", "event",
                 "tokens", "error", "finish_reason", "stream_q",
                 "stream_notify",
                 "t_submit", "t_first", "t_last", "abandoned",
                 "recoveries", "_lock", "_timeout_counted", "trace",
                 "qspan", "spec_rounds", "spec_proposed",
                 "spec_accepted", "spec_emitted", "spec_dt0", "spec_dt1",
                 "spec_vt0", "spec_vt1", "pipe_d0", "pipe_w0")

    def __init__(self, prompt, max_tokens, temperature, top_k, seed,
                 eos_id, deadline, stream: bool,
                 priority: str = "interactive",
                 session_id: Optional[str] = None):
        self.prompt = prompt
        self.session_id = session_id
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.eos_id = eos_id
        self.deadline = deadline
        self.priority = priority
        self.event = threading.Event()
        self.tokens: List[int] = []
        self.error: Optional[BaseException] = None
        self.finish_reason: Optional[str] = None
        # unbounded on purpose: admission is already bounded by the
        # request queue + slot count; the scheduler must never block on
        # a slow streaming consumer (head-of-line for every other slot)
        self.stream_q: Optional["queue.Queue"] = (
            queue.Queue() if stream else None)
        # optional post-put hook for event-loop consumers: lets an
        # async front-end park on an asyncio.Event instead of holding
        # a blocking-get thread per open stream. Must never raise into
        # the scheduler, so pushes go through _stream_push.
        self.stream_notify: Optional[Callable[[], None]] = None
        self.t_submit = time.perf_counter()
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.abandoned = False  # submitter gave up: skip, don't recount
        self.recoveries = 0     # recompute-recovery re-admissions
        self._lock = threading.Lock()
        self._timeout_counted = False
        self.trace = None   # tracing.Trace when the request is traced
        self.qspan = None   # its open queue-wait span
        # speculative-decoding participation, aggregated per request so
        # the terminal trace can rebuild draft/verify spans
        # retroactively (zero cost in the hot loop beyond 8 stores)
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_dt0: Optional[float] = None
        self.spec_dt1: Optional[float] = None
        self.spec_vt0: Optional[float] = None
        self.spec_vt1: Optional[float] = None
        # the scheduler account's (busy, blocked) seconds snapshotted
        # at decode entry; the terminal span reports the deltas over
        # this request's decode lifetime (engine-wide, not per-lane —
        # the sync is shared by the whole batch). None = never decoded
        self.pipe_d0: Optional[float] = None
        self.pipe_w0: Optional[float] = None

    def _stream_push(self, kind: str, payload, t_emit=None) -> None:
        """Queue one stream item. ``t_emit`` is the scheduler's emit
        stamp of a token: the front-end measures from it to the
        socket write (``stream.delay_s`` in ``/stats``)."""
        self.stream_q.put((kind, payload, t_emit))
        cb = self.stream_notify
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — consumer bug, not ours
                pass

    def count_timeout_once(self, metrics) -> None:
        """The waiter and the scheduler can both observe this request's
        deadline expiring at the same instant — the timeouts counter
        must move exactly once per request, so the decision is a CAS
        under the request's own lock."""
        with self._lock:
            if self._timeout_counted:
                return
            self._timeout_counted = True
        metrics.inc("timeouts")

    def result(self) -> Dict[str, Any]:
        return {"tokens": list(self.tokens),
                "prompt_tokens": len(self.prompt),
                "finish_reason": self.finish_reason}


class _TokenStream:
    """Iterator over one streaming generation. ``close()`` — invoked
    explicitly by the HTTP layer on disconnect, and by GC as a
    backstop — abandons an unfinished request so the scheduler frees
    its slot, EVEN if the consumer never started iterating (a plain
    generator's ``finally`` would not run in that case)."""

    def __init__(self, engine: "GenerationEngine", req: _GenRequest):
        self._engine = engine
        self._req = req
        self._i = 0
        self._done = False
        self._t_emit: Optional[float] = None  # of the item last returned

    def wrote(self) -> None:
        """The front-end has handed the item last returned to the
        socket: account the time since the scheduler emitted it."""
        t = self._t_emit
        if t is not None:
            self._t_emit = None
            self._engine.metrics.note_stream_write(
                time.perf_counter() - t)

    def __iter__(self) -> "Iterator[Dict]":
        return self

    def __next__(self) -> Dict:
        if self._done:
            raise StopIteration
        req = self._req
        budget = req.deadline - time.perf_counter() + 1.0
        try:
            kind, payload, self._t_emit = req.stream_q.get(
                timeout=max(budget, 0.001))
        except queue.Empty:
            self._done = True
            req.abandoned = True
            req.count_timeout_once(self._engine.metrics)
            raise DeadlineExceededError("stream stalled past the "
                                        "deadline")
        if kind == "token":
            i = self._i
            self._i += 1
            return {"token": int(payload), "index": i}
        self._done = True
        if kind == "done":
            self._engine.metrics.inc("responses")
            final = req.result()
            final["done"] = True
            return final
        raise payload  # "error"

    def close(self):
        if not self._done and self._req.finish_reason is None \
                and self._req.error is None:
            self._req.abandoned = True  # scheduler frees the slot
        self._done = True

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — never raise from GC
            pass


class _ChunkState:
    """One request mid-prefill on the paged backend: its slot, its
    block table, and the chunk plan with a cursor. The scheduler
    processes ONE chunk per loop iteration, interleaved with decode
    steps, so a long prompt's prefill never stalls the decode loop for
    longer than one chunk (Sarathi-Serve, PAPERS.md).

    ``seq`` is the token prefix the chunks run over: the prompt for a
    fresh admission, or prompt + already-emitted tokens (minus the
    last, whose K/V the next decode step writes) when re-admitted by
    recompute-recovery. ``start`` is the prefix-cache match length —
    positions below it hold valid K/V from shared/copied blocks, so
    the plan's first chunk begins there and ``done_tokens`` counts
    them as live from the moment of admission."""

    __slots__ = ("req", "slot", "table", "tbl_bucket", "plan", "idx",
                 "seq", "start")

    def __init__(self, req: "_GenRequest", slot: int, table: BlockTable,
                 tbl_bucket: int, plan: List[Tuple[int, int, int]],
                 seq: np.ndarray, start: int = 0):
        self.req = req
        self.slot = slot
        self.table = table
        self.tbl_bucket = tbl_bucket
        self.plan = plan                  # [(p0, chunk_bucket, len)]
        self.idx = 0
        self.seq = seq
        self.start = start

    @property
    def done_tokens(self) -> int:
        return self.plan[self.idx - 1][0] + self.plan[self.idx - 1][2] \
            if self.idx else self.start


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class GenerationEngine:
    """Slot-based continuous-batching decode engine over a
    :class:`~deeplearning4j_tpu.zoo.transformer_lm.CausalTransformerLM`
    (or any model exposing the same ``forward_prefill`` /
    ``forward_decode`` / ``cache_shapes`` surface).

    ``num_slots`` bounds concurrent in-flight sequences (the device
    batch of every decode step); ``max_seq_len`` bounds prompt +
    generated tokens per sequence and sizes the KV cache. Both are
    STATIC — admission control handles everything dynamic.

    Two cache backends (``cache=``):

    - ``"slots"`` (default) — dense per-slot panels
      ``[num_slots, H, max_seq_len, Dh]``: memory scales with the
      WORST-CASE sequence length per slot.
    - ``"paged"`` — a shared block pool
      ``[num_blocks, H, block_size, 2 * Dh]`` (`serving/paging.py`): a
      request claims ``ceil((prompt + max_tokens) / block_size)``
      blocks at admission (all-or-nothing — when blocks run out the
      request WAITS at the queue head instead of over-committing), so
      at equal pool bytes the engine holds as many more concurrent
      sequences as real lengths are shorter than ``max_seq_len``.
      Prefill runs in CHUNKS of at most ``prefill_chunk_tokens``
      interleaved with decode steps, so a long prompt admitted
      mid-stream cannot stall every other request's inter-token
      latency for more than one chunk. Token outputs are identical to
      the slot backend (test-asserted). With ``enable_prefix_sharing``
      (default on), admission matches the prompt against an LRU index
      of chained-content-hashed full prompt blocks and against
      ``session_id``-pinned conversation state: matched blocks join
      the request's table by refcount (skipping their prefill
      entirely, copy-on-write isolating any mid-block tail), so a
      fleet-wide system prompt is prefilled once and a chat turn
      re-prefills only its new suffix (docs/generation.md "Prefix
      sharing").
    """

    def __init__(self, model, num_slots: int = 8,
                 max_seq_len: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 min_prompt_bucket: int = 8,
                 max_queue: int = 256,
                 default_timeout_ms: float = 60_000.0,
                 decode_impl: str = "auto",
                 cache: str = "slots",
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 enable_prefix_sharing: bool = True,
                 session_capacity: int = 64,
                 metrics: Optional[GenerationMetrics] = None,
                 fault_injector=None,
                 max_step_retries: int = 3,
                 retry_backoff_ms: float = 1.0,
                 retry_backoff_max_ms: float = 50.0,
                 speculation_k: int = 0,
                 draft_model=None,
                 decode_pipeline: bool = True,
                 kv_dtype: str = "f32",
                 offload_host_bytes: int = 0,
                 offload_disk_bytes: int = 0,
                 offload_dir: Optional[str] = None):
        if getattr(model, "_params", None) is None:
            model.init()
        self.model = model
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.max_seq_len = int(max_seq_len or model.max_seq_len)
        if self.max_seq_len < 2:
            raise ValueError("max_seq_len must be >= 2 (one prompt "
                             "token + one generated token)")
        if self.max_seq_len > model.max_seq_len:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"position table ({model.max_seq_len})")
        # speculative decoding (serving/speculative.py): k = 0 is OFF
        # (the default — no draft model, no extra executables, the
        # decode loop is byte-for-byte the non-speculative one)
        self.speculation_k = int(speculation_k)
        if self.speculation_k < 0:
            raise ValueError(f"speculation_k must be >= 0, "
                             f"got {speculation_k}")
        if self.speculation_k and \
                self.speculation_k + 1 >= self.max_seq_len:
            raise ValueError(
                f"speculation_k {self.speculation_k} leaves no room "
                f"under max_seq_len {self.max_seq_len}")
        self._vbucket = (verify_bucket(self.speculation_k)
                         if self.speculation_k else 0)
        self.decode_impl = decode_impl
        # quantized serving plane (ISSUE 15): storage precision of the
        # KV pool — "f32" (exact, default), "bf16" (half the bytes),
        # "int8" (quarter; per-row f32 scale sidecars ride the same
        # pytrees). The draft cache stays f32: it is tiny and its
        # tokens are only proposals, verified by the target anyway.
        self.kv_dtype = canonical_kv_dtype(kv_dtype)
        self.default_timeout_ms = float(default_timeout_ms)
        self.min_prompt_bucket = int(min_prompt_bucket)
        if prompt_buckets is None:
            prompt_buckets = []
            b = self.min_prompt_bucket
            while b < self.max_seq_len:
                prompt_buckets.append(b)
                b <<= 1
        # max_seq_len is always a bucket so every admissible prompt
        # (validated <= max_seq_len - 1) has a compiled home; a custom
        # list with gaps just routes up to the next present bucket
        self.prompt_buckets = sorted(
            set(int(b) for b in prompt_buckets) | {self.max_seq_len})
        if self.prompt_buckets[0] < 1 or \
                self.prompt_buckets[-1] > self.max_seq_len:
            raise ValueError(f"prompt_buckets {self.prompt_buckets} "
                             f"outside [1, max_seq_len]")
        if cache not in ("slots", "paged"):
            raise ValueError(f"cache must be 'slots' or 'paged', "
                             f"got {cache!r}")
        self.cache_backend = cache
        # a model whose layers keep state that is not keys and values
        # DECLARES it (``slot_state_shapes(num_slots)``: arrays with the
        # slot first) and this engine allocates it beside the pools.
        # What cannot carry such state is refused, not served wrong
        # (docs/generation.md, "Models with state a slot")
        self._state_shapes = list(
            getattr(model, "slot_state_shapes", lambda n: [])(
                self.num_slots))
        self._stateful = bool(self._state_shapes)
        if self._stateful:
            if cache != "paged":
                raise ValueError(
                    "a model with slot state is served by the paged "
                    "backend only (cache='paged'): its prefill runs in "
                    "chunks that hand the state on")
            if self.speculation_k:
                raise ValueError(
                    "speculation_k > 0 is refused for a model with slot "
                    "state: a rejected draft rolls the KV cursor back, "
                    "and the slot state has no cursor to roll")
            if int(offload_host_bytes) > 0:
                raise ValueError(
                    "offload_host_bytes > 0 is refused for a model with "
                    "slot state: a demoted run holds keys and values "
                    "only, and a restore could not rebuild the state")
            # a matched prefix skips the chunks that would have built
            # the state: neither the prefix index nor the session
            # store is consulted
            enable_prefix_sharing = False
        # a model whose layers do not all keep the same positions
        # DECLARES its cache groups (``cache_groups()``: which layers
        # share a block table, which of those groups is a window) and
        # this engine keeps a pool size, an allocator and a table a
        # group, a window group's table as a ring (`serving/paging.py`,
        # CacheGroup). What cannot carry a ring is refused, not served
        # wrong (docs/generation.md, "Cache groups")
        declared = getattr(model, "cache_groups", lambda: None)()
        self._groups: List[CacheGroup] = []
        if declared:
            if cache != "paged":
                raise ValueError(
                    "a model with cache groups is served by the paged "
                    "backend only (cache='paged'): a window group lives "
                    "in a ring of pool blocks")
            if self.speculation_k:
                raise ValueError(
                    "speculation_k > 0 is refused for a model with cache "
                    "groups: a rejected draft rolls the KV cursor back "
                    "over ring entries that an older lap no longer holds")
            if int(offload_host_bytes) > 0:
                raise ValueError(
                    "offload_host_bytes > 0 is refused for a model with "
                    "cache groups: a demoted run is one table's blocks, "
                    "and a ring keeps only a window of them")
            # a shared prefix's window blocks are overwritten by the
            # ring's next lap: neither the prefix index nor the session
            # store is consulted
            enable_prefix_sharing = False
        if cache == "paged":
            self.block_size = int(block_size)
            if not 1 <= self.block_size <= self.max_seq_len:
                raise ValueError(f"block_size {block_size} outside "
                                 f"[1, max_seq_len]")
            # dense decode-table width: every position < max_seq_len
            # has a table entry, so one decode executable serves all
            self._blocks_per_seq = blocks_for(self.max_seq_len,
                                              self.block_size)
            # one count, or ``{group: count}`` for a model with cache
            # groups (a count alone is its first group's)
            by_group: Dict[str, int] = {}
            if isinstance(num_blocks, dict):
                if not declared:
                    raise ValueError("num_blocks by group for a model "
                                     "that declares no cache groups")
                by_group = dict(num_blocks)
                num_blocks = by_group.pop(declared[0]["name"], None)
            if num_blocks is None:
                # dense-equivalent capacity (+1 for the null block);
                # shrink it to realize the memory win, or keep it and
                # raise num_slots to realize the concurrency win
                num_blocks = self.num_slots * self._blocks_per_seq + 1
            self.num_blocks = int(num_blocks)
            # chunk ladder: the prompt buckets capped at the chunk
            # size; prefill_chunk_tokens=None means whole-prompt
            # single-chunk prefill (chunking off, paging still on)
            cap = self.prompt_buckets[-1]
            if prefill_chunk_tokens is not None:
                if int(prefill_chunk_tokens) < 1:
                    raise ValueError("prefill_chunk_tokens must be >= 1")
                cap = min(pow2_bucket(int(prefill_chunk_tokens)), cap)
            self.prefill_chunk_tokens = (
                cap if prefill_chunk_tokens is not None else None)
            self._chunk_cap = cap
            self.chunk_buckets = sorted(
                set(b for b in self.prompt_buckets if b < cap) | {cap})
            # largest per-request table bucket: the last chunk's
            # bucket can overshoot the allocation by < chunk_cap, and
            # a speculative verify span's padded tail by < its bucket.
            # The overshoot MUST stay inside the table (not merely be
            # masked): an out-of-range gather index clamps to the
            # table's LAST entry, which for an exactly-sized table is
            # a REAL block — the padded rows' junk writes would land
            # in live data
            self._tbl_top = pow2_bucket(
                blocks_for(self.max_seq_len + max(cap, self._vbucket),
                           self.block_size))
            self._tbl_buckets = []
            b = 1
            while b <= self._tbl_top:
                self._tbl_buckets.append(b)
                b <<= 1
            self._allocator = BlockAllocator(self.num_blocks)
            self._tables = np.full(
                (self.num_slots, self._blocks_per_seq), NULL_BLOCK,
                np.int32)
            self._slot_blocks: List[Optional[BlockTable]] = \
                [None] * self.num_slots
            for i, g in enumerate(declared or ()):
                ring = None
                if g.get("window") is not None:
                    if i == 0:
                        raise ValueError("a model's first cache group "
                                         "keeps every position")
                    # the window, one chunk written ahead of the oldest
                    # key that chunk reads, and a block for a window
                    # that starts inside one
                    ring = blocks_for(int(g["window"]) + cap,
                                      self.block_size) + 1
                n = self.num_blocks if i == 0 else by_group.pop(
                    g["name"], self.num_slots * (
                        ring or self._blocks_per_seq) + 1)
                self._groups.append(CacheGroup(
                    g["name"], g["layers"], n, self.num_slots,
                    ring or self._blocks_per_seq, g.get("window"), ring))
            if by_group:
                raise ValueError(f"num_blocks names no cache group of "
                                 f"the model: {sorted(by_group)}")
            self._alias_first_group()
            # a model that declares slot state or cache groups takes
            # ``live`` / ``slot`` and returns counters beside its logits
            self._extended = self._stateful or bool(self._groups)
            self._prefilling: "collections.deque[_ChunkState]" = \
                collections.deque()
            self._held: Optional[_GenRequest] = None
            # the group whose pool could not cover the held request
            self._held_group: Optional[str] = None
            # prefix sharing: chained-hash index over full prompt
            # blocks + session pins; both are scheduler-thread state
            self.enable_prefix_sharing = bool(enable_prefix_sharing)
            self._prefix_index = PrefixIndex(PREFIX_INDEX_CAPACITY)
            self._sessions = SessionStore(int(session_capacity))
        else:
            self.prefill_chunk_tokens = None
            self.enable_prefix_sharing = False
        # -- hierarchical KV tier (PR 16; serving/offload.py) --------
        # offload_host_bytes > 0 turns demote-on-evict on: evicted
        # session/prefix pins copy device->host (at kv_dtype, scale
        # sidecars included) instead of being discarded, and a
        # returning session RESTORES host->device instead of
        # re-prefilling. offload_disk_bytes adds a mmap'd ring file
        # as a third tier below host RAM.
        self.offload_host_bytes = int(offload_host_bytes)
        self._offload: Optional[HostBlockStore] = None
        self._offload_prefetcher: Optional[OffloadPrefetcher] = None
        self._off_buckets: List[int] = []
        if self.offload_host_bytes > 0:
            if self.cache_backend != "paged":
                raise ValueError("offload_host_bytes requires the "
                                 "paged cache backend (cache='paged')")
            if not self.enable_prefix_sharing:
                raise ValueError(
                    "offload_host_bytes requires prefix sharing "
                    "(enable_prefix_sharing=True): restores re-enter "
                    "the engine through session/prefix matching")
            disk = None
            if int(offload_disk_bytes) > 0:
                import os as _os
                path = (_os.path.join(offload_dir, "kv_ring.bin")
                        if offload_dir else None)
                disk = DiskRing(int(offload_disk_bytes), path=path)
            self._offload = HostBlockStore(self.offload_host_bytes,
                                           disk=disk)
            # demoted runs span 1..blocks_for(max_seq_len) blocks;
            # pow2-bucketing the gather/scatter index keeps the
            # executable set finite and AOT-warmable (the same rule
            # the block tables use)
            top = pow2_bucket(self._blocks_per_seq)
            self._off_buckets = [b for b in self._tbl_buckets
                                 if b <= top]
            # restores are staged off the scheduler's thread (disk
            # read, padded operands, h2d) while the request queues
            self._offload_prefetcher = OffloadPrefetcher(
                self._stage_restore)
        self.metrics = metrics or GenerationMetrics()
        self.metrics.queue_max = int(max_queue)
        self.metrics.num_slots = self.num_slots
        self.metrics.cache_backend = self.cache_backend
        self._cache = self._fresh_cache()
        self.metrics.cache_bytes = self._cache.nbytes()
        for g in self._groups:     # bytes one block pins, its layers
            g.block_bytes = sum(
                2 * kv_nbytes((1,) + self._cache.layer_shapes[i],
                              self.kv_dtype) for i in g.layers)
        self.metrics.kv_dtype = self.kv_dtype
        self.metrics.kv_bits = {"f32": 32, "bf16": 16, "int8": 8}[
            self.kv_dtype]
        self.metrics.kv_bytes_per_token = kv_bytes_per_token(
            self._cache.layer_shapes, self.kv_dtype)
        self.metrics.quant_scale_bytes = self._cache.scale_nbytes()
        self._bind_cache()
        self._state = self._fresh_state()
        self.metrics.slot_state_bytes = int(sum(
            a.nbytes for a in self._state))
        # a model whose forwards return counters beside their logits
        # owns what they mean: ``step_account()`` gives the object that
        # takes each step's and each chunk's vector and shows in /stats
        self._model_account = getattr(model, "step_account",
                                      lambda: None)()
        self.metrics.model_account = self._model_account
        self._slots = SlotTable(self.num_slots)
        # -- speculative decoding state -----------------------------
        self._draft = None
        self._draft_cache = None
        self._draft_kcs = self._draft_vcs = None
        if self.speculation_k:
            if draft_model is None:
                from ..zoo.transformer_lm import make_draft_lm
                draft_model = make_draft_lm(model)
            if getattr(draft_model, "_params", None) is None:
                draft_model.init()
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.vocab_size} != target "
                    f"vocab {model.vocab_size}: the draft must share "
                    f"the target's tokenizer")
            if draft_model.max_seq_len < self.max_seq_len:
                raise ValueError(
                    f"draft position table ({draft_model.max_seq_len})"
                    f" shorter than max_seq_len {self.max_seq_len}")
            self._draft = draft_model
            self._reset_draft_cache()
            self.metrics.cache_bytes += self._draft_cache.nbytes()
            # draft-prime bucket ladder: pow2 steps TOPPED BY
            # max_seq_len itself (not its pow2 round-up — the draft's
            # dense cache is exactly max_seq_len deep, and the prime
            # update slab must fit inside it)
            self._prime_buckets = []
            b = self.min_prompt_bucket
            while b < self.max_seq_len:
                self._prime_buckets.append(b)
                b <<= 1
            self._prime_buckets.append(self.max_seq_len)
        self.metrics.speculation_k = self.speculation_k
        if self.cache_backend == "paged":
            self.metrics.block_size = self.block_size
            self.metrics.blocks_total = self._allocator.capacity
            self.metrics.prefix_sharing = self.enable_prefix_sharing
            self.metrics.offload_enabled = self._offload is not None
            self._update_block_gauges()
        self._profiler = OpProfiler.get_instance()
        # exactly two executable kinds: decode (one) + prefill (per
        # prompt bucket). Compiled lazily or via warmup(); the dict is
        # bounded by len(prompt_buckets), so no LRU is needed.
        self._decode_exe = None
        self._prefill_exe: Dict[int, Any] = {}
        self._cow_exe = None  # paged + sharing: block device-copy
        # hierarchical KV tier: block-run gather (demote) / scatter
        # (restore) executables, one per pow2 idx bucket
        self._offload_save_exe: Dict[int, Any] = {}
        self._offload_load_exe: Dict[int, Any] = {}
        # speculative executables: one draft-propose, draft-prime per
        # prime bucket, verify per table bucket (paged) or one (slots)
        self._draft_exe = None
        self._draft_prime_exe: Dict[int, Any] = {}
        self._verify_exe: Dict[Any, Any] = {}
        self._exe_lock = threading.Lock()
        # K/V caches are DONATED to every prefill/decode call: XLA then
        # updates the cache in place instead of copying the whole
        # [num_slots, max_seq_len, ...] arrays each step — without this
        # the per-step cost scales with num_slots and continuous
        # batching loses its amortization (measured 0.5x vs sequential
        # on CPU with copies; 4x+ with donation)
        # Slots: the K and the V panels. Paged: the pools (one array a
        # layer) and the slot state: an empty list for a model that
        # declares none, which adds nothing to its programs
        self._donate = (1, 2)
        # -- pipelined decode (ISSUE 14) ----------------------------
        # With the pipeline on (default; speculation forces it off —
        # verify rounds are inherently synchronous), the scheduler
        # dispatches decode step t+1 BEFORE syncing step t's tokens,
        # so host bookkeeping (emit, retire, admit) overlaps device
        # compute. Donation already forces device program order, so
        # the overlap changes WHEN the host learns each token, never
        # WHICH token. The knob exists for A/B identity tests.
        self.decode_pipeline = bool(decode_pipeline) \
            and not self.speculation_k
        # in-flight decode steps, oldest first (depth is at most 2 for
        # the moment between dispatching t+1 and collecting t)
        self._pending: "collections.deque" = collections.deque()
        # device handle of the LAST dispatched step's sampled tokens
        # ([num_slots] int32, never synced) — fed back as the next
        # step's tok_dev input; None until the first dispatch
        self._nxt_dev = None
        # lanes whose current token lives ONLY on the device (True
        # after a pipelined dispatch; False on prefill / free /
        # recovery, which refresh the host mirror)
        self._tok_on_dev = np.zeros(self.num_slots, bool)
        # constants for the non-pipelined path: read host tokens for
        # every lane, no device feedback (never mutated, safe to share
        # across calls without the defensive .copy())
        self._all_host = np.ones(self.num_slots, bool)
        self._no_dev_tok = np.zeros(self.num_slots, np.int32)
        # the scheduler's time account (metrics.SchedulerAccount):
        # phase counters in /stats and gen.* spans in a profiler trace
        self._sched = self.metrics.scheduler
        self._sched.declare_groups(g.name for g in self._groups)
        self._queue: "queue.Queue[_GenRequest]" = queue.Queue(
            maxsize=int(max_queue))
        # submit-wake: an idle scheduler parks on this event instead
        # of polling the queue every 50 ms (ISSUE 14) — set by
        # _enqueue after each put and by stop()/drain()
        self._wake = threading.Event()
        # priority shedding: batch-class work only gets the front
        # fraction of the queue; interactive gets all of it
        self._batch_queue_limit = max(
            1, int(BATCH_QUEUE_FRACTION * int(max_queue)))
        # cost-aware admission: measured EWMAs (per PROMPT TOKEN of
        # prefill, per STEP of decode) — 0.0 until the first call
        # lands, so a cold engine admits everything. What the decode
        # EWMA is fed is a step's DISPATCH-TO-RESULTS span (the
        # decode_step_ms samples), not the time a step adds: with the
        # pipeline on, a span covers the wait behind the step before
        # it and any chunk between, so it reads up to twice the
        # loop's cycle (scheduler.loop_s over decode steps in /stats
        # is the cycle). Admission reads it as a worst case.
        self._prefill_ms_per_tok = 0.0
        self._decode_ewma_ms = 0.0
        # -- fault tolerance (deeplearning4j_tpu/faults.py) ---------
        # seams fire only when an injector is configured; the
        # supervised loop always runs (real device faults need no
        # injector to happen)
        self._faults = fault_injector
        self._max_step_retries = int(max_step_retries)
        self._retry_backoff_s = float(retry_backoff_ms) / 1e3
        self._retry_backoff_max_s = float(retry_backoff_max_ms) / 1e3
        # requests to re-admit AHEAD of the queue: transient-faulted
        # admissions and recompute-recovery re-admissions (they were
        # already accepted — later arrivals must not starve them)
        self._requeue: "collections.deque[_GenRequest]" = \
            collections.deque()
        self._draining = False
        self._beat = time.monotonic()  # scheduler heartbeat (/healthz)
        self._running = True
        GC_ACCOUNT.start()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="generation-scheduler")
        self._thread.start()

    def _fresh_cache(self):
        """Cache sized to the ENGINE's max_seq_len (which may be below
        the model's position table) — decode attention scans the full
        cache capacity every step, so capacity must match the
        configured bound, not the architectural one. Paged: the pool's
        per-block layer shapes come from the same model surface."""
        if self.cache_backend == "paged":
            shapes = self.model.cache_shapes(self.block_size)
            blocks = [self.num_blocks] * len(shapes)
            for g in self._groups:
                for i in g.layers:
                    blocks[i] = g.num_blocks
            return PagedKVCache(shapes, blocks, kv_dtype=self.kv_dtype)
        return KVCache(self.model.cache_shapes(self.max_seq_len),
                       self.num_slots, kv_dtype=self.kv_dtype)

    def _bind_cache(self):
        """The cache's arrays as the programs thread them: ``_pools``
        (paged: one array a layer) or ``_kcs`` / ``_vcs`` (slots)."""
        if self.cache_backend == "paged":
            self._pools = self._cache.pools
        else:
            self._kcs = self._cache.ks
            self._vcs = self._cache.vs

    def _fresh_state(self):
        """The arrays a model keeps a slot (``slot_state_shapes``:
        each its own shape and type, a recurrence's float32 state
        beside a convolution's bfloat16 inputs), zeroed once here and
        never again: a request's first chunk starts from zeros
        whatever its slot held."""
        return [jnp.zeros(shape, dtype)
                for shape, dtype in self._state_shapes]

    def _alias_first_group(self):
        """A model with cache groups: the engine's own allocator, decode
        tables and per-slot tables ARE its first group's."""
        if self._groups:
            g = self._groups[0]
            self._allocator, self._tables, self._slot_blocks = (
                g.allocator, g.tables, g.slot_blocks)

    def _reset_blocks(self):
        """Nothing allocated, every table NULL (recovery: the pools
        were donated away)."""
        if self._groups:
            for g in self._groups:
                g.reset()
            self._alias_first_group()
            return
        self._allocator = BlockAllocator(self.num_blocks)
        self._tables[:] = NULL_BLOCK
        self._slot_blocks = [None] * self.num_slots

    def _step_tables(self):
        """The decode tables as the step takes them: the array, or one
        a cache group."""
        if not self._groups:
            return self._tables.copy()
        return tuple(g.tables.copy() for g in self._groups)

    def _null_tables(self, width: Optional[int] = None):
        """Tables of NULL entries in the shapes the programs take (for
        compiling them): ``width`` entries of one sequence (a chunk's
        bucket), or the decode step's ``[num_slots, table width]``."""
        def one(w):
            return np.full(w if width is not None
                           else (self.num_slots, w), NULL_BLOCK, np.int32)
        first = one(width if width is not None else self._blocks_per_seq)
        if not self._groups:
            return first
        return (first,) + tuple(one(g.table_width)
                                for g in self._groups[1:])

    def _update_block_gauges(self):
        """Push allocator + liveness gauges into the metrics object
        (snapshot() reads them lock-free from the stats thread).

        ``kv_tokens_live`` counts each UNIQUE block once at its
        maximum valid fill across owners — summing per-owner lengths
        (the pre-sharing rule) would double-count a shared prefix and
        drive fragmentation negative. With sharing disabled every
        block has one owner and this reduces to the old sum."""
        a = self._allocator
        self.metrics.blocks_free = a.free_count
        self.metrics.blocks_peak_used = a.peak_used
        bs = self.block_size
        fill: Dict[int, int] = {}

        def cover(blocks, n_tokens):
            for i, b in enumerate(blocks):
                f = min(bs, int(n_tokens) - i * bs)
                if f <= 0:
                    break
                if f > fill.get(b, 0):
                    fill[b] = f

        st = self._slots
        for s in range(self.num_slots):
            if st.requests[s] is not None and st.step[s] > 0:
                t = self._slot_blocks[s]
                if t is not None:
                    cover(t.blocks, int(st.pos[s]) + 1)
        for c in self._prefilling:
            cover(c.table.blocks, c.done_tokens)
        for blocks, n in self._sessions.iter_pins():
            cover(blocks, n)
        for b in self._prefix_index.blocks():
            fill[b] = bs  # indexed blocks are full prompt blocks
        self.metrics.kv_tokens_live = sum(fill.values())
        self.metrics.kv_tokens_allocated = a.used_count * bs
        if self.kv_dtype == "int8":
            # every allocated block holds quantize-on-write content
            self.metrics.quant_blocks_quantized = a.used_count
        if self._groups:
            # a group's live positions: what a later step can still
            # read of each sequence (its window's at most)
            lens = [int(st.pos[s]) + 1 for s in range(self.num_slots)
                    if st.requests[s] is not None and st.step[s] > 0]
            lens += [c.done_tokens for c in self._prefilling]
            self.metrics.groups = {g.name: {
                "blocks_total": g.allocator.capacity,
                "blocks_free": g.allocator.free_count,
                "blocks_peak_used": g.allocator.peak_used,
                "block_bytes": g.block_bytes, "window": g.window,
                "ring_blocks": g.ring,
                "kv_tokens_live": g.rows_read(lens)}
                for g in self._groups}
        self.metrics.shared_blocks = a.shared_count
        self.metrics.prefix_blocks = len(self._prefix_index)
        self.metrics.sessions_live = len(self._sessions)
        off = self._offload
        if off is not None:
            s = off.stats()
            m = self.metrics
            m.offload_host_runs = s["host_runs"]
            m.offload_host_blocks = s["host_blocks"]
            m.offload_host_bytes = s["host_bytes"]
            m.offload_disk_blocks = s["disk_blocks"]
            m.offload_disk_bytes = s["disk_bytes"]
            m.offload_spills = s["spills"]
            m.offload_drops = s["drops"]

    # -- executables ---------------------------------------------------
    # Every executable also returns a FINITE-LOGITS flag computed
    # in-graph (an all-reduce over isfinite — noise next to the
    # matmuls): the poison-request guard. A request whose own weights+
    # tokens drive the logits to NaN/Inf is QUARANTINED by the host
    # loop — failed alone with 500, slot/blocks freed — instead of
    # silently emitting garbage or wedging the batch.
    def _decode_fn(self):
        """One decode step over the full slot batch.

        Two ISSUE 14 additions, both in-graph so the pipelined
        scheduler never needs an extra host round-trip:

        - **Token merge.** Each lane's input token comes from EITHER
          the host mirror (``tok_host`` — fresh prefills, recovery
          resumes, the non-pipelined path) OR the PREVIOUS step's
          device output fed straight back in (``tok_dev``), selected
          per lane by ``use_host``. That is what lets the scheduler
          dispatch step t+1 before step t's tokens ever reach the
          host: a continuing lane's token never leaves the device.
        - **Fused termination.** ``done`` = sampled-EOS | length-cap,
          computed from the per-lane ``eos`` id (-1 = none; sampled
          tokens are >= 0 so -1 never matches) and ``max_steps``
          (``steps`` counts tokens already emitted, so this step is
          number ``steps + 1``). Retirement needs no host-side
          re-derivation from request state."""
        model = self.model
        impl = self.decode_impl

        if self.cache_backend == "paged":
            stateful = self._extended
            grouped = bool(self._groups)

            def step(params, pools, state, tok_host, tok_dev, use_host,
                     pos, tables, seeds, steps, temps, top_ks, eos,
                     max_steps):
                tokens = jnp.where(use_host, tok_host, tok_dev)
                counters = ()
                if stateful:
                    # a lane is LIVE when it has blocks and tokens left
                    # to emit: a mid-prefill slot (its chunks own its
                    # state) and a lane the pipeline ran once past its
                    # end write no state and count nowhere
                    first = tables[0] if grouped else tables
                    live = (first[:, 0] != NULL_BLOCK) \
                        & (steps < max_steps)
                    logits, pools, state, counters = \
                        model.forward_decode_paged(
                            params, tokens, pos, pools, tables, impl,
                            state=state, live=live)
                else:
                    logits, pools, state = model.forward_decode_paged(
                        params, tokens, pos, pools, tables, impl,
                        state=state)
                ok = jnp.all(jnp.isfinite(logits), axis=-1)  # per lane
                nxt = _sample_batch(logits, temps, top_ks, seeds, steps)
                done = ((nxt == eos) & (eos >= 0)) \
                    | (steps + 1 >= max_steps)
                return nxt, ok, done, pools, state, counters
            return step

        def step(params, kcs, vcs, tok_host, tok_dev, use_host, pos,
                 seeds, steps, temps, top_ks, eos, max_steps):
            tokens = jnp.where(use_host, tok_host, tok_dev)
            logits, kcs, vcs = model.forward_decode(params, tokens, pos,
                                                    kcs, vcs, impl)
            ok = jnp.all(jnp.isfinite(logits), axis=-1)      # per lane
            nxt = _sample_batch(logits, temps, top_ks, seeds, steps)
            done = ((nxt == eos) & (eos >= 0)) | (steps + 1 >= max_steps)
            return nxt, ok, done, kcs, vcs
        return step

    def _chunk_fn(self):
        model = self.model

        stateful = self._extended

        def chunk(params, pools, state, tokens, p0, chunk_len, table,
                  slot, seed, temp, top_k):
            counters = ()
            if stateful:
                logits, pools, state, counters = \
                    model.forward_prefill_chunk(
                        params, tokens, p0, chunk_len, pools, table,
                        state=state, slot=slot, last_only=True)
            else:
                logits, pools, state = model.forward_prefill_chunk(
                    params, tokens, p0, chunk_len, pools, table,
                    state=state, last_only=True)
            # ``logits`` is [1, V], the head for the one row sampled
            # below, and NaN when a row < chunk_len of the final hidden
            # state is not finite. Padded tail rows attend positions
            # past the live length — stale block junk that is allowed
            # to be anything (no-zeroing invariant) — and are not read
            ok = jnp.all(jnp.isfinite(logits))
            # same step-0 fold as the slot prefill — the first token's
            # sample is bit-identical across backends
            key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
            first = _sample_one(logits[0], temp, top_k, key)
            return first, ok, pools, state, counters
        return chunk

    def _prefill_fn(self):
        model = self.model

        def prefill(params, kcs, vcs, tokens, length, slot, seed, temp,
                    top_k):
            bucket = tokens.shape[1]
            key_mask = (jnp.arange(bucket)[None] < length).astype(
                jnp.float32)
            logits, ks, vs = model.forward_prefill(params, tokens,
                                                   key_mask)
            # padded rows only see keys under key_mask, so any
            # non-finite value traces back to the request's own tokens
            ok = jnp.all(jnp.isfinite(logits))
            # write this request's K/V rows into its slot; positions
            # past ``length`` hold junk from the padded prompt tail but
            # stay masked (and are overwritten as decode advances)
            kcs = [kv_update_slice(kc, k, (slot, 0, 0, 0))
                   for kc, k in zip(kcs, ks)]
            vcs = [kv_update_slice(vc, v, (slot, 0, 0, 0))
                   for vc, v in zip(vcs, vs)]
            last = jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0, keepdims=False)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
            first = _sample_one(last, temp, top_k, key)
            return first, ok, kcs, vcs
        return prefill

    def _get_decode_exe(self):
        if self._decode_exe is not None:
            return self._decode_exe
        with self._exe_lock:
            if self._decode_exe is not None:
                return self._decode_exe
            S = self.num_slots
            if self.cache_backend == "paged":
                args = (self.model._params, self._pools, self._state,
                        np.zeros(S, np.int32), np.zeros(S, np.int32),
                        np.ones(S, bool), np.zeros(S, np.int32),
                        self._null_tables(),
                        np.zeros(S, np.uint32), np.zeros(S, np.int32),
                        np.zeros(S, np.float32), np.zeros(S, np.int32),
                        np.full(S, -1, np.int32), np.zeros(S, np.int32))
            else:
                args = (self.model._params, self._kcs, self._vcs,
                        np.zeros(S, np.int32), np.zeros(S, np.int32),
                        np.ones(S, bool), np.zeros(S, np.int32),
                        np.zeros(S, np.uint32), np.zeros(S, np.int32),
                        np.zeros(S, np.float32), np.zeros(S, np.int32),
                        np.full(S, -1, np.int32), np.zeros(S, np.int32))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(self._decode_fn(), args,
                                       self._donate)
            self.metrics.inc("compiles")
            self._decode_exe = exe
            return exe

    def _get_chunk_exe(self, chunk_bucket: int, tbl_bucket: int):
        """Paged prefill executable for one (chunk bucket, table
        bucket) pair — the bounded grid replacing the slot backend's
        per-prompt-bucket prefill set."""
        key = (chunk_bucket, tbl_bucket)
        exe = self._prefill_exe.get(key)
        if exe is not None:
            return exe
        with self._exe_lock:
            exe = self._prefill_exe.get(key)
            if exe is not None:
                return exe
            args = (self.model._params, self._pools, self._state,
                    np.zeros((1, chunk_bucket), np.int32), np.int32(0),
                    np.int32(1), self._null_tables(tbl_bucket),
                    np.int32(0), np.uint32(0), np.float32(0.0),
                    np.int32(0))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(self._chunk_fn(), args,
                                       self._donate)
            self.metrics.inc("compiles")
            self._prefill_exe[key] = exe
            return exe

    def _cow_fn(self):
        def cow(pools, src, dst):
            # kv_copy_row copies the int8 block AND its scale rows
            # together — a scale-less copy would silently rescale the
            # shared prefix (tests/test_kv_quant.py::TestCOWScales)
            return [kv_copy_row(p, src, dst) for p in pools]
        return cow

    def _get_cow_exe(self):
        """Copy-on-write executable: duplicate one pool block (all
        layers, K+V) into another. src/dst are runtime scalars, so ONE
        executable covers every copy — warmed like the rest, it can
        never recompile under traffic."""
        if self._cow_exe is not None:
            return self._cow_exe
        with self._exe_lock:
            if self._cow_exe is not None:
                return self._cow_exe
            args = (self._pools, np.int32(0), np.int32(0))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(self._cow_fn(), args, (0,))
            self.metrics.inc("compiles")
            self._cow_exe = exe
            return exe

    def _cow(self, src: int, dst: int):
        """Device-copy block ``src`` into ``dst`` so the admitted
        request can write into its private copy while every other
        reader of ``src`` stays bit-unchanged. The pools are donated;
        the caller maps a failure here to recompute-recovery exactly
        like a failed prefill/decode call."""
        with self._profiler.record("generation.cow"):
            self._pools = self._get_cow_exe()(
                self._pools, np.int32(src), np.int32(dst))
            jax.block_until_ready(self._pools[0])  # surface device faults

    # -- hierarchical KV tier (PR 16; serving/offload.py) --------------
    # Demotion gathers a block run device->host; restore scatters it
    # back. Both are one executable per pow2 idx bucket, compiled
    # through the same memoized path as the COW copy — the idx array
    # and row operands are RUNTIME values, so after warmup() no
    # offload traffic can ever recompile.
    def _get_offload_save_exe(self, bucket: int):
        """Block-run gather executable (demotion read). Pools are NOT
        donated: a failed demotion must leave the device tier exactly
        as it was, so the engine can fall back to plain discard."""
        exe = self._offload_save_exe.get(bucket)
        if exe is not None:
            return exe
        with self._exe_lock:
            exe = self._offload_save_exe.get(bucket)
            if exe is not None:
                return exe
            args = (self._pools, np.full(bucket, NULL_BLOCK, np.int32))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(export_block_run, args, ())
            self.metrics.inc("compiles")
            self._offload_save_exe[bucket] = exe
            return exe

    def _get_offload_load_exe(self, bucket: int):
        """Block-run scatter executable (restore write). Pools ARE
        donated (the restore writes in place); padded idx rows point
        at the null block. A real failure here donated the pools away
        — the caller maps it to recompute-recovery, exactly like a
        failed prefill."""
        exe = self._offload_load_exe.get(bucket)
        if exe is not None:
            return exe
        with self._exe_lock:
            exe = self._offload_load_exe.get(bucket)
            if exe is not None:
                return exe
            rows = [kv_pool_zeros((bucket,) + s, self.kv_dtype)
                    for s in self._cache.layer_shapes]
            args = (self._pools, rows,
                    np.full(bucket, NULL_BLOCK, np.int32))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(import_block_run, args, (0,))
            self.metrics.inc("compiles")
            self._offload_load_exe[bucket] = exe
            return exe

    def _export_run(self, tokens: np.ndarray,
                    blocks: List[int]) -> HostRun:
        """Device half of a demotion: gather the run's pool rows (all
        layers, K+V) and pack them into contiguous host arrays at the
        pool dtype. kv_pack_host's np.asarray forces the device->host
        sync, so on return the source blocks may be freed."""
        bucket = pow2_bucket(len(blocks))
        idx = np.full(bucket, NULL_BLOCK, np.int32)
        idx[:len(blocks)] = blocks
        with self._profiler.record("generation.offload_demote"):
            rows = self._get_offload_save_exe(bucket)(self._pools, idx)
            layers = [kv_pack_host(r, len(blocks)) for r in rows]
        return HostRun(tokens, layers, self.kv_dtype)

    def _build_restore_ops(self, run: HostRun, bucket: int):
        """Zero-pad a HostRun's packed layers up to ``bucket`` rows —
        the scatter executable's operands. Pure host/h2d work: this is
        the half a prefetch overlaps with admission."""
        return [kv_unpack_host(layer, bucket) for layer in run.layers]

    def _import_run(self, run: HostRun, blocks: List[int], ops=None):
        """Device half of a restore: scatter the packed run into the
        freshly-allocated ``blocks``. Raises whatever the device call
        raises — the pools were donated, so the CALLER maps failures
        to recompute-recovery."""
        bucket = pow2_bucket(len(blocks))
        idx = np.full(bucket, NULL_BLOCK, np.int32)
        idx[:len(blocks)] = blocks
        if ops is None:
            ops = self._build_restore_ops(run, bucket)
        with self._profiler.record("generation.offload_restore"):
            self._pools = self._get_offload_load_exe(bucket)(
                self._pools, ops, idx)
            jax.block_until_ready(self._pools[0])  # surface device faults

    def _demote_session(self, sess) -> bool:
        """Copy an evicted session's block run to the host tier (the
        caller still frees the device blocks — ownership of the BYTES
        moves down a tier, ownership of the BLOCKS ends). Any failure
        — the offload_io seam or a real gather error — degrades to the
        old discard path: the gather never donates, so the device tier
        is untouched and dropping the copy is always safe."""
        off = self._offload
        sid = sess.session_id
        if off is None or sid is None:
            return False
        t0 = time.perf_counter()
        try:
            self._hit("offload_io")
            run = self._export_run(sess.tokens, sess.blocks)
        except Exception:  # noqa: BLE001 — torn demotion -> discard
            self.metrics.inc("offload_demote_failures")
            return False
        off.put(sid, run)
        self.metrics.inc("offload_demotions")
        self.metrics.offload_demote_ms.record(
            (time.perf_counter() - t0) * 1e3)
        return True

    def _demote_prefix(self, digest: bytes, block: int) -> bool:
        """Demote one evicted prefix-index block, keyed by its chained
        digest — a future admission whose prompt hashes to the same
        chain restores it instead of re-prefilling the block."""
        off = self._offload
        if off is None:
            return False
        try:
            self._hit("offload_io")
            run = self._export_run(np.zeros(0, np.int32), [block])
        except Exception:  # noqa: BLE001 — torn demotion -> discard
            self.metrics.inc("offload_demote_failures")
            return False
        off.put("px:" + digest.hex(), run)
        self.metrics.inc("offload_demotions")
        return True

    def _stage_restore(self, key: str):
        """Prefetch-thread staging: read the run (RAM or disk) and
        build the padded scatter operands. HOST + h2d work only — the
        allocator and every pool-mutating device call stay on the
        scheduler thread, so staging can never race engine state."""
        off = self._offload
        if off is None:
            return None
        run = off.get(key)
        if run is None:
            return None
        bucket = pow2_bucket(run.n_blocks)
        return run, self._build_restore_ops(run, bucket)

    def _offload_restore(self, req: _GenRequest) -> bool:
        """The restore-vs-reprefill decision for one admission: if the
        request's session was demoted, scatter its run back into
        freshly-allocated blocks and re-pin it — ``_match_prefix`` then
        finds a normal session hit and the turn pays only its suffix
        prefill (a restore is a planned cache miss, never a
        re-prefill). Falls back to the plain path (full prefill) on:
        no host copy, token mismatch, pool too full even after
        eviction, or a torn restore (offload_io seam). Only a REAL
        scatter failure escapes — as CorruptedStateFault, because the
        pools were donated to the scatter call."""
        off = self._offload
        if off is None or req.tokens or req.session_id is None:
            return False
        sid = req.session_id
        if sid in self._sessions:
            return False  # device pin is current; host copy is stale
        staged = self._offload_prefetcher.take(sid)
        run = ops = None
        if staged is not None:
            run, ops = staged
            if off.peek(sid) is not run:
                # the session was re-demoted (or popped) after staging
                # — the staged operands describe stale bytes
                run = ops = None
        if run is None:
            run = off.get(sid)
            if run is None:
                return False
        # token-granular usefulness check, same rule as _match_prefix's
        # session branch: the stored turn must prefix-match the prompt
        prompt = req.prompt
        stored = run.tokens
        n = min(len(stored), len(prompt) - 1)
        neq = stored[:n] != prompt[:n]
        m = int(np.argmax(neq)) if neq.any() else n
        if m <= 0:
            return False
        try:
            self._hit("offload_io")
        except (TransientFault, CorruptedStateFault):
            # torn restore: invalidate the host copy and re-prefill —
            # the lane never saw a device call, nothing to corrupt
            off.pop(sid)
            self._offload_prefetcher.discard(sid)
            self.metrics.inc("offload_restore_failures")
            return False
        blocks = self._alloc_with_eviction(run.n_blocks)
        if blocks is None:
            return False  # pool cannot hold the run; re-prefill
        t0 = time.perf_counter()
        try:
            self._import_run(run, blocks, ops)
        except Exception as e:  # noqa: BLE001 — pools donated
            raise CorruptedStateFault(
                f"offload restore device call failed: {e!r}")
        displaced = self._sessions.put(sid, run.tokens, list(blocks))
        evictions = 0
        for old in displaced:
            if old.session_id != sid:
                self._demote_session(old)
                evictions += 1
            self._allocator.free(old.blocks)
        if evictions:
            self.metrics.inc("session_evictions", evictions)
        off.pop(sid)
        self.metrics.inc("offload_restores")
        if ops is not None:
            self.metrics.inc("offload_prefetch_hits")
        self.metrics.offload_restore_ms.record(
            (time.perf_counter() - t0) * 1e3)
        if req.trace is not None:
            req.trace.span("offload_restore", tokens=len(stored),
                           blocks=run.n_blocks,
                           prefetched=ops is not None).end()
        return True

    def _restore_prefix_blocks(self, req: _GenRequest):
        """Restore demoted PREFIX blocks the prompt's chain hashes
        to. Runs before ``_match_prefix`` so restored entries are
        matched by the normal index path; stops at the first digest
        found in neither the index nor the host tier (the chain is
        broken there — later blocks cannot be used anyway)."""
        off = self._offload
        if off is None or req.tokens or not self.enable_prefix_sharing:
            return
        if req.session_id is not None and req.session_id in self._sessions:
            return  # the session pin already covers the prefix
        for h in chain_hashes(req.prompt, self.block_size):
            if self._prefix_index.match([h]):
                continue
            key = "px:" + h.hex()
            run = off.get(key)
            if run is None:
                return
            try:
                self._hit("offload_io")
            except (TransientFault, CorruptedStateFault):
                off.pop(key)
                self.metrics.inc("offload_restore_failures")
                return
            blocks = self._alloc_with_eviction(1)
            if blocks is None:
                return
            try:
                self._import_run(run, blocks)
            except Exception as e:  # noqa: BLE001 — pools donated
                raise CorruptedStateFault(
                    f"offload prefix restore device call failed: {e!r}")
            self._prefix_index.register(h, blocks[0])
            off.pop(key)
            self.metrics.inc("offload_restores")

    def _get_prefill_exe(self, bucket: int):
        exe = self._prefill_exe.get(bucket)
        if exe is not None:
            return exe
        with self._exe_lock:
            exe = self._prefill_exe.get(bucket)
            if exe is not None:
                return exe
            args = (self.model._params, self._kcs, self._vcs,
                    np.zeros((1, bucket), np.int32), np.int32(1),
                    np.int32(0), np.uint32(0), np.float32(0.0),
                    np.int32(0))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(self._prefill_fn(), args,
                                       self._donate)
            self.metrics.inc("compiles")
            self._prefill_exe[bucket] = exe
            return exe

    # -- speculative executables (serving/speculative.py) --------------
    def _reset_draft_cache(self, disable_lanes: bool = False):
        """(Re)build the draft model's dense slot cache. Called at
        construction, after recompute-recovery (the draft replays
        nothing — lanes re-prime at their next decode entry), and when
        a draft device call dies mid-flight (its caches were donated;
        ``disable_lanes`` then drops every lane to plain decode until
        re-primed, WITHOUT touching the target's state — a draft
        failure must never cost target work)."""
        self._draft_cache = KVCache(
            self._draft.cache_shapes(self.max_seq_len), self.num_slots)
        self._draft_kcs = self._draft_cache.ks
        self._draft_vcs = self._draft_cache.vs
        if disable_lanes:
            self._slots.spec_ok[:] = False

    def _get_draft_exe(self):
        """One batched draft-propose executable: k greedy draft steps
        over ALL slots in a single device call."""
        if self._draft_exe is not None:
            return self._draft_exe
        with self._exe_lock:
            if self._draft_exe is not None:
                return self._draft_exe
            S = self.num_slots
            args = (self._draft._params, self._draft_kcs,
                    self._draft_vcs, np.zeros(S, np.int32),
                    np.zeros(S, np.int32))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(
                    make_propose_fn(self._draft, self.speculation_k,
                                    self.decode_impl),
                    args, (1, 2))
            self.metrics.inc("compiles")
            self._draft_exe = exe
            return exe

    def _get_draft_prime_exe(self, bucket: int):
        exe = self._draft_prime_exe.get(bucket)
        if exe is not None:
            return exe
        with self._exe_lock:
            exe = self._draft_prime_exe.get(bucket)
            if exe is not None:
                return exe
            args = (self._draft._params, self._draft_kcs,
                    self._draft_vcs, np.zeros((1, bucket), np.int32),
                    np.int32(1), np.int32(0))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(make_prime_fn(self._draft),
                                       args, (1, 2))
            self.metrics.inc("compiles")
            self._draft_prime_exe[bucket] = exe
            return exe

    def _get_verify_exe(self, tbl_bucket: Optional[int] = None):
        """Target-side verification executable: per table bucket on
        the paged backend (the verify span's block table is padded to
        the same pow2 ladder the chunk prefill uses), a single one on
        slots."""
        key = tbl_bucket if self.cache_backend == "paged" else "slots"
        exe = self._verify_exe.get(key)
        if exe is not None:
            return exe
        with self._exe_lock:
            exe = self._verify_exe.get(key)
            if exe is not None:
                return exe
            vb = self._vbucket
            if self.cache_backend == "paged":
                fn = make_verify_paged_fn(self.model)
                args = (self.model._params, self._pools,
                        np.zeros((1, vb), np.int32), np.int32(0),
                        np.int32(1),
                        np.full(tbl_bucket, NULL_BLOCK, np.int32),
                        np.uint32(0), np.int32(0), np.float32(0.0),
                        np.int32(0))
            else:
                fn = make_verify_slots_fn(self.model)
                args = (self.model._params, self._kcs, self._vcs,
                        np.zeros((1, vb), np.int32), np.int32(0),
                        np.int32(1), np.int32(0), np.uint32(0),
                        np.int32(0), np.float32(0.0), np.int32(0))
            with self._profiler.record("generation.compile"):
                exe = compile_memoized(
                    fn, args,
                    (1,) if self.cache_backend == "paged"
                    else self._donate)
            self.metrics.inc("compiles")
            self._verify_exe[key] = exe
            return exe

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> List[int]:
        """AOT-compile the decode executable plus every prefill
        executable, so traffic never compiles. Slots: one prefill per
        prompt bucket (default: all of ``prompt_buckets``). Paged: one
        per (chunk bucket, table bucket) pair — only pairs where the
        table can actually hold the chunk (``tbl * block_size >=
        chunk``) exist in traffic, so only those are compiled. With
        speculation enabled, also the draft-propose, per-bucket
        draft-prime, and per-table-bucket verify executables — so a
        speculative engine is exactly as recompile-free under traffic
        as a plain one (test-asserted).
        Returns the warmed (chunk-)bucket list."""
        self._get_decode_exe()
        warmed = []
        if self.cache_backend == "paged":
            if self.enable_prefix_sharing:
                self._get_cow_exe()
            if self._offload is not None:
                # one gather + one scatter executable per pow2 run
                # bucket: warmed here, offload traffic never compiles
                for b in self._off_buckets:
                    self._get_offload_save_exe(b)
                    self._get_offload_load_exe(b)
            for c in sorted(set(int(x) for x in (buckets
                                                 or self.chunk_buckets))):
                if c not in self.chunk_buckets:
                    raise ValueError(f"bucket {c} not in chunk_buckets "
                                     f"{self.chunk_buckets}")
                for t in self._tbl_buckets:
                    if t * self.block_size >= c:
                        self._get_chunk_exe(c, t)
                warmed.append(c)
        else:
            for b in sorted(set(int(x) for x in (buckets
                                                 or self.prompt_buckets))):
                if b not in self.prompt_buckets:
                    raise ValueError(f"bucket {b} not in prompt_buckets "
                                     f"{self.prompt_buckets}")
                self._get_prefill_exe(b)
                warmed.append(b)
        if self.speculation_k:
            self._get_draft_exe()
            for b in self._prime_buckets:
                self._get_draft_prime_exe(b)
            if self.cache_backend == "paged":
                for t in self._tbl_buckets:
                    if t * self.block_size >= self._vbucket:
                        self._get_verify_exe(t)
            else:
                self._get_verify_exe()
        self.metrics.warmed_buckets = sorted(
            set(self.metrics.warmed_buckets) | set(warmed))
        return warmed

    # -- client side ---------------------------------------------------
    def _make_request(self, prompt, max_tokens, temperature, top_k, seed,
                      eos_id, timeout_ms, stream,
                      priority="interactive",
                      session_id=None) -> _GenRequest:
        if priority not in PRIORITIES:
            raise ClientError(
                f"unknown priority {priority!r}; expected one of "
                f"{PRIORITIES}")
        if session_id is not None:
            if not isinstance(session_id, str) or not session_id:
                raise ClientError("session_id must be a non-empty "
                                  "string")
            if len(session_id) > 256:
                raise ClientError("session_id must be <= 256 chars")
            if self.cache_backend != "paged":
                raise ClientError("session_id requires the paged cache "
                                  "backend (cache='paged')")
            if self._stateful:
                raise ClientError(
                    "session_id is refused for a model with slot state: "
                    "a pinned session holds keys and values only")
            if self._groups:
                raise ClientError(
                    "session_id is refused for a model with cache "
                    "groups: the ring's next lap overwrites a pinned "
                    "turn's window blocks")
            if not self.enable_prefix_sharing:
                raise ClientError(
                    "session_id requires prefix sharing "
                    "(enable_prefix_sharing=True)")
        if self._draining:
            # checked before _running: a drained replica answers 503 +
            # Retry-After (retry elsewhere), not 500, for its lifetime
            self.metrics.inc("shed")
            raise DrainingError("generation engine is draining; retry "
                                "against another replica")
        if not self._running:
            raise ServingError("generation engine is stopped")
        try:
            raw = np.asarray(prompt)
        except (TypeError, ValueError) as e:
            raise ClientError(f"prompt is not a token array: {e}")
        if not np.issubdtype(raw.dtype, np.integer):
            # np.asarray(.., int32) would silently truncate [3.7, 12.2]
            # to [3, 12] — answer for the wrong prompt, no error
            raise ClientError("prompt token ids must be integers")
        prompt = raw.astype(np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ClientError("prompt must be a non-empty 1-D list of "
                              "token ids")
        vocab = self.model.vocab_size
        if (prompt < 0).any() or (prompt >= vocab).any():
            raise ClientError(f"prompt token ids must be in [0, {vocab})")
        if len(prompt) > self.max_seq_len - 1:
            raise ClientError(
                f"prompt length {len(prompt)} leaves no room to generate "
                f"(max_seq_len {self.max_seq_len})")
        max_tokens = int(max_tokens)
        if max_tokens < 1:
            raise ClientError("max_tokens must be >= 1")
        temperature = float(temperature)
        if not np.isfinite(temperature):
            # json.loads happily parses NaN/Infinity; a NaN here would
            # silently produce argmax-of-all-False = token 0 forever
            raise ClientError("temperature must be finite")
        if timeout_ms is not None and not np.isfinite(float(timeout_ms)):
            raise ClientError("timeout_ms must be finite")
        top_k = int(top_k)
        # normalize the documented no-filter spellings HERE so every
        # value reaching the scheduler is int32-safe — an overflow at
        # the np.int32() device call would poison all in-flight work
        if top_k <= 0 or top_k >= vocab:
            top_k = 0
        elif top_k > TOP_K_CAP:
            raise ClientError(
                f"top_k {top_k} exceeds the engine's static top-k cap "
                f"({TOP_K_CAP}); use top_k=0 (or >= vocab) for "
                "unfiltered sampling")
        # the cache slot is the hard budget: prompt + generation fit it
        max_tokens = min(max_tokens, self.max_seq_len - len(prompt))
        if self.cache_backend == "paged":
            need = blocks_for(len(prompt) + max_tokens, self.block_size)
            if need > self._allocator.capacity:
                raise ClientError(
                    f"request needs {need} KV blocks but the pool has "
                    f"{self._allocator.capacity}; lower max_tokens or "
                    "grow num_blocks")
        if eos_id is None:
            eos_id = getattr(self.model, "eos_id", None)
        timeout = (self.default_timeout_ms if timeout_ms is None
                   else float(timeout_ms)) / 1000.0
        est_ms = self._est_cost_ms(len(prompt), max_tokens)
        if est_ms > timeout * 1e3:
            # cost-aware admission: the measured per-token prefill +
            # per-step decode EWMAs say this request CANNOT finish
            # inside its own deadline budget (worst case: the full
            # max_tokens) — reject before any device work, 504 (no
            # replica can serve it; lower max_tokens or raise the
            # timeout)
            self.metrics.inc("shed_deadline")
            self.metrics.inc("timeouts")
            raise DeadlineExceededError(
                f"estimated cost {est_ms:.0f} ms ({len(prompt)} prompt "
                f"tokens + {max_tokens} max_tokens at measured rates) "
                f"exceeds the {timeout * 1e3:.0f} ms deadline budget")
        return _GenRequest(prompt, max_tokens, float(temperature),
                           int(top_k), int(seed) & 0xFFFFFFFF, eos_id,
                           time.perf_counter() + timeout, stream,
                           priority=priority, session_id=session_id)

    def _padded_prefill_len(self, prompt_len: int) -> int:
        """Prompt tokens the device will actually COMPUTE over during
        prefill: the padded bucket width(s), not the raw length.
        ``_note_prefill_cost`` normalizes the per-token EWMA by padded
        width, so cost estimates must scale by the same quantity — a
        5-token prompt in a 128 bucket pays the full bucket's
        prefill. Paged: the sum of the chunk plan's buckets; slots:
        the prompt bucket the request rounds up to."""
        if self.cache_backend == "paged":
            return sum(b for _, b, _ in self._chunk_plan(prompt_len))
        return next((b for b in self.prompt_buckets if b >= prompt_len),
                    self.prompt_buckets[-1])

    def _est_cost_ms(self, prompt_len: int, max_tokens: int) -> float:
        """Worst-case service estimate from measured rates: prefill of
        the whole PADDED prompt plus ``max_tokens`` decode steps. 0.0
        on a cold engine (no data, no rejection)."""
        return (self._padded_prefill_len(prompt_len)
                * self._prefill_ms_per_tok
                + max_tokens * self._decode_ewma_ms)

    def _deadline_blown(self, req: _GenRequest,
                        now: Optional[float] = None) -> bool:
        """Dequeue-admission deadline budget: not merely 'past the
        deadline' but 'the time left cannot cover even a first token'
        (prefill of the pending prefix + one decode step, at measured
        rates) — in which case prefilling would burn device steps on
        rows nobody will read."""
        now = time.perf_counter() if now is None else now
        min_work_ms = (self._padded_prefill_len(len(req.prompt))
                       * self._prefill_ms_per_tok
                       + self._decode_ewma_ms)
        return now > req.deadline - min_work_ms / 1e3

    def _enqueue(self, req: _GenRequest):
        if self._draining:
            self.metrics.inc("shed")
            raise DrainingError("generation engine is draining; retry "
                                "against another replica")
        if req.priority == "batch" and \
                self._queue.qsize() >= self._batch_queue_limit:
            # shed order: batch first — interactive may still use the
            # remaining queue, so its p99 TTFT holds while batch sheds
            self.metrics.inc("shed")
            self.metrics.inc("shed_batch")
            raise QueueFullError(
                f"generation queue at the batch-priority limit "
                f"({self._batch_queue_limit}/{self.metrics.queue_max});"
                f" shedding batch-class work first")
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            self.metrics.inc("shed")
            raise QueueFullError(
                f"generation queue full ({self.metrics.queue_max}); "
                "shedding load")
        self._wake.set()  # unpark an idle scheduler immediately
        if not self._running:
            req.abandoned = True
            raise ServingError("generation engine is stopped")
        self.metrics.inc("requests")
        self.metrics.queue_depth = self._queue.qsize()

    def generate(self, prompt, max_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 eos_id: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 priority: str = "interactive",
                 session_id: Optional[str] = None,
                 trace=None) -> Dict[str, Any]:
        """Blocking generate: returns ``{"tokens", "prompt_tokens",
        "finish_reason"}``. Raises :class:`~.engine.ClientError` /
        :class:`~.batcher.QueueFullError` /
        :class:`~.batcher.DeadlineExceededError`. ``priority`` is
        ``"interactive"`` (default) or ``"batch"`` (shed first under
        pressure). ``session_id`` (paged backend with prefix sharing
        only) pins the finished request's KV blocks in the session
        store so the conversation's next turn re-prefills only its new
        suffix — see docs/generation.md "Prefix sharing". ``trace``
        (a :class:`~..tracing.Trace`, default ``None`` = untraced)
        records admission/queue/prefill spans plus a retroactive
        decode span — the decode loop itself carries no
        instrumentation, so tracing costs nothing per step."""
        req = self._submit(prompt, max_tokens, temperature, top_k,
                           seed, eos_id, timeout_ms, stream=False,
                           priority=priority, session_id=session_id,
                           trace=trace)
        budget = req.deadline - time.perf_counter()
        if not req.event.wait(budget + 1.0):  # grace for the device call
            req.abandoned = True
            req.count_timeout_once(self.metrics)
            raise DeadlineExceededError(
                f"no result within {budget * 1e3:.0f} ms")
        if req.error is not None:
            raise req.error
        self.metrics.inc("responses")
        return req.result()

    def stream(self, prompt, max_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0, seed: int = 0,
               eos_id: Optional[int] = None,
               timeout_ms: Optional[float] = None,
               priority: str = "interactive",
               session_id: Optional[str] = None,
               trace=None) -> Iterator[Dict]:
        """Streaming generate: yields ``{"token", "index"}`` per token
        as the scheduler produces it, then ``{"done": True,
        "finish_reason", ...}``. Admission (validation, queue bounds)
        happens HERE — synchronously — so callers can still map those
        to status codes; later failures raise from the iterator."""
        req = self._submit(prompt, max_tokens, temperature, top_k,
                           seed, eos_id, timeout_ms, stream=True,
                           priority=priority, session_id=session_id,
                           trace=trace)
        return _TokenStream(self, req)

    def _submit(self, *args, trace=None, **kw) -> _GenRequest:
        """Validate + enqueue, counting pre-admission 5xx here — the
        engine owns ALL of its server_errors accounting (requests that
        never reach the scheduler have no _fail to count them; the
        HTTP layer deliberately counts none for generation)."""
        t0 = time.perf_counter()
        try:
            req = self._make_request(*args, **kw)
            if trace is not None:
                # attach BEFORE enqueue: the scheduler can admit the
                # request the instant it lands in the queue
                req.trace = trace
                trace.span(
                    "admission", t_start=t0, verdict="admitted",
                    est_cost_ms=round(self._est_cost_ms(
                        len(req.prompt), req.max_tokens), 3),
                    prefill_ms_per_tok=round(
                        self._prefill_ms_per_tok, 4),
                    decode_ewma_ms=round(self._decode_ewma_ms, 3)).end()
                req.qspan = trace.span("queue",
                                       priority=req.priority)
            if (self._offload is not None
                    and req.session_id is not None
                    and req.session_id not in self._sessions
                    and req.session_id in self._offload):
                # async prefetch: start staging the demoted run (disk
                # read + padded operand build + h2d) NOW, so it
                # overlaps this request's queue wait — the scheduler
                # takes the staged operands at admission and pays only
                # the scatter. Staleness is re-checked at take time,
                # so a racy glance at the session store here is safe.
                self._offload_prefetcher.request(req.session_id)
            self._enqueue(req)
            return req
        except (ClientError, QueueFullError, DeadlineExceededError) as e:
            if trace is not None:
                trace.span(
                    "admission", t_start=t0, verdict="shed",
                    error=str(e),
                    prefill_ms_per_tok=round(
                        self._prefill_ms_per_tok, 4),
                    decode_ewma_ms=round(self._decode_ewma_ms, 3)).end()
            raise  # counted via their own counters / client's fault
        except Exception:
            self.metrics.inc("server_errors")
            raise

    # -- scheduler side ------------------------------------------------
    def _hit(self, seam: str):
        """Fire the fault-injection seam (no-op without an injector:
        one attribute load)."""
        fi = self._faults
        if fi is not None:
            fi.fire(seam)

    def _trace_terminal(self, req: _GenRequest, reason=None, exc=None):
        """Record the request's terminal span RETROACTIVELY from fields
        the engine already tracks (t_first/t_last/token count) — this
        is how the decode hot loop stays entirely free of tracing code
        while enabled traces still show per-request decode timing and
        the PR 4 fault counters (recoveries/quarantine)."""
        tr = req.trace
        if tr is None:
            return
        if req.qspan is not None:
            req.qspan.end()  # idempotent; covers never-admitted sheds
        attrs = {"steps": len(req.tokens),
                 "recoveries": req.recoveries}
        if reason is not None:
            attrs["finish_reason"] = reason
        if exc is not None:
            attrs["error"] = repr(exc)
            if isinstance(exc, PoisonRequestError):
                attrs["quarantined"] = True
        if req.t_first is not None:
            end = req.t_last if req.t_last is not None else req.t_first
            tr.span("decode", t_start=req.t_first, t_end=end, **attrs)
        else:
            tr.span("error" if exc is not None else "decode",
                    **attrs).end()
        if req.pipe_d0 is not None:
            # the scheduler's time account over this request's decode
            # lifetime (whole iterations), from the two numbers
            # snapshotted at decode entry. ENGINE-wide, not per-lane:
            # every lane in the batch shares one dispatch and one
            # sync. wall_ms is the loop's non-idle wall; sync_wait_ms
            # is the part of it spent blocked in a step's or a
            # chunk's fetch — their gap is host work, the only part
            # that dispatching ahead of the sync can hide.
            busy_s, blocked_s = self._sched.busy_and_blocked()
            wall_s = busy_s - req.pipe_d0
            wait_s = blocked_s - req.pipe_w0
            if wall_s > 0:
                tr.span("step_pipeline",
                        wall_ms=round(wall_s * 1e3, 3),
                        sync_wait_ms=round(wait_s * 1e3, 3),
                        overlap_frac=round(
                            max(0.0, 1.0 - wait_s / wall_s), 4)).end()
        if req.spec_rounds:
            # speculative participation, rebuilt retroactively from the
            # per-request aggregates (the hot loop never touches the
            # tracer): one draft span + one verify span covering first
            # to last round, with the accounting as attributes
            rate = round(req.spec_accepted / max(req.spec_proposed, 1),
                         4)
            tr.span("draft", t_start=req.spec_dt0, t_end=req.spec_dt1,
                    rounds=req.spec_rounds,
                    proposed=req.spec_proposed)
            tr.span("verify", t_start=req.spec_vt0, t_end=req.spec_vt1,
                    rounds=req.spec_rounds,
                    proposed=req.spec_proposed,
                    accepted=req.spec_accepted,
                    accept_rate=rate,
                    spec_tokens=req.spec_emitted,
                    saved_est_ms=round(
                        max(req.spec_emitted - req.spec_rounds, 0)
                        * self._decode_ewma_ms, 3))

    def _fail(self, req: _GenRequest, exc: BaseException,
              count: bool = True):
        """``count=False`` for graceful-shutdown drains: a deploy
        restart is not an outage and must not spike server_errors
        (matching the MicroBatcher's uncounted drain)."""
        req.error = exc
        if isinstance(exc, DeadlineExceededError):
            req.count_timeout_once(self.metrics)
        elif count and not isinstance(exc, ClientError):
            self.metrics.inc("server_errors")
        self._trace_terminal(req, exc=exc)
        if req.stream_q is not None:
            req._stream_push("error", exc)
        req.event.set()

    def _emit(self, req: _GenRequest, token: int, now: float,
              itl_out: Optional[List[float]] = None):
        """Deliver one generated token. Latency samples are appended to
        ``itl_out`` (when given) so the decode loop can record the
        whole step's batch under one histogram lock; the tokens-rate
        meter is likewise batched per device call by the callers."""
        req.tokens.append(token)
        if req.t_first is None:
            req.t_first = now
            self.metrics.ttft_ms.record((now - req.t_submit) * 1e3)
        elif itl_out is not None:
            itl_out.append((now - req.t_last) * 1e3)
        else:
            self.metrics.itl_ms.record((now - req.t_last) * 1e3)
        req.t_last = now
        if req.stream_q is not None:
            req._stream_push("token", token, now)
            fi = self._faults
            if fi is not None and fi.fire("client_disconnect"):
                # simulate the HTTP consumer hanging up mid-stream:
                # exactly what _TokenStream.close() does on a real
                # disconnect — the scheduler frees the slot/blocks at
                # the next retirement check
                req.abandoned = True

    def _release_slot(self, slot: int):
        """Free a slot AND (paged) its blocks + decode-table row. No
        zeroing either way: the next occupant's writes overwrite what
        it uses and lengths mask the rest (`serving/paging.py`
        invariants)."""
        self._slots.free(slot)
        self._tok_on_dev[slot] = False
        if self.cache_backend == "paged":
            table = self._slot_blocks[slot]
            if table is not None:
                self._allocator.free(table.blocks)
                self._slot_blocks[slot] = None
            self._tables[slot] = NULL_BLOCK
            for g in self._groups[1:]:
                if g.slot_blocks[slot] is not None:
                    g.allocator.free(g.slot_blocks[slot].blocks)
                    g.slot_blocks[slot] = None
                g.tables[slot] = NULL_BLOCK
            self._update_block_gauges()
        self.metrics.active_slots = self._slots.active_count

    def _finish(self, slot: int, req: _GenRequest, reason: str):
        req.finish_reason = reason
        if (req.session_id is not None
                and self.cache_backend == "paged"
                and self.enable_prefix_sharing):
            # clean finish with a session: pin the blocks for turn N+1
            # (failure paths — quarantine, deadline, abandonment — all
            # release via _release_slot and never reach here)
            self._pin_session(slot, req)
        else:
            self._release_slot(slot)
        self._trace_terminal(req, reason=reason)
        if req.stream_q is not None:
            req._stream_push("done", reason)
        req.event.set()

    def _check_done(self, slot: int, req: _GenRequest, token: int,
                    now: Optional[float] = None) -> bool:
        """Retirement test after each emitted token. EOS wins over
        length so the reason is stable when both trip at once."""
        if req.abandoned:
            # the waiter gave up (and counted its own timeout): free
            # the slot now instead of decoding tokens nobody will read
            self._release_slot(slot)
            return True
        if req.eos_id is not None and token == req.eos_id:
            self._finish(slot, req, "eos")
            return True
        if len(req.tokens) >= req.max_tokens:
            self._finish(slot, req, "length")
            return True
        if (time.perf_counter() if now is None else now) > req.deadline:
            self._release_slot(slot)
            self._fail(req, DeadlineExceededError(
                "deadline exceeded mid-generation "
                f"({len(req.tokens)} tokens emitted)"))
            return True
        return False

    def _retire(self, slot: int, req: _GenRequest, token: int,
                done: bool, now: float) -> bool:
        """Retirement off the decode executable's FUSED ``done`` flag
        (EOS | length, computed in-graph — see :meth:`_decode_fn`):
        the host only disambiguates WHICH of the two tripped, for the
        finish_reason, with EOS winning when both trip at once —
        identical semantics to :meth:`_check_done`, which remains the
        host-side test for paths without fused flags (prefill's first
        token, speculative commits). Abandonment and deadline stay
        host-side: both are wall-clock/consumer conditions the device
        cannot know."""
        if req.abandoned:
            self._release_slot(slot)
            return True
        if done:
            if req.eos_id is not None and token == req.eos_id:
                self._finish(slot, req, "eos")
            else:
                self._finish(slot, req, "length")
            return True
        if now > req.deadline:
            self._release_slot(slot)
            self._fail(req, DeadlineExceededError(
                "deadline exceeded mid-generation "
                f"({len(req.tokens)} tokens emitted)"))
            return True
        return False

    def _next_queued(self, busy: bool) -> Optional[_GenRequest]:
        """Pop the next queued request without idle-spinning. A BUSY
        engine (active lanes / chunks mid-prefill) must keep its
        decode loop stepping, so the pop is non-blocking exactly as
        before. A fully IDLE engine used to poll ``get(timeout=0.05)``
        — 20 wakeups/s and up to 50 ms of added TTFT per idle engine;
        it now parks on the submit-wake event (_enqueue sets it after
        every put; stop()/drain() set it too), with a 1 s backstop
        wait in case a wake is ever lost."""
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            if busy:
                return None
        # clear-then-recheck closes the lost-wakeup race: a submit
        # landing between the failed pop and clear() re-sets the
        # event and the second pop sees its request. The backstop
        # wait is bounded well under the stall watchdog so an idle
        # engine's heartbeat never looks wedged to /healthz.
        self._wake.clear()
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            with self._sched.phase("idle"):
                self._wake.wait(
                    max(0.05, min(1.0, STALL_TIMEOUT_S / 4.0)))
            return None

    def _admit(self):
        """Fill free slots from the queue (the re-admission deque
        first — transient-faulted and recovery re-admissions were
        accepted earlier than anything still queued). Blocks briefly
        only when the engine is fully idle — with active slots the
        decode loop must keep stepping, so admission is non-blocking.

        Fault contract for one admission: a :class:`TransientFault`
        (injected before any state changed) re-stashes the request and
        propagates so the loop retries with backoff; a
        :class:`CorruptedStateFault` propagates for recompute-recovery
        (re-stashing the request unless it was already failed — the
        attributed-device-failure path fails it inside
        :meth:`_prefill`); anything else fails just this request."""
        with self._sched.phase("admit", step=self.metrics.decode_steps,
                               slots=self._slots.active_count):
            try:
                if self.cache_backend == "paged":
                    self._admit_paged()
                else:
                    self._admit_slots()
            finally:
                cause = self._head_blocked_cause()
                self._sched.head_blocked(
                    cause, self._held_group if cause == "blocks" else None)

    def _head_blocked_cause(self) -> Optional[str]:
        """Why the request at the head of the queue is still there
        after an admission pass: "slots" (none free), "blocks" (a slot
        is free, so only a failed block allocation holds a request),
        or None (nothing waits)."""
        held = self.cache_backend == "paged" and self._held is not None
        if self._slots.free_count:
            return "blocks" if held else None
        waiting = held or self._requeue or self._queue.qsize()
        return "slots" if waiting else None

    def _admit_slots(self):
        while self._running and self._slots.free_count:
            if self._requeue:
                req = self._requeue.popleft()
            else:
                req = self._next_queued(
                    busy=bool(self._slots.active_count))
                if req is None:
                    return
                self.metrics.queue_depth = self._queue.qsize()
            if req.abandoned:
                continue
            if self._deadline_blown(req):
                # deadline budget gone while queued: shed at dequeue-
                # admission — zero prefill/decode steps spent on it
                self.metrics.inc("shed_deadline")
                if req.trace is not None:
                    req.trace.span(
                        "admission", verdict="expired",
                        prefill_ms_per_tok=round(
                            self._prefill_ms_per_tok, 4),
                        decode_ewma_ms=round(
                            self._decode_ewma_ms, 3)).end()
                self._fail(req, DeadlineExceededError(
                    "deadline budget exhausted in the generation queue"))
                continue
            try:
                self._prefill(req)
            except TransientFault:
                self._requeue.appendleft(req)
                raise
            except CorruptedStateFault:
                if req.error is None and req.finish_reason is None:
                    self._requeue.appendleft(req)
                raise
            except Exception as e:  # noqa: BLE001 — fail one request
                self._fail(req, e)

    def _chunk_plan(self, prompt_len: int,
                    start: int = 0) -> List[Tuple[int, int, int]]:
        """Split a prompt into (start, chunk bucket, valid length)
        pieces: full ``_chunk_cap`` chunks, then the remainder routed
        to the smallest configured chunk bucket that holds it.
        ``start`` > 0 (a prefix-cache match) skips the matched tokens:
        the first chunk begins mid-prompt, at the same chunk-bucket
        ladder — prefill position is a runtime scalar, so a partial
        plan reuses the exact executables the full plan would."""
        plan = []
        p0 = int(start)
        while p0 < prompt_len:
            rem = prompt_len - p0
            if rem >= self._chunk_cap:
                bucket = clen = self._chunk_cap
            else:
                bucket = next(c for c in self.chunk_buckets if c >= rem)
                clen = rem
            plan.append((p0, bucket, clen))
            p0 += clen
        return plan

    def _match_prefix(self, req: _GenRequest
                      ) -> Tuple[int, List[int], Optional[int],
                                 Optional[str]]:
        """Longest cached prefix of a FRESH admission's prompt →
        ``(match_len, shared_blocks, cow_src, source)``.

        The session store is consulted first (token-granular: the
        pinned turn is almost always a strict prefix of the next
        turn's prompt), then the cross-request index (block-granular
        via chained hashes). ``shared_blocks`` are matched full blocks
        the request will READ through its table; ``cow_src`` is the
        block holding the matched tail when the match ends mid-block —
        the request must WRITE there from position ``match_len`` on,
        so admission copies it into a private block first.
        ``match_len`` is capped at prompt_len - 1: the last prompt
        position must be computed to sample the first output token.
        Recovery re-admissions never match — their block budget and
        token stream are already settled."""
        if not self.enable_prefix_sharing or req.tokens:
            return 0, [], None, None
        bs = self.block_size
        prompt = req.prompt
        L = len(prompt)
        if req.session_id is not None:
            sess = self._sessions.get(req.session_id)
            if sess is not None:
                stored = sess.tokens
                n = min(len(stored), L - 1)
                neq = stored[:n] != prompt[:n]
                m = int(np.argmax(neq)) if neq.any() else n
                if m > 0:
                    self.metrics.inc("session_hits")
                    self.metrics.inc("prefix_hits")
                    self.metrics.inc("prefix_tokens_matched", m)
                    shared = sess.blocks[:m // bs]
                    cow = sess.blocks[m // bs] if m % bs else None
                    return m, list(shared), cow, "session"
            self.metrics.inc("session_misses")
        matched = self._prefix_index.match(chain_hashes(prompt, bs))
        if not matched:
            return 0, [], None, None
        m = len(matched) * bs
        cow = None
        if m >= L:
            # every full block matched and the prompt is block-aligned:
            # keep the last matched block as a COW source so only the
            # final prompt position re-prefills (for its logits)
            m = L - 1
            matched, cow = matched[:m // bs], matched[m // bs]
        self.metrics.inc("prefix_hits")
        self.metrics.inc("prefix_tokens_matched", m)
        return m, list(matched), cow, "index"

    def _evict_one_pin(self) -> bool:
        """Release ONE cache pin under block pressure: the LRU prefix-
        index entry first (one block, finest granularity), then the
        LRU session. False when nothing is evictable — every block is
        held by in-flight work.

        With the hierarchical KV tier enabled, eviction DEMOTES
        instead of discarding: the pin's block run copies device->host
        before its blocks are freed, so the state is a planned cache
        miss (restorable) rather than gone. A torn demotion degrades
        to the old discard — the free below runs either way."""
        ent = self._prefix_index.evict_lru_entry()
        if ent is not None:
            digest, b = ent
            self._demote_prefix(digest, b)
            self._allocator.free([b])
            self.metrics.inc("prefix_evictions")
            return True
        sess = self._sessions.evict_lru()
        if sess is not None:
            self._demote_session(sess)
            self._allocator.free(sess.blocks)
            self.metrics.inc("session_evictions")
            return True
        return False

    def _alloc_with_eviction(self, n: int) -> Optional[List[int]]:
        """All-or-nothing alloc that reclaims cache pins (prefix index
        entries, then sessions) under pressure — in-flight requests
        always outrank opportunistic caching. None only when even a
        fully-evicted pool cannot cover ``n``."""
        while True:
            blocks = self._allocator.alloc(n)
            if blocks is not None:
                return blocks
            if not self._evict_one_pin():
                return None

    def _admit_paged(self):
        """Paged admission: claim a slot AND the request's full
        worst-case block count, all-or-nothing. When blocks run out
        the request is HELD at the queue head (FIFO — admitting later
        arrivals first would starve it) until retirements free blocks;
        the engine never admits work it could fail to finish.
        Admission only STARTS the prefill — chunks run interleaved
        with decode steps in the scheduler loop.

        With prefix sharing, admission first matches the prompt
        against the session store + prefix index: matched full blocks
        join the request's table by refcount (no allocation, no
        prefill), a mid-block match tail is copy-on-write duplicated,
        and the chunk plan starts at the first unmatched token."""
        while self._running and self._slots.free_count:
            if self._requeue:
                req = self._requeue.popleft()
            elif self._held is not None:
                req, self._held = self._held, None
            else:
                req = self._next_queued(
                    busy=bool(self._slots.active_count
                              or self._prefilling))
                if req is None:
                    return
                self.metrics.queue_depth = self._queue.qsize()
            if req.abandoned:
                continue
            if self._deadline_blown(req):
                # deadline budget gone while queued: shed at dequeue-
                # admission — zero prefill/decode steps spent on it
                self.metrics.inc("shed_deadline")
                if req.trace is not None:
                    req.trace.span(
                        "admission", verdict="expired",
                        prefill_ms_per_tok=round(
                            self._prefill_ms_per_tok, 4),
                        decode_ewma_ms=round(
                            self._decode_ewma_ms, 3)).end()
                self._fail(req, DeadlineExceededError(
                    "deadline budget exhausted in the generation queue"))
                continue
            seq = _recovery_seq(req)
            L = len(seq)
            # block budget is unchanged by recovery: prefix + remaining
            # generation == prompt + max_tokens positions either way
            need = blocks_for(len(req.prompt) + req.max_tokens,
                              self.block_size)
            try:
                self._hit("alloc")
            except (TransientFault, CorruptedStateFault):
                # nothing allocated yet — re-stash the request so the
                # retry (or recovery) re-admits it, in order
                self._requeue.appendleft(req)
                raise
            if self._offload is not None:
                # restore-vs-reprefill decision: a demoted session (or
                # demoted prefix blocks) scatters back into the pool
                # BEFORE matching, so _match_prefix sees a normal hit.
                # Torn restores were already degraded to re-prefill
                # inside; only a real device failure escapes (pools
                # donated to the scatter) -> recompute-recovery, with
                # the request re-admitted in order like any other
                # corrupting admission fault
                try:
                    self._offload_restore(req)
                    self._restore_prefix_blocks(req)
                except CorruptedStateFault:
                    self._requeue.appendleft(req)
                    raise
                self._update_block_gauges()
            match_len, shared, cow_src, source = self._match_prefix(req)
            pinned = shared + ([cow_src] if cow_src is not None else [])
            if pinned:
                # pin the matched blocks BEFORE allocating: the alloc
                # below may evict the very index/session entries that
                # own them — without this extra reference an evicted
                # match would re-enter the free list and come back as
                # someone's "fresh" block while this request still
                # reads it
                self._allocator.share(pinned)
            fresh = self._alloc_with_eviction(need - len(shared))
            # every other cache group's blocks too, or nothing at all
            extra: List[List[int]] = []
            short = self._groups[0].name if self._groups \
                and fresh is None else None
            if fresh is not None:
                for g in self._groups[1:]:
                    got = g.allocator.alloc(g.blocks_needed(need))
                    if got is None:
                        for h, blk in zip(self._groups[1:], extra):
                            h.allocator.free(blk)
                        self._allocator.free(fresh)
                        fresh, short = None, g.name
                        break
                    extra.append(got)
            self._held_group = short
            if fresh is None:
                if pinned:
                    self._allocator.free(pinned)
                if self._held is None:
                    self._held = req
                else:
                    # a different request already waits at the head
                    # for blocks (req came from the re-admission
                    # deque) — it must go back there, NOT overwrite
                    # the held one into oblivion
                    self._requeue.appendleft(req)
                return
            if cow_src is not None:
                # the match ends mid-block: the request must write
                # positions >= match_len into that block, so it gets a
                # private copy (its first fresh block — table index
                # len(shared)) and drops its pin on the original
                try:
                    self._cow(cow_src, fresh[0])
                except Exception as e:  # noqa: BLE001 — pools donated
                    self._requeue.appendleft(req)
                    raise CorruptedStateFault(
                        f"copy-on-write device call failed: {e!r}")
                self._allocator.free([cow_src])
                self.metrics.inc("cow_copies")
            blocks = shared + fresh
            plan = self._chunk_plan(L, start=match_len)
            table = BlockTable(blocks, self.block_size)
            if req.trace is not None and match_len:
                full = sum(b for _, b, _ in self._chunk_plan(L))
                part = sum(b for _, b, _ in plan)
                req.trace.span(
                    "prefix_match", source=source,
                    matched_tokens=match_len,
                    matched_blocks=len(shared),
                    cow=cow_src is not None,
                    saved_est_ms=round(
                        (full - part) * self._prefill_ms_per_tok,
                        3)).end()
            # the table bucket must also cover the LAST chunk's padded
            # tail. Its junk writes stay harmless two ways: rows inside
            # the allocation hit positions beyond the live length of
            # THIS request's own blocks (masked until decode overwrites
            # them at pos before ever unmasking), and rows past the
            # allocation hit padded NULL entries -> the null block.
            # Either way, never another request's blocks — which is
            # exactly what an undersized table would break.
            span = max(len(req.prompt) + req.max_tokens,
                       plan[-1][0] + plan[-1][1])
            tbl_bucket = pow2_bucket(
                blocks_for(span, self.block_size), cap=self._tbl_top)
            slot = self._slots.alloc(req)
            assert slot is not None  # guarded by free_count
            self._slot_blocks[slot] = table
            for g, blk in zip(self._groups[1:], extra):
                g.slot_blocks[slot] = BlockTable(blk, self.block_size)
            if req.trace is not None:
                req.qspan.end()  # queue wait ends at the block claim
                if self._groups:
                    req.trace.span("admission", verdict="reserved", **{
                        "blocks_" + g.name: len(g.slot_blocks[slot])
                        for g in self._groups}).end()
            self._prefilling.append(
                _ChunkState(req, slot, table, tbl_bucket, plan, seq,
                            start=match_len))
            self.metrics.active_slots = self._slots.active_count
            self._update_block_gauges()

    def _dispatch_chunk(self):
        """Launch ONE prefill chunk for the oldest mid-prefill request
        WITHOUT waiting for its result: the scheduler interleaves
        chunks with decode steps, so the decode loop's stall per
        iteration is bounded by one chunk's compute regardless of
        prompt length, and it dispatches the next decode step behind
        the chunk before it blocks in :meth:`_collect_chunk`, so the
        device goes from the chunk to that step with no host in
        between. Returns what ``_collect_chunk`` needs, or None when
        nothing was launched."""
        st = self._prefilling[0]
        req = st.req
        sched = self._sched
        n_chunk = self.metrics.prefill_chunks   # this chunk's ordinal
        with sched.phase("chunk_dispatch", chunk=n_chunk,
                         slots=self._slots.active_count) as t0:
            if req.abandoned:
                self._prefilling.popleft()
                self._release_slot(st.slot)
                return None
            if t0 > req.deadline:
                self._prefilling.popleft()
                self._release_slot(st.slot)
                self._fail(req, DeadlineExceededError(
                    "deadline exceeded during chunked prefill "
                    f"({st.done_tokens}/{len(st.seq)} prompt tokens)"))
                return None
            # injection seam: BEFORE any mutation — a TransientFault
            # here leaves the chunk state at the deque head, so the
            # retried iteration re-runs this same chunk
            self._hit("prefill")
            p0, bucket, clen = st.plan[st.idx]
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :clen] = st.seq[p0:p0 + clen]
            table = st.table.padded(st.tbl_bucket)
            if self._groups:
                table = (table,) + tuple(
                    g.slot_blocks[st.slot].padded(g.table_width)
                    for g in self._groups[1:])
            c0 = self.metrics.compiles
            try:
                exe = self._get_chunk_exe(bucket, st.tbl_bucket)
            except Exception as e:  # noqa: BLE001 — compile failed
                # BEFORE any donation: only this request is affected
                self._prefilling.popleft()
                self._release_slot(st.slot)
                self._fail(req, e)
                return None
            try:
                (first, okd, self._pools, self._state,
                 counters) = exe(
                    self.model._params, self._pools,
                    self._state, tokens, np.int32(p0), np.int32(clen),
                    table, np.int32(st.slot), np.uint32(req.seed),
                    np.float32(req.temperature), np.int32(req.top_k))
            except Exception as e:  # noqa: BLE001
                self._chunk_call_failed(st, e)
        return st, first, okd, counters, n_chunk, bucket, clen, c0, t0

    def _collect_chunk(self, launched):
        """Wait for a chunk :meth:`_dispatch_chunk` launched and do its
        host bookkeeping."""
        st, first, okd, counters, n_chunk, bucket, clen, c0, t0 = launched
        sched = self._sched
        with sched.phase("chunk_wait", chunk=n_chunk):
            try:
                first = int(np.asarray(first))  # device sync
                ok = bool(np.asarray(okd))
                if self._model_account is not None:
                    self._model_account.chunk(np.asarray(counters))
            except Exception as e:  # noqa: BLE001
                self._chunk_call_failed(st, e)
        t1 = sched.t        # the stamp that closed chunk_wait
        with sched.phase("emit", chunk=n_chunk):
            self._chunk_landed(st, first, ok, bucket, clen, c0, t0, t1)

    def _chunk_call_failed(self, st: _ChunkState, e: BaseException):
        """A chunk's device call (or its fetch) died with the pools
        donated: attribute the failure to THIS request (fail it
        alone), then let the loop recompute-recover every other
        in-flight sequence's lost prefix."""
        self._prefilling.popleft()
        self._release_slot(st.slot)
        self._fail(st.req, e)
        raise CorruptedStateFault(
            f"prefill chunk device call failed: {e!r}")

    def _chunk_landed(self, st: _ChunkState, first: int, ok: bool,
                      bucket: int, clen: int, c0: int, t0: float,
                      t1: float):
        """Host bookkeeping once a chunk's results are on the host;
        after a prompt's final chunk the request becomes a decode
        lane and its first token is emitted."""
        req = st.req
        dt_ms = (t1 - t0) * 1e3
        self._profiler.note("generation.prefill", t1 - t0)
        self.metrics.prefill_ms.record(dt_ms)
        if self.metrics.compiles == c0:
            # a sample that paid a lazy compile would poison the
            # cost-admission estimate for thousands of requests
            self._note_prefill_cost(dt_ms, bucket)
        if req.trace is not None:
            req.trace.span("prefill", t_start=t0, t_end=t1,
                           bucket=bucket, chunk=st.idx,
                           chunks=len(st.plan))
        self.metrics.inc("prefill_chunks")
        self.metrics.inc("prefill_tokens", clen)
        self.metrics.prompt_bucket_hist.record(bucket)
        if not ok:
            # poison quarantine: this request's own tokens drove the
            # logits non-finite — fail it alone, free its blocks now
            self._prefilling.popleft()
            self._release_slot(st.slot)
            self.metrics.inc("quarantined")
            self._fail(req, PoisonRequestError(
                "request produced non-finite logits during prefill; "
                "quarantined"))
            return
        st.idx += 1
        if st.idx < len(st.plan):
            return
        # final chunk: the request becomes a decode lane. Fresh
        # admission: its sampled token is generated token #1 (TTFT
        # stops here). Recovery re-admission: the already-emitted
        # stream stands — restore the decode cursor (last token, pos,
        # PRNG fold index) instead of emitting; the re-sampled first
        # token is discarded.
        self._prefilling.popleft()
        self.metrics.inc("prefills")
        if len(st.plan) > 1:
            self.metrics.inc("chunked_prefills")
        L = len(st.seq)
        slots = self._slots
        resumed = bool(req.tokens)
        slots.token[st.slot] = req.tokens[-1] if resumed else first
        slots.pos[st.slot] = L
        slots.step[st.slot] = len(req.tokens) if resumed else 1
        slots.seed[st.slot] = req.seed
        slots.temp[st.slot] = req.temperature
        slots.top_k[st.slot] = req.top_k
        slots.eos[st.slot] = -1 if req.eos_id is None else req.eos_id
        slots.max_steps[st.slot] = req.max_tokens
        # the lane's current token was just written host-side — the
        # next dispatch must feed it from tok_host, not the device
        self._tok_on_dev[st.slot] = False
        if req.pipe_d0 is None:
            req.pipe_d0, req.pipe_w0 = self._sched.busy_and_blocked()
        self._tables[st.slot] = st.table.padded(self._blocks_per_seq)
        for g in self._groups[1:]:
            g.tables[st.slot] = g.slot_blocks[st.slot].padded(
                g.table_width)
        if self.enable_prefix_sharing and not resumed:
            # the prompt's full blocks now hold finished, immutable
            # K/V (decode writes land at pos >= prompt_len): publish
            # them for cross-request reuse
            self._register_prefix(req, st.table)
        self._update_block_gauges()
        if self.speculation_k:
            # decode entry: prime the draft over the whole committed
            # prefix. The DRAFT always prefills from scratch — prefix
            # sharing may have skipped most of the target's prefill,
            # but the draft shares nothing
            self._spec_prime(st.slot, st.seq)
        if resumed:
            return
        self.metrics.tokens.record(1)
        self._emit(req, first, time.perf_counter())
        self._check_done(st.slot, req, first)

    def _register_prefix(self, req: _GenRequest, table: BlockTable):
        """Publish a freshly-prefilled prompt's FULL blocks into the
        prefix index. A newly-registered block gains one reference
        owned by the index (so it outlives the request); a digest
        already present keeps its existing block — identical content,
        and the old block may be mid-read by other tables."""
        n_full = len(req.prompt) // self.block_size
        if not n_full:
            return
        hashes = chain_hashes(req.prompt, self.block_size)
        for h, b in zip(hashes, table.blocks[:n_full]):
            if self._prefix_index.register(h, b):
                self._allocator.share([b])
        evicted = self._prefix_index.evict_over_capacity()
        if evicted:
            self._allocator.free(evicted)
            self.metrics.inc("prefix_evictions", len(evicted))

    def _pin_session(self, slot: int, req: _GenRequest):
        """Transfer a cleanly-finished request's live blocks to the
        session store instead of freeing them. The store inherits the
        request's own reference on the kept blocks (ownership moves,
        refcounts don't); trailing blocks past the K/V-valid prefix
        (prompt + emitted minus the last token, whose K/V was never
        written) are freed now. Mirrors :meth:`_release_slot`'s slot
        bookkeeping."""
        table = self._slot_blocks[slot]
        seq = _recovery_seq(req)  # the K/V-valid token prefix
        keep = blocks_for(len(seq), self.block_size)
        kept, trailing = table.blocks[:keep], table.blocks[keep:]
        if trailing:
            self._allocator.free(trailing)
        displaced = self._sessions.put(req.session_id, seq, kept)
        evictions = 0
        for sess in displaced:
            if sess.session_id == req.session_id:
                # the same session's superseded pin: the new pin is
                # the truth, nothing to demote
                self._allocator.free(sess.blocks)
            else:
                # LRU displacement: demote to the host tier (or
                # discard if demotion tears), then free
                self._demote_session(sess)
                self._allocator.free(sess.blocks)
                evictions += 1
        if evictions:
            self.metrics.inc("session_evictions", evictions)
        if self._offload is not None:
            # the freshly-pinned device copy supersedes any demoted
            # one — a stale host run must never be restored over it
            self._offload.pop(req.session_id)
            self._offload_prefetcher.discard(req.session_id)
        self._slots.free(slot)
        self._slot_blocks[slot] = None
        self._tables[slot] = NULL_BLOCK
        self._update_block_gauges()
        self.metrics.active_slots = self._slots.active_count

    def _poison(self, why: str):
        """LAST RESORT (recovery itself failed): every in-flight
        sequence lost its prefix and cannot be rebuilt. Fail them all
        loudly (silently decoding from a zeroed cache would be worse)
        and reallocate so the engine stays servable."""
        for slot in self._slots.active_slots():
            req = self._slots.requests[slot]
            self._slots.free(slot)
            self._fail(req, ServingError(f"generation step failed: "
                                         f"{why}"))
        self.metrics.active_slots = 0
        self._drop_pending()
        if self.cache_backend == "paged":
            # mid-prefill requests hold slots too, so they were failed
            # above; reset the block bookkeeping wholesale — including
            # the prefix/session pins, whose K/V went with the pools
            self._prefilling.clear()
            self._reset_blocks()
            self._prefix_index.clear()
            self._sessions.clear()
            # the HOST tier deliberately survives: demoted runs are
            # host numpy, independent of the donated-away device
            # pools, so previously-demoted sessions stay restorable
            # after the rebuild
            self._update_block_gauges()
        self._cache = self._fresh_cache()
        self._bind_cache()
        self._state = self._fresh_state()   # chunks rebuild it
        if self.speculation_k:
            self._reset_draft_cache()

    def _recover(self, why: str):
        """Recompute-recovery (the vLLM preempt-and-recompute insight:
        decode state is CHEAP to rebuild — it is a pure function of
        prompt + emitted tokens). After a cache-corrupting failure,
        every in-flight request is re-admitted at the FRONT of the
        line and re-prefilled from prompt + already-emitted tokens;
        its PRNG stream continues at ``fold_in(seed, len(emitted))``,
        so post-recovery output is token-identical to a fault-free
        run and NO accepted request is ever lost. Only requests that
        keep triggering recoveries (``MAX_RECOVERIES_PER_REQUEST``) or
        age past their deadline are failed."""
        recovered: List[_GenRequest] = []
        st = self._slots
        for slot in st.active_slots():
            recovered.append(st.requests[slot])
            st.free(slot)
        self.metrics.active_slots = 0
        # any in-flight pipelined step died with the caches; its
        # tokens were never emitted, so the recovery replay below
        # regenerates them bit-identically (same PRNG fold indices)
        self._drop_pending()
        if self.cache_backend == "paged":
            # mid-prefill requests hold slots too, so the slot sweep
            # above already collected them EXACTLY once (collecting
            # from _prefilling as well would re-admit them twice);
            # they re-prefill from scratch — req.tokens carries
            # whatever they had already emitted. Block bookkeeping
            # resets wholesale: the pool arrays were donated away with
            # the caches.
            self._prefilling.clear()
            self._reset_blocks()
            # cached prefixes and session pins died with the pools:
            # drop the bookkeeping (no frees — the allocator is new)
            # so post-recovery admissions rebuild refcounts from zero
            # instead of matching blocks whose K/V no longer exists.
            # The HOST tier survives on purpose — demoted runs are
            # host numpy, untouched by device donation, so sessions
            # demoted BEFORE the fault still restore afterwards
            self._prefix_index.clear()
            self._sessions.clear()
        self._cache = self._fresh_cache()
        self._bind_cache()
        self._state = self._fresh_state()   # chunks rebuild it
        if self.speculation_k:
            # the draft cache may hold donated-away device state too;
            # it replays nothing — each re-admitted lane re-primes at
            # its decode entry (spec_ok was cleared with the slots)
            self._reset_draft_cache()
        now = time.perf_counter()
        for req in recovered:
            if req.abandoned:
                continue
            if now > req.deadline:
                self._fail(req, DeadlineExceededError(
                    "deadline exceeded during fault recovery "
                    f"({len(req.tokens)} tokens emitted)"))
            elif req.recoveries >= MAX_RECOVERIES_PER_REQUEST:
                # a request that rides every crash is probably causing
                # them — attribution of last resort
                self._fail(req, ServingError(
                    f"request failed {req.recoveries} recovery "
                    f"attempts: {why}"))
            else:
                req.recoveries += 1
                if req.trace is not None:
                    req.trace.span("recovery", why=why,
                                   tokens_kept=len(req.tokens)).end()
                self._requeue.append(req)
        if self.cache_backend == "paged":
            self._update_block_gauges()

    def _prefill(self, req: _GenRequest):
        # injection seam: BEFORE the slot claim, so a TransientFault
        # leaves nothing to unwind — _admit re-stashes the request and
        # the loop retries with backoff
        self._hit("prefill")
        if req.trace is not None:
            req.qspan.end()  # queue wait ends at the slot claim
        resumed = bool(req.tokens)
        seq = _recovery_seq(req)
        slot = self._slots.alloc(req)
        assert slot is not None  # guarded by free_count in _admit
        L = len(seq)
        # route to the smallest CONFIGURED bucket, not the raw pow2
        # ladder — warmup() covered exactly prompt_buckets, and an
        # off-list bucket here would compile under traffic. Recovery
        # prefixes fit too: prompt + emitted <= max_seq_len, and
        # max_seq_len is always a bucket.
        bucket = next(b for b in self.prompt_buckets if b >= L)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :L] = seq
        c0 = self.metrics.compiles
        sched = self._sched
        n_chunk = self.metrics.prefills     # this prefill's ordinal
        # a whole-prompt prefill is this backend's one chunk; the
        # phases pause the admit phase they run inside
        with sched.phase("chunk_dispatch", chunk=n_chunk,
                         slots=self._slots.active_count) as t0:
            try:
                exe = self._get_prefill_exe(bucket)
            except Exception:
                # compile failed BEFORE any donation: only this
                # request is affected — free its slot and let the
                # caller fail it
                self._release_slot(slot)
                raise
            try:
                first, okd, self._kcs, self._vcs = exe(
                    self.model._params, self._kcs, self._vcs, tokens,
                    np.int32(L), np.int32(slot), np.uint32(req.seed),
                    np.float32(req.temperature), np.int32(req.top_k))
            except Exception as e:  # noqa: BLE001
                self._prefill_call_failed(slot, req, e)
        with sched.phase("chunk_wait", chunk=n_chunk):
            try:
                first = int(np.asarray(first))  # device sync
                ok = bool(np.asarray(okd))
            except Exception as e:  # noqa: BLE001
                self._prefill_call_failed(slot, req, e)
        t1 = sched.t        # the stamp that closed chunk_wait
        self._profiler.note("generation.prefill", t1 - t0)
        dt_ms = (t1 - t0) * 1e3
        self.metrics.prefill_ms.record(dt_ms)
        if self.metrics.compiles == c0:
            # a sample that paid a lazy compile would poison the
            # cost-admission estimate for thousands of requests
            self._note_prefill_cost(dt_ms, bucket)
        if req.trace is not None:
            req.trace.span("prefill", t_start=t0, t_end=t1,
                           bucket=bucket, chunks=1, resumed=resumed)
        self.metrics.inc("prefills")
        self.metrics.prompt_bucket_hist.record(bucket)
        if not ok:
            # poison quarantine: only this request's logits are
            # non-finite — fail it alone with 500, free the slot now.
            # Its NaN K/V rows stay in the cache but are stale-tail
            # data the no-zeroing invariant already masks.
            self._release_slot(slot)
            self.metrics.inc("quarantined")
            self._fail(req, PoisonRequestError(
                "request produced non-finite logits during prefill; "
                "quarantined"))
            return
        st = self._slots
        st.token[slot] = req.tokens[-1] if resumed else first
        st.pos[slot] = L          # where the next token's K/V will go
        st.step[slot] = len(req.tokens) if resumed else 1  # PRNG fold
        st.seed[slot] = req.seed
        st.temp[slot] = req.temperature
        st.top_k[slot] = req.top_k
        st.eos[slot] = -1 if req.eos_id is None else req.eos_id
        st.max_steps[slot] = req.max_tokens
        # the lane's current token was just written host-side — the
        # next dispatch must feed it from tok_host, not the device
        self._tok_on_dev[slot] = False
        if req.pipe_d0 is None:
            req.pipe_d0, req.pipe_w0 = self._sched.busy_and_blocked()
        if self.speculation_k:
            self._spec_prime(slot, seq)
        self.metrics.active_slots = st.active_count
        if resumed:
            # the emitted stream stands — the re-sampled first token is
            # discarded; decode continues at fold_in(seed, step), the
            # same stream position a fault-free run would use
            return
        # prefill's own sampled token is generated token #1
        self.metrics.tokens.record(1)
        self._emit(req, first, time.perf_counter())
        self._check_done(slot, req, first)

    def _prefill_call_failed(self, slot: int, req: _GenRequest,
                             e: BaseException):
        """The prefill call (or its fetch) died mid-flight with the
        caches donated: attribute the failure to THIS request (fail it
        alone), then raise for recompute-recovery of everyone else."""
        self._release_slot(slot)
        self._fail(req, e)
        raise CorruptedStateFault(f"prefill device call failed: {e!r}")

    def _note_prefill_cost(self, dt_ms: float, bucket: int):
        """Feed the per-PROMPT-TOKEN prefill EWMA (scheduler thread
        only). Normalized by the padded bucket width — that is what
        the device call actually computed over."""
        per_tok = dt_ms / max(bucket, 1)
        self._prefill_ms_per_tok = per_tok \
            if not self._prefill_ms_per_tok else \
            0.8 * self._prefill_ms_per_tok + 0.2 * per_tok

    # -- speculative decoding (serving/speculative.py) -----------------
    def _spec_prime(self, slot: int, seq: np.ndarray):
        """Prefill the DRAFT over a lane's committed prefix at decode
        entry, marking the lane speculation-eligible on success. Any
        draft-side failure here — compile, device call, non-finite
        draft logits — costs speculation only, never the request: the
        lane (or, after a donation-destroying call failure, every
        lane until re-primed) simply decodes plainly."""
        seq = np.asarray(seq, np.int32)
        L = len(seq)
        bucket = next(b for b in self._prime_buckets if b >= L)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :L] = seq
        try:
            ok, self._draft_kcs, self._draft_vcs = \
                self._get_draft_prime_exe(bucket)(
                    self._draft._params, self._draft_kcs,
                    self._draft_vcs, tokens, np.int32(L),
                    np.int32(slot))
            ok = bool(np.asarray(ok))
        except Exception:  # noqa: BLE001 — draft caches were donated
            # to the dead call: rebuild them; the target is untouched
            self._reset_draft_cache(disable_lanes=True)
            self.metrics.inc("spec_draft_fallbacks")
            return
        self._slots.spec_ok[slot] = ok
        if not ok:
            self.metrics.inc("spec_draft_fallbacks")

    def _spec_cow_guard(self, slot: int, p0: int) -> bool:
        """Copy-on-write isolation BEFORE any speculative write: a
        verify span scatters K/V across ``[p0, p0 + vbucket)`` (plus
        the passenger decode write at ``p0 + emitted``), and none of
        those positions may land in a block other tables still read.
        Today's sharing paths only ever share prompt-prefix blocks —
        always below a decode cursor — but the guard is cheap
        (refcount loads) and makes speculation safe against ANY future
        sharing pattern. False = could not isolate (pool exhausted):
        the caller skips speculation for this lane this round."""
        table = self._slot_blocks[slot]
        bs = self.block_size
        last = min((p0 + self._vbucket) // bs, len(table.blocks) - 1)
        for i in range(p0 // bs, last + 1):
            b = table.blocks[i]
            if self._allocator.ref(b) <= 1:
                continue
            fresh = self._alloc_with_eviction(1)
            if fresh is None:
                return False
            try:
                self._cow(b, fresh[0])
            except Exception as e:  # noqa: BLE001 — pools donated
                raise CorruptedStateFault(
                    f"speculative COW device copy failed: {e!r}")
            self._allocator.free([b])
            table.blocks[i] = fresh[0]
            self._tables[slot] = table.padded(self._blocks_per_seq)
            self.metrics.inc("cow_copies")
        return True

    def _spec_step(self) -> frozenset:
        """One speculative round: ONE batched draft call proposes k
        tokens for every eligible lane, then each lane's proposals are
        verified in ONE target forward over the chunk-ladder kernels.
        Returns the slots whose cursors this round advanced (the plain
        decode step skips them). Acceptance, rollback, and the
        bit-identity contract live in `serving/speculative.py`."""
        st = self._slots
        k = self.speculation_k
        lanes = []
        for s in self._ready_slots():
            if not st.spec_ok[s]:
                continue
            req = st.requests[s]
            # a lane within k tokens of its budget plain-decodes to
            # the finish line: every verify span then has full width,
            # and speculative writes can never run past the lane's
            # block allocation / slot capacity
            if req.max_tokens - len(req.tokens) >= k + 1:
                lanes.append(s)
        if not lanes:
            return frozenset()
        # -- draft: one batched proposal call for all lanes ---------
        t0 = time.perf_counter()
        try:
            # the injection seam lives INSIDE the except scope: any
            # draft-side fault — injected or real, transient or
            # corrupting — costs speculation only, never a recovery
            self._hit("draft")
            # a synchronous call, dispatch and fetch in one: the
            # scheduler is blocked on the device for all of it
            with self._profiler.record("generation.spec_draft"), \
                    self._sched.phase("decode_wait", spec="draft"):
                props, dok, self._draft_kcs, self._draft_vcs = \
                    self._get_draft_exe()(
                        self._draft._params, self._draft_kcs,
                        self._draft_vcs, st.token.copy(),
                        st.pos.copy())
                props = np.asarray(props)
                dok = np.asarray(dok)
        except Exception:  # noqa: BLE001 — the draft call died with
            # ITS OWN caches donated; the target state is intact, so
            # this costs speculation (until lanes re-prime), never
            # recovery and never a request
            self._reset_draft_cache(disable_lanes=True)
            self.metrics.inc("spec_draft_fallbacks", len(lanes))
            return frozenset()
        t1 = time.perf_counter()
        # -- verify: one target forward per lane --------------------
        vb = self._vbucket
        paged = self.cache_backend == "paged"
        serviced = set()
        emitted = 0
        itl: List[float] = []
        for s in lanes:
            req = st.requests[s]
            if not dok[s]:
                # draft NaN: fail ONLY speculation for this lane — it
                # decodes plainly from here on (re-primes on recovery)
                st.spec_ok[s] = False
                self.metrics.inc("spec_draft_fallbacks")
                continue
            p0 = int(st.pos[s])
            tokens = np.zeros((1, vb), np.int32)
            tokens[0, 0] = st.token[s]
            tokens[0, 1:k + 1] = props[s, :k]
            if paged:
                if not self._spec_cow_guard(s, p0):
                    continue
                table = self._slot_blocks[s]
                # the padded table must COVER the span's padded tail:
                # an out-of-range gather clamps to the table's last
                # entry — a real block — so junk rows would otherwise
                # write into live data
                tv = pow2_bucket(
                    max(blocks_for(p0 + vb, self.block_size),
                        len(table.blocks)), cap=self._tbl_top)
                extra = (table.padded(tv),)
            else:
                extra = (np.int32(s),)
            self._hit("verify")
            v0 = time.perf_counter()
            try:
                with self._profiler.record("generation.spec_verify"), \
                        self._sched.phase("decode_wait", spec="verify"):
                    exe = self._get_verify_exe(tv if paged else None)
                    span = (tokens, np.int32(p0), np.int32(k + 1),
                            *extra, np.uint32(req.seed),
                            np.int32(st.step[s]),
                            np.float32(req.temperature),
                            np.int32(req.top_k))
                    if paged:
                        tgt, n_acc, vok, self._pools = exe(
                            self.model._params, self._pools, *span)
                    else:
                        tgt, n_acc, vok, self._kcs, self._vcs = exe(
                            self.model._params, self._kcs, self._vcs,
                            *span)
                    tgt = np.asarray(tgt)
                    n_acc = int(np.asarray(n_acc))
                    vok = bool(np.asarray(vok))
            except Exception as e:  # noqa: BLE001 — the TARGET pools
                # were donated to the dead call: same attribution as a
                # failed prefill chunk — fail this request alone, then
                # recompute-recover everyone else
                self._release_slot(s)
                self._fail(req, e)
                raise CorruptedStateFault(
                    f"speculative verify device call failed: {e!r}")
            v1 = time.perf_counter()
            if not vok:
                # the TARGET's logits went non-finite on this lane's
                # own tokens: the standard poison quarantine, exactly
                # as a plain decode step would rule
                self.metrics.inc("quarantined")
                exc = PoisonRequestError(
                    "request produced non-finite logits during "
                    f"speculative verify at step {int(st.step[s])}; "
                    "quarantined")
                self._release_slot(s)
                self._fail(req, exc)
                continue
            n_emit = n_acc + 1
            self.metrics.inc("spec_verify_batches")
            self.metrics.inc("spec_draft_tokens_proposed", k)
            self.metrics.inc("spec_draft_tokens_accepted", n_acc)
            if n_acc < k:
                # rejected tail: rolled back by NOT committing it —
                # the draft cursor and the target write position both
                # rewind for free because pos is the only commit
                # pointer and stale K/V past it stays masked
                self.metrics.inc("spec_rollbacks")
            req.spec_rounds += 1
            req.spec_proposed += k
            req.spec_accepted += n_acc
            req.spec_emitted += n_emit
            if req.spec_dt0 is None:
                req.spec_dt0 = t0
            req.spec_dt1 = t1
            if req.spec_vt0 is None:
                req.spec_vt0 = v0
            req.spec_vt1 = v1
            serviced.add(s)
            committed = 0
            last_tok = 0
            done = False
            for j in range(n_emit):
                token = int(tgt[j])
                self._emit(req, token, v1, itl_out=itl)
                emitted += 1
                committed += 1
                last_tok = token
                if self._check_done(s, req, token, v1):
                    done = True
                    break
            if not done:
                st.commit(s, last_tok, committed)
        if emitted:
            self.metrics.tokens.record(emitted)
        if itl:
            self.metrics.itl_ms.record_many(itl)
        if paged:
            self._update_block_gauges()
        return frozenset(serviced)

    def _ready_slots(self) -> List[int]:
        """Slots in the DECODE phase. On the paged backend a slot is
        claimed at admission but only decode-ready after its final
        prefill chunk (step > 0); mid-prefill slots ride the decode
        batch as masked lanes (NULL tables — their writes land in the
        null block) and their sampled junk is never read."""
        st = self._slots
        return [s for s in range(self.num_slots)
                if st.requests[s] is not None and st.step[s] > 0]

    def _account_step_blocks(self, active: List[int]):
        """Tell the account what the paged step about to be dispatched
        attends: from the lengths it is dispatched with (``pos + 1`` of
        the decode lanes), no device read."""
        if self.cache_backend == "paged":
            lengths = self._slots.pos[active] + 1
            self._sched.step_dispatched(
                int((-(-lengths // self.block_size)).sum()),
                self.num_slots * self._blocks_per_seq)
            if self._groups:
                # keys read a group (its window's at most, times its
                # layers), and what one lifetime for every layer reads;
                # a lane the pipeline runs once past its end reads none
                st = self._slots
                lengths = lengths[st.step[active] < st.max_steps[active]]
                self._sched.step_rows(
                    {g.name: g.rows_read(lengths) * len(g.layers)
                     for g in self._groups},
                    int(lengths.sum()) * sum(
                        len(g.layers) for g in self._groups))

    def _account_step_collected(self):
        """A paged step's results are applied: refresh the gauges and
        add the live KV positions it ran over (a cache group's too)."""
        self._update_block_gauges()
        self._sched.step_collected(
            self.metrics.kv_tokens_live,
            {n: g["kv_tokens_live"]
             for n, g in self.metrics.groups.items()})

    def _account_step_counters(self, counters):
        """The small integer vector a decode step returns beside its
        tokens (fetched with them: no round-trip of its own) goes to
        the model's own account."""
        if self._model_account is not None:
            self._model_account.decode_step(np.asarray(counters))

    def _decode_step(self, skip=frozenset()):
        """One plain decode step. ``skip`` holds slots a speculative
        round already advanced this iteration: they ride the batch as
        masked passengers (the executable's shape is the full slot
        panel either way) and their lane results are simply not
        applied — the passenger's one K/V write lands at the position
        the NEXT verify span rewrites before attending, so it leaves
        no observable residue."""
        st = self._slots
        active = [s for s in self._ready_slots() if s not in skip]
        if not active:
            return
        # injection seam: BEFORE the device call (and its donation), so
        # a TransientFault here is retryable with all state intact
        self._hit("device_step")
        c0 = self.metrics.compiles
        sched = self._sched
        n_step = self.metrics.decode_steps      # this step's ordinal
        with sched.phase("decode_dispatch", step=n_step,
                         slots=len(active)) as t0:
            self._account_step_blocks(active)
            counters = ()
            if self.cache_backend == "paged":
                (nxt, okd, dnd, self._pools, self._state,
                 counters) = self._get_decode_exe()(
                        self.model._params, self._pools, self._state,
                        st.token.copy(), self._no_dev_tok,
                        self._all_host, st.pos.copy(),
                        self._step_tables(), st.seed.copy(),
                        st.step.copy(), st.temp.copy(),
                        st.top_k.copy(), st.eos.copy(),
                        st.max_steps.copy())
            else:
                nxt, okd, dnd, self._kcs, self._vcs = \
                    self._get_decode_exe()(
                        self.model._params, self._kcs, self._vcs,
                        st.token.copy(), self._no_dev_tok,
                        self._all_host, st.pos.copy(), st.seed.copy(),
                        st.step.copy(), st.temp.copy(),
                        st.top_k.copy(), st.eos.copy(),
                        st.max_steps.copy())
        with sched.phase("decode_wait", step=n_step):
            nxt = np.asarray(nxt)  # device sync: the step really ran
            ok = np.asarray(okd)
            done = np.asarray(dnd)
            self._account_step_counters(counters)
        now = sched.t           # the stamp that closed decode_wait
        self._profiler.note("generation.decode_step", now - t0)
        with sched.phase("emit", step=n_step, slots=len(active)):
            self._apply_decode_step(active, nxt, ok, done, now, t0, c0)

    def _apply_decode_step(self, active, nxt, ok, done, now: float,
                           t0: float, c0: int):
        """Host side of one synchronous decode step, its results on
        the host: samples, token fan-out, retirement, gauges."""
        st = self._slots
        dt_ms = (now - t0) * 1e3
        self.metrics.decode_step_ms.record(dt_ms)
        # feed the cost-aware-admission EWMA (scheduler thread only) —
        # but never from a sample that paid a lazy compile, which
        # would poison the estimate for thousands of requests
        if self.metrics.compiles == c0:
            self._decode_ewma_ms = dt_ms if not self._decode_ewma_ms \
                else 0.8 * self._decode_ewma_ms + 0.2 * dt_ms
        self.metrics.inc("decode_steps")
        self.metrics.occupancy_hist.record(len(active))
        tokens = nxt.tolist()
        flags = done.tolist()
        emitted = 0
        itl: List[float] = []
        for slot in active:
            req = st.requests[slot]
            if not ok[slot]:
                # poison quarantine: only THIS lane's logits are
                # non-finite (the guard is per-row, sampling is
                # per-row) — fail the offending request with 500 and
                # free its slot/blocks immediately; every other lane
                # in this same batch keeps decoding untouched
                self.metrics.inc("quarantined")
                exc = PoisonRequestError(
                    "request produced non-finite logits at decode "
                    f"step {int(st.step[slot])}; quarantined")
                self._release_slot(slot)  # zeroes the slot row — build
                self._fail(req, exc)      # the message first
                continue
            token = tokens[slot]
            st.token[slot] = token
            st.pos[slot] += 1
            st.step[slot] += 1
            self._emit(req, token, now, itl_out=itl)
            emitted += 1
            self._retire(slot, req, token, flags[slot], now)
        # count only tokens actually delivered — a quarantined lane
        # emitted nothing, and pre-counting len(active) would inflate
        # tokens/sec under poison load
        if emitted:
            self.metrics.tokens.record(emitted)
        if itl:
            self.metrics.itl_ms.record_many(itl)
        if self.cache_backend == "paged":
            self._account_step_collected()

    def _dispatch_decode(self) -> bool:
        """Launch one decode step WITHOUT waiting for its results (the
        pipelined half of ISSUE 14). The sampled-token array stays on
        the device and feeds the NEXT dispatch directly (tok_dev);
        pos/step are pure +1 increments the host advances immediately,
        so the next step's inputs never depend on anything the sync
        would deliver. Donation already serializes device execution in
        program order — a later prefill or chunk can never overtake
        this step on the device."""
        st = self._slots
        active = self._ready_slots()
        if not active:
            return False
        # injection seam: BEFORE the device call (and its donation), so
        # a TransientFault here is retryable with all state intact —
        # the not-yet-collected previous step stays queued
        self._hit("device_step")
        c0 = self.metrics.compiles
        n_step = self.metrics.decode_steps      # this step's ordinal
        with self._sched.phase("decode_dispatch", step=n_step,
                               slots=len(active)) as t0:
            self._account_step_blocks(active)
            tok_dev = self._nxt_dev
            if tok_dev is None:
                tok_dev = self._no_dev_tok
            use_host = ~self._tok_on_dev
            counters = ()
            if self.cache_backend == "paged":
                (nxt, okd, dnd, self._pools, self._state,
                 counters) = self._get_decode_exe()(
                        self.model._params, self._pools, self._state,
                        st.token.copy(), tok_dev, use_host,
                        st.pos.copy(), self._step_tables(),
                        st.seed.copy(), st.step.copy(), st.temp.copy(),
                        st.top_k.copy(), st.eos.copy(),
                        st.max_steps.copy())
            else:
                nxt, okd, dnd, self._kcs, self._vcs = \
                    self._get_decode_exe()(
                        self.model._params, self._kcs, self._vcs,
                        st.token.copy(), tok_dev, use_host,
                        st.pos.copy(), st.seed.copy(), st.step.copy(),
                        st.temp.copy(), st.top_k.copy(), st.eos.copy(),
                        st.max_steps.copy())
            self._nxt_dev = nxt
            self._tok_on_dev[:] = False
            self._tok_on_dev[active] = True
            # batched cursor bookkeeping: two vectorized adds, no
            # per-lane Python in the dispatch path
            st.pos[active] += 1
            st.step[active] += 1
            self.metrics.inc("decode_steps")
            self.metrics.occupancy_hist.record(len(active))
            self._pending.append(
                (nxt, okd, dnd, counters,
                 [(s, st.requests[s]) for s in active], t0, c0, n_step))
        return True

    def _collect_decode(self, keep: int = 0):
        """Sync and apply in-flight decode steps, oldest first, until
        only ``keep`` remain (keep=1 right after a dispatch: the new
        step stays in flight while THIS host work overlaps it — that
        overlap is the entire point of the pipeline). The sync is the
        only blocking point; everything after runs off host arrays."""
        sched = self._sched
        while len(self._pending) > keep:
            nxt_d, okd, dnd, counters, lanes, t0, c0, n_step = \
                self._pending.popleft()
            with sched.phase("decode_wait", step=n_step) as t_wait:
                nxt = np.asarray(nxt_d)  # device sync: the step ran
                ok = np.asarray(okd)
                done = np.asarray(dnd)
                self._account_step_counters(counters)
            now = sched.t       # the stamp that closed decode_wait
            with sched.phase("emit", step=n_step, slots=len(lanes)):
                self._apply_collected(lanes, nxt, ok, done, now,
                                      now - t0, now - t_wait, c0)

    def _apply_collected(self, lanes, nxt, ok, done, now: float,
                         span_s: float, wait_s: float, c0: int):
        """Host side of one collected step: ``span_s`` from its
        dispatch to its results on the host (it overlaps the spans of
        its neighbours once the pipeline is on: not a step's time),
        ``wait_s`` of it blocked in the fetch."""
        st = self._slots
        self._profiler.note("generation.decode_step", span_s)
        dt_ms = span_s * 1e3
        self.metrics.decode_step_ms.record(dt_ms)
        self.metrics.decode_sync_wait_ms.record(wait_s * 1e3)
        if self.metrics.compiles == c0:
            self._decode_ewma_ms = dt_ms \
                if not self._decode_ewma_ms \
                else 0.8 * self._decode_ewma_ms + 0.2 * dt_ms
        tokens = nxt.tolist()
        flags = done.tolist()
        emitted = 0
        itl: List[float] = []
        for slot, req in lanes:
            if st.requests[slot] is not req \
                    or req.finish_reason is not None \
                    or req.error is not None:
                # the lane retired (or its slot changed hands)
                # while this step was in flight: its junk write
                # landed past the retired sequence's valid length
                # — masked and later overwritten, per the
                # no-zeroing invariant — and its sampled token is
                # simply never read
                continue
            if not ok[slot]:
                # poison quarantine, same contract as the
                # synchronous path
                self.metrics.inc("quarantined")
                exc = PoisonRequestError(
                    "request produced non-finite logits at decode "
                    f"step {int(st.step[slot])}; quarantined")
                self._release_slot(slot)
                self._fail(req, exc)
                continue
            token = tokens[slot]
            # backfill the host mirror; the NEXT step's input (if
            # already dispatched) came from tok_dev, not this
            st.token[slot] = token
            self._emit(req, token, now, itl_out=itl)
            emitted += 1
            self._retire(slot, req, token, flags[slot], now)
        if emitted:
            self.metrics.tokens.record(emitted)
        if itl:
            self.metrics.itl_ms.record_many(itl)
        if self.cache_backend == "paged":
            self._account_step_collected()

    def _drop_pending(self):
        """Discard in-flight pipelined state (recovery/poison/stop:
        the device buffers it refers to are gone or about to be).
        Nothing from a dropped step was ever emitted, so a recovery
        replay regenerates the same tokens from the same PRNG folds."""
        self._pending.clear()
        self._nxt_dev = None
        self._tok_on_dev[:] = False

    def _loop(self):
        """The supervised scheduler loop. One iteration = admit, one
        prefill chunk (paged) dispatched, one decode step dispatched
        behind it, the step before collected, the chunk collected.
        Failure ladder:

        - :class:`~..faults.TransientFault` (raised before any
          donation): retry the iteration with bounded exponential
          backoff, up to ``max_step_retries`` consecutive strikes.
        - strikes exhausted, :class:`~..faults.CorruptedStateFault`, or
          ANY other exception (a device call dying after the caches
          were donated): recompute-recovery via :meth:`_recover`.
        - recovery itself failing: :meth:`_poison` (fail all in-flight
          loudly, reallocate, keep serving).

        The loop itself never dies to a fault — the heartbeat
        (``/healthz`` watchdog) goes stale only when an iteration
        genuinely hangs.

        Every iteration is accounted for in ``metrics.scheduler``
        (:class:`~.metrics.SchedulerAccount`): the callees open the
        phases ``admit``, ``chunk_dispatch``, ``chunk_wait``,
        ``decode_dispatch``, ``decode_wait``, ``emit``, ``idle`` and
        ``fault``; what none of them claims is ``other``."""
        paged = self.cache_backend == "paged"
        backoff = self._retry_backoff_s
        strikes = 0
        sched = self._sched
        sched.start()
        while self._running:
            self._beat = time.monotonic()
            try:
                self._hit("latency")  # injected tail latency (sleeps)
                self._admit()
                chunk = self._dispatch_chunk() \
                    if paged and self._prefilling else None
                if self.decode_pipeline:
                    # dispatch step t+1 FIRST, then collect step t:
                    # the admit/prefill work above and the emit/retire
                    # work inside the collect all overlap the device
                    # computing the step just dispatched. A chunk is
                    # waited for LAST: step t+1 is queued behind it, so
                    # the device goes from the chunk to the step with
                    # no host work in between, and step t (ahead of the
                    # chunk on the device) hands out its tokens
                    # meanwhile. The chunk's request joins the decode
                    # batch at the next dispatch.
                    try:
                        launched = self._dispatch_decode()
                        self._collect_decode(keep=1 if launched else 0)
                    finally:
                        # also when the step's seam faulted: a chunk
                        # that ran must land (its rerun would read the
                        # slot state it has itself written)
                        if chunk is not None:
                            self._collect_chunk(chunk)
                else:
                    if chunk is not None:
                        self._collect_chunk(chunk)
                    if self._ready_slots():
                        # speculative round first (no-op at k=0);
                        # lanes it advanced sit out the plain step
                        # that finishes everyone else
                        spun = (self._spec_step() if self.speculation_k
                                else frozenset())
                        self._decode_step(skip=spun)
            except TransientFault as e:
                strikes += 1
                if strikes > self._max_step_retries:
                    # bounded give-up: rebuild rather than spin forever
                    self.metrics.inc("recoveries")
                    with sched.phase("fault", why="retries_exhausted"):
                        try:
                            self._recover(f"retries exhausted: {e!r}")
                        except Exception as e2:  # noqa: BLE001
                            self._poison(repr(e2))
                    strikes = 0
                    backoff = self._retry_backoff_s
                else:
                    self.metrics.inc("retries")
                    with sched.phase("fault", why="backoff"):
                        time.sleep(backoff)
                    backoff = min(backoff * 2.0,
                                  self._retry_backoff_max_s)
            except Exception as e:  # noqa: BLE001 — cache-corrupting
                # (donated buffers gone) or an unexpected scheduler
                # error: rebuild all in-flight state by recompute
                self.metrics.inc("recoveries")
                with sched.phase("fault", why="recover"):
                    try:
                        self._recover(repr(e))
                    except Exception as e2:  # noqa: BLE001
                        self._poison(repr(e2))
                strikes = 0
                backoff = self._retry_backoff_s
            else:
                strikes = 0
                backoff = self._retry_backoff_s
            # one stamp ends this iteration's account and starts the
            # next: what no phase claimed above went to ``other``
            sched.tick(self.metrics.decode_steps)
        # shutdown cleanup runs HERE, on the scheduler thread — stop()
        # must not mutate the slot table from another thread while a
        # final device call might still be in flight
        self._drop_pending()
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            self._fail(req, ServingError("generation engine stopped"),
                       count=False)
        for req in self._requeue:
            self._fail(req, ServingError("generation engine stopped"),
                       count=False)
        self._requeue.clear()
        if paged:
            self._prefilling.clear()  # their slots drain just below
            if self._held is not None:
                self._fail(self._held,
                           ServingError("generation engine stopped"),
                           count=False)
                self._held = None
        for slot in self._slots.active_slots():
            req = self._slots.requests[slot]
            self._slots.free(slot)
            self._fail(req, ServingError("generation engine stopped"),
                       count=False)
        self.metrics.active_slots = 0

    # -- admin ---------------------------------------------------------
    def stats(self) -> Dict:
        return self.metrics.snapshot()

    def evict_sessions(self) -> int:
        """Release every session pin, returning how many sessions were
        evicted. The session store is scheduler-thread state — call
        only on an idle/drained engine (tests, admin maintenance), not
        under traffic."""
        if self.cache_backend != "paged":
            return 0
        sessions = self._sessions.clear()
        for sess in sessions:
            self._allocator.free(sess.blocks)
        if sessions:
            self.metrics.inc("session_evictions", len(sessions))
        self._update_block_gauges()
        return len(sessions)

    def offload_sessions(self) -> int:
        """Demote EVERY session pin to the host tier (freeing its
        device blocks), returning how many demoted cleanly. The bulk
        version of demote-on-evict — admin maintenance before a
        planned restart, or tests forcing the cold path. Same
        idle-engine-only contract as :meth:`evict_sessions`."""
        if self.cache_backend != "paged" or self._offload is None:
            return 0
        sessions = self._sessions.clear()
        demoted = 0
        for sess in sessions:
            if self._demote_session(sess):
                demoted += 1
            self._allocator.free(sess.blocks)
        if sessions:
            self.metrics.inc("session_evictions", len(sessions))
        self._update_block_gauges()
        return demoted

    def clear_offload(self) -> int:
        """Drop every demoted run from the host AND disk tiers,
        returning how many runs were discarded. Sessions fall back to
        re-prefill on their next turn — correctness is unaffected,
        only the planned-miss optimization is reset."""
        off = self._offload
        if off is None:
            return 0
        n = len(off.keys())
        off.clear()
        self._update_block_gauges()
        return n

    def clear_prefix_cache(self) -> int:
        """Release every prefix-index pin, returning how many blocks
        were unpinned. Same idle-engine-only contract as
        :meth:`evict_sessions`."""
        if self.cache_backend != "paged":
            return 0
        blocks = self._prefix_index.clear()
        if blocks:
            self._allocator.free(blocks)
            self.metrics.inc("prefix_evictions", len(blocks))
        self._update_block_gauges()
        return len(blocks)

    def set_fault_injector(self, injector) -> None:
        """Swap the fault injector (``None`` disables injection). The
        seams read it per call, so this is safe between workloads —
        chaos tests and staging probes can reuse one warmed engine
        instead of paying a fresh compile set per fault scenario."""
        self._faults = injector

    @property
    def draining(self) -> bool:
        """True once :meth:`drain` has started (new submissions shed
        with 503 + Retry-After). Surfaced in the /stats summary so
        external load balancers steer away without parsing error
        counters."""
        return self._draining

    def alive(self) -> bool:
        """Liveness for ``/healthz``: False only when the scheduler is
        WEDGED — thread dead while it should be running, or no
        heartbeat within ``STALL_TIMEOUT_S`` (the loop beats every
        iteration; its longest legitimate pause is one device call).
        A deliberately stopped/drained engine is not wedged."""
        if not self._running:
            return True
        if not self._thread.is_alive():
            return False
        return (time.monotonic() - self._beat) <= STALL_TIMEOUT_S

    def _idle(self) -> bool:
        empty = (self._queue.empty() and not self._requeue
                 and self._slots.active_count == 0)
        if self.cache_backend == "paged":
            empty = empty and not self._prefilling \
                and self._held is None
        return empty

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown: new submissions are rejected with 503
        (:class:`~.batcher.DrainingError`), every queued and in-flight
        generation runs to completion, then the scheduler thread
        joins. Returns True when the engine fully drained within
        ``timeout_s``; leftovers past the budget are failed by
        :meth:`stop`'s shutdown path (uncounted, as for any deploy
        restart). Safe to call from a signal handler's thread."""
        first = not self._draining
        self._draining = True
        if first:
            self.metrics.inc("drains")
        self._wake.set()  # an idle-parked scheduler should re-check
        clean = poll_until_idle(self._idle, timeout_s)
        self.stop()
        return clean

    def stop(self, timeout_s: float = 5.0):
        """Stop the scheduler. Queued and in-flight requests are
        failed by the scheduler thread's own exit path (mutating the
        slot table from here would race a final in-flight device call
        if the join times out); waiters are additionally bounded by
        their deadlines."""
        self._running = False
        self._wake.set()  # unpark an idle scheduler immediately
        self._thread.join(timeout=timeout_s)
        if self._offload is not None:
            self._offload_prefetcher.stop()
            # drops the host entries and unlinks the disk ring's
            # tempfile; runs after the scheduler join so no demote/
            # restore can still be writing into the store
            self._offload.close()

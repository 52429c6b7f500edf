"""Paged KV-cache memory manager: block-granular allocation for the
generation runtime (vLLM/PagedAttention, SOSP '23 — PAPERS.md).

The slot cache (:mod:`.kvcache`) preallocates ``max_seq_len`` tokens of
K/V per slot, so memory scales with the WORST-CASE sequence length:
a slot serving an 8-token completion pins the same bytes as one serving
a 500-token one. Here the unit of allocation is a BLOCK of
``block_size`` token positions inside one shared pool per layer, a
position's key and value side by side in one row::

    K | V : [num_blocks, n_heads, block_size, 2 * head_dim]

A sequence owns ceil((prompt + max_tokens) / block_size) blocks — its
ACTUAL worst case, not the engine's — and a block table maps its
logical positions to pool blocks. The pool arrays never change shape,
so the compiled decode executable never changes either; "which block
belongs to whom" is host-side bookkeeping, exactly like the slot
table's "which slot belongs to whom", one granularity finer.

Invariants, shared with the slot cache and test-asserted:

- **No zeroing on reuse.** A freed block re-enters the free list with
  its stale K/V intact; the next owner's writes overwrite the prefix
  it uses and the per-sequence length masks everything beyond. There
  is never a zeroing pass between occupants.
- **Block 0 is the null block.** It is never allocated to a request.
  Padded block-table entries point at it, so (a) gathers through
  padding read garbage that the length mask discards, and (b) writes
  from padded lanes (inactive decode slots, the padded tail of a
  prefill chunk past a request's allocation) land in memory nobody
  ever unmasks.
- **No over-commit.** :meth:`BlockAllocator.alloc` is all-or-nothing:
  a request's full worst-case block count is claimed at admission or
  the request stays queued — the engine never admits work it could be
  unable to finish (the alternative, swapping/preemption, trades that
  guarantee for recompute; see docs/generation.md).
- **Shared blocks are immutable.** A block referenced by more than
  one owner (another request's table, the prefix index, a session
  pin) is never written in place: a writer gets a copy-on-write
  duplicate first (`GenerationEngine._cow` copies it into a fresh
  block and swaps the writer's table entry), so readers observe
  bit-identical content for the block's whole shared lifetime.

Prefix sharing (vLLM block sharing + RadixAttention-style reuse,
PAPERS.md) layers three pieces on the allocator: per-block REFCOUNTS
(:meth:`BlockAllocator.share` / a decrementing :meth:`~BlockAllocator.
free`), a :class:`PrefixIndex` mapping chained content hashes of full
prompt blocks to pool blocks, and a :class:`SessionStore` pinning a
finished request's prefix+generated blocks under a client-provided
``session_id`` so the next turn re-prefills only its new suffix.
"""
from __future__ import annotations

import collections
import hashlib
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..kernels.kv_quant import (canonical_kv_dtype, kv_bytes_per_token,
                                kv_gather_rows, kv_nbytes,
                                kv_scatter_rows)
from ..kernels.paged_attention import kv_pool_zeros

#: Block index reserved as the write/read target for padded table
#: entries. Never handed out by the allocator.
NULL_BLOCK = 0


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` positions."""
    return -(-int(tokens) // int(block_size))


def pow2_bucket(n: int, cap: Optional[int] = None) -> int:
    """Smallest power of two >= n (>= 1), optionally clamped to
    ``cap`` — the block-table padding rule that keeps the set of
    prefill executables finite and AOT-warmable. Delegates to the
    serving engine's :func:`~.engine.next_bucket` so the paged and
    dense bucket policies can never silently diverge."""
    from .engine import next_bucket
    return next_bucket(max(int(n), 1), 1,
                       (1 << 30) if cap is None else cap)


class BlockAllocator:
    """Free-list allocator over the pool's block indices.

    Block 0 (:data:`NULL_BLOCK`) is reserved; ``capacity`` counts only
    allocatable blocks. Allocation is all-or-nothing and LIFO, so a
    just-freed (cache-warm) block is reused first — same policy as the
    slot table's free list.

    Blocks are REFCOUNTED so prefix sharing can hand one physical
    block to several owners: :meth:`alloc` sets each block's count to
    1, :meth:`share` bumps it for every additional owner, and
    :meth:`free` decrements — the block re-enters the free list only
    when its last owner releases it. ``used_count`` keeps counting
    UNIQUE blocks (physical pool occupancy), which is what peak/
    fragmentation accounting must reflect under sharing."""

    def __init__(self, num_blocks: int):
        self.num_blocks = int(num_blocks)
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             f"reserved null block), got {num_blocks}")
        self._free = list(range(self.num_blocks - 1, NULL_BLOCK, -1))
        # mirror of _free for O(1) double-free checks: free() runs on
        # the scheduler thread at every retirement, and a linear scan
        # of the free list there would tax every stream's ITL
        self._free_set = set(self._free)
        self._refs: Dict[int, int] = {}
        self.peak_used = 0

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.capacity - len(self._free)

    @property
    def shared_count(self) -> int:
        """Unique blocks currently held by more than one owner."""
        return sum(1 for c in self._refs.values() if c > 1)

    def ref(self, block: int) -> int:
        """Current refcount of ``block`` (0 if free/unallocated)."""
        return self._refs.get(int(block), 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` blocks (each at refcount 1), or None (claim
        NOTHING) if fewer than ``n`` are free — the no-over-commit
        contract."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(blocks)
        for b in blocks:
            self._refs[b] = 1
        self.peak_used = max(self.peak_used, self.used_count)
        return blocks

    def share(self, blocks: Sequence[int]):
        """Add one owner to each (already-allocated) block. Raises if
        any block is free — sharing can never resurrect a block, so the
        caller's ordering bug (e.g. freeing matched blocks via eviction
        before pinning them) surfaces as an error, not aliasing."""
        for b in blocks:
            b = int(b)
            if self._refs.get(b, 0) < 1:
                raise ValueError(f"cannot share unallocated block {b}")
        for b in blocks:
            self._refs[int(b)] += 1

    def free(self, blocks: Sequence[int]):
        """Drop one owner per block; a block re-enters the free list
        only at refcount 0. No zeroing — stale contents stay masked by
        the next owner's length. Validates the WHOLE batch before
        mutating anything so a bad call can't half-free."""
        counted: Dict[int, int] = {}
        for b in blocks:
            b = int(b)
            if b == NULL_BLOCK or not 0 < b < self.num_blocks:
                raise ValueError(f"block {b} is not allocatable")
            if b in self._free_set:
                raise ValueError(f"double free of block {b}")
            counted[b] = counted.get(b, 0) + 1
            if counted[b] > self._refs.get(b, 0):
                raise ValueError(f"double free of block {b}")
        released = []
        for b in blocks:
            b = int(b)
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                released.append(b)
        self._free.extend(released)
        self._free_set.update(released)

    def stats(self) -> dict:
        return {"total": self.capacity, "free": self.free_count,
                "used": self.used_count, "peak_used": self.peak_used,
                "shared": self.shared_count}


class BlockTable:
    """One request's logical-position → pool-block mapping (host-side
    int32). ``padded(n)`` emits the device-facing row, padded with
    :data:`NULL_BLOCK` to a caller-chosen length (a pow2 bucket for
    prefill executables; the engine-wide max for the decode batch), so
    executable shapes depend on the BUCKET, never the request."""

    def __init__(self, blocks: Sequence[int], block_size: int):
        self.blocks = [int(b) for b in blocks]
        self.block_size = int(block_size)

    def __len__(self) -> int:
        return len(self.blocks)

    @property
    def capacity_tokens(self) -> int:
        return len(self.blocks) * self.block_size

    def padded(self, n: int) -> np.ndarray:
        if n < len(self.blocks):
            raise ValueError(f"cannot pad {len(self.blocks)} blocks "
                             f"into a table of {n}")
        out = np.full(n, NULL_BLOCK, np.int32)
        out[:len(self.blocks)] = self.blocks
        return out


class CacheGroup:
    """The layers of a model that keep the same positions, and so share
    one block table a sequence and one allocator: what a model declares
    with ``cache_groups()`` (``name``, ``layers``: indices into its
    ``cache_shapes()``, ``window``: how many of the newest positions
    the layers read, None for all of them), sized by the engine.

    A window group's table is a RING of ``ring`` entries a slot:
    position ``p`` lives in entry ``(p // block_size) % ring``, so a
    sequence holds ``min(blocks_for(prompt + max_tokens), ring)`` blocks
    from admission to retirement however long it grows, and nothing is
    freed in between. The ring covers the window, one prefill chunk
    written ahead of the oldest key that chunk still reads, and one
    block for a window that starts inside a block. A model without
    ``cache_groups`` is one group with no window: the engine's
    allocator and tables as they always were."""

    def __init__(self, name: str, layers: Sequence[int], num_blocks: int,
                 num_slots: int, table_width: int,
                 window: Optional[int] = None,
                 ring: Optional[int] = None):
        self.name = str(name)
        self.layers = [int(i) for i in layers]
        self.window = None if window is None else int(window)
        self.ring = None if ring is None else int(ring)
        self.num_blocks = int(num_blocks)
        self.num_slots = int(num_slots)
        self.table_width = int(table_width)
        self.reset()

    def reset(self):
        """Nothing allocated, every table row NULL (construction, and
        recovery: the pools were donated away)."""
        self.allocator = BlockAllocator(self.num_blocks)
        self.tables = np.full((self.num_slots, self.table_width),
                              NULL_BLOCK, np.int32)
        self.slot_blocks: List[Optional[BlockTable]] = \
            [None] * self.num_slots

    def blocks_needed(self, seq_blocks: int) -> int:
        """Blocks a sequence of ``seq_blocks`` blocks holds here."""
        return int(seq_blocks) if self.ring is None \
            else min(int(seq_blocks), self.ring)

    def rows_read(self, lengths) -> int:
        """Keys a decode step reads a layer of this group, summed over
        lanes at ``lengths``: all of them, or the window's."""
        n = np.asarray(lengths)
        return int((n if self.window is None
                    else np.minimum(n, self.window)).sum())


class PagedKVCache:
    """Per-layer pooled K/V blocks, the paged sibling of
    :class:`~.kvcache.KVCache`: same pytree-threaded-through-donated-
    executables lifecycle, but the leading axis is POOL BLOCKS shared
    by every sequence instead of per-sequence slots.

    ``layer_shapes`` are per-layer ``(n_heads, block_size, head_dim)``
    — i.e. ``model.cache_shapes(block_size)``. ``pools`` holds ONE
    array a layer, ``[num_blocks, n_heads, block_size, 2 * head_dim]``:
    a position's key in the first ``head_dim`` lanes of its row and its
    value in the rest (`kernels/paged_attention.py` owns the layout and
    says why: for a head of 64 a row is one 128-lane vector row, the
    default device layout is the row-major one every program asks for,
    and no program relays a pool). At block 16 a f32 or bf16 pool is
    stored without padding (8- and 16-row tiles of 128 lanes); an int8
    pool's values are tiled 32 rows deep, so a block of 16 positions
    takes the room of 32 on the device (:meth:`nbytes` counts the
    bytes held, not the padding), and block 32 is the size that wastes
    nothing there.

    ``kv_dtype`` selects the storage precision (ROADMAP item 3):
    ``"f32"`` (exact, default), ``"bf16"``, or ``"int8"`` — a layer's
    pool becomes a
    :class:`~deeplearning4j_tpu.kernels.kv_quant.QuantArray` pytree
    with a ``[num_blocks, 2, H, block_size]`` f32 scale sidecar (the
    keys' scales, then the values'), i.e. per-block-per-head scales
    indexed by block id (the block is the quantization granule).
    Copy-on-write and the no-zeroing-on-reuse contract carry over
    unchanged: a block copy copies its scale rows, a recycled block's
    stale (quantized) tail stays masked by the next owner's length."""

    def __init__(self, layer_shapes: Sequence[Tuple[int, int, int]],
                 num_blocks, kv_dtype: str = "f32"):
        self.layer_shapes = [tuple(s) for s in layer_shapes]
        # one count, or one a layer where the layers lie in cache
        # groups with pools of their own (:class:`CacheGroup`)
        self.layer_blocks = [int(n) for n in num_blocks] \
            if isinstance(num_blocks, (list, tuple)) \
            else [int(num_blocks)] * len(self.layer_shapes)
        self.num_blocks = self.layer_blocks[0]
        self.block_size = int(self.layer_shapes[0][1])
        self.kv_dtype = canonical_kv_dtype(kv_dtype)
        self.pools: List = [
            kv_pool_zeros((n,) + s, self.kv_dtype)
            for n, s in zip(self.layer_blocks, self.layer_shapes)]

    def nbytes(self) -> int:
        """Device bytes the pool pins: ``num_blocks * block_size * H *
        Dh * 2 (K+V) * layers * itemsize``, plus the f32 scale
        sidecars for int8 — the number to budget against HBM
        (docs/generation.md has the sizing guidance)."""
        return int(sum(2 * kv_nbytes((n,) + s, self.kv_dtype)
                       for n, s in zip(self.layer_blocks,
                                       self.layer_shapes)))

    def block_nbytes(self) -> int:
        """Bytes one block pins across all layers (K+V, sidecar
        included)."""
        return self.nbytes() // self.num_blocks

    def scale_nbytes(self) -> int:
        """Bytes of the f32 scale sidecars alone (0 unless int8)."""
        if self.kv_dtype != "int8":
            return 0
        return int(sum(2 * int(np.prod((n,) + s[:-1])) * 4
                       for n, s in zip(self.layer_blocks,
                                       self.layer_shapes)))

    def bytes_per_token(self) -> int:
        """K+V bytes one token position costs across all layers at the
        pool dtype — the per-session sizing unit for both the device
        pool AND the host tier below it (a demoted run stores the same
        bytes per token; see docs/generation.md "Hierarchical KV
        tier")."""
        return kv_bytes_per_token(self.layer_shapes, self.kv_dtype)


def export_block_run(pools, idx):
    """Pure fn: gather pool rows ``idx`` out of every layer's pool —
    the device half of a demotion. Traced into one executable
    per pow2 idx bucket by the engine (pools NOT donated: a failed
    demotion must leave the device tier untouched)."""
    return [kv_gather_rows(p, idx) for p in pools]


def import_block_run(pools, rows, idx):
    """Pure fn: scatter gathered runs back into pool rows ``idx`` —
    the device half of a restore. Padded idx entries point at
    :data:`NULL_BLOCK` so junk writes land where nothing is ever read.
    The engine compiles this with pools DONATED (a restore writes in
    place), so a real failure here is a
    :class:`~deeplearning4j_tpu.faults.CorruptedStateFault`."""
    return [kv_scatter_rows(p, r, idx) for p, r in zip(pools, rows)]


def chain_hashes(tokens: Sequence[int], block_size: int) -> List[bytes]:
    """Chained content hash per FULL block of ``tokens``:
    ``h_i = blake2b(h_{i-1} || tokens[i*Bs:(i+1)*Bs])``.

    Chaining makes each digest identify the block's content AND its
    whole prefix, so two requests share block i only when their first
    ``(i+1)*block_size`` tokens are identical — the property that
    lets the engine reuse the block's K/V verbatim (K/V are pure
    per-position projections of the prefix). Partial tail blocks are
    never hashed: their content is still growing, so they are only
    shareable via session pins + copy-on-write."""
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    bs = int(block_size)
    out: List[bytes] = []
    prev = b""
    for i in range(len(toks) // bs):
        h = hashlib.blake2b(prev + toks[i * bs:(i + 1) * bs].tobytes(),
                            digest_size=16).digest()
        out.append(h)
        prev = h
    return out


class PrefixIndex:
    """LRU map from chained block hash → pool block, the cross-request
    half of prefix sharing (RadixAttention's radix tree flattened to a
    hash map — chained digests already encode the path, PAPERS.md).

    The index OWNS one reference per registered block (the engine
    ``share()``s on register, ``free()``s on evict), so an indexed
    block survives the registering request and stays bit-stable for
    future matches. Pure bookkeeping: no allocator calls happen here —
    every method returns the block ids whose ownership changed and the
    caller settles refcounts, keeping one thread (the scheduler) in
    charge of allocator state."""

    def __init__(self, capacity: int = 1024):
        self.capacity = int(capacity)
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._entries: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def blocks(self) -> Iterator[int]:
        """All indexed blocks, eviction order first."""
        return iter(self._entries.values())

    def match(self, hashes: Sequence[bytes]) -> List[int]:
        """Longest indexed chain prefix of ``hashes`` → its blocks.
        Matched entries are LRU-touched (a shared system prompt stays
        hot no matter how old its registration is)."""
        out: List[int] = []
        for h in hashes:
            b = self._entries.get(h)
            if b is None:
                break
            self._entries.move_to_end(h)
            out.append(b)
        return out

    def register(self, digest: bytes, block: int) -> bool:
        """Insert ``digest → block``; True iff the entry is NEW (the
        caller then owns transferring a reference to the index). An
        existing entry is kept — its block already holds the content —
        and merely LRU-touched."""
        if digest in self._entries:
            self._entries.move_to_end(digest)
            return False
        self._entries[digest] = int(block)
        return True

    def evict_lru(self) -> Optional[int]:
        """Drop the least-recently-matched entry; returns its block
        (caller frees the index's reference) or None when empty."""
        if not self._entries:
            return None
        _, block = self._entries.popitem(last=False)
        return block

    def evict_lru_entry(self) -> Optional[Tuple[bytes, int]]:
        """Like :meth:`evict_lru` but returns ``(digest, block)`` so a
        demoting caller can key the host copy by the chained digest
        (the engine's demote-on-evict path needs the identity, not
        just the block to free)."""
        if not self._entries:
            return None
        return self._entries.popitem(last=False)

    def evict_over_capacity(self) -> List[int]:
        """Evict LRU entries until within capacity; returns their
        blocks for the caller to free."""
        out: List[int] = []
        while len(self._entries) > self.capacity:
            out.append(self._entries.popitem(last=False)[1])
        return out

    def clear(self) -> List[int]:
        """Drop every entry; returns all previously indexed blocks."""
        out = list(self._entries.values())
        self._entries.clear()
        return out


class Session:
    """One pinned conversation: the K/V-valid token prefix (prompt +
    generated tokens whose K/V were actually written) and the blocks
    holding it. Held by :class:`SessionStore`. ``session_id`` is
    stamped by :meth:`SessionStore.put` so a displaced/evicted Session
    still knows which conversation it belongs to — the demote-on-evict
    path keys the host-tier copy by it."""
    __slots__ = ("tokens", "blocks", "session_id")

    def __init__(self, tokens: np.ndarray, blocks: List[int],
                 session_id: Optional[str] = None):
        self.tokens = tokens
        self.blocks = blocks
        self.session_id = session_id


class SessionStore:
    """LRU map ``session_id`` → :class:`Session`, the persistent half
    of prefix sharing: a finished turn's blocks stay pinned (the store
    owns one reference per block) so the next turn of the same
    conversation re-prefills only its new suffix.

    Like :class:`PrefixIndex` this is pure bookkeeping — methods
    return displaced :class:`Session` objects and the caller frees
    their blocks."""

    def __init__(self, capacity: int = 64):
        self.capacity = int(capacity)
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._entries: "collections.OrderedDict[str, Session]" = \
            collections.OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, session_id: str) -> bool:
        return session_id in self._entries

    def ids(self) -> List[str]:
        return list(self._entries.keys())

    def get(self, session_id: str) -> Optional[Session]:
        """LRU-touching lookup."""
        sess = self._entries.get(session_id)
        if sess is not None:
            self._entries.move_to_end(session_id)
        return sess

    def put(self, session_id: str, tokens: np.ndarray,
            blocks: List[int]) -> List[Session]:
        """Pin a finished turn, displacing (a) the session's previous
        pin if any and (b) LRU entries past capacity. Returns every
        displaced Session; the caller frees their blocks."""
        displaced: List[Session] = []
        old = self._entries.pop(session_id, None)
        if old is not None:
            displaced.append(old)
        self._entries[session_id] = Session(tokens, blocks, session_id)
        while len(self._entries) > self.capacity:
            displaced.append(self._entries.popitem(last=False)[1])
        return displaced

    def evict_lru(self) -> Optional[Session]:
        """Drop the least-recently-used session; caller frees its
        blocks. None when empty."""
        if not self._entries:
            return None
        return self._entries.popitem(last=False)[1]

    def clear(self) -> List[Session]:
        out = list(self._entries.values())
        self._entries.clear()
        return out

    def iter_pins(self) -> Iterator[Tuple[List[int], int]]:
        """(blocks, n_valid_tokens) per live session — the inputs the
        engine's kv_tokens_live gauge needs."""
        for sess in self._entries.values():
            yield sess.blocks, len(sess.tokens)

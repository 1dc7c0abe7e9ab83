"""Paged KV block manager with hash-based prefix caching.

TPU-native equivalent of the KV-block bookkeeping the reference stack gets
from vLLM + LMCache (the router scrapes its effects as
`vllm:gpu_cache_usage_perc` / `vllm:gpu_prefix_cache_hit_rate`, reference:
src/vllm_router/stats/engine_stats.py:63-76). Pure host-side Python: the
device only ever sees flat slot indices, so this logic never enters jit.

Prefix caching: a *full* block of block_size tokens is content-addressed by
the chain hash of all tokens up to and including that block. Blocks with
ref_count 0 stay in an LRU "evictable" pool and can be resurrected on a hash
hit (same design as vLLM's prefix caching / LMCache's local backend).

Block 0 is reserved as the null/trash block: padded batch lanes write their
garbage K/V there, so it is never handed to a sequence.

A model whose window-attention layers keep their own KV arrays (a second
CACHE GROUP, models/layer_groups.py) is served by `WindowedBlockManager`:
the pool above stays THE pool — its ids fill every sequence's table, its
hash chain decides prefix hits — and the window group's smaller pool hangs
on it through one block map (primary id -> window-group id, 0 = not
resident). A sequence holds a window-group block only while some position
in it can still fall inside a later query's window.
"""

from __future__ import annotations

import sys
from array import array
from collections import OrderedDict

import xxhash

NULL_BLOCK = 0


# array's typecode of 4 bytes: "I" wherever a C int has 32 bits
_U32 = next(c for c in "IL" if array(c).itemsize == 4)


def token_bytes(token_ids) -> memoryview:
    """`token_ids` as ONE buffer of little-endian uint32s, the bytes
    `hash_block` folds; a block is a slice of it. Refuses what does not
    fit (an id below 0 or from 2**32 on: OverflowError; a non-integer:
    TypeError)."""
    ids = array(_U32, token_ids)
    if sys.byteorder != "little":
        ids.byteswap()
    return memoryview(ids).cast("B")


def hash_block(prev_hash: int, token_ids, extra: tuple = ()) -> int:
    """Chain hash for a full block given the previous block's hash:
    xxh64 over the previous hash's 8 bytes, then each token as 4
    little-endian bytes. `token_ids` is the block's ids, or its slice
    of a `token_bytes` buffer (a caller that hashes a whole prompt
    converts it once): the same bytes, so the same digest."""
    if not isinstance(token_ids, memoryview):
        token_ids = token_bytes(token_ids)
    data = prev_hash.to_bytes(8, "little", signed=False) + token_ids
    for e in extra:
        data += str(e).encode()
    return xxhash.xxh64_intdigest(data)


def iter_chain_hashes(token_ids, block_size: int, seed: int = 0,
                      start: int = 0):
    """Chain hashes for each *full* block of token_ids, lazily.

    THE one token->block-hash folding, shared by the BlockManager, the
    KV controller's prefix matcher, and the router's shared-cache
    lookup hints — every copy of this loop that drifts (seed, chunk
    boundary, partial-block handling) makes cross-component prefix
    matches miss silently, so there is exactly one. Lazy so matchers
    can stop hashing at the first miss. `start` resumes a chain at that
    block, with `seed` the hash of the block before it."""
    n_blocks = len(token_ids) // block_size
    if start >= n_blocks:
        return
    # the ids become bytes once, whatever number of blocks follows
    buf = token_bytes(token_ids[start * block_size:n_blocks * block_size])
    step = 4 * block_size
    prev = seed
    for off in range(0, len(buf), step):
        prev = hash_block(prev, buf[off:off + step])
        yield prev


class Block:
    __slots__ = ("block_id", "ref_count", "block_hash")

    def __init__(self, block_id: int):
        self.block_id = block_id
        self.ref_count = 0
        self.block_hash: int | None = None


class BlockManager:
    """Allocator for a fixed pool of KV blocks, with prefix caching."""

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
    ):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (one is the null block)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching

        self.blocks = [Block(i) for i in range(num_blocks)]
        # bumped on every free(): see the note there
        self.free_epoch = 0
        # block 0 reserved as null/trash
        self.free_blocks: list[int] = list(range(num_blocks - 1, 0, -1))
        # hash -> block_id for cached full blocks (ref>=0)
        self.cached_blocks: dict[int, int] = {}
        # block_id -> None, LRU order, for ref_count==0 cached blocks
        self.evictable: OrderedDict[int, None] = OrderedDict()

        # token-level prefix-cache counters (engine /metrics contract)
        self.prefix_queries = 0
        self.prefix_hits = 0
        # hash_block calls on a PROMPT's blocks (tpu:prefix_blocks_
        # hashed_total): over prefix_queries / block_size, how often a
        # queried block was hashed; at most once where every caller
        # hands its sequence's `block_hashes` along. The blocks that
        # generated tokens fill are hashed too (once, when registered)
        # and are not counted: nobody queried them
        self.blocks_hashed = 0

        # KV offload hooks (wired by LLMEngine when offload is configured):
        # on_admit(hashes)      -> new cached blocks live in HBM
        # on_evict(hashes)      -> cached blocks dropped from HBM
        # on_freed_cached(pairs)-> [(block_id, hash)] just became evictable;
        #                          contents still intact, safe to d2h-export
        self.on_admit = None
        self.on_evict = None
        self.on_freed_cached = None
        # deferred-export pins: freed-but-cached blocks whose device-side
        # snapshot has not been enqueued yet (see pin_for_export)
        self._export_pins: set[int] = set()

    # -- capacity ---------------------------------------------------------
    @property
    def num_free_blocks(self) -> int:
        return len(self.free_blocks) + len(self.evictable)

    @property
    def usage(self) -> float:
        """Fraction of blocks actively referenced (the vllm:gpu_cache_usage_perc)."""
        usable = self.num_blocks - 1
        return (usable - self.num_free_blocks) / max(1, usable)

    def can_allocate(self, num_new_blocks: int) -> bool:
        return self.num_free_blocks >= num_new_blocks

    # -- low-level alloc --------------------------------------------------
    def _pop_free_block(self) -> int:
        if self.free_blocks:
            return self.free_blocks.pop()
        if self.evictable:
            bid, _ = self.evictable.popitem(last=False)  # LRU
            blk = self.blocks[bid]
            if blk.block_hash is not None:
                self.cached_blocks.pop(blk.block_hash, None)
                if self.on_evict is not None:
                    self.on_evict([blk.block_hash])
                blk.block_hash = None
            return bid
        raise RuntimeError("out of KV blocks")

    def _take(self, bid: int) -> None:
        blk = self.blocks[bid]
        if blk.ref_count == 0 and bid in self.evictable:
            del self.evictable[bid]
        blk.ref_count += 1

    # -- sequence-level API ----------------------------------------------
    def iter_hashes(self, token_ids: list[int], seed: int = 0,
                    hashes: list[int] | None = None):
        """Chain hashes of token_ids' full blocks, lazily. `hashes` is
        what the caller knows of this chain already (a sequence's
        `block_hashes`): read first, then extended by every block this
        has to hash, so that no block of a prompt is hashed twice
        whoever asks (restore, admission and its retries, the
        registration of computed blocks)."""
        n_blocks = len(token_ids) // self.block_size
        known = hashes[:n_blocks] if hashes else []
        yield from known
        for h in iter_chain_hashes(token_ids, self.block_size,
                                   known[-1] if known else seed,
                                   start=len(known)):
            self.blocks_hashed += 1
            if hashes is not None:
                hashes.append(h)
            yield h

    def block_hashes_for(self, token_ids: list[int], seed: int = 0,
                         hashes: list[int] | None = None) -> list[int]:
        """Chain hashes for each *full* block of token_ids.

        `seed` starts the chain (0 = base model; LoRA requests pass a
        per-adapter seed so adapters never share KV blocks); `hashes`
        as in `iter_hashes`."""
        return list(self.iter_hashes(token_ids, seed, hashes))

    def contains_hash(self, h: int) -> bool:
        return h in self.cached_blocks

    def match_prefix(self, token_ids: list[int], seed: int = 0,
                     hashes: list[int] | None = None,
                     ) -> tuple[list[int], int]:
        """Longest cached prefix: returns (block_ids, num_cached_tokens).
        Hashes no block past the first miss (`hashes` as in
        `iter_hashes`).

        Does NOT take references; pairs with allocate_prompt.
        """
        if not self.enable_prefix_caching:
            return [], 0
        matched: list[int] = []
        for h in self.iter_hashes(token_ids, seed, hashes):
            bid = self.cached_blocks.get(h)
            if bid is None:
                break
            matched.append(bid)
        return matched, len(matched) * self.block_size

    def allocate_prompt(
        self, token_ids: list[int], seed: int = 0,
        reuse_cache: bool = True,
        hashes: list[int] | None = None,
    ) -> tuple[list[int], int] | None:
        """Allocate the block table for a prompt, reusing cached prefix blocks.

        Returns (block_table, num_cached_tokens) or None if out of blocks.
        num_cached_tokens is capped at len(token_ids)-1 so at least one token
        is computed (we need its logits to start decoding).

        `reuse_cache=False` skips prefix matching (the computed blocks
        still REGISTER afterwards): prompt_logprobs needs every position
        actually computed — a cache hit would skip its rows. `hashes` as
        in `iter_hashes`: the first `num_cached_tokens // block_size` of
        them are then the adopted blocks', which are registered
        already."""
        n = len(token_ids)
        self.prefix_queries += n
        if not reuse_cache:
            matched, cached_tokens = [], 0
        else:
            matched, cached_tokens = self.match_prefix(
                token_ids, seed, hashes
            )
        cached_tokens = min(cached_tokens, n - 1)
        num_matched_blocks = cached_tokens // self.block_size
        matched = matched[:num_matched_blocks]
        # re-floor to the adopted block boundary: after the n-1 cap the
        # token count must match the blocks actually taken, otherwise a
        # fully-cached prompt whose length is a block multiple starts
        # computing at a position whose preceding KV was never adopted
        # (attention over zero blocks => corrupt logits)
        cached_tokens = num_matched_blocks * self.block_size

        total_blocks = (n + self.block_size - 1) // self.block_size
        need_new = total_blocks - len(matched)
        # matched blocks sitting in the evictable pool stop being free the
        # moment we take them, so they must not count toward need_new
        evictable_matched = sum(1 for b in matched if b in self.evictable)
        if self.num_free_blocks - evictable_matched < need_new:
            self.prefix_queries -= n  # admission failed; don't skew stats
            return None

        self.prefix_hits += cached_tokens
        table = []
        for bid in matched:
            self._take(bid)
            table.append(bid)
        for _ in range(need_new):
            bid = self._pop_free_block()
            self._take(bid)
            table.append(bid)
        return table, cached_tokens

    def ensure_capacity(
        self, num_tokens: int, block_table: list[int]
    ) -> bool:
        """Grow block_table (in place) until it covers num_tokens positions.

        Returns False if a new block was needed but none was available.
        """
        while len(block_table) * self.block_size < num_tokens:
            if self.num_free_blocks == 0:
                return False
            bid = self._pop_free_block()
            self._take(bid)
            block_table.append(bid)
        return True

    def register_block(
        self, prev_hash: int, token_ids: tuple[int, ...], block_id: int,
        of_prompt: bool = False,
    ) -> int:
        """Incrementally content-address one full block; returns its
        hash. `of_prompt`: the block lies inside a prompt that was
        counted in prefix_queries, so its hashing counts too."""
        h = hash_block(prev_hash, token_ids)
        self.blocks_hashed += of_prompt
        self.register_hash(h, block_id)
        return h

    def register_hash(self, h: int, block_id: int) -> None:
        """Content-address a full block whose chain hash is known."""
        if not self.enable_prefix_caching:
            return
        blk = self.blocks[block_id]
        if blk.block_hash is None and h not in self.cached_blocks:
            blk.block_hash = h
            self.cached_blocks[h] = block_id
            if self.on_admit is not None:
                self.on_admit([h])

    def adopt_cached_block(self, h: int) -> int | None:
        """Claim a free block to hold offload-restored contents for hash h.

        The block enters the cache ref_count==0 and evictable, exactly like
        a block left behind by a finished sequence; the caller must import
        the KV contents before the next model step. Returns None when no
        block can be claimed (restore is best-effort, admission continues
        with whatever prefix is already in HBM).
        """
        if not self.enable_prefix_caching or h in self.cached_blocks:
            return None
        if not self.free_blocks and not self.evictable:
            return None
        bid = self._pop_free_block()
        blk = self.blocks[bid]
        blk.block_hash = h
        self.cached_blocks[h] = bid
        self.evictable[bid] = None
        if self.on_admit is not None:
            self.on_admit([h])
        return bid

    def can_adopt_another(self, n_adopted: int) -> bool:
        """True while one more adopt_cached_block cannot cannibalize the
        caller's own freshly-adopted blocks. Adopted blocks enter the
        evictable pool (newest end), so _pop_free_block only reaches
        them once free_blocks is empty AND every OLDER evictable entry
        is consumed — i.e. when the caller's n_adopted blocks are all
        that remains. Evicting one would hand its block id out twice in
        the same restore: a donated scatter with duplicate destination
        indices has undefined write order, leaving a live cache hash
        holding another hash's KV."""
        return len(self.free_blocks) + len(self.evictable) > n_adopted

    def drop_cached_block(self, h: int) -> None:
        """Remove an UNREFERENCED cached block from the cache and return
        it to the free pool (a restore landing failed AFTER adoption —
        leaving the entry would serve never-written garbage KV to every
        later prefix hit on this hash)."""
        bid = self.cached_blocks.pop(h, None)
        if bid is None:
            return
        blk = self.blocks[bid]
        assert blk.ref_count == 0, "drop_cached_block on a live block"
        blk.block_hash = None
        if bid in self.evictable:
            del self.evictable[bid]
        self.free_blocks.append(bid)
        if self.on_evict is not None:
            self.on_evict([h])

    # -- deferred-export pinning -------------------------------------------
    def pin_for_export(self, block_ids: list[int]) -> None:
        """Take freed-but-cached blocks out of the reusable pools until
        their deferred d2h export snapshot is enqueued (unpin_exported).

        A pinned block keeps its cache entry — prefix hits may still
        re-take it (contents are immutable for a registered hash) — it
        just stops being allocatable, so no later dispatch can overwrite
        it before the export's device-side copy is ordered. Idempotent:
        re-pinning an already-pinned or re-taken block is a no-op."""
        for bid in block_ids:
            blk = self.blocks[bid]
            if blk.ref_count == 0 and bid in self.evictable:
                del self.evictable[bid]
                self._export_pins.add(bid)

    def unpin_exported(self, block_ids: list[int]) -> None:
        """The export snapshot is enqueued (device-ordered before any
        later write): return still-free pinned blocks to their pools."""
        for bid in block_ids:
            if bid not in self._export_pins:
                continue
            self._export_pins.discard(bid)
            blk = self.blocks[bid]
            if blk.ref_count == 0 and bid not in self.evictable:
                if blk.block_hash is not None:
                    self.evictable[bid] = None
                else:
                    self.free_blocks.append(bid)

    def prepare_chunk(self, block_table: list[int], start: int,
                      end: int) -> None:
        """Before a dispatch computes positions [start, end) of the
        table's sequence. One pool, nothing to do: the table already
        covers them (WindowedBlockManager overrides)."""

    def release_behind(self, block_table: list[int], next_pos: int) -> None:
        """The sequence's next query is at `next_pos` and none will come
        before it. One pool keeps every block (WindowedBlockManager
        overrides)."""

    def note_saved(self, block_table: list[int], block_index: int) -> None:
        """The table's sequence registered its block `block_index`. One
        kind of memory, nothing to note (StateBlockManager overrides)."""

    def free(self, block_table: list[int]) -> None:
        """Release a sequence's references; cached blocks become evictable."""
        # table-identity epoch: freed block ids may be handed to another
        # sequence, so anything caching a snapshot of LIVE page tables
        # (the staged h2d prefetch, llm_engine._stage_fingerprint) must
        # observe a bump and rebuild — a same-length re-allocated table
        # is indistinguishable by shape alone
        self.free_epoch += 1
        freed_cached: list[tuple[int, int]] = []
        for bid in block_table:
            blk = self.blocks[bid]
            blk.ref_count -= 1
            assert blk.ref_count >= 0, f"double free of block {bid}"
            if blk.ref_count == 0:
                if blk.block_hash is not None:
                    if bid not in self._export_pins:
                        # keep contents, LRU-evictable; a still-pinned
                        # block stays out of the pool until its export
                        # snapshot is enqueued (unpin_exported)
                        self.evictable[bid] = None
                    freed_cached.append((bid, blk.block_hash))
                else:
                    self.free_blocks.append(bid)
        if freed_cached and self.on_freed_cached is not None:
            # one batched d2h export per freed sequence (see kv/offload.py)
            self.on_freed_cached(freed_cached)


class WindowTable(list):
    """A sequence's block table (primary ids, as every program ships
    them) that also says which of its blocks the sequence holds in the
    window group: those at indices [lo, hi). Below `lo` they were
    released (or, after a prefix hit, never taken); from `hi` on they
    are not allocated there yet."""
    __slots__ = ("lo", "hi")

    def __init__(self, ids=(), lo: int = 0, hi: int = 0):
        super().__init__(ids)
        self.lo, self.hi = lo, hi


class WindowedBlockManager(BlockManager):
    """BlockManager plus the window cache group's pool.

    The per-sequence table of the window group is the primary table
    mapped through `block_map` — on the device too (the runner uploads
    the map when `map_version` moved), so no program ships a second
    table. A window-group block ("twin") belongs to ONE primary block
    for as long as it is resident:

    - allocated when a dispatch is about to write the primary block's
      positions (`prepare_chunk`, `ensure_capacity`), referenced by the
      sequence;
    - referenced again by every sequence that adopts the primary block
      on a prefix hit AND still needs it (the last `window` positions
      before the hit's end);
    - released by a sequence once every position in the block lies
      behind `next query - window` (`release_behind`), or with the
      sequence (`free`);
    - at reference count 0: freed at once if the primary block is not
      hash-registered, else kept evictable so that a later prefix hit
      can end there; dropped when the primary block is evicted.

    A prefix hit of n blocks is granted only where the window group
    still has the blocks covering the last `window` positions before
    n * block_size; otherwise it is cut back to the longest n for which
    that holds (`match_prefix`), at worst to nothing: a cut costs a
    prefill of everything behind it.

    So which evictable twins go first decides what a returning session
    costs (measured: PERF.md, Findings PR 43). Two classes: a twin let
    go BEHIND a window (`release_behind`) was passed over by its
    sequence: `_wtrail`, recycled before any other, oldest first (a long
    prefill recycles the trails of earlier ones and then its own, and
    evicts nobody's end); a twin let go with its sequence (`free`: the
    last `window` positions of a prompt and its answer, where the
    session's next turn will hit) is an END: `_wevictable`, least
    recently used first, only once no trail is left. A twin passed over
    counts as an end where prefix hits of at least two prompts have
    ended in front of it (`_tail_ends`: the end of a shared document,
    where every new session's hit ends; a hit that was cut back there
    counts twice). And a cut heals: where a recomputed block is
    registered and the cached block of its hash has no twin, the
    recomputed one (which has) takes the hash over (`register_hash`),
    so the next prompt's hit ends where this one was cut; left as the
    base class registers, the recomputed copies stay unregistered, the
    cached ones without twins, and every later prompt is cut again.

    The pool is sized by the runner for every lane's window and chunk at
    once plus the ends of several times as many cached sequences
    (`ModelRunner._window_blocks_needed`), so the lanes the scheduler
    admits (at most max_num_seqs) can always be served after evicting
    cached twins: running out is a bug, and raises."""

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool, *, window: int,
                 num_window_blocks: int):
        super().__init__(num_blocks, block_size, enable_prefix_caching)
        import numpy as np

        if num_window_blocks < 2:
            raise ValueError("the window group needs at least 2 blocks")
        self.window = window
        self.num_window_blocks = num_window_blocks
        # primary block id -> window-group block id (0 = not resident);
        # the array the runner uploads
        self.block_map = np.zeros((num_blocks,), np.int32)
        self.map_version = 0
        self._wfree: list[int] = list(range(num_window_blocks - 1, 0, -1))
        self._wref = [0] * num_window_blocks
        self._wowner = [0] * num_window_blocks
        # twin id -> None: reference count 0, primary hash-registered.
        # The ends (LRU) and the trails (oldest first), which go before
        # any end (the class docstring has why)
        self._wevictable: OrderedDict[int, None] = OrderedDict()
        self._wtrail: OrderedDict[int, None] = OrderedDict()
        # primary block id -> admitted prompts whose prefix hit ended
        # with this block in its window (a hit cut back there: twice)
        self._tail_ends: dict[int, int] = {}
        self.window_blocks_released = 0
        # [blocks the admitted prompts' prefix hits were shortened by
        # because the twins at their end were gone, admitted prompts
        # whose prefix hit at all] (tpu:prefix_window_cutback_blocks)
        self.prefix_cutback = [0, 0]
        # the last match's (blocks cut back, the window before the
        # uncut hit's end); None = no hit
        self._cutback: tuple[int, list[int]] | None = None

    # -- the window pool ----------------------------------------------------
    @property
    def window_blocks_in_use(self) -> int:
        """Twins some sequence references."""
        return (self.num_window_blocks - 1 - len(self._wfree)
                - len(self._wevictable) - len(self._wtrail))

    def _walloc(self, bid: int) -> None:
        assert not self.block_map[bid], f"block {bid} already has a twin"
        if self._wfree:
            w = self._wfree.pop()
        elif self._wtrail or self._wevictable:
            w, _ = (self._wtrail or self._wevictable).popitem(last=False)
            self.block_map[self._wowner[w]] = 0
        else:
            raise RuntimeError(
                "out of window-group KV blocks: the pool is sized for "
                "every lane at once, so this is a bookkeeping bug"
            )
        self.block_map[bid] = w
        self._wowner[w] = bid
        self._wref[w] = 1
        self.map_version += 1

    def _wtake(self, bid: int) -> None:
        w = int(self.block_map[bid])
        assert w, f"block {bid} has no twin to take"
        if self._wref[w] == 0:
            del (self._wtrail if w in self._wtrail else self._wevictable)[w]
        self._wref[w] += 1

    def _wrelease(self, bid: int, passed_over: bool = False) -> None:
        w = int(self.block_map[bid])
        assert w and self._wref[w] > 0, f"twin of block {bid} not held"
        self._wref[w] -= 1
        if self._wref[w]:
            return
        if self.blocks[bid].block_hash is None:
            self._drop_twin(bid)
            return
        if passed_over and self._tail_ends.get(bid, 0) < 2:
            self._wtrail[w] = None
        else:
            self._wevictable[w] = None

    def _drop_twin(self, bid: int) -> None:
        w = int(self.block_map[bid])
        if not w:
            return
        assert self._wref[w] == 0, f"twin of block {bid} still referenced"
        self._wevictable.pop(w, None)
        self._wtrail.pop(w, None)
        self._wfree.append(w)
        self.block_map[bid] = 0
        self.map_version += 1

    def _pop_free_block(self) -> int:
        bid = super()._pop_free_block()
        # a primary block that starts a new life (evicted from the
        # cache, or freed unregistered) takes no twin along
        self._drop_twin(bid)
        self._tail_ends.pop(bid, None)
        return bid

    def register_hash(self, h: int, block_id: int) -> None:
        old = self.cached_blocks.get(h)
        if (old is not None and old != block_id
                and self.blocks[block_id].block_hash is None
                and self.block_map[block_id] and not self.block_map[old]):
            # the same content computed again (a hit was cut back in
            # front of it): the cached copy has no twin and this one
            # has, so this one becomes the cached block. Who still reads
            # the old copy keeps it by its id; it is freed unregistered
            self.blocks[old].block_hash = None
            del self.cached_blocks[h]
            if old in self.evictable:
                del self.evictable[old]
                self.free_blocks.append(old)
            if old in self._tail_ends:
                self._tail_ends[block_id] = self._tail_ends.pop(old)
        super().register_hash(h, block_id)

    def _tail_start(self, n_blocks: int) -> int:
        """First block a query at position n_blocks * block_size still
        attends in the window group."""
        first_key = n_blocks * self.block_size - self.window + 1
        return max(0, first_key // self.block_size)

    # -- sequence-level API -------------------------------------------------
    def match_prefix(self, token_ids: list[int], seed: int = 0,
                     hashes: list[int] | None = None,
                     ) -> tuple[list[int], int]:
        matched, _ = super().match_prefix(token_ids, seed, hashes)
        # allocate_prompt computes at least one token: check the window
        # at the boundary it will really start from
        hit = n = min(len(matched), (len(token_ids) - 1) // self.block_size)
        while n > 0 and not all(
            self.block_map[b] for b in matched[self._tail_start(n):n]
        ):
            n -= 1
        self._cutback = (
            (hit - n, matched[self._tail_start(hit):hit]) if hit else None)
        return matched[:n], n * self.block_size

    def allocate_prompt(self, token_ids, seed: int = 0,
                        reuse_cache: bool = True, hashes=None):
        self._cutback = None
        alloc = super().allocate_prompt(token_ids, seed, reuse_cache,
                                        hashes)
        if alloc is None:
            return None
        if self._cutback is not None:
            # counted where the prompt is admitted: the scheduler asks
            # `match_prefix` about a waiting prompt more than once
            cut, tail = self._cutback
            self.prefix_cutback[0] += cut
            self.prefix_cutback[1] += 1
            for bid in tail:
                self._tail_ends[bid] = (
                    self._tail_ends.get(bid, 0) + 1 + (cut > 0))
        table, cached = alloc
        n = cached // self.block_size
        table = WindowTable(table, lo=self._tail_start(n), hi=n)
        for bid in table[table.lo:table.hi]:
            self._wtake(bid)
        return table, cached

    def _extend(self, table: WindowTable, upto: int) -> None:
        """Twins for the table's blocks [hi, upto)."""
        for i in range(table.hi, min(upto, len(table))):
            bid = table[i]
            if self.block_map[bid]:
                self._wtake(bid)  # resident already: share it
            else:
                self._walloc(bid)
            table.hi = i + 1

    def ensure_capacity(self, num_tokens: int, block_table) -> bool:
        if not super().ensure_capacity(num_tokens, block_table):
            return False
        self._extend(block_table, len(block_table))
        return True

    def prepare_chunk(self, block_table, start: int, end: int) -> None:
        self.release_behind(block_table, start)
        self._extend(block_table, -(-end // self.block_size))

    def release_behind(self, block_table, next_pos: int) -> None:
        if not isinstance(block_table, WindowTable):
            return  # already freed (a finished lane still in a batch)
        # block i lies wholly behind the window of every later query
        # when its last position (i+1)*bs - 1 <= next_pos - window
        new_lo = min(
            max(0, (next_pos - self.window + 1) // self.block_size),
            len(block_table),
        )
        for i in range(block_table.lo, min(new_lo, block_table.hi)):
            self._wrelease(block_table[i], passed_over=True)
            self.window_blocks_released += 1
        if new_lo > block_table.lo:
            block_table.lo = new_lo
            block_table.hi = max(block_table.hi, new_lo)

    def free(self, block_table) -> None:
        if isinstance(block_table, WindowTable):
            for bid in block_table[block_table.lo:block_table.hi]:
                self._wrelease(bid)
            block_table.lo = block_table.hi = len(block_table)
        super().free(block_table)


class StateTable(list):
    """A sequence's block table that also names the sequence's STATE
    SLOT (`slot`), the snapshot its prefix hit is restored from while
    that is pinned (`load`, a snapshot slot), the first block it
    computes (`first`, an index: where the restored state is read), and
    the last snapshot it saved itself (`last_saved`)."""
    __slots__ = ("slot", "load", "first", "last_saved")

    def __init__(self, ids=(), slot: int = 0, first: int = 0):
        super().__init__(ids)
        self.slot, self.first = slot, first
        self.load = self.last_saved = 0


class StateBlockManager(BlockManager):
    """BlockManager for a model whose layers hold RECURRENT STATE beside
    the paged KV cache (state-space layers, ops/ssm.py): a second kind
    of per-sequence memory that is not pages.

    - A running sequence owns one STATE SLOT (1..num_state_slots) for
      its life: taken with its table (`allocate_prompt`), freed with it
      (`free`; a preempted sequence recomputes).
    - A SNAPSHOT is a sequence's whole state at a token boundary that is
      a multiple of `interval_blocks` blocks, kept in a pool of
      `num_snapshots` further slots of the same device arrays, keyed by
      the chain hash of the block that ends at the boundary.
    - A prefix hit is worth only what the deepest snapshot under it
      allows: `match_prefix` cuts the run of hashed blocks back to the
      deepest boundary that has BOTH its blocks and a snapshot; the
      rest is recomputed and counted (`cutback_tokens`).

    Nothing here copies a state. The device finds every row's sequence
    through `maps` (3, blocks: a row a map, so that the device's tiles
    pad 3 to 8 and not to 128, and an upload is the maps' own size),
    which the runner uploads when `map_version` moved: a block's [state
    slot of the sequence writing it, snapshot slot to SAVE the state to when the block's last
    position is computed, snapshot slot to LOAD it from when its first
    is]. A block being written belongs to one sequence, so the block of
    a row's write slot names the sequence; the step programs ship
    nothing more (`ssm.plan_rows`).

    A snapshot slot is PENDING from the allocation of its boundary block
    until that block's hash is registered (the state was then saved by
    the program that computed the block's last position); a chunk that
    would run across a boundary, which would leave the state there
    unsaved, gives the pending slot back (`prepare_chunk`). Eviction:
    snapshots their own sequence has passed (it saved a deeper one) go
    first, oldest first, unless prefix hits of at least two prompts
    ended there (a shared system prompt's end); then least recently
    used. A snapshot goes with its block when that is evicted. A
    snapshot a waiting hit will load from is pinned until the chunk
    that reads it has been dispatched."""

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool, *, num_state_slots: int,
                 num_snapshots: int, interval_blocks: int):
        super().__init__(num_blocks, block_size, enable_prefix_caching)
        import numpy as np

        self.interval = max(1, interval_blocks)
        self.num_state_slots = num_state_slots
        self.num_snapshots = num_snapshots if enable_prefix_caching else 0
        self.maps = np.zeros((3, num_blocks), np.int32)
        self.map_version = 0
        self._free_slots = list(range(num_state_slots, 0, -1))
        first = num_state_slots + 1
        self._free_snaps = list(
            range(first + self.num_snapshots - 1, first - 1, -1))
        # snapshot slot -> the boundary block it waits for (pending)
        self._pending: dict[int, int] = {}
        # chain hash -> snapshot slot, and back
        self.snapshots: dict[int, int] = {}
        self._snap_hash: dict[int, int] = {}
        # resident snapshot slot -> None: least recently used first, and
        # the ones their own sequence has passed, which go before those
        self._lru: OrderedDict[int, None] = OrderedDict()
        self._trail: OrderedDict[int, None] = OrderedDict()
        self._pins: dict[int, int] = {}
        self._ends: dict[int, int] = {}   # slot -> hits that ended there
        # counters (engine/metrics.py: tpu:ssm_*, tpu:prefix_state_*)
        self.snapshot_saves = 0
        self.snapshot_restores = 0
        self.snapshot_evictions = 0
        self.cutback_tokens = 0
        self._cut = 0  # the last match's cut-back blocks

    # -- the pools ----------------------------------------------------------
    @property
    def state_slots_in_use(self) -> int:
        return self.num_state_slots - len(self._free_slots)

    @property
    def snapshots_resident(self) -> int:
        return len(self.snapshots)

    def _set(self, bid: int, col: int, value: int) -> None:
        if self.maps[col, bid] != value:
            self.maps[col, bid] = value
            self.map_version += 1

    def _drop_snapshot(self, slot: int) -> None:
        h = self._snap_hash.pop(slot)
        del self.snapshots[h]
        self._lru.pop(slot, None)
        self._trail.pop(slot, None)
        self._ends.pop(slot, None)
        self._free_snaps.append(slot)

    def _take_snapshot_slot(self) -> int:
        """A free snapshot slot, evicting where none is; 0 where every
        one is pending or pinned (the boundary then gets no snapshot)."""
        if self._free_snaps:
            return self._free_snaps.pop()
        for pool in (self._trail, self._lru):
            slot = next((s for s in pool if not self._pins.get(s)), None)
            if slot is not None:
                self._drop_snapshot(slot)
                self.snapshot_evictions += 1
                return self._free_snaps.pop()
        return 0

    def _unpend(self, bid: int) -> None:
        slot = int(self.maps[1, bid])
        if slot and self._pending.get(slot) == bid:
            del self._pending[slot]
            self._free_snaps.append(slot)
        self._set(bid, 1, 0)

    def _unpin(self, table: StateTable) -> None:
        if table.load:
            self._pins[table.load] -= 1
            table.load = 0
            if table.first < len(table):
                self._set(table[table.first], 2, 0)

    def _pop_free_block(self) -> int:
        gone = None
        if not self.free_blocks and self.evictable:
            # the cached block this will evict
            gone = self.blocks[next(iter(self.evictable))].block_hash
        bid = super()._pop_free_block()
        # a block that starts a new life names no sequence and no
        # snapshot, and the snapshot at its old hash goes with it
        if gone is not None and gone in self.snapshots:
            slot = self.snapshots[gone]
            if not self._pins.get(slot):
                self._drop_snapshot(slot)
        self._unpend(bid)
        for col in (0, 2):
            self._set(bid, col, 0)
        return bid

    def _own(self, table: StateTable, start: int) -> None:
        """The table's fresh blocks from index `start` on name its
        sequence."""
        for i in range(start, len(table)):
            self._set(table[i], 0, table.slot)

    def _expect_snapshot(self, bid: int) -> None:
        """The dispatch that is being planned computes the last position
        of block `bid`, which ends at a boundary: a snapshot slot for it,
        pending. Taken this late so that a long prompt recycles the
        boundaries it has passed itself, and evicts nobody's end."""
        if self.num_snapshots and not self.maps[1, bid] and (
                self.blocks[bid].block_hash is None):
            slot = self._take_snapshot_slot()
            if slot:
                self._pending[slot] = bid
                self._set(bid, 1, slot)

    # -- sequence-level API -------------------------------------------------
    def match_prefix(self, token_ids: list[int], seed: int = 0,
                     hashes: list[int] | None = None,
                     ) -> tuple[list[int], int]:
        matched, _ = super().match_prefix(token_ids, seed, hashes)
        # allocate_prompt computes at least one token
        hit = min(len(matched), (len(token_ids) - 1) // self.block_size)
        n = hit - hit % self.interval
        while n > 0 and (
                self.blocks[matched[n - 1]].block_hash not in self.snapshots):
            n -= self.interval
        self._cut = hit - n
        return matched[:n], n * self.block_size

    def allocate_prompt(self, token_ids, seed: int = 0,
                        reuse_cache: bool = True, hashes=None):
        if not self._free_slots:
            return None
        self._cut = 0
        alloc = super().allocate_prompt(token_ids, seed, reuse_cache,
                                        hashes)
        if alloc is None:
            return None
        table, cached = alloc
        n = cached // self.block_size
        # counted where the prompt is admitted: the scheduler asks
        # `match_prefix` about a waiting prompt more than once
        self.cutback_tokens += self._cut * self.block_size
        table = StateTable(table, slot=self._free_slots.pop(), first=n)
        self._own(table, n)
        if n:
            slot = self.snapshots[self.blocks[table[n - 1]].block_hash]
            # where this sequence starts is the first boundary it will
            # have passed: once it has saved a deeper one
            table.load = table.last_saved = slot
            self._pins[slot] = self._pins.get(slot, 0) + 1
            self._ends[slot] = self._ends.get(slot, 0) + 1
            if slot in self._trail and self._ends[slot] >= 2:
                del self._trail[slot]
                self._lru[slot] = None
            elif slot in self._lru:
                self._lru.move_to_end(slot)
            self._set(table[n], 2, slot)
            self.snapshot_restores += 1
        return table, cached

    def ensure_capacity(self, num_tokens: int, block_table) -> bool:
        had = len(block_table)
        ok = super().ensure_capacity(num_tokens, block_table)
        if isinstance(block_table, StateTable):
            self._own(block_table, had)
            # a decode lane: the chunk that read the snapshot has run,
            # and the fused steps ahead may cross a boundary: in one of
            # the blocks they write, the table's last two
            self._unpin(block_table)
            for i in range(max(0, min(had, len(block_table) - 2)),
                           len(block_table)):
                if (i + 1) % self.interval == 0:
                    self._expect_snapshot(block_table[i])
        return ok

    def prepare_chunk(self, block_table, start: int, end: int) -> None:
        if not isinstance(block_table, StateTable):
            return
        bs = self.block_size
        if start > block_table.first * bs:
            self._unpin(block_table)
        # a boundary INSIDE the chunk: the state there is never alone
        # in the slot, so nothing is saved for it
        step = self.interval * bs
        for edge in range(start - start % step + step, end, step):
            self._unpend(block_table[edge // bs - 1])
        if end % step == 0:
            self._expect_snapshot(block_table[end // bs - 1])

    def register_hash(self, h: int, block_id: int) -> None:
        super().register_hash(h, block_id)
        slot = int(self.maps[1, block_id])
        if not slot or self._pending.get(slot) != block_id:
            return
        del self._pending[slot]
        self._set(block_id, 1, 0)
        if h in self.snapshots or h not in self.cached_blocks:
            self._free_snaps.append(slot)  # one is there, or no block is
            return
        self.snapshots[h] = slot
        self._snap_hash[slot] = h
        self._lru[slot] = None
        self.snapshot_saves += 1

    def note_saved(self, block_table, block_index: int) -> None:
        """The table's sequence registered its block `block_index`: the
        snapshot it saved before, which it has now passed, goes to the
        front of the eviction order."""
        if not isinstance(block_table, StateTable):
            return
        slot = self.snapshots.get(
            self.blocks[block_table[block_index]].block_hash or 0, 0)
        if not slot or slot == block_table.last_saved:
            return
        old = block_table.last_saved
        if old in self._lru and self._ends.get(old, 0) < 2:
            del self._lru[old]
            self._trail[old] = None
        block_table.last_saved = slot

    def free(self, block_table) -> None:
        if isinstance(block_table, StateTable) and block_table.slot:
            self._unpin(block_table)
            for i in range(self.interval - 1, len(block_table),
                           self.interval):
                self._unpend(block_table[i])
            self._free_slots.append(block_table.slot)
            block_table.slot = 0
        super().free(block_table)

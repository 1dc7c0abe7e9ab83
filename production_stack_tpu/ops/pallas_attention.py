"""Pallas TPU kernels: paged GQA attention over the HBM KV cache.

This is the hot op of the serving engine (the capability the reference
stack gets from vLLM's PagedAttention CUDA kernels; our TPU-first design
replaces the gather-based XLA path in ops/attention.py on TPU):

- The KV cache is HEAD-MAJOR: (L, nkv, slots, d). This is the layout
  the hardware wants twice over: (a) a page slice
  `cache[layer, :, row0:row0+bs]` lands in VMEM as (nkv, bs, d) in ONE
  strided DMA with the tiled (slots, d) dims sliced tile-aligned, and
  (b) the attention dots batch over kv heads with batch dims at
  matching operand positions — Mosaic rejects the slot-major layout's
  mismatched-batch matmul outright ("batch dims must be equal" on v5e)
  and slot-major per-head slices break (nkv, d) tiling.
- The cache stays in HBM (`memory_space=ANY`). ONE page walk (`_walk`)
  serves all three kernels. It advances by a KV BLOCK of N pages: the N
  page DMAs of a block land side by side in one (nkv, N*bs, d) VMEM
  buffer, a ring of `_KV_RING` such buffers keeps the following blocks
  in flight while the current one is computed, and one iteration does
  one QK^T, one masked online-softmax update and one PV over the whole
  block — N*bs keys on the lane axis (a multiple of 128 wherever the
  page size allows), so the vregs and the MXU's tiles are full. N comes
  from `_kv_block_pages`: what the kernel sees at trace time (kv heads
  — per chip under tensor parallelism —, head_dim, cache dtype, page
  size) against a VMEM budget. The gathered (batch, ctx, ...) context
  copy the XLA path materialises is never built.
- KV blocks sit at ABSOLUTE multiples of N pages: a walk that starts
  inside a block (sliding window) or ends inside one (every context's
  tail) masks what lies outside and points the copies of pages past
  the walk's last page at that last page, so no table entry beyond a
  lane's pages is ever used as an address. Which keys are summed
  together therefore never depends on who asks, and a wholly masked
  block is an exact no-op of the online softmax: that is what makes
  the ragged kernel bit-identical per row to the composed ones.
- Two tile heights on the sublane axis, one algorithm: a segment that
  owns ONE query row (every decode lane) walks its context with
  (nkv, g, d) queries — g rows a kv head, padded to the 8-row sublane
  tile — while a segment with more rows (a prefill chunk's tile) fuses
  them as (nkv, TQ*g, d). The ragged kernel picks per segment from the
  segment's own `n_rows`.
- Operands enter the MXU as stored: q, K and V in the cache dtype with
  float32 accumulation, the softmax scale on the float32 scores. The
  running max, sum, p and the accumulator are float32; for a bf16 cache
  p goes through the PV product as three exact bf16 pieces stacked on
  the row axis (`_pv`), so V is loaded once and p is never rounded.
- The block table rides in scalar-prefetch SMEM (PrefetchScalarGridSpec)
  so page addresses are known before the body runs — this is the "dense
  tiling, not gather-heavy layout" recipe for TPU paged attention.
- The layer index is a scalar argument indexing the full cache, so jit
  never slices (= copies) a per-layer cache to feed the kernel.
- K and V may have different head widths (d_k from the K cache, d_v
  from the V cache; q has K's width, the output V's), and a layer may
  bring a SINK: one learned logit per q head that joins the softmax
  denominator and adds nothing to the numerator — in the online softmax
  that is the start state (m, l, acc) = (sink, 1, 0) instead of
  (-1e30, 0, 0). Both come from what the call is given; a model with
  one head width and no sink lowers to what it did without them.
- A sliding window (`window`) is masked per key and the walk starts at
  the window's first KV block, so the pages behind it never stream in.
- A LATENT kind (`latent_v`, DeepSeek-V2's MLA served absorbed): the
  cache holds one row a token and there is no V cache. A row is the
  key, all of it, and its first `latent_v` lanes are the value, for
  every q head alike (nkv = 1), so the walk copies each page once and
  feeds the same VMEM block to both products.

- A SHARED RUN (the ragged kernel; `_attend`): decode lanes over
  one cached prefix hold the same pages at the head of their tables.
  Where the caller says so for a row block, those keys are walked ONCE
  for the block's rows fused as the tall tile, the un-normalised
  (m, l, acc) of every row is kept in VMEM, and each lane's own walk
  starts where the run ends, from its rows of that state (as a sink's
  start state, which then starts the shared pass). The run ends at an
  absolute multiple of the KV block, so every row still sums the same
  keys in the same blocks in the same order: the same bits.

Numerics match ops/attention.py (f32 softmax, same masking); parity is
enforced by tests/test_pallas_attention.py in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -1e30

# Query-tile rows of the unified ragged kernel's row blocks: the unit
# the runner packs prefill chunks in (pow2 buckets >= 8 tile it
# exactly). Decode lanes share a block, one row each, and are walked
# one row at a time (see _attend), so the tile costs them nothing.
RAGGED_TQ = 8

# KV blocks resident in VMEM per kernel: one under the MXU, the others
# streaming in behind it.
_KV_RING = 3

# Launch accounting: the model runner's `_attn` dispatch seam counts
# every kernel CALL it stages while a program traces (counting inside
# the jitted bodies would under-count — jax's trace cache dedupes
# identical inner-jit calls, but each call still launches at
# runtime). The ragged kernel stages ONCE per forward regardless of
# the lane mix — tests/test_ragged_dispatch.py pins the one-launch
# contract on exactly this counter.
_LAUNCHES = {"prefill": 0, "ragged": 0}


def launch_counts() -> dict:
    return dict(_LAUNCHES)


def reset_launch_counts() -> None:
    for k in _LAUNCHES:
        _LAUNCHES[k] = 0


def _note_trace(kind: str) -> None:
    _LAUNCHES[kind] += 1


def _kv_block_pages(nkv: int, d: int, itemsize: int, block_size: int,
                    d_v: int | None = None) -> int:
    """Pages per KV block: the most keys, in multiples of the 128-lane
    vreg width from 128 to 512, whose ring of K and V buffers fits a
    2 MiB VMEM budget — a K block of about 256 KiB whatever the head
    count. It is the block's BYTES that pay for a walk step (the page
    DMAs' issue, one wait, a loop turn, a softmax update), while every
    key past a lane's last costs MXU time for nothing (a lane that
    holds no sequence is a zero-row segment and walks no block at
    all). Measured on a v5e
    (PERF.md, Findings PR 25): 8 kv heads are fastest at 128 keys, 4 at
    256, 2 (a tensor-parallel shard) at 512. `d` is K's head width and
    `d_v` V's where it differs (0: a latent kind, which has no V
    buffer)."""
    budget = 2 * 2**20
    per_key = _KV_RING * nkv * (d + (d if d_v is None else d_v)) * itemsize
    keys = min(512, max(128, budget // per_key // 128 * 128))
    return max(1, keys // block_size)


def _pv(p, v):
    """(nkv, rows, keys) float32 x (nkv, keys, d) -> (nkv, rows, d)
    float32, with V entering the MXU as stored. For a bf16 V, p is split
    into three bf16 pieces that sum to it exactly (3 x 8 significand
    bits cover float32's 24) and the pieces ride the row axis of ONE
    product: V is loaded into the MXU once, nothing is rounded."""
    dims = (((2,), (1,)), ((0,), (0,)))
    if v.dtype == p.dtype:
        return jax.lax.dot_general(
            p, v, dims, preferred_element_type=jnp.float32
        )
    rows = p.shape[1]
    pieces = []
    for _ in range(3):
        piece = p.astype(v.dtype).astype(jnp.float32)
        pieces.append(piece)
        p = p - piece
    o = jax.lax.dot_general(
        jnp.concatenate(pieces, axis=1).astype(v.dtype), v, dims,
        preferred_element_type=jnp.float32,
    )
    return o[:, :rows] + o[:, rows:2 * rows] + o[:, 2 * rows:]


def _walk(
    q,                  # (nkv, rows, d), cache dtype
    q_pos,              # int32, broadcastable to (1, rows, 1): the
                        # absolute position of each fused row's query
    lo,                 # first key position any row attends
    hi,                 # one past the last key position any row attends
    page_of,            # logical page index -> physical page id (SMEM)
    layer,
    kv,                 # the program's cache refs and scratch:
                        # k_cache, v_cache (L, nkv, slots, d) HBM;
                        # k_buf, v_buf (_KV_RING, nkv, N*bs, d) VMEM;
                        # DMA sems (_KV_RING, 2). A latent kind has
                        # neither v_cache nor v_buf (None)
    static,             # block_size, num_pages, scale, window, latent_v
    state,              # (m, l, acc) the online softmax starts from:
                        # `_start_state`, or what an earlier walk over
                        # the keys before `lo` returned
):
    """THE page walk: causal (and windowed) attention of `rows` fused
    query rows over keys [lo, hi) of one sequence's pages, online
    softmax over KV blocks of N pages at absolute multiples of N.
    Returns the un-normalised state (m, l, acc), float32 (nkv, rows, 1),
    (nkv, rows, 1) and (nkv, rows, d_v): `_normalised` makes the output
    of it, another walk over the keys from `hi` on may start from it.
    hi <= lo walks nothing, starts no copy and returns `state`."""
    k_cache_ref, v_cache_ref, k_buf, v_buf, sem = kv
    latent_v = static["latent_v"]
    ring, nkv, c, _ = k_buf.shape
    bs, scale, window = static["block_size"], static["scale"], static["window"]
    n = c // bs
    b_lo = jax.lax.div(lo, c)
    b_hi = jax.lax.div(hi + c - 1, c)
    last_page = jax.lax.div(hi - 1, bs)
    halves = ((k_cache_ref, k_buf),)
    if not latent_v:
        halves += ((v_cache_ref, v_buf),)

    def start(b):
        # one strided DMA per page and cache: all heads' rows of the
        # page's slot range (a tile-aligned slice of the head-major
        # cache) into the page's place in the block buffer
        @pl.when(b < b_hi)
        def _():
            slot = jax.lax.rem(b, ring)

            def page(p, _):
                row0 = page_of(jnp.minimum(b * n + p, last_page)) * bs
                dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
                for which, (cache_ref, buf) in enumerate(halves):
                    pltpu.make_async_copy(
                        cache_ref.at[layer, :, pl.ds(row0, bs)],
                        buf.at[slot, :, dst],
                        sem.at[slot, which],
                    ).start()
                return 0

            jax.lax.fori_loop(0, n, page, 0)

    for ahead in range(ring - 1):
        start(b_lo + ahead)

    # the causal limit, cut to the walk's end: keys past `hi` inside the
    # last block hold a repeated page
    q_hi = jnp.minimum(q_pos, hi - 1)
    key_of = jax.lax.broadcasted_iota(jnp.int32, (1, 1, c), 2)

    def body(b, carry):
        m, l, acc = carry
        start(b + ring - 1)
        slot = jax.lax.rem(b, ring)
        for which, (_, buf) in enumerate(halves):
            # ONE wait per cache for the block's N page copies: they
            # signal one semaphore, and a wait takes a copy's size only
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sem.at[slot, which]
            ).wait()

        # (nkv, rows, d) x (nkv, c, d) -> (nkv, rows, c), batched over
        # kv heads
        s = jax.lax.dot_general(
            q, k_buf[slot].astype(q.dtype),
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        k_pos = b * c + key_of
        valid = k_pos <= q_hi
        if window is not None:
            valid &= k_pos > q_pos - window
        s = jnp.where(valid, s, MASK_VALUE)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        return m_new, l_new, acc * corr + _pv(
            p, k_buf[slot, :, :, :latent_v] if latent_v else v_buf[slot])

    return jax.lax.fori_loop(b_lo, b_hi, body, state)


def _start_state(nkv, rows, d_v, sink=None):
    """What the online softmax of `rows` query rows a kv head starts
    from: nothing seen, or the sink ((nkv, rows, 1) float32 logits), a
    key whose logit is given and whose value is 0."""
    if sink is None:
        m0 = jnp.full((nkv, rows, 1), MASK_VALUE, jnp.float32)
        l0 = jnp.zeros((nkv, rows, 1), jnp.float32)
    else:
        m0 = sink
        l0 = jnp.ones((nkv, rows, 1), jnp.float32)
    return m0, l0, jnp.zeros((nkv, rows, d_v), jnp.float32)


def _normalised(state):
    _, l, acc = state
    return acc / jnp.maximum(l, 1e-30)


def _attend(
    q_ref,              # (TQ, nq, d_k) VMEM — the program's query tile
    sink_ref,           # (nkv, g, 1) float32 VMEM, or None
    out_ref,            # (TQ, nq, d_v) VMEM
    row0,               # first tile row the segment owns
    n_rows,             # rows it owns; `one_row` promises n_rows == 1
    qpos0,              # absolute position of row0's query
    page_of,
    layer,
    kv,
    static,
    *,
    one_row: bool,
    run=None,           # the ragged kernel's shared run of this row
                        # block: (its keys, the (m, l, acc) VMEM refs
                        # of its state, whether this turn IS the run)
):
    """One segment — `n_rows` contiguous positions of one sequence, in
    tile rows [row0, row0 + n_rows) — attends its context and stores its
    rows. Row r of the segment sees keys up to qpos0 + r (and down to
    its window). Two tile heights: a one-row segment takes its row out
    of the tile and walks with (nkv, g, d) queries, g padded to the
    8-row sublane tile; any other fuses the whole tile as
    (nkv, TQ*g, d), row t*g + j being head j of tile row t, and rows
    outside the segment compute garbage the masked store never
    writes.

    A row block's SHARED RUN (`_ragged_kernel`) goes through the tall
    tile as a segment of its own kind: every row of the tile at the
    run's last key, `qpos0` (each key of the run lies before every
    row's own position, so the mask passes them all, as it does in a
    lane's walk alone), and what is kept is the un-normalised state of
    every row, in `run`'s refs ((TQ, nkv, g padded to 8, .) VMEM each,
    m and l over 128 lanes). A one-row segment of that block starts
    where the run ends, from its rows of that state, picked by `row0`:
    an index on an untiled axis, no g rows cut out of a sublane tile.
    Tile rows of lanes that hold no sequence compute garbage that no
    segment picks up."""
    tq, nq, d = q_ref.shape
    k_buf = kv[2]
    nkv = k_buf.shape[1]
    d_v = static["latent_v"] or kv[3].shape[-1]
    g = nq // nkv
    # q and K meet in the MXU in their common dtype: as stored when the
    # cache has the model's dtype (the reshapes want 32-bit rows)
    q_dtype = jnp.promote_types(q_ref.dtype, k_buf.dtype)
    window = static["window"]
    run_keys, run_refs, is_run = run or (None, None, None)
    # a run's rows stand still at `qpos0`, a segment's go up by one
    step = 1 if run is None or one_row else jnp.where(is_run, 0, 1)
    hi = jnp.minimum(
        qpos0 + 1 + step * (n_rows - 1),
        static["num_pages"] * static["block_size"],
    )
    lo = jnp.int32(0)
    if window is not None:
        # the EARLIEST row needs keys down to qpos0 - window + 1;
        # earlier KV blocks never stream in
        lo = jnp.clip(qpos0 - window + 1, 0, hi)

    sink = None if sink_ref is None else sink_ref[...]
    if one_row:
        q = q_ref[row0].astype(jnp.float32).reshape(nkv, g, d)
        if g % 8:
            pad = jnp.zeros((nkv, 8 - g % 8, d), jnp.float32)
            q = jnp.concatenate([q, pad], axis=1)
            if sink is not None:
                sink = jnp.concatenate([sink, pad[:, :, :1]], axis=1)
        state = _start_state(nkv, q.shape[1], d_v, sink)
        if run is not None:
            lo = run_keys
            state = tuple(
                jnp.where(lo > 0, ref[row0][:, :, :x.shape[-1]], x)
                for ref, x in zip(run_refs, state))
        out = _normalised(_walk(
            q.astype(q_dtype), qpos0, lo, hi, page_of, layer, kv, static,
            state,
        ))
        out_ref[row0] = out[:, :g].reshape(nq, d_v).astype(out_ref.dtype)
        return

    q = (
        q_ref[...].astype(jnp.float32)
        .reshape(tq, nkv, g, d)
        .transpose(1, 0, 2, 3)
        .reshape(nkv, tq * g, d)
    )
    row_of = jax.lax.broadcasted_iota(jnp.int32, (1, tq * g, 1), 1) // g
    if sink is not None:
        # fused row t*g + j is head j of tile row t
        sink = jnp.concatenate([sink] * tq, axis=1)
    state = _walk(
        q.astype(q_dtype), qpos0 + step * (row_of - row0), lo, hi,
        page_of, layer, kv, static, _start_state(nkv, tq * g, d_v, sink),
    )

    def by_tile_row(x):
        """(nkv, TQ*g, w) of the tall tile -> (TQ, nkv, g, w)."""
        return x.reshape(nkv, tq, g, x.shape[-1]).transpose(1, 0, 2, 3)

    def store_rows():
        out = by_tile_row(_normalised(state)).reshape(tq, nq, d_v)
        # row-masked merge: segments of one block write disjoint row
        # ranges sequentially (read-modify-write within the program)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (tq, 1, 1), 0)
        keep = (row_ids >= row0) & (row_ids < row0 + n_rows)
        out_ref[...] = jnp.where(
            keep, out.astype(out_ref.dtype), out_ref[...])

    def keep_state():
        for ref, x in zip(run_refs, state):
            w = ref.shape[-1]
            x = by_tile_row(jnp.broadcast_to(x, (nkv, tq * g, w)))
            if g % 8:
                x = jnp.concatenate(
                    [x, jnp.zeros((tq, nkv, 8 - g % 8, w), jnp.float32)],
                    axis=2)
            ref[...] = x

    if run is None:
        store_rows()
    else:
        pl.when(is_run)(keep_state)
        pl.when(jnp.logical_not(is_run))(store_rows)


def _refs(rest, static):
    """(sink_ref or None, out_ref, kv, run refs or None) from a kernel's
    refs after q: k_cache, v_cache, [sink,] out, k_buf, v_buf, sems,
    [m, l, acc of a shared run] — without v_cache and v_buf for a
    latent kind, whose kv carries None in their place."""
    run = None
    if static["run"]:
        *rest, m_ref, l_ref, acc_ref = rest
        run = (m_ref, l_ref, acc_ref)
    if static["latent_v"]:
        k_cache_ref, *sink_ref, out_ref, k_buf, sem = rest
        v_cache_ref = v_buf = None
    else:
        k_cache_ref, v_cache_ref, *sink_ref, out_ref, k_buf, v_buf, sem = rest
    return (sink_ref[0] if sink_ref else None, out_ref,
            (k_cache_ref, v_cache_ref, k_buf, v_buf, sem), run)


def _decode_kernel(
    # scalar prefetch
    layer_ref,          # (1,) int32
    block_tables_ref,   # (b, P) int32
    context_lens_ref,   # (b,) int32
    # array inputs
    q_ref,              # (1, nq, d_k) VMEM — this program's query
    *rest,              # k_cache_ref (L, nkv, slots, d_k) ANY/HBM —
                        # head-major, v_cache_ref (L, nkv, slots, d_v),
                        # [sink_ref (nkv, g, 1) VMEM,] then
                        # out_ref (1, nq, d_v) VMEM, and the scratch:
                        # k_buf (_KV_RING, nkv, N*bs, d_k), v_buf VMEM,
                        # DMA sems (_KV_RING, 2); no v_cache_ref and no
                        # v_buf for a latent kind (`_refs`)
    **static,           # block_size, num_pages, scale, window, latent_v,
                        # run
):
    """One grid program per sequence: its one query row at position
    ctx_len - 1 over its own pages (the sliding window, HF semantics:
    keys j > q_pos - window, starts the walk at the window's first KV
    block)."""
    i = pl.program_id(0)
    sink_ref, out_ref, kv, _ = _refs(rest, static)
    _attend(
        q_ref, sink_ref, out_ref, 0, 1,
        context_lens_ref[i] - 1,
        lambda j: block_tables_ref[i, j], layer_ref[0],
        kv, static,
        one_row=True,
    )


def _prefill_kernel(
    # scalar prefetch
    meta_ref,           # (2,) int32: [layer, q_start]
    block_table_ref,    # (P,) int32 — this sequence's pages
    # array inputs
    q_ref,              # (Tq, nq, d_k) VMEM — this program's query tile
    *rest,              # the caches, [sink_ref,] out_ref (Tq, nq, d_v),
                        # scratch (`_refs`)
    **static,
):
    """Ragged chunked-prefill attention for ONE sequence over the paged
    HBM cache (SURVEY §7 hard-part #1, prefill half).

    Kernel contract: query rows are CONTIGUOUS absolute positions
    q_start + row (the model runner always prefills a contiguous chunk;
    padded tail rows simply read garbage that the runner discards, exactly
    like the XLA path's padded rows). Causality is per-element:
    key_pos <= q_pos, evaluated against the online softmax, so one pass
    over the context pages serves every query row of a tile — the
    per-layer (ctx, nkv, d) gathered copy the XLA path materialises is
    never built, and later tiles see (and stream) more pages.
    """
    tq = q_ref.shape[0]
    sink_ref, out_ref, kv, _ = _refs(rest, static)
    _attend(
        q_ref, sink_ref, out_ref, 0, tq,
        meta_ref[1] + pl.program_id(0) * tq,
        lambda j: block_table_ref[j], meta_ref[0],
        kv, static,
        one_row=False,
    )


def _ragged_kernel(
    # scalar prefetch
    meta_ref,           # (1,) int32: [layer]
    blk_seg_ref,        # (G+1,) int32 — CSR: block i owns segments
                        # [blk_seg[i], blk_seg[i+1])
    seg_meta_ref,       # (SC, 4) int32 — per segment:
                        # [lane, row0_in_block, n_rows, q_pos_of_row0]
    shared_ref,         # (G, 2) int32 — per row block: [shared_keys,
                        # the lane whose table row addresses them]
    block_tables_ref,   # (S, P) int32 — per-LANE page tables
    # array inputs
    q_ref,              # (TQ, nq, d_k) VMEM — this block's query rows
    *rest,              # the caches, [sink_ref,] out_ref (TQ, nq, d_v),
                        # scratch (`_refs`)
    **static,
):
    """Unified ragged paged attention: ONE grid over the flattened
    query-row space of an arbitrary lane mix (the "Ragged Paged
    Attention" recipe, PAPERS.md).

    Every lane of the round — decode lanes contributing one query row,
    prefill lanes contributing their chunk's q-tiles — packs
    back-to-back on the row axis with no cross-lane padding; the grid
    iterates TQ-row blocks of that axis. A block may span several
    lanes (a decode-heavy mix puts up to TQ single-row lanes in one
    block), so per-block SEGMENT metadata rides the scalar-prefetch
    SMEM path as a CSR list: each segment names its lane's page-table
    row, its row range within the block, and the absolute position of
    its first query row. Each segment walks its own pages through the
    same `_attend` as the composed kernels — a one-row segment (decode,
    q_pos = ctx-1) at the decode kernel's tile height, any other at the
    prefill kernel's — so outputs are bit-identical per row: one
    kernel, any lane mix, one launch. A segment costs what its keys
    cost: n_rows == 0 (a decode lane that holds no sequence this step,
    a row block no prefill lane covers) walks nothing, starts no copy
    and stores ZEROS into tile row `row0`, which it still names: the
    row is then a number whatever VMEM held (it goes on through the
    layer and is written to the null block, which a windowed lane
    reads masked: 0 x NaN is NaN).

    A SHARED RUN: where the caller says that every one-row segment of a
    row block reads its first `shared_keys` keys from the same pages
    (decode lanes over one cached prefix), those keys go through the
    walk once, as one tall tile (a turn of the segment loop of its own,
    before the block's first segment; `_attend`), and each segment's
    own walk starts where the run ends, from its rows of the run's
    state. The run is cut down to a multiple of this kernel's KV block;
    0 (and a kind with a window, whose walk starts at its window) is
    the walk of each segment alone, operation for operation. The
    caller's promise: the run's pages are the first pages of every
    one-row segment with a row, and shared_keys is below the position
    of each.
    """
    i = pl.program_id(0)
    sink_ref, out_ref, kv, refs = _refs(rest, static)
    first = start = blk_seg_ref[i]
    if refs is not None:
        c = kv[2].shape[2]
        shared = shared_ref[i, 0] // c * c
        start = first - (shared > 0).astype(jnp.int32)

    def seg_body(s, _):
        seg = [seg_meta_ref[jnp.maximum(s, first), col] for col in range(4)]
        run = None
        if refs is not None:
            # the turn before the block's first segment is its shared
            # run's, where it has one: a segment of the whole tile, its
            # rows all at the run's last key, over the named lane's pages
            is_run = s < first
            seg = [jnp.where(is_run, of_run, of_seg) for of_run, of_seg
                   in zip((shared_ref[i, 1], 0, q_ref.shape[0], shared - 1),
                          seg)]
            run = (shared, refs, is_run)
        lane, row0, n_rows, qpos0 = seg
        attend = functools.partial(
            _attend, q_ref, sink_ref, out_ref, row0, n_rows, qpos0,
            lambda j: block_tables_ref[lane, j], meta_ref[0], kv, static,
            run=run,
        )
        pl.when(n_rows == 1)(functools.partial(attend, one_row=True))
        pl.when(n_rows > 1)(functools.partial(attend, one_row=False))

        @pl.when(n_rows == 0)
        def _():
            out_ref[row0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

        return 0

    jax.lax.fori_loop(start, blk_seg_ref[i + 1], seg_body, 0)


def _paged_call(
    kernel, name, tq, scalars, q, k_cache, v_cache, *,
    num_pages, block_size, scale, window, interpret, sink=None,
    latent_v=None, run=False,
):
    """The pallas_call the three kernels share: a grid over `tq`-row
    tiles of q, the caches left in HBM, the scalars prefetched to SMEM,
    a ring of KV-block buffers as scratch. `sink` ((nq,) logits) rides
    as one more VMEM input where the layer has one. A latent kind
    (`latent_v`, `v_cache` None) has one cache and one ring. `run`: the
    ragged kernel's scratch for a shared run's state (`_attend`)."""
    r, nq, d = q.shape
    nkv = k_cache.shape[1]
    assert (v_cache is None) == bool(latent_v), (latent_v, v_cache)
    d_v = latent_v or v_cache.shape[-1]
    assert k_cache.shape[-1] == d, (k_cache.shape, q.shape)
    keys = block_size * _kv_block_pages(
        nkv, d, k_cache.dtype.itemsize, block_size,
        0 if latent_v else d_v,
    )

    def tile(width):
        return pl.BlockSpec(
            (tq, nq, width), lambda i, *_: (i, 0, 0),
            memory_space=pltpu.VMEM,
        )

    cache = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs, inputs = [tile(d), cache], [q, k_cache]
    scratch = [pltpu.VMEM((_KV_RING, nkv, keys, d), k_cache.dtype)]
    if not latent_v:
        in_specs.append(cache)
        inputs.append(v_cache)
        scratch.append(
            pltpu.VMEM((_KV_RING, nkv, keys, d_v), v_cache.dtype))
    if sink is not None:
        in_specs.append(pl.BlockSpec(
            (nkv, nq // nkv, 1), lambda i, *_: (0, 0, 0),
            memory_space=pltpu.VMEM,
        ))
        inputs.append(
            sink.astype(jnp.float32).reshape(nkv, nq // nkv, 1))
    scratch.append(pltpu.SemaphoreType.DMA((_KV_RING, 2)))
    if run:
        g_pad = -(-(nq // nkv) // 8) * 8
        scratch += [
            pltpu.VMEM((tq, nkv, g_pad, width), jnp.float32)
            for width in (128, 128, d_v)]
    return pl.pallas_call(
        functools.partial(
            kernel, block_size=block_size, num_pages=num_pages,
            scale=scale, window=window, latent_v=latent_v, run=run,
        ),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(r // tq,),
            in_specs=in_specs,
            out_specs=tile(d_v),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((r, nq, d_v), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # large f32 q/accumulator tiles exceed the default 16 MiB
            # scoped-vmem stack; v5e has 128 MiB — allow half of it
            vmem_limit_bytes=64 * 2**20,
        ),
    )(*(jnp.asarray(s, jnp.int32) for s in scalars), *inputs)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "window",
                     "latent_v"),
)
def ragged_paged_attention(
    q: jax.Array,             # (R, nq, d) — flattened mixed query rows
    k_cache: jax.Array,       # (L, nkv, num_slots, d) — head-major
    v_cache: jax.Array | None,  # None for a latent kind (`latent_v`)
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # (S, P) int32 — page table per LANE
    blk_seg: jax.Array,       # (G+1,) int32 — CSR segment offsets,
                              # G = R // RAGGED_TQ
    seg_meta: jax.Array,      # (SC, 4) int32 — [lane, row0, n_rows,
                              # q_pos0] per segment
    sink: jax.Array | None = None,  # (nq,) float32 logits
    shared: jax.Array | None = None,  # (G, 2) int32 — a row block's
                                      # [shared_keys, lane]; None: no
                                      # block has a shared run
    *,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
    latent_v: int | None = None,
) -> jax.Array:
    """One launch of ragged paged attention over any lane mix.

    The caller packs every lane's query rows back-to-back on the row
    axis (prefill chunks RAGGED_TQ-aligned; decode lanes one row each,
    sharing row blocks) and describes the layout with the CSR segment
    metadata — see _ragged_kernel. Returns (R, nq, d) in q.dtype; a
    zero-row segment's `row0` holds zeros, rows covered by no segment
    are undefined (callers discard them, the same contract as the
    composed kernels' padded rows).

    `shared`, where the caller has found that the one-row segments of a
    row block read their leading keys from the same pages (decode lanes
    over one cached prefix; `model_runner.shared_runs`), has those keys
    walked once for the block (`_ragged_kernel`).

    The decode rows' walk alone, on a v5e, against the time of the bytes
    it must read at 819 GB/s (K and V of every key a lane reads; with a
    shared run, of the run once a row block); us a layer call at the
    cells' decode shapes: the parent commit's kernel, this one with
    `shared` None ("alone": every lane its own walk) and with the runs
    the runner finds (my chip run, PR 44, call 2:
    `scripts/bench_attention_walk.py --parent ...`; all three outputs
    are the same bits in every shape):

                                          live   kv  alone         shared
                                         lanes  blk  bytes parent  time  bytes  time
        mistral batch-fewshot2k, 8 kv x 4,  32  128    460  576.5 586.1    173 279.3
          contexts 2.4-3.3k, 2,080 shared
        mimo batch-doc8k full kind, 4 kv    64  128   2202   3391  3385    481  1420
          x 16, K 256 / V 128, a sink,
          contexts 8.4-9.9k, 8,288 shared
        mistral chat-sys2k, 5 of 32 lanes    5  128     80  123.9 127.4     80 126.2
          over 4 prompts (no run)
        qwen2 chat-sys2k, 4 kv x 7, same     5  256     39   78.1  82.5     39  85.6
        ouro, 16 kv x 1, 16 lanes over      16  128    455  550.9 552.7    168 245.2
          one 2,080-key preamble
        xing4 latent, 8 of 32 lanes over     8  512    218  421.4 435.3     39 199.2
          one 16,512-key document

    What the shared pass leaves: the lanes' own tails, each a segment
    with its ~3 us of start-up (32 x 3 of mistral's 279), and the tall
    tile's own arithmetic where the tile is tall: at 8 lanes x 16 heads
    = 128 rows a kv head (mimo) a 128-key block takes 2.1 us to compute
    against 0.48 us to copy, so that walk gains 2.4x where its bytes
    fall 4.6x. Where no row block shares, the kernel is the parent's
    plus 2-6% (2-4 us a call at 8 and 4 kv heads: the walk's first
    block is a value and no longer the constant 0; 14-18 at the latent
    kind: the start state's read and select; PERF.md, Open questions
    9 f), in cells where the walk is 5-25% of the device's busy time."""
    r = q.shape[0]
    n_blocks = blk_seg.shape[0] - 1
    tq = r // n_blocks
    assert tq * n_blocks == r, (
        f"ragged row space {r} must tile into {n_blocks} blocks"
    )
    if shared is None:
        shared = jnp.zeros((n_blocks, 2), jnp.int32)
    return _paged_call(
        _ragged_kernel, "ragged_paged_attention", tq,
        (jnp.reshape(layer, 1), blk_seg, seg_meta, shared, block_tables),
        q, k_cache, v_cache, num_pages=block_tables.shape[1],
        block_size=block_size, scale=scale, window=window,
        interpret=interpret, sink=sink, latent_v=latent_v,
        # a windowed walk starts at its window: it has no leading run
        run=window is None,
    )


def _prefill_q_tile(t: int, nq: int, d: int) -> int:
    """Largest pow2 query tile whose f32 q + accumulator fit a ~4 MiB VMEM
    budget each (v5e VMEM is 128 MiB but leave room for the ring of KV
    blocks, the output tile, and Mosaic's own spills). One tile per chunk
    (the common case) means the context streams from HBM exactly once."""
    budget = 4 * 2**20
    per_row = nq * d * 4
    tile = 1 << max(3, (budget // per_row).bit_length() - 1)
    while t % tile:
        tile //= 2
    return max(1, min(tile, t))


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "window",
                     "latent_v"),
)
def paged_prefill_attention(
    q: jax.Array,            # (t, nq, d) — one chunk, contiguous positions
    k_cache: jax.Array,      # (L, nkv, num_slots, d) — head-major
    v_cache: jax.Array,
    layer: jax.Array,        # scalar int32
    block_table: jax.Array,  # (P,) int32 — pages of THIS sequence
    q_start: jax.Array,      # scalar int32 — absolute position of q row 0
    sink: jax.Array | None = None,  # (nq,) float32 logits
    *,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
    latent_v: int | None = None,
) -> jax.Array:
    """Chunked-prefill paged attention for one sequence. -> (t, nq, d)."""
    t, nq, d = q.shape
    return _paged_call(
        _prefill_kernel, "paged_prefill_attention",
        _prefill_q_tile(t, nq, d),
        (jnp.stack([jnp.asarray(layer, jnp.int32),
                    jnp.asarray(q_start, jnp.int32)]), block_table),
        q, k_cache, v_cache, num_pages=block_table.shape[0],
        block_size=block_size, scale=scale, window=window,
        interpret=interpret, sink=sink, latent_v=latent_v,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "window",
                     "latent_v"),
)
def paged_decode_attention(
    q: jax.Array,             # (b, nq, d)
    k_cache: jax.Array,       # (L, nkv, num_slots, d) — head-major
    v_cache: jax.Array,
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # (b, P) int32 — page ids per sequence
    context_lens: jax.Array,  # (b,) int32
    sink: jax.Array | None = None,  # (nq,) float32 logits
    *,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
    latent_v: int | None = None,
) -> jax.Array:
    """One decode step of paged attention, a grid program a sequence.
    Returns (b, nq, d) in q.dtype. The runner does not call it: its
    decode rows are one-row segments of `ragged_paged_attention`. Its
    only use is as the reference that tests/test_pallas_attention.py
    and tests/test_pallas_attention_groups.py hold those rows to, bit
    for bit."""
    return _paged_call(
        _decode_kernel, "paged_decode_attention", 1,
        (jnp.reshape(layer, 1), block_tables, context_lens),
        q, k_cache, v_cache, num_pages=block_tables.shape[1],
        block_size=block_size, scale=scale, window=window,
        interpret=interpret, sink=sink, latent_v=latent_v,
    )


def _resolve_tp_axis(mesh: jax.sharding.Mesh) -> str:
    """Resolve the tensor-parallel axis by name: on the multihost (dp, tp)
    mesh, axis_names[0] would be the DP axis and silently reshard the
    cache; only a single-axis mesh may fall back to its sole axis."""
    if "tp" in mesh.axis_names:
        return "tp"
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(
        f"mesh {mesh.axis_names} has no 'tp' axis; paged attention "
        "needs the kv-head-sharded tensor-parallel axis"
    )


def _over_heads(kernel_fn, mesh, q, k_cache, v_cache, *replicated, **static):
    """Tensor-parallel paged attention via shard_map.

    The KV cache is sharded over the kv-head axis and q heads are split
    congruently (parallel/sharding.py), so each chip's GQA groups are fully
    local: the kernel body needs zero cross-chip communication — the psum
    stays where GSPMD already puts it, after the wo row-parallel projection.
    shard_map hands each chip its (rows, nq/tp, d) query slice and
    (L, nkv/tp, slots, d) cache shard (the KV block is sized from that
    per-chip head count); the layer index, tables, lengths and segment
    metadata ride replicated. check_vma=False because pallas_call does
    not participate in varying-axes inference.
    """
    tp = _resolve_tp_axis(mesh)
    P = jax.sharding.PartitionSpec
    cache = P(None, tp, None, None)
    return jax.shard_map(
        functools.partial(kernel_fn, **static),
        mesh=mesh,
        in_specs=(P(None, tp, None), cache, cache)
        + tuple(P(*[None] * jnp.ndim(x)) for x in replicated),
        out_specs=P(None, tp, None),
        check_vma=False,
    )(q, k_cache, v_cache, *replicated)


def ragged_paged_attention_tp(
    q, k_cache, v_cache, layer, block_tables, blk_seg, seg_meta, *,
    mesh: jax.sharding.Mesh, block_size: int, scale: float,
    interpret: bool = False, window: int | None = None,
) -> jax.Array:
    """ragged_paged_attention with heads sharded over the mesh's tp
    axis. No shared run here (`shared` stays None: every lane walks
    alone); no cell runs a mesh."""
    return _over_heads(
        ragged_paged_attention, mesh, q, k_cache, v_cache, layer,
        block_tables, blk_seg, seg_meta, block_size=block_size,
        scale=scale, interpret=interpret, window=window,
    )


def paged_prefill_attention_tp(
    q, k_cache, v_cache, layer, block_table, q_start, *,
    mesh: jax.sharding.Mesh, block_size: int, scale: float,
    interpret: bool = False, window: int | None = None,
) -> jax.Array:
    """paged_prefill_attention with heads sharded over the tp axis."""
    return _over_heads(
        paged_prefill_attention, mesh, q, k_cache, v_cache, layer,
        block_table, q_start, block_size=block_size, scale=scale,
        interpret=interpret, window=window,
    )

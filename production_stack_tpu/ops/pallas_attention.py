"""Pallas TPU kernel: paged GQA decode attention over the HBM KV cache.

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
- The cache stays in HBM (`memory_space=ANY`); the kernel DMAs one page
  at a time into VMEM, double-buffered so the next page streams in
  while the current one is on the MXU. The gathered (batch, ctx, ...)
  context copy the XLA path materialises is never built — decode reads
  each KV byte exactly once.
- The block table rides in scalar-prefetch SMEM (PrefetchScalarGridSpec)
  so page addresses are known before the body runs — this is the "dense
  tiling, not gather-heavy layout" recipe for TPU paged attention.
- Online softmax (running max / sum / accumulator in f32) over pages,
  one grid program per sequence.
- The layer index is a scalar argument indexing the full cache, so jit
  never slices (= copies) a per-layer cache to feed the kernel.

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

# Query-tile rows of the unified ragged kernel's row blocks. 8 is the
# f32 sublane minimum: decode lanes contribute ONE query row each, so a
# bigger tile only grows the masked-row waste of decode-heavy mixes,
# while prefill chunks (pow2 buckets >= 8) tile it exactly.
RAGGED_TQ = 8

# Launch accounting: the model runner's `_attn` dispatch seam counts
# every kernel CALL it stages while a program traces (counting inside
# the jitted bodies would under-count — jax's trace cache dedupes
# identical inner-jit calls, but each call still launches at
# runtime). A composed mixed round stages the prefill kernel once PER
# LANE inside the layer scan; the unified kernel stages ONCE per
# forward regardless of the lane mix — tests/test_ragged_dispatch.py
# pins the one-launch contract on exactly this counter.
_LAUNCHES = {"decode": 0, "prefill": 0, "ragged": 0}


def launch_counts() -> dict:
    return dict(_LAUNCHES)


def reset_launch_counts() -> None:
    for k in _LAUNCHES:
        _LAUNCHES[k] = 0


def _note_trace(kind: str) -> None:
    _LAUNCHES[kind] += 1


def _decode_kernel(
    # scalar prefetch
    layer_ref,          # (1,) int32
    block_tables_ref,   # (b, P) int32
    context_lens_ref,   # (b,) int32
    # array inputs
    q_ref,              # (1, nq, d) VMEM — this program's query
    k_cache_ref,        # (L, nkv, slots, d) ANY/HBM — head-major
    v_cache_ref,
    # outputs
    out_ref,            # (1, nq, d) VMEM
    # scratch
    k_buf,              # (2, nkv, bs, d) VMEM
    v_buf,
    sem,                # DMA sems (2, 2)
    *,
    block_size: int,
    num_pages: int,
    scale: float,
    window: int | None = None,
):
    i = pl.program_id(0)
    layer = layer_ref[0]
    ctx_len = context_lens_ref[i]
    nq, d = q_ref.shape[1], q_ref.shape[2]
    nkv = k_buf.shape[1]
    g = nq // nkv
    bs = block_size

    # number of pages this sequence actually uses
    n_used = jnp.minimum(
        (ctx_len + bs - 1) // bs, jnp.int32(num_pages)
    )
    # sliding window (HF semantics: keys j > q_pos - window, q_pos =
    # ctx_len-1): pages wholly below the window are never even DMA'd —
    # the page walk starts at the window's first page
    if window is None:
        n_start = jnp.int32(0)
    else:
        n_start = jnp.maximum(ctx_len - window, 0) // bs

    # one strided DMA per page: all heads' rows for the page's slot
    # range (the head-major cache makes this a tile-aligned slice)
    def page_dma(slot, page_idx, buf, cache_ref, which):
        row0 = block_tables_ref[i, page_idx] * bs
        return pltpu.make_async_copy(
            cache_ref.at[layer, :, pl.ds(row0, bs)],
            buf.at[slot],
            sem.at[slot, which],
        )

    @pl.when(n_used > n_start)
    def _():
        s0 = jax.lax.rem(n_start, 2)
        page_dma(s0, n_start, k_buf, k_cache_ref, 0).start()
        page_dma(s0, n_start, v_buf, v_cache_ref, 1).start()

    q = q_ref[0].astype(jnp.float32).reshape(nkv, g, d) * scale

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)
        nxt = jax.lax.rem(j + 1, 2)

        @pl.when(j + 1 < n_used)
        def _():
            page_dma(nxt, j + 1, k_buf, k_cache_ref, 0).start()
            page_dma(nxt, j + 1, v_buf, v_cache_ref, 1).start()

        page_dma(slot, j, k_buf, k_cache_ref, 0).wait()
        page_dma(slot, j, v_buf, v_cache_ref, 1).wait()

        k = k_buf[slot].astype(jnp.float32)  # (nkv, bs, d)
        v = v_buf[slot].astype(jnp.float32)
        # (nkv, g, d) x (nkv, bs, d) -> (nkv, g, bs), batched over kv heads
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        valid = pos < ctx_len
        if window is not None:
            # mask within the boundary page of the window
            valid &= pos > ctx_len - 1 - window
        s = jnp.where(valid, s, MASK_VALUE)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)  # (nkv, g, bs)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        # (nkv, g, bs) x (nkv, bs, d) -> (nkv, g, d)
        pv = jax.lax.dot_general(
            p, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((nkv, g, 1), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((nkv, g, 1), jnp.float32)
    acc0 = jnp.zeros((nkv, g, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(n_start, n_used, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)
    out_ref[0] = out.reshape(nq, d).astype(out_ref.dtype)


def _prefill_kernel(
    # scalar prefetch
    meta_ref,           # (2,) int32: [layer, q_start]
    block_table_ref,    # (P,) int32 — this sequence's pages
    # array inputs
    q_ref,              # (Tq, nq, d) VMEM — this program's query tile
    k_cache_ref,        # (L, nkv, slots, d) ANY/HBM — head-major
    v_cache_ref,
    # outputs
    out_ref,            # (Tq, nq, d) VMEM
    # scratch
    k_buf,              # (2, nkv, bs, d) VMEM
    v_buf,
    sem,                # DMA sems (2, 2)
    *,
    block_size: int,
    num_pages: int,
    scale: float,
    window: int | None = None,
):
    """Ragged chunked-prefill attention for ONE sequence over the paged
    HBM cache (SURVEY §7 hard-part #1, prefill half).

    Kernel contract: query rows are CONTIGUOUS absolute positions
    q_start + row (the model runner always prefills a contiguous chunk;
    padded tail rows simply read garbage that the runner discards, exactly
    like the XLA path's padded rows). Causality is per-element:
    key_pos <= q_pos, evaluated against the online softmax, so one pass
    over the context pages serves every query row — the per-layer
    (ctx, nkv, d) gathered copy the XLA path materialises is never built
    and each KV byte streams from HBM exactly once per chunk.
    """
    i = pl.program_id(0)
    layer = meta_ref[0]
    q_start = meta_ref[1]
    tq, nq, d = q_ref.shape
    nkv = k_buf.shape[1]
    g = nq // nkv
    bs = block_size

    tile_base = q_start + i * tq
    # pages holding positions [0, tile_base + tq): later tiles see more
    n_used = jnp.minimum(
        (tile_base + tq + bs - 1) // bs, jnp.int32(num_pages)
    )
    # sliding window: the tile's EARLIEST row needs keys down to
    # tile_base - window + 1; pages wholly below that never stream in.
    # n_start < n_used always (a tile's own page is inside its window).
    if window is None:
        n_start = jnp.int32(0)
    else:
        n_start = jnp.maximum(tile_base - window + 1, 0) // bs

    def page_dma(slot, page_idx, buf, cache_ref, which):
        row0 = block_table_ref[page_idx] * bs
        return pltpu.make_async_copy(
            cache_ref.at[layer, :, pl.ds(row0, bs)],
            buf.at[slot],
            sem.at[slot, which],
        )

    s0 = jax.lax.rem(n_start, 2)
    page_dma(s0, n_start, k_buf, k_cache_ref, 0).start()
    page_dma(s0, n_start, v_buf, v_cache_ref, 1).start()

    # (Tq, nq, d) -> (nkv, Tq*g, d): batch kv heads on the MXU; row r of
    # the fused axis belongs to query row r // g
    q = q_ref[...].astype(jnp.float32)
    q = (
        q.reshape(tq, nkv, g, d)
        .transpose(1, 0, 2, 3)
        .reshape(nkv, tq * g, d)
        * scale
    )
    q_pos = tile_base + (
        jax.lax.broadcasted_iota(jnp.int32, (1, tq * g, 1), 1) // g
    )

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)
        nxt = jax.lax.rem(j + 1, 2)

        @pl.when(j + 1 < n_used)
        def _():
            page_dma(nxt, j + 1, k_buf, k_cache_ref, 0).start()
            page_dma(nxt, j + 1, v_buf, v_cache_ref, 1).start()

        page_dma(slot, j, k_buf, k_cache_ref, 0).wait()
        page_dma(slot, j, v_buf, v_cache_ref, 1).wait()

        k = k_buf[slot].astype(jnp.float32)  # (nkv, bs, d)
        v = v_buf[slot].astype(jnp.float32)
        # (nkv, Tq*g, d) x (nkv, bs, d) -> (nkv, Tq*g, bs)
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        k_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, bs), 2
        )
        valid = k_pos <= q_pos
        if window is not None:
            valid &= k_pos > q_pos - window
        s = jnp.where(valid, s, MASK_VALUE)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * corr + pv

    m0 = jnp.full((nkv, tq * g, 1), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((nkv, tq * g, 1), jnp.float32)
    acc0 = jnp.zeros((nkv, tq * g, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(n_start, n_used, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)
    out = (
        out.reshape(nkv, tq, g, d)
        .transpose(1, 0, 2, 3)
        .reshape(tq, nq, d)
    )
    out_ref[...] = out.astype(out_ref.dtype)


def _ragged_kernel(
    # scalar prefetch
    meta_ref,           # (1,) int32: [layer]
    blk_seg_ref,        # (G+1,) int32 — CSR: block i owns segments
                        # [blk_seg[i], blk_seg[i+1])
    seg_meta_ref,       # (SC, 4) int32 — per segment:
                        # [lane, row0_in_block, n_rows, q_pos_of_row0]
    block_tables_ref,   # (S, P) int32 — per-LANE page tables
    # array inputs
    q_ref,              # (TQ, nq, d) VMEM — this block's query rows
    k_cache_ref,        # (L, nkv, slots, d) ANY/HBM — head-major
    v_cache_ref,
    # outputs
    out_ref,            # (TQ, nq, d) VMEM
    # scratch
    k_buf,              # (2, nkv, bs, d) VMEM
    v_buf,
    sem,                # DMA sems (2, 2)
    *,
    block_size: int,
    num_pages: int,
    scale: float,
    window: int | None = None,
    tq: int = RAGGED_TQ,
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
    its first query row. The kernel walks each segment's own pages
    (double-buffered HBM->VMEM DMA, online softmax — the same per-row
    math as the composed _prefill_kernel/_decode_kernel, so outputs
    are bit-identical per row) and row-masks its store, which makes
    decode the degenerate n_rows=1 / q_pos=ctx-1 case of the causal
    prefill body: one kernel, any lane mix, one launch.
    """
    i = pl.program_id(0)
    layer = meta_ref[0]
    nq, d = q_ref.shape[1], q_ref.shape[2]
    nkv = k_buf.shape[1]
    g = nq // nkv
    bs = block_size
    s_lo = blk_seg_ref[i]
    s_hi = blk_seg_ref[i + 1]

    # (TQ, nq, d) -> (nkv, TQ*g, d): batch kv heads on the MXU; fused
    # row r belongs to query row r // g (same packing as the composed
    # prefill kernel, so per-row arithmetic is identical)
    q = q_ref[...].astype(jnp.float32)
    q = (
        q.reshape(tq, nkv, g, d)
        .transpose(1, 0, 2, 3)
        .reshape(nkv, tq * g, d)
        * scale
    )
    row_of = (
        jax.lax.broadcasted_iota(jnp.int32, (1, tq * g, 1), 1) // g
    )  # row index 0..tq-1 of each fused row
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (tq, 1, 1), 0)

    def seg_body(s, _):
        lane = seg_meta_ref[s, 0]
        row0 = seg_meta_ref[s, 1]
        n_rows = seg_meta_ref[s, 2]
        qpos0 = seg_meta_ref[s, 3]
        # pages holding positions [0, qpos0 + n_rows): the segment's
        # LAST owned row attends up to its own position. n_rows == 0
        # (idle slot) walks nothing and stores nothing.
        n_used = jnp.minimum(
            (qpos0 + n_rows + bs - 1) // bs, jnp.int32(num_pages)
        )
        # sliding window: the segment's EARLIEST row needs keys down
        # to qpos0 - window + 1; earlier pages never stream in
        if window is None:
            n_start = jnp.int32(0)
        else:
            n_start = jnp.maximum(qpos0 - window + 1, 0) // bs
        n_start = jnp.minimum(n_start, n_used)

        def page_dma(slot, page_idx, buf, cache_ref, which):
            r0 = block_tables_ref[lane, page_idx] * bs
            return pltpu.make_async_copy(
                cache_ref.at[layer, :, pl.ds(r0, bs)],
                buf.at[slot],
                sem.at[slot, which],
            )

        @pl.when(n_used > n_start)
        def _():
            s0 = jax.lax.rem(n_start, 2)
            page_dma(s0, n_start, k_buf, k_cache_ref, 0).start()
            page_dma(s0, n_start, v_buf, v_cache_ref, 1).start()

        # per-row absolute query positions for THIS segment's causal
        # mask; rows outside [row0, row0+n_rows) compute garbage that
        # the masked store below never writes
        q_pos = qpos0 + (row_of - row0)

        def body(j, carry):
            m, l, acc = carry
            slot = jax.lax.rem(j, 2)
            nxt = jax.lax.rem(j + 1, 2)

            @pl.when(j + 1 < n_used)
            def _():
                page_dma(nxt, j + 1, k_buf, k_cache_ref, 0).start()
                page_dma(nxt, j + 1, v_buf, v_cache_ref, 1).start()

            page_dma(slot, j, k_buf, k_cache_ref, 0).wait()
            page_dma(slot, j, v_buf, v_cache_ref, 1).wait()

            k = k_buf[slot].astype(jnp.float32)  # (nkv, bs, d)
            v = v_buf[slot].astype(jnp.float32)
            s_dots = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # (nkv, TQ*g, bs)
            k_pos = j * bs + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, bs), 2
            )
            valid = k_pos <= q_pos
            if window is not None:
                valid &= k_pos > q_pos - window
            s_dots = jnp.where(valid, s_dots, MASK_VALUE)

            m_new = jnp.maximum(
                m, jnp.max(s_dots, axis=-1, keepdims=True)
            )
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s_dots - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc * corr + pv

        m0 = jnp.full((nkv, tq * g, 1), MASK_VALUE, jnp.float32)
        l0 = jnp.zeros((nkv, tq * g, 1), jnp.float32)
        acc0 = jnp.zeros((nkv, tq * g, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(
            n_start, n_used, body, (m0, l0, acc0)
        )

        out = acc / jnp.maximum(l, 1e-30)
        out = (
            out.reshape(nkv, tq, g, d)
            .transpose(1, 0, 2, 3)
            .reshape(tq, nq, d)
        )
        # row-masked merge: segments of one block write disjoint row
        # ranges sequentially (read-modify-write within the program)
        keep = (row_ids >= row0) & (row_ids < row0 + n_rows)
        out_ref[...] = jnp.where(
            keep, out.astype(out_ref.dtype), out_ref[...]
        )
        return 0

    jax.lax.fori_loop(s_lo, s_hi, seg_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "window"),
)
def ragged_paged_attention(
    q: jax.Array,             # (R, nq, d) — flattened mixed query rows
    k_cache: jax.Array,       # (L, nkv, num_slots, d) — head-major
    v_cache: jax.Array,
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # (S, P) int32 — page table per LANE
    blk_seg: jax.Array,       # (G+1,) int32 — CSR segment offsets,
                              # G = R // RAGGED_TQ
    seg_meta: jax.Array,      # (SC, 4) int32 — [lane, row0, n_rows,
                              # q_pos0] per segment
    *,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """One launch of ragged paged attention over any lane mix.

    The caller packs every lane's query rows back-to-back on the row
    axis (prefill chunks RAGGED_TQ-aligned; decode lanes one row each,
    sharing row blocks) and describes the layout with the CSR segment
    metadata — see _ragged_kernel. Returns (R, nq, d) in q.dtype; rows
    covered by no segment are undefined (callers discard them, the
    same contract as the composed kernels' padded rows)."""
    r, nq, d = q.shape
    nkv = k_cache.shape[1]
    num_pages = block_tables.shape[1]
    n_blocks = blk_seg.shape[0] - 1
    tq = r // n_blocks
    assert tq * n_blocks == r, (
        f"ragged row space {r} must tile into {n_blocks} blocks"
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(
                (tq, nq, d), lambda i, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(
            (tq, nq, d), lambda i, *_: (i, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((2, nkv, block_size, d), k_cache.dtype),
            pltpu.VMEM((2, nkv, block_size, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel,
        block_size=block_size,
        num_pages=num_pages,
        scale=scale,
        window=window,
        tq=tq,
    )
    return pl.pallas_call(
        kernel,
        name="ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, nq, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2**20,
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        blk_seg.astype(jnp.int32),
        seg_meta.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        q,
        k_cache,
        v_cache,
    )


def ragged_paged_attention_tp(
    q: jax.Array,             # (R, nq, d) — heads sharded over tp
    k_cache: jax.Array,       # (L, nkv, num_slots, d) — kv heads sharded
    v_cache: jax.Array,
    layer: jax.Array,
    block_tables: jax.Array,  # (S, P) replicated
    blk_seg: jax.Array,       # (G+1,) replicated
    seg_meta: jax.Array,      # (SC, 4) replicated
    *,
    mesh: jax.sharding.Mesh,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Tensor-parallel ragged paged attention via shard_map (same
    head-congruence argument as paged_decode_attention_tp: GQA groups
    are chip-local, so the kernel body needs no collectives)."""
    tp = _resolve_tp_axis(mesh)
    P = jax.sharding.PartitionSpec
    body = functools.partial(
        ragged_paged_attention,
        block_size=block_size, scale=scale, interpret=interpret,
        window=window,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, tp, None),
            P(None, tp, None, None),
            P(None, tp, None, None),
            P(),
            P(None, None),
            P(None),
            P(None, None),
        ),
        out_specs=P(None, tp, None),
        check_vma=False,
    )(q, k_cache, v_cache, layer, block_tables, blk_seg, seg_meta)


def _prefill_q_tile(t: int, nq: int, d: int) -> int:
    """Largest pow2 query tile whose f32 q + accumulator fit a ~4 MiB VMEM
    budget each (v5e VMEM is 128 MiB but leave room for double-buffered KV
    pages, the output tile, and Mosaic's own spills). One tile per chunk
    (the common case) means the context streams from HBM exactly once."""
    budget = 4 * 2**20
    per_row = nq * d * 4
    tile = 1 << max(3, (budget // per_row).bit_length() - 1)
    while t % tile:
        tile //= 2
    return max(1, min(tile, t))


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "window"),
)
def paged_prefill_attention(
    q: jax.Array,            # (t, nq, d) — one chunk, contiguous positions
    k_cache: jax.Array,      # (L, nkv, num_slots, d) — head-major
    v_cache: jax.Array,
    layer: jax.Array,        # scalar int32
    block_table: jax.Array,  # (P,) int32 — pages of THIS sequence
    q_start: jax.Array,      # scalar int32 — absolute position of q row 0
    *,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Chunked-prefill paged attention for one sequence. -> (t, nq, d)."""
    t, nq, d = q.shape
    nkv = k_cache.shape[1]
    num_pages = block_table.shape[0]
    tq = _prefill_q_tile(t, nq, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t // tq,),
        in_specs=[
            pl.BlockSpec(
                (tq, nq, d), lambda i, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(
            (tq, nq, d), lambda i, *_: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((2, nkv, block_size, d), k_cache.dtype),
            pltpu.VMEM((2, nkv, block_size, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        block_size=block_size,
        num_pages=num_pages,
        scale=scale,
        window=window,
    )
    meta = jnp.stack(
        [jnp.asarray(layer, jnp.int32), jnp.asarray(q_start, jnp.int32)]
    )
    return pl.pallas_call(
        kernel,
        name="paged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, nq, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # large f32 q/accumulator tiles exceed the default 16 MiB
            # scoped-vmem stack; v5e has 128 MiB — allow half of it
            vmem_limit_bytes=64 * 2**20,
        ),
    )(
        meta,
        block_table.astype(jnp.int32),
        q,
        k_cache,
        v_cache,
    )


def paged_prefill_attention_tp(
    q: jax.Array,            # (t, nq, d) — heads sharded over tp
    k_cache: jax.Array,      # (L, nkv, num_slots, d) — head-major — kv heads sharded
    v_cache: jax.Array,
    layer: jax.Array,
    block_table: jax.Array,  # (P,) replicated
    q_start: jax.Array,      # scalar replicated
    *,
    mesh: jax.sharding.Mesh,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Tensor-parallel chunked-prefill paged attention via shard_map (same
    head-congruence argument as paged_decode_attention_tp: GQA groups are
    chip-local, so the kernel body needs no collectives)."""
    tp = _resolve_tp_axis(mesh)
    P = jax.sharding.PartitionSpec
    body = functools.partial(
        paged_prefill_attention,
        block_size=block_size, scale=scale, interpret=interpret,
        window=window,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, tp, None),
            P(None, tp, None, None),
            P(None, tp, None, None),
            P(),
            P(None),
            P(),
        ),
        out_specs=P(None, tp, None),
        check_vma=False,
    )(q, k_cache, v_cache, layer, block_table, q_start)


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


def paged_decode_attention_tp(
    q: jax.Array,             # (b, nq, d) — heads sharded over tp
    k_cache: jax.Array,       # (L, nkv, num_slots, d) — kv heads sharded
    v_cache: jax.Array,
    layer: jax.Array,
    block_tables: jax.Array,  # (b, P) replicated
    context_lens: jax.Array,  # (b,) replicated
    *,
    mesh: jax.sharding.Mesh,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Tensor-parallel paged decode attention via shard_map.

    The KV cache is sharded over the kv-head axis and q heads are split
    congruently (parallel/sharding.py), so each chip's GQA groups are fully
    local: the kernel body needs zero cross-chip communication — the psum
    stays where GSPMD already puts it, after the wo row-parallel projection.
    shard_map hands each chip its (b, nq/tp, d) query slice and
    (L, nkv/tp, slots, d) cache shard; block tables and context lens ride
    replicated. check_vma=False because pallas_call does not participate in
    varying-axes inference.
    """
    tp = _resolve_tp_axis(mesh)
    P = jax.sharding.PartitionSpec
    body = functools.partial(
        paged_decode_attention,
        block_size=block_size, scale=scale, interpret=interpret,
        window=window,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, tp, None),
            P(None, tp, None, None),
            P(None, tp, None, None),
            P(),
            P(None, None),
            P(None),
        ),
        out_specs=P(None, tp, None),
        check_vma=False,
    )(q, k_cache, v_cache, layer, block_tables, context_lens)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "interpret", "window"),
)
def paged_decode_attention(
    q: jax.Array,             # (b, nq, d)
    k_cache: jax.Array,       # (L, nkv, num_slots, d) — head-major
    v_cache: jax.Array,
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # (b, P) int32 — page ids per sequence
    context_lens: jax.Array,  # (b,) int32
    *,
    block_size: int,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """One decode step of paged attention. Returns (b, nq, d) in q.dtype."""
    b, nq, d = q.shape
    nkv = k_cache.shape[1]
    num_pages = block_tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec(
                (1, nq, d), lambda i, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(
            (1, nq, d), lambda i, *_: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((2, nkv, block_size, d), k_cache.dtype),
            pltpu.VMEM((2, nkv, block_size, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        block_size=block_size,
        num_pages=num_pages,
        scale=scale,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # large f32 q/accumulator tiles exceed the default 16 MiB
            # scoped-vmem stack; v5e has 128 MiB — allow half of it
            vmem_limit_bytes=64 * 2**20,
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        q,
        k_cache,
        v_cache,
    )

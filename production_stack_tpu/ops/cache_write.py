"""A layer's new K and V rows into the head-major cache: one operation.

`write_kv` is the one cache write of `models/llama.py::decoder_layer`
and `models/layer_groups.py::_layer`. Where the Pallas walk runs
(`kernel=True`: the runner's `attention_impl` resolved to `pallas`, on
one device) it is ONE Mosaic kernel a layer for both arrays; elsewhere
(the XLA attention path: CPU, head_dim 64, pipeline parallel; a
tensor-parallel mesh) it is one XLA scatter a kv head and array.

Why not one XLA scatter `[l, :, write_slots]`: it made XLA prefer a
slot-major cache layout inside the layer scan and insert a full-cache
copy a step (2 x 3.8 GiB on a 3B model, out of memory), where per-head
scatters over one (slots, d) plane keep the row-major layout the walk's
custom calls are constrained to (`ModelRunner._enter_caches` pins it).
And why not the per-head scatters on the chip: each costs its launch
and its rows again for every head (16 heads, run 4 passes a token: 1,536
scatters a decode step, a sixth of the device's busy time; PERF.md,
Findings PR 38 and 39), whatever the rows hold.

The kernel moves TILES. A plane is stored in (16, 128) bf16 tiles over
(slots, d) — `_tiling` for other widths — and a row is half a
sublane, so no DMA can write one. For every tile a live row falls in,
all kv heads' copies of it come into VMEM in one strided DMA, the rows
are stored at `slot % 16`, and the tile goes back; the cache operands
are aliased to the outputs, the slots ride scalar-prefetch SMEM as the
walk's tables do. Rows that share a tile (a prefill chunk's consecutive
slots) are applied to ONE copy of it: `plan_rows` names, for each row, the
first row of its row block with the same tile, whose buffer the tile
lives in; row blocks run one after the other, each awaiting its writes.
A row whose slot is 0, the null block's trash slot that padding rows
and lanes without a sequence write, is skipped — nothing reads that row
with weight — so the cost follows the rows that hold a token: the
tiles' DMAs, two round trips a row block, and some tens of cycles a
row, not rows x kv heads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of a row block: one grid step's tiles are all in flight at once,
# so its buffers hold a tile per row (the worst case: decode rows, each
# in a sequence of its own). 64 rows x 16 heads x a 4 KiB tile = 4 MiB.
_ROW_BLOCK = 64


def _tiling(kc: jax.Array, n: int) -> tuple[int, int]:
    """(slots a DMA moves whole, rows of a row block) for n rows into
    `kc`: the sublane tile of the dtype (8 rows of 32 bits: 16 of bf16),
    or what of it divides a cache whose slot count is no multiple (tiny
    test caches; the chip's compiler refuses a slice that is not whole
    tiles)."""
    tile = math.gcd(8 * 4 // jnp.dtype(kc.dtype).itemsize, kc.shape[2])
    return tile, min(n, _ROW_BLOCK)


def plan_rows(write_slots: jax.Array, kc: jax.Array) -> jax.Array:
    """(5, n) int32, a column a row, for `write_kv` in place of the n
    slots: [the slot | the row's tile if it is the FIRST live row of its
    row block in that tile, else -1 | the row of the block whose buffer
    holds its tile, -1 for a trash row | slot % tile rows | first rows
    in the block]. A forward makes it once, before its layer scan: made
    inside, XLA leaves part of it in the loop's body (three small
    operations a layer, 3 us of a 9 us write; PERF.md, Findings PR 39)."""
    n = write_slots.shape[0]
    tr, rb = _tiling(kc, n)
    blocks = -(-n // rb)
    # rows past n, where the last block is not whole, write nothing
    slots = jnp.pad(write_slots, (0, blocks * rb - n))
    live = slots > 0
    # a trash row shares no tile with any row
    tile = jnp.where(live, slots // tr, -1 - jnp.arange(blocks * rb))
    t = tile.reshape(blocks, rb)
    first = jnp.argmax(t[:, :, None] == t[:, None, :], axis=-1)
    leads = live.reshape(blocks, rb) & (first == jnp.arange(rb))
    count = jnp.broadcast_to(
        jnp.sum(leads, axis=-1, keepdims=True), leads.shape)
    return jnp.stack([
        slots,
        jnp.where(leads, t, -1).reshape(-1),
        jnp.where(live, first.reshape(-1), -1),
        slots % tr,
        count.reshape(-1),
    ]).astype(jnp.int32)


def _write_kernel(
    # scalar prefetch
    layer_ref,          # (1,) int32
    plan_ref,           # (5, rows) int32 (`plan_rows`)
    # array inputs
    k_ref,              # (rb, nkv, d_k) VMEM — this block's new rows
    v_ref,              # (rb, nkv, d_v)
    _kc_in, _vc_in,     # the caches, aliased to the outputs below
    # outputs
    kc_ref,             # (L, nkv, slots, d_k) HBM
    vc_ref,
    # scratch
    k_buf,              # (rb, nkv, tile_rows, d_k) VMEM
    v_buf,
    sem,                # DMA (2,)
):
    rb, _, tr, _ = k_buf.shape
    base = pl.program_id(0) * rb
    layer = layer_ref[0]
    halves = ((k_ref, kc_ref, k_buf, sem.at[0]),
              (v_ref, vc_ref, v_buf, sem.at[1]))

    def tile_copy(cache, buf, s, r, t, out: bool):
        src = cache.at[layer, :, pl.ds(pl.multiple_of(t * tr, tr), tr)]
        dst = buf.at[r]
        return pltpu.make_async_copy(
            *((dst, src) if out else (src, dst)), s)

    def move(out: bool):
        """Start every first row's tile copies, then await them all."""
        def start(r, _):
            t = plan_ref[1, base + r]

            @pl.when(t >= 0)
            def _():
                for _, cache, buf, s in halves:
                    tile_copy(cache, buf, s, r, t, out).start()
            return 0

        def wait(_, carry):
            for _, cache, buf, s in halves:
                tile_copy(cache, buf, s, 0, 0, out).wait()
            return carry

        jax.lax.fori_loop(0, rb, start, 0)
        jax.lax.fori_loop(0, plan_ref[4, base], wait, 0)

    def store(r, _):
        at = plan_ref[2, base + r]

        @pl.when(at >= 0)
        def _():
            here = jax.lax.broadcasted_iota(
                jnp.int32, (1, tr, 1), 1) == plan_ref[3, base + r]
            for rows, _, buf, _ in halves:
                # (nkv, d) -> a row a head, over the tile's sublanes
                # (32-bit for the relayout, as the walk's q rows)
                row = rows[r].astype(jnp.float32)[:, None, :]
                buf[at] = jnp.where(here, row.astype(buf.dtype), buf[at])
        return 0

    move(out=False)
    jax.lax.fori_loop(0, rb, store, 0)
    move(out=True)


def _write_tiles(kc, vc, l, plan, k, v, interpret):
    n, nkv, dk = k.shape
    dv = v.shape[-1]
    tr, rb = _tiling(kc, n)

    def rows(width):
        return pl.BlockSpec((rb, nkv, width), lambda i, *_: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    cache = pl.BlockSpec(memory_space=pltpu.HBM)
    scalars = (jnp.reshape(l, 1).astype(jnp.int32), plan)
    return pl.pallas_call(
        _write_kernel,
        name="kv_cache_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(plan.shape[1] // rb,),
            in_specs=[rows(dk), rows(dv), cache, cache],
            out_specs=[cache, cache],
            scratch_shapes=[
                pltpu.VMEM((rb, nkv, tr, dk), kc.dtype),
                pltpu.VMEM((rb, nkv, tr, dv), vc.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(kc.shape, kc.dtype),
                   jax.ShapeDtypeStruct(vc.shape, vc.dtype)],
        # operand indices count the scalars: the caches come 4th and 5th
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2**20,
        ),
    )(*scalars, k, v, kc, vc)


def write_kv(
    kc: jax.Array,           # (L, nkv, slots, d_k stored) — head-major
    vc: jax.Array,           # (L, nkv, slots, d_v)
    l: jax.Array,            # scalar int32 layer index into kc / vc
    write_slots: jax.Array,  # (n,) int32; slot 0 is the trash slot.
                             # Or `plan_rows` of them (a layer scan)
    k: jax.Array,            # (n, nkv, d_k) — the rows' keys, roped
    v: jax.Array,            # (n, nkv, d_v)
    *,
    kernel: bool = False,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """kc and vc with the n rows' keys and values at `write_slots` of
    layer `l`, every kv head. A cache that stores K wider than d_k
    (`ModelRunner._k_store_dim`: zero lanes up to the kernel's 128-lane
    tile) gets the pad written with the row. With `kernel` a row at
    slot 0 is not written at all; every other slot holds bit for bit
    what the scatters write."""
    k, v = k.astype(kc.dtype), v.astype(vc.dtype)
    if kc.shape[-1] > k.shape[-1]:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kc.shape[-1] - k.shape[-1])))
    if kernel:
        if write_slots.ndim == 1:
            write_slots = plan_rows(write_slots, kc)
        return _write_tiles(kc, vc, l, write_slots, k, v, interpret)
    if write_slots.ndim == 2:
        write_slots = write_slots[0, :k.shape[0]]
    for head in range(k.shape[1]):
        kc = kc.at[l, head, write_slots].set(k[:, head])
        vc = vc.at[l, head, write_slots].set(v[:, head])
    return kc, vc

"""The ONE page walk with what a layer-group model asks of it (interpret
mode on the CPU) against ops/attention.py: K heads of width 192 beside V
heads of width 128, a learned sink per q head as the online softmax's
start state, the window, both tile heights (a one-row segment and a
fused tile), and the ragged kernel bit-identical per row to the composed
ones. The dense models' shapes are tests/test_pallas_attention.py's."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import attention as xla_attn
from production_stack_tpu.ops import pallas_attention as pa

BS, DK, DV = 16, 192, 128
SCALE = DK ** -0.5


def case(seed, *, lanes=3, pages=20, nkv=2, g=4, dk=DK, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    blocks = 1 + lanes * pages
    kc = rng.randn(2, nkv, blocks * BS, dk).astype(np.float32)
    vc = rng.randn(2, nkv, blocks * BS, DV).astype(np.float32)
    tables = rng.permutation(np.arange(1, blocks)).reshape(lanes, pages)
    sink = rng.randn(nkv * g).astype(np.float32)
    return (jnp.asarray(kc, dtype), jnp.asarray(vc, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(sink), rng)


def gathered(kc, vc, table):
    slots = xla_attn.block_table_slots(table, BS)
    return kc[1][:, slots].swapaxes(0, 1), vc[1][:, slots].swapaxes(0, 1)


@pytest.mark.parametrize("g", [4, 8])  # 4 pads the one-row tile to 8 rows
@pytest.mark.parametrize("window, with_sink", [
    (None, False), (None, True), (40, True), (40, False)])
def test_decode_rows_dk192_dv128(g, window, with_sink):
    kc, vc, tables, sink, rng = case(g, g=g)
    nq = 2 * g
    lens = jnp.asarray([1, 37, 20 * BS], jnp.int32)
    q = jnp.asarray(rng.randn(3, nq, DK), jnp.float32)
    s = sink if with_sink else None
    out = pa.paged_decode_attention(
        q, kc, vc, jnp.int32(1), tables, lens, s, block_size=BS,
        scale=SCALE, interpret=True, window=window)
    assert out.shape == (3, nq, DV)
    for i in range(3):
        k_ctx, v_ctx = gathered(kc, vc, tables[i])
        ref = xla_attn.context_attention_decode(
            q[i:i + 1], k_ctx[None], v_ctx[None], lens[i:i + 1], SCALE,
            window=window, sink=s)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 40])
def test_prefill_tile_with_sink(window):
    kc, vc, tables, sink, rng = case(7)
    q = jnp.asarray(rng.randn(16, 8, DK), jnp.float32)
    start = 150
    out = pa.paged_prefill_attention(
        q, kc, vc, jnp.int32(1), tables[0], jnp.int32(start), sink,
        block_size=BS, scale=SCALE, interpret=True, window=window)
    k_ctx, v_ctx = gathered(kc, vc, tables[0])
    ref = xla_attn.context_attention_prefill(
        q, k_ctx, v_ctx, start + jnp.arange(16), jnp.int32(start + 16),
        SCALE, window=window, sink=sink)
    assert out.shape == (16, 8, DV)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_rows_are_bit_identical_to_the_composed_kernels():
    """A prefill chunk of two tiles beside two decode rows in one
    launch: each row equals, bit for bit, what the composed kernel of
    its kind gives (sink and window on)."""
    kc, vc, tables, sink, rng = case(9)
    tq, window = pa.RAGGED_TQ, 40
    q = jnp.asarray(rng.randn(3 * tq, 8, DK), jnp.float32)
    start, lens = 90, jnp.asarray([61, 300], jnp.int32)
    blk_seg = jnp.asarray([0, 1, 2, 4], jnp.int32)
    seg_meta = jnp.asarray([
        [0, 0, tq, start], [0, 0, tq, start + tq],
        [1, 0, 1, 60], [2, 1, 1, 299]], jnp.int32)
    kw = dict(block_size=BS, scale=SCALE, interpret=True, window=window)
    out = pa.ragged_paged_attention(
        q, kc, vc, jnp.int32(1), tables, blk_seg, seg_meta, sink, **kw)
    pre = pa.paged_prefill_attention(
        q[:2 * tq], kc, vc, jnp.int32(1), tables[0], jnp.int32(start),
        sink, **kw)
    dec = pa.paged_decode_attention(
        q[2 * tq:2 * tq + 2], kc, vc, jnp.int32(1), tables[1:], lens,
        sink, **kw)
    np.testing.assert_array_equal(np.asarray(out[:2 * tq]),
                                  np.asarray(pre))
    np.testing.assert_array_equal(np.asarray(out[2 * tq:2 * tq + 2]),
                                  np.asarray(dec))


@pytest.mark.parametrize("window", [None, 40])
def test_zero_row_segments_at_k256_v128_with_a_sink(window):
    """A layer-group model's decode rows as the runner ships them since
    PR 29: K stored at 256 lanes (192 + zeros) beside V at 128, a sink,
    the window; lanes that hold no sequence are zero-row segments among
    live ones. Live rows keep the bits of the all-ones packing (idle
    lanes as one-row segments over one key of the null page), idle rows
    are exactly zero where the output tile held NaN (interpret mode
    fills it with NaN: the rows no segment names show it), and a sink
    does not leak into a zero row (a walk of no keys with a sink would
    give 0 / 1, the kernel does not even start it)."""
    kc, vc, tables, sink, rng = case(13, lanes=6, dtype=jnp.bfloat16)
    pad = ((0, 0), (0, 0), (0, 0), (0, 64))
    kc = jnp.pad(kc, pad)
    ctx = np.asarray([130, 0, 37, 0, 20 * BS, 0], np.int32)
    live = ctx > 0
    tables = jnp.where(jnp.asarray(live)[:, None], tables, 0)
    q = jnp.pad(jnp.asarray(rng.randn(8, 8, DK), jnp.bfloat16), pad[1:])
    lanes = np.arange(6, dtype=np.int32)
    blk_seg = jnp.asarray([0, 6], jnp.int32)

    def run(n_rows, lens):
        seg = np.stack([lanes, lanes, n_rows, lens - 1], axis=1)
        return np.asarray(pa.ragged_paged_attention(
            q, kc, vc, jnp.int32(1), tables, blk_seg, jnp.asarray(seg),
            sink, block_size=BS, scale=SCALE, interpret=True,
            window=window).astype(jnp.float32))

    ones = run(np.ones(6, np.int32), np.maximum(ctx, 1))
    out = run(live.astype(np.int32), ctx)
    assert out.shape == (8, 8, DV)
    np.testing.assert_array_equal(out[:6][live], ones[:6][live])
    assert not out[:6][~live].any()
    assert np.isnan(out[6:]).all(), "the tile was not poisoned"
    dec = np.asarray(pa.paged_decode_attention(
        q[:6], kc, vc, jnp.int32(1), tables, jnp.asarray(ctx), sink,
        block_size=BS, scale=SCALE, interpret=True,
        window=window).astype(jnp.float32))
    np.testing.assert_array_equal(out[:6], dec)


def test_k_stored_at_256_lanes_gives_the_same_rows():
    """What the runner does on the chip: K rows stored with zero lanes
    up to the next 128 (192 -> 256) and q padded to match."""
    kc, vc, tables, sink, rng = case(11, dtype=jnp.bfloat16)
    lens = jnp.asarray([130, 37, 20 * BS], jnp.int32)
    q = jnp.asarray(rng.randn(3, 8, DK), jnp.bfloat16)
    kw = dict(block_size=BS, scale=SCALE, interpret=True, window=40)
    out = pa.paged_decode_attention(
        q, kc, vc, jnp.int32(1), tables, lens, sink, **kw)
    pad = ((0, 0), (0, 0), (0, 0), (0, 64))
    wide = pa.paged_decode_attention(
        jnp.pad(q, pad[1:]), jnp.pad(kc, pad), vc, jnp.int32(1), tables,
        lens, sink, **kw)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(wide, np.float32))
    k_ctx, v_ctx = gathered(kc, vc, tables[0])
    ref = xla_attn.context_attention_decode(
        q[:1], k_ctx[None], v_ctx[None], lens[:1], SCALE, window=40,
        sink=sink)
    np.testing.assert_allclose(
        np.asarray(out[0], np.float32), np.asarray(ref[0], np.float32),
        rtol=2e-2, atol=2e-2)


def test_dense_shapes_size_their_kv_block_as_before():
    # one head width: the budget is what PR 25 measured it at
    for nkv, want in ((8, 128), (4, 256), (2, 512)):
        assert pa._kv_block_pages(nkv, 128, 2, 32) * 32 == want
    # K at 256 lanes beside V at 128, bf16: 128 keys for 8 and 4 kv heads
    assert pa._kv_block_pages(8, 256, 2, 32, 128) * 32 == 128
    assert pa._kv_block_pages(4, 256, 2, 32, 128) * 32 == 128


# -- the latent kind: one cached row a token, key and value alike ------------
LAT, ROT, STORED = 512, 64, 640


def latent_case(seed, *, lanes=4, pages=12, nq=8, dtype=jnp.float32):
    """A latent cache as the runner stores it on the chip: rows of 512
    latent + 64 rotary dims at 640 lanes (zeros behind), no V array."""
    rng = np.random.RandomState(seed)
    blocks = 1 + lanes * pages
    kc = np.zeros((2, 1, blocks * BS, STORED), np.float32)
    kc[..., :LAT + ROT] = rng.randn(2, 1, blocks * BS, LAT + ROT)
    tables = rng.permutation(np.arange(1, blocks)).reshape(lanes, pages)
    q = np.zeros((3 * pa.RAGGED_TQ, nq, STORED), np.float32)
    q[..., :LAT + ROT] = rng.randn(3 * pa.RAGGED_TQ, nq, LAT + ROT)
    return (jnp.asarray(kc, dtype), jnp.asarray(tables, jnp.int32),
            jnp.asarray(q, dtype))


def test_latent_rows_are_keys_and_their_first_lanes_values():
    """Decode rows and a prefill tile of the latent kind against
    ops/attention.py given the same rows as K and their first 512 lanes
    as V: what `latent_v` means."""
    kc, tables, q = latent_case(21)
    kw = dict(block_size=BS, scale=0.07, interpret=True, latent_v=LAT)
    lens = jnp.asarray([1, 37, 12 * BS, 100], jnp.int32)
    out = pa.paged_decode_attention(
        q[:4], kc, None, jnp.int32(1), tables, lens, **kw)
    assert out.shape == (4, 8, LAT)
    for i in range(4):
        slots = xla_attn.block_table_slots(tables[i], BS)
        k_ctx = kc[1][:, slots].swapaxes(0, 1)
        ref = xla_attn.context_attention_decode(
            q[i:i + 1], k_ctx[None], k_ctx[None, ..., :LAT],
            lens[i:i + 1], 0.07)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   rtol=2e-5, atol=2e-5)
    start = 70
    pre = pa.paged_prefill_attention(
        q[:16], kc, None, jnp.int32(1), tables[0], jnp.int32(start), **kw)
    slots = xla_attn.block_table_slots(tables[0], BS)
    k_ctx = kc[1][:, slots].swapaxes(0, 1)
    ref = xla_attn.context_attention_prefill(
        q[:16], k_ctx, k_ctx[..., :LAT], start + jnp.arange(16),
        jnp.int32(start + 16), 0.07)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_latent_ragged_rows_equal_the_composed_kernels_bit_for_bit():
    """The latent kind in the ONE launch: a two-tile prefill chunk
    beside three decode lanes, one of which holds no sequence (a
    zero-row segment: exact zeros, no copy started), bf16 as on the
    chip. Each row has the bits of the composed kernel of its kind."""
    kc, tables, q = latent_case(23, dtype=jnp.bfloat16)
    tq = pa.RAGGED_TQ
    start = 90
    ctx = np.asarray([61, 0, 12 * BS], np.int32)
    blk_seg = jnp.asarray([0, 1, 2, 5], jnp.int32)
    seg_meta = jnp.asarray([
        [0, 0, tq, start], [0, 0, tq, start + tq],
        [1, 0, 1, 60], [2, 1, 0, -1], [3, 2, 1, 12 * BS - 1]], jnp.int32)
    kw = dict(block_size=BS, scale=0.07, interpret=True, latent_v=LAT)
    out = pa.ragged_paged_attention(
        q, kc, None, jnp.int32(1), tables, blk_seg, seg_meta, **kw)
    assert out.shape == (3 * tq, 8, LAT)
    pre = pa.paged_prefill_attention(
        q[:2 * tq], kc, None, jnp.int32(1), tables[0], jnp.int32(start),
        **kw)
    dec = pa.paged_decode_attention(
        q[2 * tq:2 * tq + 3], kc, None, jnp.int32(1), tables[1:],
        jnp.asarray(ctx), **kw)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    np.testing.assert_array_equal(f32(out[:2 * tq]), f32(pre))
    np.testing.assert_array_equal(f32(out[2 * tq:2 * tq + 3]), f32(dec))
    assert not f32(out[2 * tq + 1]).any()


def test_a_latent_kind_sizes_its_kv_block_from_one_buffer():
    # 640 lanes of bf16, one ring: 512 keys (a block of 640 KiB)
    assert pa._kv_block_pages(1, 640, 2, 32, 0) * 32 == 512
    with pytest.raises(AssertionError):
        kc, tables, q = latent_case(25)
        pa.paged_decode_attention(
            q[:4], kc, kc, jnp.int32(1), tables,
            jnp.ones((4,), jnp.int32), block_size=BS, scale=1.0,
            interpret=True, latent_v=LAT)

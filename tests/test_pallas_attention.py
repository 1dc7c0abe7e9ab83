"""Parity tests: Pallas paged decode attention (interpret mode on CPU) vs
the XLA gather reference in ops/attention.py. The kernel itself runs
compiled only on TPU; interpret mode executes the same program logic so
masking/online-softmax/block-table indexing are fully covered here."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from production_stack_tpu.ops import attention as xla_attn
from production_stack_tpu.ops import pallas_attention as pa
from production_stack_tpu.ops.pallas_attention import paged_decode_attention


def make_case(seed, b=4, layers=2, pages_per_seq=4, bs=8, nkv=2, g=2, d=128,
              dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    nq = nkv * g
    num_blocks = 1 + b * pages_per_seq  # block 0 is the null/trash block
    num_slots = num_blocks * bs
    k_cache = rng.randn(layers, nkv, num_slots, d).astype(np.float32)
    v_cache = rng.randn(layers, nkv, num_slots, d).astype(np.float32)
    q = rng.randn(b, nq, d).astype(np.float32)
    # each sequence owns `pages_per_seq` distinct pages, shuffled order
    all_pages = rng.permutation(np.arange(1, num_blocks))
    block_tables = all_pages[: b * pages_per_seq].reshape(b, pages_per_seq)
    context_lens = rng.randint(1, pages_per_seq * bs + 1, size=b)
    return (
        jnp.asarray(q, dtype),
        jnp.asarray(k_cache, dtype),
        jnp.asarray(v_cache, dtype),
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(context_lens, jnp.int32),
    )


def reference(q, k_cache, v_cache, layer, block_tables, context_lens, bs,
              scale, window=None):
    slots = xla_attn.block_table_slots(block_tables, bs)  # (b, P*bs)
    k_ctx = k_cache[layer][:, slots].transpose(1, 2, 0, 3)  # (b,c,nkv,d)
    v_ctx = v_cache[layer][:, slots].transpose(1, 2, 0, 3)
    return xla_attn.context_attention_decode(
        q, k_ctx, v_ctx, context_lens, scale, window=window
    )


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("layer", [0, 1])
def test_parity_vs_xla(seed, layer):
    q, kc, vc, bt, ctx = make_case(seed)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out_p = paged_decode_attention(
        q, kc, vc, jnp.int32(layer), bt, ctx,
        block_size=8, scale=scale, interpret=True,
    )
    out_r = reference(q, kc, vc, layer, bt, ctx, 8, scale)
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


def test_single_token_context():
    q, kc, vc, bt, ctx = make_case(7)
    ctx = jnp.ones_like(ctx)  # only position 0 valid per sequence
    scale = 0.125
    out_p = paged_decode_attention(
        q, kc, vc, jnp.int32(0), bt, ctx,
        block_size=8, scale=scale, interpret=True,
    )
    out_r = reference(q, kc, vc, 0, bt, ctx, 8, scale)
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


def test_full_pages_and_gqa_groups():
    q, kc, vc, bt, ctx = make_case(3, b=2, pages_per_seq=3, nkv=1, g=8)
    ctx = jnp.full_like(ctx, 3 * 8)  # every page fully used
    scale = 0.1
    out_p = paged_decode_attention(
        q, kc, vc, jnp.int32(1), bt, ctx,
        block_size=8, scale=scale, interpret=True,
    )
    out_r = reference(q, kc, vc, 1, bt, ctx, 8, scale)
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


def test_bfloat16_cache():
    q, kc, vc, bt, ctx = make_case(5, dtype=jnp.bfloat16, bs=16)
    scale = 0.125
    out_p = paged_decode_attention(
        q, kc, vc, jnp.int32(0), bt, ctx,
        block_size=16, scale=scale, interpret=True,
    )
    out_r = reference(q, kc, vc, 0, bt, ctx, 16, scale)
    np.testing.assert_allclose(
        np.asarray(out_p, np.float32), np.asarray(out_r, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_engine_decode_parity_pallas_vs_xla():
    """Whole-engine greedy decode must be identical under both attention
    impls (pallas runs in interpret mode on CPU)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams

    kw = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=32,
        max_num_seqs=2, max_prefill_chunk=32,
    )
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    prompts = ["hello pallas attention", "another prompt here"]
    eng_x = LLMEngine(EngineConfig(attention_impl="xla", **kw))
    out_x = [o.token_ids for o in eng_x.generate(prompts, sp)]
    eng_p = LLMEngine(EngineConfig(attention_impl="pallas", **kw))
    assert eng_p.runner.attention_impl == "pallas"
    out_p = [o.token_ids for o in eng_p.generate(prompts, sp)]
    assert out_p == out_x


# ---- ragged prefill kernel ------------------------------------------------

def make_prefill_case(seed, t=16, prefix_pages=3, bs=8, nkv=2, g=2, d=128,
                      dtype=jnp.float32):
    """One sequence mid-prefill: `prefix_pages` pages already hold
    positions [0, q_start); the current chunk of t tokens at positions
    [q_start, q_start + t) has already been written into the cache (the
    model writes K/V before attention), spanning further pages."""
    rng = np.random.RandomState(seed)
    nq = nkv * g
    q_start = prefix_pages * bs - 3  # chunk starts mid-page
    total_len = q_start + t
    num_real_pages = -(-total_len // bs)
    num_pages = num_real_pages + 2  # padded table tail -> null page 0
    num_blocks = 1 + num_real_pages
    num_slots = num_blocks * bs
    k_cache = rng.randn(2, nkv, num_slots, d).astype(np.float32)
    v_cache = rng.randn(2, nkv, num_slots, d).astype(np.float32)
    q = rng.randn(t, nq, d).astype(np.float32)
    table = np.zeros((num_pages,), np.int32)
    table[:num_real_pages] = rng.permutation(
        np.arange(1, num_blocks)
    )[:num_real_pages]
    return (
        jnp.asarray(q, dtype), jnp.asarray(k_cache, dtype),
        jnp.asarray(v_cache, dtype), jnp.asarray(table, jnp.int32),
        q_start, total_len,
    )


def prefill_reference(q, kc, vc, layer, table, q_start, total_len, bs,
                      scale):
    slots = xla_attn.block_table_slots(table, bs)  # (P*bs,)
    k_ctx = kc[layer][:, slots].transpose(1, 0, 2)  # (c, nkv, d)
    v_ctx = vc[layer][:, slots].transpose(1, 0, 2)
    t = q.shape[0]
    q_positions = jnp.arange(q_start, q_start + t)
    return xla_attn.context_attention_prefill(
        q, k_ctx, v_ctx, q_positions, jnp.int32(total_len), scale
    )


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("layer", [0, 1])
def test_prefill_parity_vs_xla(seed, layer):
    from production_stack_tpu.ops.pallas_attention import (
        paged_prefill_attention,
    )

    q, kc, vc, table, q_start, total_len = make_prefill_case(seed)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out_p = paged_prefill_attention(
        q, kc, vc, jnp.int32(layer), table, jnp.int32(q_start),
        block_size=8, scale=scale, interpret=True,
    )
    out_r = prefill_reference(
        q, kc, vc, layer, table, q_start, total_len, 8, scale
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


def test_prefill_parity_multi_tile():
    """Chunk longer than one query tile: force tq < t so the tile loop and
    per-tile page horizons are exercised."""
    from production_stack_tpu.ops import pallas_attention

    q, kc, vc, table, q_start, total_len = make_prefill_case(
        2, t=32, prefix_pages=2, nkv=1, g=2, d=128
    )
    scale = 1.0 / np.sqrt(q.shape[-1])
    orig = pallas_attention._prefill_q_tile
    pallas_attention._prefill_q_tile = lambda t, nq, d: 8
    try:
        out_p = pallas_attention.paged_prefill_attention(
            q, kc, vc, jnp.int32(0), table, jnp.int32(q_start),
            block_size=8, scale=scale, interpret=True,
        )
    finally:
        pallas_attention._prefill_q_tile = orig
    out_r = prefill_reference(
        q, kc, vc, 0, table, q_start, total_len, 8, scale
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


def test_prefill_tp_shard_map_parity():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from production_stack_tpu.ops.pallas_attention import (
        paged_prefill_attention_tp,
    )
    from production_stack_tpu.parallel.sharding import make_mesh

    q, kc, vc, table, q_start, total_len = make_prefill_case(
        3, nkv=8, g=2, d=128
    )
    scale = 1.0 / np.sqrt(q.shape[-1])
    mesh = make_mesh(8)
    kc_sh = jax.device_put(kc, NamedSharding(mesh, P(None, None, "tp", None)))
    vc_sh = jax.device_put(vc, NamedSharding(mesh, P(None, None, "tp", None)))
    q_sh = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
    out_p = paged_prefill_attention_tp(
        q_sh, kc_sh, vc_sh, jnp.int32(1), table, jnp.int32(q_start),
        mesh=mesh, block_size=8, scale=scale, interpret=True,
    )
    out_r = prefill_reference(
        q, kc, vc, 1, table, q_start, total_len, 8, scale
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


# -- sliding-window variants (round-5: SWA models ride the kernels too) --

@pytest.mark.parametrize("window", [3, 8, 13, 100])
def test_decode_window_parity(window):
    """Windowed decode: the page walk starts at the window's first page
    and masks within the boundary page; parity vs the XLA window mask
    for windows inside one page, page-crossing, and > context."""
    q, kc, vc, bt, ctx = make_case(5)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out_p = paged_decode_attention(
        q, kc, vc, jnp.int32(0), bt, ctx,
        block_size=8, scale=scale, interpret=True, window=window,
    )
    slots = xla_attn.block_table_slots(bt, 8)
    k_ctx = kc[0][:, slots].transpose(1, 2, 0, 3)
    v_ctx = vc[0][:, slots].transpose(1, 2, 0, 3)
    out_r = xla_attn.context_attention_decode(
        q, k_ctx, v_ctx, ctx, scale, window=window
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window", [5, 16, 21])
def test_prefill_window_parity(window):
    from production_stack_tpu.ops.pallas_attention import (
        paged_prefill_attention,
    )

    q, kc, vc, table, q_start, total_len = make_prefill_case(9, t=16)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out_p = paged_prefill_attention(
        q, kc, vc, jnp.int32(1), table, jnp.int32(q_start),
        block_size=8, scale=scale, interpret=True, window=window,
    )
    slots = xla_attn.block_table_slots(table, 8)
    k_ctx = kc[1][:, slots].transpose(1, 0, 2)
    v_ctx = vc[1][:, slots].transpose(1, 0, 2)
    t = q.shape[0]
    q_positions = jnp.arange(q_start, q_start + t)
    out_r = xla_attn.context_attention_prefill(
        q, k_ctx, v_ctx, q_positions, jnp.int32(total_len), scale,
        window=window,
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


def test_prefill_window_parity_multi_tile():
    """Window + tile loop: per-tile page-walk starts advance with the
    tiles (later tiles skip early pages entirely)."""
    from production_stack_tpu.ops import pallas_attention

    q, kc, vc, table, q_start, total_len = make_prefill_case(
        4, t=32, prefix_pages=2, nkv=1, g=2, d=128
    )
    scale = 1.0 / np.sqrt(q.shape[-1])
    orig = pallas_attention._prefill_q_tile
    pallas_attention._prefill_q_tile = lambda t, nq, d: 8
    try:
        out_p = pallas_attention.paged_prefill_attention(
            q, kc, vc, jnp.int32(0), table, jnp.int32(q_start),
            block_size=8, scale=scale, interpret=True, window=7,
        )
    finally:
        pallas_attention._prefill_q_tile = orig
    slots = xla_attn.block_table_slots(table, 8)
    k_ctx = kc[0][:, slots].transpose(1, 0, 2)
    v_ctx = vc[0][:, slots].transpose(1, 0, 2)
    q_positions = jnp.arange(q_start, q_start + q.shape[0])
    out_r = xla_attn.context_attention_prefill(
        q, k_ctx, v_ctx, q_positions, jnp.int32(total_len), scale,
        window=7,
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


def test_engine_swa_selects_pallas_and_matches_xla():
    """A sliding-window model must now SELECT the pallas kernels (no
    silent XLA fallback — round-4 verdict Missing #5) and produce
    identical greedy output to the XLA window path, with generation
    running beyond the window."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams
    from production_stack_tpu.models import config as mcfg

    cfg = mcfg.ModelConfig(
        name="pst-swa-pallas-test",
        vocab_size=384, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=128,
        max_model_len=128, rope_theta=10000.0, tie_word_embeddings=True,
        sliding_window=24,
    )
    mcfg._PRESETS[cfg.name] = cfg
    try:
        kw = dict(
            model=cfg.name, tokenizer="byte", dtype="float32",
            cache_dtype="float32", block_size=8, num_kv_blocks=32,
            max_num_seqs=2, max_prefill_chunk=32, seed=0,
        )
        # prompt + generation cross the 24-token window
        prompts = ["the quick brown fox jumps over the lazy dog again"]
        sp = SamplingParams(max_tokens=16, temperature=0.0,
                            ignore_eos=True)
        eng_x = LLMEngine(EngineConfig(attention_impl="xla", **kw))
        out_x = [o.token_ids for o in eng_x.generate(prompts, sp)]
        eng_p = LLMEngine(EngineConfig(attention_impl="pallas", **kw))
        assert eng_p.runner.attention_impl == "pallas"  # no fallback
        out_p = [o.token_ids for o in eng_p.generate(prompts, sp)]
        assert out_p == out_x
    finally:
        mcfg._PRESETS.pop(cfg.name, None)


# ---- unified ragged paged attention kernel --------------------------------
# ONE batched-grid kernel over a flattened row space: decode lanes are
# single-row segments, prefill lanes contribute their chunk's q-tiles,
# CSR per-block segment metadata rides scalar prefetch. Parity bar is
# BIT-IDENTITY against the composed kernels per row (the masked-page
# online-softmax no-op argument), not allclose.

def _dec_rows_meta(ctx, tq=8):
    """CSR metadata for an all-decode row space (one single-row segment
    per lane, lanes sharing TQ-row blocks)."""
    b = len(ctx)
    r_pad = -(-b // tq) * tq
    n_blk = r_pad // tq
    blk_seg = np.minimum(np.arange(n_blk + 1, dtype=np.int32) * tq, b)
    lanes = np.arange(b, dtype=np.int32)
    seg = np.stack([lanes, lanes % tq, np.ones(b, np.int32),
                    np.asarray(ctx, np.int32) - 1], axis=1)
    return r_pad, jnp.asarray(blk_seg), jnp.asarray(seg)


def _ragged(q, kc, vc, layer, tables, blk_seg, seg_meta, bs=8,
            window=None):
    from production_stack_tpu.ops.pallas_attention import (
        ragged_paged_attention,
    )

    scale = 1.0 / np.sqrt(q.shape[-1])
    return ragged_paged_attention(
        q, kc, vc, jnp.int32(layer), tables, blk_seg, seg_meta,
        block_size=bs, scale=scale, interpret=True, window=window,
    )


def test_ragged_tq_constants_agree():
    """The runner packs lanes RAGGED_TQ-aligned and the kernel derives
    its tile from the caller's shapes — the two module constants must
    agree or a kernel-side retune silently never takes effect."""
    from production_stack_tpu.engine import model_runner as mr
    from production_stack_tpu.ops import pallas_attention as pa

    assert mr.RAGGED_TQ == pa.RAGGED_TQ


@pytest.mark.parametrize("layer", [0, 1])
def test_ragged_kernel_decode_rows_bit_identical(layer):
    """Decode-only row space (b=5 lanes sharing one 8-row block, one
    ragged length per lane) is bit-identical to the composed per-
    sequence-grid decode kernel."""
    q, kc, vc, bt, ctx = make_case(0, b=5)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = paged_decode_attention(
        q, kc, vc, jnp.int32(layer), bt, ctx,
        block_size=8, scale=scale, interpret=True,
    )
    r_pad, blk_seg, seg = _dec_rows_meta(np.asarray(ctx))
    qp = jnp.pad(q, ((0, r_pad - q.shape[0]), (0, 0), (0, 0)))
    out = _ragged(qp, kc, vc, layer, bt, blk_seg, seg)[: q.shape[0]]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_ragged_kernel_prefill_rows_bit_identical():
    """A 16-row chunk starting mid-page (ragged length straddling page
    boundaries) as two 8-row segments is bit-identical to the composed
    prefill kernel's one launch."""
    q, kc, vc, table, q_start, total_len = make_prefill_case(1, t=16)
    from production_stack_tpu.ops.pallas_attention import (
        paged_prefill_attention,
    )

    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = paged_prefill_attention(
        q, kc, vc, jnp.int32(0), table, jnp.int32(q_start),
        block_size=8, scale=scale, interpret=True,
    )
    g = q.shape[0] // 8
    blk_seg = jnp.arange(g + 1, dtype=jnp.int32)
    seg = np.stack([
        np.zeros(g, np.int32), np.zeros(g, np.int32),
        np.full(g, 8, np.int32),
        q_start + 8 * np.arange(g, dtype=np.int32),
    ], axis=1)
    out = _ragged(q, kc, vc, 0, table[None], blk_seg, jnp.asarray(seg))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("window", [None, 7, 100])
def test_ragged_kernel_mixed_rows(window):
    """THE lane-mix case: one 16-row prefill chunk + 4 decode lanes
    with ragged context lengths share ONE grid; every region matches
    its composed-kernel reference bit for bit (windowed variants
    included — the windowed page-walk start is per segment)."""
    from production_stack_tpu.ops.pallas_attention import (
        paged_prefill_attention,
    )

    rng = np.random.RandomState(3)
    bs, nkv, g, d = 8, 2, 2, 128
    nq = nkv * g
    # prefill lane: chunk of 16 at q_start mid-page over its own pages
    qp, kc, vc, pf_table, q_start, total_len = make_prefill_case(
        3, t=16, prefix_pages=2, nkv=nkv, g=g, d=d
    )
    # decode lanes: 4 lanes over DISTINCT trailing slots of the same
    # cache (disjoint tables, like disjoint sequences in a round)
    b = 4
    pages = 2
    extra = rng.randn(2, nkv, (1 + b * pages) * bs, d).astype(
        np.float32
    )
    kc2 = jnp.concatenate([kc, jnp.asarray(extra)], axis=2)
    vc2 = jnp.concatenate(
        [vc, jnp.asarray(rng.randn(*extra.shape).astype(np.float32))],
        axis=2,
    )
    base = kc.shape[2] // bs
    dec_tables = (
        base + 1 + np.arange(b * pages, dtype=np.int32).reshape(b, pages)
    )
    dec_ctx = np.asarray([1, 7, 9, 16], np.int32)  # straddle pages
    qd = jnp.asarray(rng.randn(b, nq, d).astype(np.float32))
    scale = 1.0 / np.sqrt(d)

    ref_pf = paged_prefill_attention(
        qp, kc2, vc2, jnp.int32(1), pf_table, jnp.int32(q_start),
        block_size=bs, scale=scale, interpret=True, window=window,
    )
    ref_dec = paged_decode_attention(
        qd, kc2, vc2, jnp.int32(1), jnp.asarray(dec_tables),
        jnp.asarray(dec_ctx), block_size=bs, scale=scale,
        interpret=True, window=window,
    )

    # one grid: 2 prefill blocks + 1 decode block
    r_pf = qp.shape[0]
    n_pf_blk = r_pf // 8
    n_pages = max(pf_table.shape[0], pages)
    tables = np.zeros((1 + b, n_pages), np.int32)
    tables[0, : pf_table.shape[0]] = np.asarray(pf_table)
    tables[1:, :pages] = dec_tables
    pf_seg = np.stack([
        np.zeros(n_pf_blk, np.int32), np.zeros(n_pf_blk, np.int32),
        np.full(n_pf_blk, 8, np.int32),
        q_start + 8 * np.arange(n_pf_blk, dtype=np.int32),
    ], axis=1)
    lanes = np.arange(b, dtype=np.int32)
    dec_seg = np.stack([
        1 + lanes, lanes % 8, np.ones(b, np.int32), dec_ctx - 1,
    ], axis=1)
    seg = np.concatenate([pf_seg, dec_seg])
    blk_seg = np.concatenate([
        np.arange(n_pf_blk + 1, dtype=np.int32),
        np.asarray([n_pf_blk + b], np.int32),
    ])
    q_all = jnp.concatenate(
        [qp, qd, jnp.zeros((8 - b, nq, d), jnp.float32)]
    )
    out = _ragged(
        q_all, kc2, vc2, 1, jnp.asarray(tables),
        jnp.asarray(blk_seg), jnp.asarray(seg), bs=bs, window=window,
    )
    np.testing.assert_array_equal(
        np.asarray(out[:r_pf]), np.asarray(ref_pf)
    )
    np.testing.assert_array_equal(
        np.asarray(out[r_pf: r_pf + b]), np.asarray(ref_dec)
    )


def test_ragged_kernel_idle_segments_and_blocks():
    """Zero-row segments (idle lanes) and blocks with no segments walk
    no pages and leave other rows' outputs untouched — real rows stay
    bit-identical to a run without the idle entries."""
    q, kc, vc, bt, ctx = make_case(2, b=3)
    r_pad, blk_seg, seg = _dec_rows_meta(np.asarray(ctx))
    qp = jnp.pad(q, ((0, r_pad - 3), (0, 0), (0, 0)))
    out_ref = _ragged(qp, kc, vc, 0, bt, blk_seg, seg)[:3]
    # same rows + an idle zero-row segment + a trailing empty block
    seg_idle = jnp.concatenate([
        seg, jnp.asarray([[0, 3, 0, 0]], jnp.int32)
    ])
    blk_idle = jnp.asarray([0, 4, 4], jnp.int32)  # block 1: no segs
    q_idle = jnp.concatenate([qp, jnp.zeros_like(qp)])
    out = _ragged(q_idle, kc, vc, 0, bt, blk_idle, seg_idle)[:3]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))


def test_ragged_kernel_tp_shard_map_parity():
    """The shard_mapped TP ragged kernel (8-device CPU mesh, kv heads
    sharded) matches the single-device composed decode reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from production_stack_tpu.ops.pallas_attention import (
        ragged_paged_attention_tp,
    )
    from production_stack_tpu.parallel.sharding import make_mesh

    q, kc, vc, bt, ctx = make_case(4, b=4, nkv=8, g=2, d=128)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = reference(q, kc, vc, 1, bt, ctx, 8, scale)
    r_pad, blk_seg, seg = _dec_rows_meta(np.asarray(ctx))
    qp = jnp.pad(q, ((0, r_pad - 4), (0, 0), (0, 0)))
    mesh = make_mesh(8)
    kc_sh = jax.device_put(
        kc, NamedSharding(mesh, P(None, None, "tp", None))
    )
    vc_sh = jax.device_put(
        vc, NamedSharding(mesh, P(None, None, "tp", None))
    )
    q_sh = jax.device_put(qp, NamedSharding(mesh, P(None, "tp", None)))
    out = ragged_paged_attention_tp(
        q_sh, kc_sh, vc_sh, jnp.int32(1), bt, blk_seg, seg,
        mesh=mesh, block_size=8, scale=scale, interpret=True,
    )[:4]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_engine_single_kernel_vs_xla():
    """Whole-engine greedy decode is identical on the XLA path and on
    the Pallas path (the ragged kernel) — chunked prompts + multi-step
    decode so the packed-prefill rows program AND the kernel-mode
    decode loop both run."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams

    kw = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=64,
        max_num_seqs=2, max_prefill_chunk=8, seed=0,
        num_scheduler_steps=4,
    )
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    prompts = ["a chunked prompt long enough for several chunks",
               "short one"]
    out_x = [o.token_ids for o in LLMEngine(
        EngineConfig(attention_impl="xla", **kw)).generate(prompts, sp)]
    e_k = LLMEngine(EngineConfig(attention_impl="pallas", **kw))
    assert e_k.runner.ragged_kernel
    out_k = [o.token_ids for o in e_k.generate(prompts, sp)]
    assert out_k == out_x


def test_engine_multistep_pallas_path():
    """pallas + num_scheduler_steps>1 (the TPU default serving config)
    must trace and match the XLA engine — regression for the undefined
    `window` NameError in the decode_multi closure (review r5)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams

    kw = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=32,
        max_num_seqs=2, max_prefill_chunk=32,
        num_scheduler_steps=4,
    )
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    prompts = ["multi step pallas"]
    out_x = [o.token_ids for o in LLMEngine(
        EngineConfig(attention_impl="xla", **kw)).generate(prompts, sp)]
    eng_p = LLMEngine(EngineConfig(attention_impl="pallas", **kw))
    assert eng_p.runner.attention_impl == "pallas"
    out_p = [o.token_ids for o in eng_p.generate(prompts, sp)]
    assert out_p == out_x


# ---- the walk's edges ------------------------------------------------------
# One page walk serves the three kernels: KV blocks of N pages at
# absolute multiples of N, a ring of block buffers, a one-row and a
# fused tile height. These cases sit on its edges. Each compares the
# composed kernel with the XLA reference at the file's tolerances AND
# the ragged kernel with the composed one bit for bit.

_KERNELS = (
    pa.paged_decode_attention, pa.paged_prefill_attention,
    pa.ragged_paged_attention,
)


@pytest.fixture
def kv_block_pages(monkeypatch):
    """force(n) pins the walk's KV block to n pages (None: the size
    `_kv_block_pages` picks for the case's shapes). The kernels are
    jitted on shapes, not on N, so their caches are dropped around a
    forced size."""
    def force(n):
        if n is not None:
            monkeypatch.setattr(pa, "_kv_block_pages", lambda *_: n)
        return n

    for f in _KERNELS:
        f.clear_cache()
    yield force
    for f in _KERNELS:
        f.clear_cache()


def _natural_pages(nkv, d, dtype, bs):
    return pa._kv_block_pages(nkv, d, jnp.dtype(dtype).itemsize, bs)


def _lanes_case(seed, ctx, pages, bs=8, nkv=2, g=2, d=128,
                dtype=jnp.float32):
    """Decode lanes with GIVEN context lengths over disjoint shuffled
    pages; tables `pages` wide, entries past a lane's last page 0."""
    rng = np.random.RandomState(seed)
    b = len(ctx)
    need = [-(-c // bs) for c in ctx]
    num_blocks = 1 + sum(need)
    shape = (2, nkv, num_blocks * bs, d)
    kc = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    vc = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    q = jnp.asarray(rng.randn(b, nkv * g, d).astype(np.float32), dtype)
    order = rng.permutation(np.arange(1, num_blocks))
    tables = np.zeros((b, pages), np.int32)
    at = 0
    for i, n in enumerate(need):
        tables[i, :n] = order[at:at + n]
        at += n
    return q, kc, vc, jnp.asarray(tables), jnp.asarray(ctx, jnp.int32)


def _decode_three_ways(q, kc, vc, bt, ctx, bs, window=None, layer=1,
                       tol=2e-5):
    """composed decode ~ XLA reference; ragged decode rows == composed."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    out_c = paged_decode_attention(
        q, kc, vc, jnp.int32(layer), bt, ctx,
        block_size=bs, scale=scale, interpret=True, window=window,
    )
    out_r = reference(q, kc, vc, layer, bt, ctx, bs, scale, window=window)
    np.testing.assert_allclose(
        np.asarray(out_c, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol,
    )
    r_pad, blk_seg, seg = _dec_rows_meta(np.asarray(ctx))
    qp = jnp.pad(q, ((0, r_pad - q.shape[0]), (0, 0), (0, 0)))
    out_k = _ragged(qp, kc, vc, layer, bt, blk_seg, seg, bs=bs,
                    window=window)[: q.shape[0]]
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_c))


@pytest.mark.parametrize("window", [None, "inside"])
@pytest.mark.parametrize("n_pages", [2, 3, None])
def test_walk_block_boundaries(kv_block_pages, n_pages, window):
    """Contexts of one key, shorter than one KV block, ending exactly
    on a block boundary and one key past it (first and second block),
    in a table whose width is no multiple of N; `inside` is a sliding
    window that starts in the middle of a KV block."""
    bs = 8
    n = kv_block_pages(n_pages) or _natural_pages(2, 128, jnp.float32, bs)
    c = n * bs
    ctx = [1, c - 3, c, c + 1, 2 * c, 2 * c + 1, 9]
    pages = -(-(2 * c + 1) // bs)
    assert pages % n, "the table must not tile into KV blocks"
    w = None if window is None else c // 2 + 3
    _decode_three_ways(*_lanes_case(11, ctx, pages), bs, window=w)


@pytest.mark.parametrize("nkv,g", [(8, 3), (8, 4), (4, 7)])
def test_walk_gqa_groups(kv_block_pages, nkv, g):
    """The one-row tile pads g = 3, 4, 7 query rows a kv head to the
    sublane tile: llama-3.2-3b's, mistral-7b's and qwen2-7b's groups,
    at the KV block their head counts pick."""
    bs = 8
    c = bs * _natural_pages(nkv, 128, jnp.float32, bs)
    ctx = [5, c, c + 1, c + bs + 2]
    _decode_three_ways(
        *_lanes_case(12, ctx, -(-(2 * c) // bs) + 1, nkv=nkv, g=g), bs
    )


@pytest.mark.parametrize("n_pages", [2, None])
def test_walk_bfloat16_cache(kv_block_pages, n_pages):
    """bf16 q, K and V enter the products as stored and p crosses PV
    as three exact bf16 pieces: same bits from both kernels, the
    reference to bf16's tolerance."""
    bs = 16
    n = kv_block_pages(n_pages) or _natural_pages(2, 128, jnp.bfloat16, bs)
    c = n * bs
    ctx = [3, c, c + 1, 2 * c - 1]
    case = _lanes_case(13, ctx, 2 * n + 1, bs=bs, dtype=jnp.bfloat16)
    _decode_three_ways(*case, bs, tol=2e-2)


def test_pv_pieces_are_exact():
    """_pv against a float64 product of the float32 p with the bf16 V:
    nothing of p is lost (a bf16-rounded p would err by ~4e-3)."""
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.rand(2, 8, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 64, 128).astype(np.float32), jnp.bfloat16)
    want = np.einsum(
        "hrk,hkd->hrd", np.asarray(p, np.float64),
        np.asarray(v.astype(jnp.float32), np.float64),
    )
    np.testing.assert_allclose(np.asarray(pa._pv(p, v)), want, rtol=2e-6,
                               atol=2e-6)


@pytest.mark.parametrize("n_pages", [2, None])
def test_walk_idle_slot_between_live_lanes(kv_block_pages, n_pages):
    """An idle slot (n_rows == 0) BETWEEN two live one-row segments of
    one grid block starts no copy and stores zeros into the row it
    names: the live rows keep the bits of the composed decode kernel."""
    bs = 8
    n = kv_block_pages(n_pages) or _natural_pages(2, 128, jnp.float32, bs)
    c = n * bs
    ctx = [c + 2, 7, 2 * c]
    q, kc, vc, bt, ctx_a = _lanes_case(14, ctx, 2 * n + 1)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = paged_decode_attention(
        q, kc, vc, jnp.int32(0), bt, ctx_a,
        block_size=bs, scale=scale, interpret=True,
    )
    seg = jnp.asarray(
        [[0, 0, 1, ctx[0] - 1], [1, 1, 0, 0], [2, 2, 1, ctx[2] - 1]],
        jnp.int32,
    )
    qp = jnp.pad(q, ((0, 5), (0, 0), (0, 0)))
    out = _ragged(qp, kc, vc, 0, bt, jnp.asarray([0, 3], jnp.int32), seg)
    np.testing.assert_array_equal(
        np.asarray(out)[[0, 2]], np.asarray(ref)[[0, 2]]
    )
    assert not np.asarray(out)[1].any()


@pytest.mark.parametrize("window", [None, "inside"])
@pytest.mark.parametrize("n_pages", [2, None])
def test_zero_row_decode_segments_among_live_ones(kv_block_pages, n_pages,
                                                  window):
    """What the runner ships since PR 29: a lane that holds no sequence
    is a ZERO-row segment (context 0) where it was a one-row segment
    over one key of the null page (context 1). Thirteen lanes over two
    grid blocks, idle ones between live ones and at the end, contexts
    of one, two and three KV blocks side by side. The live rows keep
    their bits
    — against the all-ones packing and against the composed decode
    kernel — and the idle rows are exactly zero although the output
    tile held NaN before the kernel ran: interpret mode hands the
    kernel an output full of NaN, which the rows no segment names
    still show."""
    bs = 8
    n = kv_block_pages(n_pages) or _natural_pages(2, 128, jnp.float32, bs)
    c = n * bs
    ctx = [2 * c + 3, 0, c, 5, 0, 0, 3 * c - 1, c + 1,
           1, 2 * c, 0, c - 1, 0]
    live = np.asarray(ctx) > 0
    w = None if window is None else c // 2 + 3
    q, kc, vc, bt, _ = _lanes_case(
        17, [max(x, 1) for x in ctx], -(-3 * c // bs) + 1)
    bt = jnp.where(jnp.asarray(live)[:, None], bt, 0)  # idle: null page
    r_pad, blk_seg, ones = _dec_rows_meta(np.maximum(ctx, 1))
    zero_rows = ones.at[:, 2].set(jnp.asarray(live, jnp.int32))
    zero_rows = zero_rows.at[:, 3].set(jnp.asarray(ctx, jnp.int32) - 1)
    qp = jnp.pad(q, ((0, r_pad - len(ctx)), (0, 0), (0, 0)))
    out_ones = np.asarray(
        _ragged(qp, kc, vc, 1, bt, blk_seg, ones, window=w))
    out = np.asarray(
        _ragged(qp, kc, vc, 1, bt, blk_seg, zero_rows, window=w))
    b = len(ctx)
    np.testing.assert_array_equal(out[:b][live], out_ones[:b][live])
    assert np.isnan(out[b:]).all(), "the tile was not poisoned"
    assert not out[:b][~live].any()
    # the composed decode kernel skips a context-0 lane the same way
    scale = 1.0 / np.sqrt(q.shape[-1])
    composed = np.asarray(paged_decode_attention(
        q, kc, vc, jnp.int32(1), bt, jnp.asarray(ctx, jnp.int32),
        block_size=bs, scale=scale, interpret=True, window=w,
    ))
    np.testing.assert_array_equal(out[:b], composed)


@pytest.mark.parametrize("window", [None, 11])
@pytest.mark.parametrize("n_pages", [2, None])
def test_walk_decode_rows_beside_prefill_tail(kv_block_pages, n_pages,
                                              window):
    """ONE grid block holds a prefill chunk's 3-row tail (fused tile
    height) and four decode rows (one-row height): each region keeps
    the bits of its composed kernel, and the XLA reference holds."""
    bs, nkv, g, d = 8, 2, 2, 128
    n = kv_block_pages(n_pages) or _natural_pages(nkv, d, jnp.float32, bs)
    c = n * bs
    tail = 3
    q_start = c + 5  # the tail sits just past a KV-block boundary
    ctx = [q_start + tail, 1, c, c + 1, 2 * c + 3]
    pages = 2 * n + 1
    q, kc, vc, bt, ctx_a = _lanes_case(15, ctx, pages, nkv=nkv, g=g, d=d)
    scale = 1.0 / np.sqrt(d)
    rng = np.random.RandomState(16)
    q_pf = jnp.asarray(rng.randn(8, nkv * g, d).astype(np.float32))
    ref_pf = pa.paged_prefill_attention(
        q_pf, kc, vc, jnp.int32(1), bt[0], jnp.int32(q_start),
        block_size=bs, scale=scale, interpret=True, window=window,
    )
    ref_dec = paged_decode_attention(
        q[1:], kc, vc, jnp.int32(1), bt[1:], ctx_a[1:],
        block_size=bs, scale=scale, interpret=True, window=window,
    )
    seg = np.asarray(
        [[0, 0, tail, q_start]]
        + [[i, tail + i - 1, 1, ctx[i] - 1] for i in range(1, 5)],
        np.int32,
    )
    q_blk = jnp.concatenate([q_pf[:tail], q[1:], jnp.zeros_like(q[:1])])
    out = _ragged(
        q_blk, kc, vc, 1, bt, jnp.asarray([0, 5], jnp.int32),
        jnp.asarray(seg), window=window,
    )
    np.testing.assert_array_equal(
        np.asarray(out[:tail]), np.asarray(ref_pf[:tail])
    )
    np.testing.assert_array_equal(
        np.asarray(out[tail:tail + 4]), np.asarray(ref_dec)
    )
    slots = xla_attn.block_table_slots(bt[0], bs)
    out_r = xla_attn.context_attention_prefill(
        q_pf[:tail], kc[1][:, slots].transpose(1, 0, 2),
        vc[1][:, slots].transpose(1, 0, 2),
        jnp.arange(q_start, q_start + tail), jnp.int32(q_start + tail),
        scale, window=window,
    )
    np.testing.assert_allclose(
        np.asarray(out[:tail]), np.asarray(out_r), rtol=2e-5, atol=2e-5
    )


# -- a shared run: the leading keys that every decode lane of a row block
# reads from the same pages go through the walk once, as one tall tile, and
# each lane's own walk starts where the run ends. The bar is the walk of
# each lane alone (`shared` None), bit for bit.

def _shared_prefix_case(seed, ctx, prefix_pages, *, bs=8, nkv=2, g=2,
                        d=128, d_v=None, latent=False, dtype=jnp.float32,
                        prefix_a_block=False):
    """Decode lanes whose tables start with the SAME `prefix_pages`
    pages (`prefix_a_block`: the lanes of a row block of 8 with the
    same, each block with other pages, as `place_lanes` seats them) and
    go on with pages of their own; a lane with context 0 holds no
    sequence and its table row is the null page's."""
    rng = np.random.RandomState(seed)
    b = len(ctx)
    own = [max(0, -(-c // bs) - prefix_pages) if c else 0 for c in ctx]
    n_prefixes = -(-b // 8) if prefix_a_block else 1
    num_blocks = 1 + n_prefixes * prefix_pages + sum(own)
    pages = prefix_pages + max(own) + 1
    order = rng.permutation(np.arange(1, num_blocks))
    tables = np.zeros((b, pages), np.int32)
    at = n_prefixes * prefix_pages
    for i, n in enumerate(own):
        if ctx[i]:
            first = (i // 8 if prefix_a_block else 0) * prefix_pages
            tables[i, :prefix_pages] = order[first:first + prefix_pages]
            tables[i, prefix_pages:prefix_pages + n] = order[at:at + n]
            at += n

    def cache(width):
        return jnp.asarray(
            rng.randn(2, nkv, num_blocks * bs, width).astype(np.float32),
            dtype)

    kc = cache(d)
    vc = None if latent else cache(d_v or d)
    q = jnp.asarray(rng.randn(b, nkv * g, d).astype(np.float32), dtype)
    return q, kc, vc, jnp.asarray(tables)


_SHARED_CASES = {
    # name: (kernel shape, contexts in KV blocks of c keys (float) or
    # None for a lane that holds no sequence, shared keys handed to the
    # kernel in KV blocks, what the kernel is told beside them)
    "8kv-g4": (dict(nkv=8, g=4), [2.5, 3.2, 2.1, 4.0, 2.9, 3.3, 2.2, 3.9],
               2, {}),
    "4kv-g7": (dict(nkv=4, g=7), [2.5, 3.2, 2.1, 4.0, 2.9], 2, {}),
    "16kv-g1": (dict(nkv=16, g=1), [2.5, 3.2, 2.1, 4.0, 2.9, 3.3], 2, {}),
    "4kv-g16-k256-v128-sink": (
        dict(nkv=4, g=16, d=256, d_v=128),
        [2.5, 3.2, 2.1, 4.0, 2.9, 3.3, 2.2, 3.9], 2, dict(sink=True)),
    "latent": (dict(nkv=1, g=8, d=640, latent=True),
               [2.5, 3.2, 2.1, 4.0], 2, dict(latent_v=512)),
    "bf16-cache": (dict(nkv=8, g=4, dtype=jnp.bfloat16),
                   [2.5, 3.2, 2.1, 4.0, 2.9, 3.3, 2.2, 3.9], 2, {}),
    # the run is addressed through the block's first LIVE lane
    "idle-lanes-inside": (dict(nkv=8, g=4),
                          [None, 3.2, 2.1, None, 2.9, 3.3, None, 3.9], 2,
                          {}),
    "contexts-apart-by-blocks": (dict(nkv=2, g=2),
                                 [1.1, 6.5, 3.0, 9.25, 1.5], 1, {}),
    # 2 blocks and 5 keys: cut down to 2 blocks
    "run-no-multiple-of-the-block": (
        dict(nkv=2, g=4), [2.5, 3.2, 2.9, 4.0], 2 + 5 / 16, {}),
    "run-shorter-than-a-block": (dict(nkv=2, g=4), [2.5, 3.2], 0.9, {}),
    "one-live-row": (dict(nkv=2, g=4), [None, 3.2, None], 2, {}),
    # a windowed walk starts at its window: the kernel has no run
    "window": (dict(nkv=2, g=4), [2.5, 3.2, 2.9, 4.0], 2,
               dict(window=21)),
    # two row blocks: the second shares nothing (and says so)
    "second-block-alone": (
        dict(nkv=2, g=2),
        [2.5, 3.2, 2.1, 4.0, 2.9, 3.3, 2.2, 3.9, 1.5, 0.4, 2.0], 2,
        dict(second_block=0)),
    # lanes seated by the prefix they hold (`model_runner.place_lanes`):
    # another prefix in each row block, a run in each, and the lanes
    # that nobody took at the end of each block
    "a-prefix-a-block-idle-tails": (
        dict(nkv=2, g=2, prefix_a_block=True),
        [2.5, 3.2, 2.1] + [None] * 5 + [2.9, 4.0] + [None] * 6, 2,
        dict(second_block="a run of its own")),
}


@pytest.mark.parametrize("name", list(_SHARED_CASES))
def test_shared_run_equals_each_lane_alone(kv_block_pages, name):
    """A row's result does not depend on who shares its block: with a
    shared run the ragged kernel returns what it returns without one,
    bit for bit, in every live row; zero-row segments are zeros."""
    shape, blocks, run_blocks, told = _SHARED_CASES[name]
    told = dict(told)
    bs = 8
    c = bs * kv_block_pages(2)
    ctx = [0 if x is None else int(x * c) for x in blocks]
    prefix_pages = int(run_blocks) * c // bs + 1
    q, kc, vc, tables = _shared_prefix_case(
        17, ctx, prefix_pages, bs=bs, **shape)
    b = len(ctx)
    r_pad, blk_seg, seg = _dec_rows_meta(ctx)
    seg = seg.at[:, 2].set(jnp.asarray([int(x > 0) for x in ctx]))
    qp = jnp.pad(q, ((0, r_pad - b), (0, 0), (0, 0)))
    live = [i for i, x in enumerate(ctx) if x]
    shared = np.zeros((r_pad // 8, 2), np.int32)
    shared[0] = int(run_blocks * c), live[0]
    if told.get("second_block") == "a run of its own":
        told["second_block"] = int(run_blocks * c)
    if "second_block" in told:
        shared[1] = told.pop("second_block"), 8
    sink = None
    if told.pop("sink", False):
        sink = jnp.asarray(
            np.random.RandomState(3).randn(q.shape[1]), jnp.float32)
    kw = dict(block_size=bs, scale=1.0 / np.sqrt(q.shape[-1]),
              interpret=True, **told)
    alone = pa.ragged_paged_attention(
        qp, kc, vc, jnp.int32(1), tables, blk_seg, seg, sink, **kw)
    together = pa.ragged_paged_attention(
        qp, kc, vc, jnp.int32(1), tables, blk_seg, seg, sink,
        jnp.asarray(shared), **kw)
    np.testing.assert_array_equal(
        np.asarray(together[:b], np.float32),
        np.asarray(alone[:b], np.float32))
    assert np.isfinite(np.asarray(together[:b], np.float32)).all()
    # and the lanes alone are the composed kernel's, as ever
    if "latent_v" not in told:
        composed = paged_decode_attention(
            q, kc, vc, jnp.int32(1), tables, jnp.asarray(ctx, jnp.int32),
            sink, **kw)
        np.testing.assert_array_equal(
            np.asarray(together, np.float32)[live],
            np.asarray(composed, np.float32)[live])


def test_shared_run_reads_the_named_lanes_pages(kv_block_pages):
    """The run is taken, and taken from the table row the caller names:
    over lanes whose leading pages are NOT the same, a claimed run gives
    the named lane its own result and every other lane another one."""
    bs = 8
    c = bs * kv_block_pages(2)
    ctx = [int(x * c) for x in (2.5, 3.2, 2.1)]
    q, kc, vc, tables, _ = _lanes_case(5, ctx, pages=8, nkv=2, g=4)
    r_pad, blk_seg, seg = _dec_rows_meta(ctx)
    qp = jnp.pad(q, ((0, r_pad - 3), (0, 0), (0, 0)))
    kw = dict(block_size=bs, scale=0.09, interpret=True)
    alone = np.asarray(pa.ragged_paged_attention(
        qp, kc, vc, jnp.int32(1), tables, blk_seg, seg, **kw))
    claimed = np.asarray(pa.ragged_paged_attention(
        qp, kc, vc, jnp.int32(1), tables, blk_seg, seg, None,
        jnp.asarray([[2 * c, 1]], jnp.int32), **kw))
    np.testing.assert_array_equal(claimed[1], alone[1])
    assert np.abs(claimed[0] - alone[0]).max() > 1e-3
    assert np.abs(claimed[2] - alone[2]).max() > 1e-3

"""A stack of layer groups (models/layer_groups.py) against the plain
reference (tests/reference_model.py::mimo_v2_forward), at the tiny widths
of `pst-tiny-groups-debug`: full and window layers with different kv
heads, d_k 24 / d_v 16, rotary on 8 dims, a non-zero sink, a non-zero
selection bias, V scale 0.707, a leading dense layer, 16 routed experts
of which 4 held, window 8 with contexts of 40+.

TOLERANCE 2e-4 on float32 logits: both sides compute in float32, so what
separates them is the order of sums (the served path attends chunk by
chunk through the cache and gathers padded contexts; the reference holds
one dense mask), a few float32 roundings on logits of magnitude ~1. A
wrong term moves logits by 1e-2 to whole units (test (f) shows each).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_model as rm
from moved_block_map import fused_rounds_across_a_moved_map
from production_stack_tpu.engine.block_manager import (
    WindowedBlockManager,
    WindowTable,
)
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.models import layer_groups
from production_stack_tpu.models.config import TINY_GROUPS_DEBUG as MC
from production_stack_tpu.ops import attention as xla_attn
from production_stack_tpu.ops import moe

TOL = 2e-4
BS = 4


def engine(**kw) -> LLMEngine:
    cfg = dict(
        model=MC.name, tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=BS, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, seed=3,
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg))


@pytest.fixture(scope="module")
def eng():
    return engine()


def ids(n: int, seed: int = 0) -> list[int]:
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, MC.vocab_size - 4, n)]


def settled_stats(runner) -> tuple[int, ...]:
    """`moe_stats()` once every dispatched program's counters have
    landed. The runner never waits for them (a scrape reads what has
    arrived, and may lag by a round): a program's counters can still be
    on their way when the round's tokens are already on the host, so a
    test that counts to the last row waits here."""
    jax.block_until_ready(list(runner._stats_pending))
    return runner.moe_stats()


def reference(params, tokens) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return np.asarray(rm.mimo_v2_forward(MC, params, tokens))


def serve(e: LLMEngine, tokens: list[int], n_prompt: int, chunk: int,
          reuse: bool = True):
    """Prefill `tokens[:n_prompt]` in chunks of `chunk` and decode the
    rest teacher-forced, through the runner's programs and the engine's
    block manager. Returns ({position: logits row}, cached tokens, the
    table): the last row of every chunk and every decode row."""
    r, bm = e.runner, e.block_manager
    table, cached = bm.allocate_prompt(tokens[:n_prompt], reuse_cache=reuse)
    rows, start, prev_hash, done = {}, cached, 0, 0
    while start < n_prompt:
        end = min(start + chunk, n_prompt)
        bm.prepare_chunk(table, start, end)
        _, logits = r.prefill(tokens[start:end], start, table, end)
        rows[end - 1] = np.asarray(logits)
        start = end
        # what LLMEngine does when it applies a chunk: every block the
        # chunk filled is content-addressed
        for i in range(done, end // BS):
            prev_hash = bm.register_block(
                prev_hash, tuple(tokens[i * BS:(i + 1) * BS]), table[i])
        done = end // BS
    for pos in range(n_prompt, len(tokens)):
        assert bm.ensure_capacity(pos + 1, table)
        bm.release_behind(table, pos)
        logits = r.decode([tokens[pos]], [pos], [table], [pos + 1])
        rows[pos] = np.asarray(logits[0])
    return rows, cached, table


def assert_rows(rows: dict, ref: np.ndarray, tol: float = TOL) -> None:
    for pos, row in rows.items():
        np.testing.assert_allclose(row, ref[pos], rtol=tol, atol=tol,
                                   err_msg=f"position {pos}")


# -- (a) prefill then decode through the cache -----------------------------
@pytest.mark.parametrize("chunk", [16, 7])
def test_chunked_prefill_then_decode_equals_the_reference(eng, chunk):
    tokens = ids(58, seed=chunk)
    rows, cached, table = serve(eng, tokens, 45, chunk, reuse=False)
    assert cached == 0
    assert_rows(rows, reference(eng.runner.params, tokens))
    eng.block_manager.free(table)


def test_packed_prefill_of_two_sequences_equals_the_reference(eng):
    r, bm = eng.runner, eng.block_manager
    a, b = ids(29, seed=21), ids(22, seed=22)
    tables = []
    for t in (a, b):
        table, _ = bm.allocate_prompt(t, reuse_cache=False)
        tables.append(table)
    # second chunks packed: a's [16, 29) beside b's [16, 22), after each
    # one's first chunk alone
    for t, table in zip((a, b), tables):
        bm.prepare_chunk(table, 0, 16)
        r.prefill(t[:16], 0, table, 16)
        bm.prepare_chunk(table, 16, len(t))
    _, logits = r.prefill_batch(
        [a[16:], b[16:]], [16, 16], tables, [len(a), len(b)])
    for i, t in enumerate((a, b)):
        np.testing.assert_allclose(
            np.asarray(logits[i]), reference(r.params, t)[-1],
            rtol=TOL, atol=TOL)
    for table in tables:
        bm.free(table)


def test_the_engine_generates_the_reference_greedy_tokens():
    e = engine(num_scheduler_steps=4)
    prompt = ids(45, seed=5)
    out = e.generate(
        [prompt], SamplingParams(max_tokens=12, temperature=0.0,
                                 ignore_eos=True))[0]
    ref = reference(e.runner.params, prompt + list(out.token_ids))
    want = [int(np.argmax(ref[len(prompt) - 1 + i]))
            for i in range(len(out.token_ids))]
    assert list(out.token_ids) == want
    routed, local, active = settled_stats(e.runner)
    # 3 routed layers, 4 experts a token, 45 prompt + 11 decoded rows
    assert routed == 3 * 4 * (45 + 11)
    assert 0.15 < local / routed < 0.35 and 0 < active <= 3 * 4 * 56


def test_the_counters_are_each_programs_own_and_summed_on_the_host():
    """Nothing of the counters stays in the cache between programs (a
    scrape has no device array to wait for), and the totals are Python
    ints: past 2**31 they go on where an int32 on the device wrapped."""
    e = engine(num_scheduler_steps=4)
    r = e.runner
    assert "stats" not in r.k_cache
    r._stats_total[0] = 2**31 - 1
    e.generate([ids(20, seed=6)], SamplingParams(
        max_tokens=5, temperature=0.0, ignore_eos=True))
    assert "stats" not in r.k_cache
    assert settled_stats(r)[0] == 2**31 - 1 + 3 * 4 * (20 + 4)
    assert not r._stats_pending


def test_the_kernel_path_serves_mixed_rounds_like_the_reference():
    """The programs the chip runs (ragged-rows prefill groups, fused
    decode rounds, lane-typed mixed rounds; the Pallas walk in interpret
    mode): two requests, the second admitted while the first decodes and
    sharing its first 24 tokens through the prefix cache."""
    e = engine(attention_impl="pallas", num_scheduler_steps=4)
    assert e.runner.ragged_kernel
    a = ids(41, seed=31)
    b = a[:24] + ids(19, seed=32)
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    e.add_request("a", prompt_token_ids=a, sampling_params=sp)
    done = {}
    for step in range(200):
        if step == 4:
            e.add_request("b", prompt_token_ids=b, sampling_params=sp)
        for o in e.step():
            if o.finished:
                done[o.request_id] = o
        if len(done) == 2:
            break
    assert done["b"].num_cached_tokens == 24
    for rid, prompt in (("a", a), ("b", b)):
        got = list(done[rid].token_ids)
        ref = reference(e.runner.params, prompt + got)
        want = [int(np.argmax(ref[len(prompt) - 1 + i]))
                for i in range(len(got))]
        assert got == want, rid


def test_fused_rounds_across_a_moved_block_map_are_the_single_steps():
    """Fused rounds map their tables into the window group's pool once,
    each with the map of its own dispatch: the map moves between them
    (pages let go, pages taken, a returning session's twinned anew) and
    the tokens are the single-step path's (tests/moved_block_map.py)."""
    fused_rounds_across_a_moved_map(
        engine(attention_impl="pallas", num_scheduler_steps=4), serve, ids)


# -- (b) after a prefix-cache hit ---------------------------------------------
def test_a_prefix_hit_serves_the_same_logits():
    e = engine()
    first = ids(40, seed=7)
    rows, cached, table = serve(e, first, 40, 16)
    assert cached == 0
    e.block_manager.free(table)
    second = first[:36] + ids(14, seed=8)
    rows, cached, table = serve(e, second, 44, 16)
    # 36 shared tokens are 9 blocks, and their window blocks are there
    assert cached == 36
    assert_rows(rows, reference(e.runner.params, second))


def test_a_hit_is_cut_back_where_the_window_blocks_are_gone():
    e = engine()
    bm = e.block_manager
    first = ids(40, seed=9)
    _, _, table = serve(e, first, 40, 16)
    bm.free(table)
    # the window group loses the twin of block 7 (positions 28..31): a
    # hit may end at block 5 (its window reaches back into block 4 only)
    # or earlier; block 8's hit would need blocks 7 and 8
    bm._drop_twin(table[7])
    second = first[:36] + ids(14, seed=10)
    matched, n = bm.match_prefix(second[:44])
    assert n < 36 and n == 28  # blocks 0..6: window blocks 5, 6 present
    rows, cached, _ = serve(e, second, 44, 16)
    assert cached == 28
    assert_rows(rows, reference(e.runner.params, second))


# -- (c) the shares add up -------------------------------------------------------
def test_the_ranks_shares_sum_to_the_uncut_layer():
    full = dataclasses.replace(MC, ep_size=1)  # holds all 16
    keys = jax.random.split(jax.random.key(11), 6)
    n, h, f, e_all = 40, MC.hidden_size, MC.moe_intermediate_size, 16
    x = jax.random.normal(keys[0], (n, h))
    router = jax.random.normal(keys[1], (h, e_all))
    bias = 0.3 * jax.random.normal(keys[2], (e_all,))
    wg = 0.2 * jax.random.normal(keys[3], (e_all, h, f))
    wu = 0.2 * jax.random.normal(keys[4], (e_all, h, f))
    wd = 0.2 * jax.random.normal(keys[5], (e_all, f, h))
    whole = rm.mimo_v2_routed_layer(full, x, router, bias, wg, wu, wd, 0)
    total, pairs = 0.0, 0
    for rank in range(4):
        sl = slice(rank * 4, rank * 4 + 4)
        share, stats = moe.routed_experts(
            x, router, bias, wg[sl], wu[sl], wd[sl], top_k=4,
            first_expert=rank * 4, scoring="sigmoid")
        np.testing.assert_allclose(
            np.asarray(share),
            np.asarray(rm.mimo_v2_routed_layer(
                full, x, router, bias, wg[sl], wu[sl], wd[sl], rank * 4)),
            rtol=1e-5, atol=1e-5)
        total = total + share
        assert int(stats[0]) == n * 4
        pairs += int(stats[1])
    assert pairs == n * 4  # every pair is some rank's
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [24, 128])
def test_padded_rows_take_nothing_and_change_no_row(n):
    """A row marked invalid keeps no pair, and the valid rows' results
    do not depend on what the invalid rows hold: not a number, even
    (the rows go in and out through matrices, and 0 x NaN is NaN)."""
    keys = jax.random.split(jax.random.key(n), 6)
    h, f = 32, 16
    x = jax.random.normal(keys[0], (n, h))
    args = (jax.random.normal(keys[1], (h, 16)), None,
            *(0.2 * jax.random.normal(k, s) for k, s in zip(
                keys[2:5], [(4, h, f), (4, h, f), (4, f, h)])))
    kw = dict(top_k=4, first_expert=4, scoring="sigmoid")
    valid = jnp.arange(n) % 3 != 0
    out, stats = moe.routed_experts(x, *args, valid=valid, **kw)
    noisy = jnp.where(valid[:, None], x, jnp.nan)
    out2, stats2 = moe.routed_experts(noisy, *args, valid=valid, **kw)
    alone, _ = moe.routed_experts(x, *args, **kw)
    keep = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(out)[keep],
                               np.asarray(alone)[keep], 1e-5, 1e-5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    assert not np.asarray(out)[~keep].any()
    assert int(stats[0]) == int(keep.sum()) * 4
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats2))


def all_experts_form(x, router, bias, wg, wu, wd, *, top_k, first_expert,
                     scoring):
    """The layer written the plain way: every local expert on every
    row (`moe_dense`), weighted by the routing's sparse gates."""
    idx, w = moe.route(x, router, bias, top_k, scoring)
    e_loc = wg.shape[0]
    local = idx - first_expert
    w = jnp.where((local >= 0) & (local < e_loc), w, 0.0)
    gates = jnp.zeros((x.shape[0], e_loc + 1)).at[
        jnp.arange(x.shape[0])[:, None], jnp.clip(local, 0, e_loc)
    ].add(w)[:, :e_loc]
    return moe.moe_dense(x, gates, wg, wu, wd)


@pytest.mark.parametrize("n", [5, 128])
def test_the_grouped_form_equals_every_expert_on_every_row(n):
    keys = jax.random.split(jax.random.key(1), 6)
    h, f = 32, 16
    x = jax.random.normal(keys[0], (n, h))
    args = (jax.random.normal(keys[1], (h, 16)),
            0.1 * jax.random.normal(keys[5], (16,)),
            *(0.2 * jax.random.normal(k, s) for k, s in zip(
                keys[2:5], [(4, h, f), (4, h, f), (4, f, h)])))
    kw = dict(top_k=4, first_expert=8, scoring="sigmoid")
    grouped, _ = moe.routed_experts(x, *args, **kw)
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(all_experts_form(x, *args, **kw)),
        rtol=1e-5, atol=1e-5)


def test_a_skewed_routing_takes_more_passes_and_drops_nothing():
    """Every row chooses the four local experts (a selection bias of +10
    on them): 512 local pairs where one pass of the grouped form holds
    384. The second pass takes the rest; the result is that of every
    expert on every row."""
    n = 128
    keys = jax.random.split(jax.random.key(2), 6)
    h, f = 32, 16
    x = jax.random.normal(keys[0], (n, h))
    bias = jnp.zeros((16,)).at[4:8].set(10.0)
    args = (jax.random.normal(keys[1], (h, 16)), bias,
            *(0.2 * jax.random.normal(k, s) for k, s in zip(
                keys[2:5], [(4, h, f), (4, h, f), (4, f, h)])))
    kw = dict(top_k=4, first_expert=4, scoring="sigmoid")
    grouped, stats = moe.routed_experts(x, *args, **kw)
    assert [int(v) for v in stats] == [n * 4, n * 4, 4]
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(all_experts_form(x, *args, **kw)),
        rtol=1e-5, atol=1e-5)


# -- (e) the window group stays bounded ----------------------------------------
def test_a_long_sequence_holds_a_bounded_number_of_window_blocks():
    e = engine(num_scheduler_steps=4)
    bm = e.block_manager
    window = MC.attn_kinds[1].window
    e.add_request("long", prompt_token_ids=ids(10, seed=12),
                  sampling_params=SamplingParams(
                      max_tokens=10 * window + 20, temperature=0.0,
                      ignore_eos=True))
    held, primary = [], []
    while e.scheduler.has_unfinished():
        e.step()
        for s in e.scheduler.running:
            held.append(s.block_table.hi - s.block_table.lo)
            primary.append(len(s.block_table))
            assert bm.window_blocks_in_use == held[-1]
    # the window behind, the fused steps (and their lookahead) ahead
    bound = -(-window // BS) + -(-2 * 4 // BS) + 2
    assert max(held) <= bound
    # with most rounds started at the fetch before them, which no
    # schedule() chose: such a round lets go behind its window itself
    assert e._early_dispatch_total >= e._decode_rounds_total // 2 > 10
    assert max(primary) >= (10 * window) // BS  # the full group grew
    assert bm.window_blocks_in_use == 0
    assert bm.window_blocks_released >= max(primary) - bound


# -- (f) every term is seen ------------------------------------------------------
def plain_logits(cfg, params, tokens) -> np.ndarray:
    """layer_groups.forward over one contiguous cache per kind (row =
    position), the XLA attention: the model's own code, no runner."""
    t = len(tokens)
    pos = jnp.arange(t, dtype=jnp.int32)
    layers = [cfg.layer_kinds.count(i) for i in range(len(cfg.attn_kinds))]
    kc = {"g": tuple(
        jnp.zeros((layers[i], ak.num_kv_heads, t + BS, cfg.head_dim))
        for i, ak in enumerate(cfg.attn_kinds)),
        "map": jnp.arange(t // BS + 2, dtype=jnp.int32),
        "stats": jnp.zeros((layer_groups.N_STATS,), jnp.int32)}
    vc = {"g": tuple(
        jnp.zeros((layers[i], ak.num_kv_heads, t + BS, cfg.v_dim))
        for i, ak in enumerate(cfg.attn_kinds))}

    def attn(q, l, k, v, spec):
        # rows are shifted by one block: slot 0 belongs to the null block
        return xla_attn.context_attention_prefill(
            q, k[l].swapaxes(0, 1)[BS:], v[l].swapaxes(0, 1)[BS:], pos,
            jnp.int32(t), cfg.head_dim ** -0.5, window=spec.window,
            sink=spec.sink)

    logits, _, _ = layer_groups.forward(
        cfg, params, jnp.asarray(tokens, jnp.int32), pos, kc, vc, pos + BS,
        attn, logits_rows=pos, block_size=BS)
    return np.asarray(logits)


def test_rows_that_are_no_tokens_reach_no_token_whatever_the_kernel_left():
    """The paged kernels leave the rows of a tile that belong to no
    segment as the tile held them. Served with NaN there (what the chip
    showed after rounds with padded rows), a token's logits are what
    they are without, and the null block those rows write stays
    finite: a windowed lane reads it, masked, for pages it let go."""
    params = layer_groups.init_params(MC, jax.random.key(4), jnp.float32)
    tokens = ids(21, seed=14)
    t, pad = len(tokens), 11
    pos = jnp.arange(t + pad, dtype=jnp.int32)
    real = pos < t
    slots = jnp.where(real, pos + BS, 0)
    layers = [MC.layer_kinds.count(i) for i in range(len(MC.attn_kinds))]

    def caches():
        kc = {"g": tuple(
            jnp.zeros((layers[i], ak.num_kv_heads, t + 2 * BS, MC.head_dim))
            for i, ak in enumerate(MC.attn_kinds)),
            "map": jnp.arange(t // BS + 3, dtype=jnp.int32),
            "stats": jnp.zeros((layer_groups.N_STATS,), jnp.int32)}
        vc = {"g": tuple(
            jnp.zeros((layers[i], ak.num_kv_heads, t + 2 * BS, MC.v_dim))
            for i, ak in enumerate(MC.attn_kinds))}
        return kc, vc

    def run(left_in_the_tile):
        def attn(q, l, k, v, spec):
            out = xla_attn.context_attention_prefill(
                q[:t], k[l].swapaxes(0, 1)[BS:BS + t],
                v[l].swapaxes(0, 1)[BS:BS + t], pos[:t], jnp.int32(t),
                MC.head_dim ** -0.5, window=spec.window, sink=spec.sink)
            return jnp.concatenate([out, jnp.full(
                (pad,) + out.shape[1:], left_in_the_tile, out.dtype)])

        kc, vc = caches()
        ids_ = jnp.asarray(tokens + [0] * pad, jnp.int32)
        return layer_groups.forward(
            MC, params, ids_, jnp.where(real, pos, 0), kc, vc, slots, attn,
            logits_rows=pos[:t], block_size=BS)

    clean, _, _ = run(0.0)
    dirty, kc, vc = run(jnp.nan)
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    np.testing.assert_allclose(np.asarray(clean), reference(params, tokens),
                               rtol=TOL, atol=TOL)
    for g in kc["g"] + vc["g"]:
        assert np.isfinite(np.asarray(g)).all()
    assert int(kc["stats"][0]) == 3 * 4 * t  # 3 routed layers, top-4


def _zero(params, name):
    segs = [{k: (jnp.zeros_like(v) if k == name else v)
             for k, v in seg.items()} for seg in params["segments"]]
    return {**params, "segments": segs}


def test_the_models_own_code_equals_the_reference():
    params = layer_groups.init_params(MC, jax.random.key(4), jnp.float32)
    tokens = ids(44, seed=13)
    np.testing.assert_allclose(plain_logits(MC, params, tokens),
                               reference(params, tokens), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("term", ["sink", "router_bias", "v_scale",
                                  "window"])
def test_dropping_a_term_fails_the_comparison(term):
    params = layer_groups.init_params(MC, jax.random.key(4), jnp.float32)
    tokens = ids(44, seed=13)
    cfg, served = MC, params
    if term in ("sink", "router_bias"):
        served = _zero(params, term)
    elif term == "v_scale":
        cfg = dataclasses.replace(MC, v_scale=1.0)
    else:
        cfg = dataclasses.replace(MC, attn_kinds=(
            MC.attn_kinds[0],
            dataclasses.replace(MC.attn_kinds[1], window=None)))
    diff = np.abs(plain_logits(cfg, served, tokens)
                  - reference(params, tokens)).max()
    assert diff > 50 * TOL, (term, diff)


# -- the block manager of two pools ----------------------------------------------
def manager(**kw) -> WindowedBlockManager:
    args = dict(num_blocks=64, block_size=BS, enable_prefix_caching=True,
                window=8, num_window_blocks=24)
    args.update(kw)
    return WindowedBlockManager(**args)


def test_window_blocks_follow_the_chunks_and_are_released_behind():
    bm = manager()
    table, cached = bm.allocate_prompt(list(range(40)))
    assert isinstance(table, WindowTable) and cached == 0
    assert len(table) == 10 and bm.window_blocks_in_use == 0
    bm.prepare_chunk(table, 0, 16)
    assert (table.lo, table.hi) == (0, 4)
    bm.prepare_chunk(table, 16, 32)
    # the query at 16 attends keys > 8: blocks 0 and 1 lie behind
    assert (table.lo, table.hi) == (2, 8)
    assert bm.window_blocks_in_use == 6
    assert all(bm.block_map[b] for b in table[2:8])
    assert not any(bm.block_map[b] for b in table[:2] + table[8:])
    v = bm.map_version
    bm.release_behind(table, 17)
    assert bm.map_version == v and table.lo == 2  # 17 - 8 + 1 = 10 -> 2
    bm.free(table)
    assert bm.window_blocks_in_use == 0
    assert len(bm._wfree) == 23 and not bm._wevictable


def test_registered_blocks_keep_their_twins_until_evicted():
    bm = manager(num_window_blocks=7)
    tokens = list(range(24))
    table, _ = bm.allocate_prompt(tokens)
    bm.prepare_chunk(table, 0, 24)
    prev = 0
    for i in range(6):
        prev = bm.register_block(prev, tuple(tokens[i * 4:i * 4 + 4]),
                                 table[i])
    bm.free(table)
    assert bm.window_blocks_in_use == 0 and len(bm._wevictable) == 6
    # a full hit: 5 blocks (one token is always computed), twins 3, 4
    t2, cached = bm.allocate_prompt(tokens)
    assert cached == 20 and (t2.lo, t2.hi) == (3, 5)
    assert bm.window_blocks_in_use == 2
    # the pool's other twins go, oldest first, when room is needed
    bm.prepare_chunk(t2, 20, 24)
    other, _ = bm.allocate_prompt(list(range(100, 112)))
    bm.prepare_chunk(other, 0, 12)
    assert not bm.block_map[table[0]] and bm.block_map[table[4]]
    # and then a hit on the first sequence is cut back to nothing: the
    # twins behind its end are gone
    bm.free(t2)
    bm.free(other)
    assert bm.match_prefix(tokens[:9])[1] == 0
    # an evicted primary block takes its twin along
    held = int(bm.block_map[table[4]])
    while bm.free_blocks:
        bm._pop_free_block()
    while bm.blocks[table[4]].block_hash is not None:
        bm._pop_free_block()
    assert not bm.block_map[table[4]] and held in bm._wfree


def test_running_out_of_window_blocks_raises():
    bm = manager(num_window_blocks=3)
    table, _ = bm.allocate_prompt(list(range(40)))
    with pytest.raises(RuntimeError, match="window-group"):
        bm.prepare_chunk(table, 0, 16)


# -- start-up --------------------------------------------------------------------
@pytest.mark.parametrize("kw, name", [
    (dict(enable_lora=True), "--enable-lora"),
    (dict(num_speculative_tokens=2), "--num-speculative-tokens"),
    (dict(cpu_offload_bytes=1 << 20), "KV offload tiers"),
    (dict(kv_role="prefill"), "PD transfer"),
    (dict(long_prefill_threshold=64, context_parallel_size=2),
     "ring prefill lane"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
])
def test_out_of_scope_features_are_refused_by_name(kw, name):
    with pytest.raises(ValueError, match=name):
        engine(**kw)


def test_a_mimo_v2_config_json_becomes_layer_groups(tmp_path):
    import json

    from production_stack_tpu.models.config import from_hf_config

    hf = {
        "model_type": "mimo_v2", "hidden_size": 64, "head_dim": 24,
        "v_head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
        "swa_head_dim": 24, "swa_v_head_dim": 16,
        "swa_num_attention_heads": 4, "num_hidden_layers": 4,
        "hybrid_layer_pattern": [0, 1, 1, 0],
        "moe_layer_freq": [0, 1, 1, 1], "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 16,
        "num_experts_per_tok": 4, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "n_shared_experts": None,
        "routed_scaling_factor": None, "rope_theta": 1e7,
        "swa_rope_theta": 1e4, "sliding_window": 8,
        "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "vocab_size": 384,
        "max_position_embeddings": 256, "layernorm_epsilon": 1e-5,
        "tie_word_embeddings": False, "ep_size": 4, "ep_rank": 0,
    }
    (tmp_path / "config.json").write_text(json.dumps(hf))
    mc = from_hf_config(str(tmp_path), name=MC.name)
    assert mc == MC
    assert mc.segments() == ((0, False, 1, 0), (1, True, 2, 0),
                             (0, True, 1, 1))
    params = layer_groups.init_params(mc, jax.random.key(0), jnp.float32)
    held = sum(a.size for a in jax.tree.leaves(params))
    assert mc.num_params() == held
    # a shared expert is a field since PR 33 (this file leaves it at
    # none); what has no code path is still refused by name
    hf["n_shared_experts"] = 1
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert from_hf_config(str(tmp_path), name=MC.name) == (
        dataclasses.replace(MC, shared_experts=1))
    hf["n_group"] = 4
    (tmp_path / "config.json").write_text(json.dumps(hf))
    with pytest.raises(ValueError, match="n_group"):
        from_hf_config(str(tmp_path))

"""A stack of single-sublayer blocks (`ModelConfig.block_pattern`):
state-space mixers with a recurrent state a sequence beside the paged KV
cache, attention without positional encoding, routed experts at a latent
width with no gate matrix, against the plain reference of the nemotron_h
family (benchmarks/chip/families/nemotron_h.py::forward_logprobs: a
sequential scan over the tokens, nothing of the program imported), at
the tiny widths of `pst-tiny-nemotron-debug`: EMEMEM*, 8 heads of 8 in 2
groups with a state of 8, 4 taps, chunks of 8 rows under prompts of
40-70, 16 experts top-4 (rank 0 of 2 holds 8) of width 24 at a latent 16
under a hidden 32, relu squared. Blocks of 4 tokens, prefill chunks of
16: a snapshot boundary every 16 tokens.

TOLERANCE 2e-4 on float32 log-probabilities, as tests/test_layer_groups.py
states it: both sides compute in float32 and differ in the order of
sums. A wrong term moves them by 1e-2 to whole units.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.block_manager import StateBlockManager
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.models import config as mcfg
from production_stack_tpu.models import layer_groups
from production_stack_tpu.models.config import TINY_NEMOTRON_DEBUG as MC
from production_stack_tpu.ops import expert_ffn as ef
from production_stack_tpu.ops import moe, ssm

TOL = 2e-4
BS = 4
CHUNK = 16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "nemotron_h_family_for_layer_group_tests",
    os.path.join(ROOT, "benchmarks", "chip", "families", "nemotron_h.py"))
family = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(family)


def engine(**kw) -> LLMEngine:
    cfg = dict(
        model=MC.name, tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=BS, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=CHUNK, seed=3,
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg))


@pytest.fixture(scope="module")
def eng():
    return engine()


@pytest.fixture(scope="module")
def kernel_eng():
    """The programs the chip runs (ragged rows, the fused decode round),
    the Pallas walk in interpret mode."""
    e = engine(attention_impl="pallas", num_scheduler_steps=4)
    assert e.runner.ragged_kernel
    return e


def ids(n: int, seed: int = 0) -> list[int]:
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, MC.vocab_size - 4, n)]


def reference(params, tokens, cfg=MC) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return np.asarray(family.forward_logprobs(
            cfg, params, jnp.asarray(tokens, jnp.int32),
            jnp.arange(len(tokens))))


def logprobs(logits) -> np.ndarray:
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits), -1))


def serve(e: LLMEngine, tokens, n_prompt: int, chunk: int, reuse=True):
    """Chunked prefill, then decode teacher-forced, through the runner
    and the block manager as the engine drives them. -> ({position:
    log-probabilities}, cached tokens, table)."""
    r, bm = e.runner, e.block_manager
    table, cached = bm.allocate_prompt(tokens[:n_prompt], reuse_cache=reuse)
    rows, start, done = {}, cached, cached // BS
    prev = bm.blocks[table[done - 1]].block_hash if done else 0

    def register(upto):
        nonlocal prev, done
        for i in range(done, upto // BS):
            prev = bm.register_block(
                prev, tuple(tokens[i * BS:(i + 1) * BS]), table[i])
            bm.note_saved(table, i)
        done = max(done, upto // BS)

    while start < n_prompt:
        end = min(start + chunk, n_prompt)
        bm.prepare_chunk(table, start, end)
        _, logits = r.prefill(tokens[start:end], start, table, end)
        rows[end - 1] = logprobs(logits)
        start = end
        register(end)
    for pos in range(n_prompt, len(tokens)):
        assert bm.ensure_capacity(pos + 1, table)
        logits = r.decode([tokens[pos]], [pos], [table], [pos + 1])
        rows[pos] = logprobs(logits[0])
        register(pos + 1)
    return rows, cached, table


def assert_rows(rows: dict, ref: np.ndarray, tol: float = TOL) -> None:
    for pos, row in rows.items():
        np.testing.assert_allclose(row, ref[pos], rtol=tol, atol=tol,
                                   err_msg=f"position {pos}")


# -- (a) the scan ---------------------------------------------------------------
def _scan_inputs(t, seed):
    k = jax.random.split(jax.random.key(seed), 6)
    h, p, g, n = 8, 4, 2, 8
    return (jax.random.normal(k[0], (t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (t, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (t, g, n)),
            jax.random.normal(k[4], (t, g, n)),
            jax.random.normal(k[5], (h, p, n)))


@pytest.mark.parametrize("t", [1, 7, 8, 13, 29])
def test_the_chunked_scan_is_the_recurrence_from_a_state_that_is_not_zero(t):
    x, dt, a, b, c, s0 = _scan_inputs(t, seed=t)
    y, s_end = ssm.scan_chunked(x, dt, a, b, c, s0, chunk=8)
    s, want = s0, []
    for i in range(t):
        yi, s = ssm.scan_step(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1],
                              c[i:i + 1], s[None])
        s = s[0]
        want.append(yi[0])
    np.testing.assert_allclose(y, jnp.stack(want), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_end, s, rtol=1e-4, atol=1e-4)


def test_rows_that_are_no_tokens_pass_the_state_unchanged():
    x, dt, a, b, c, s0 = _scan_inputs(13, seed=3)
    dt = dt.at[9:].set(0.0)
    _, s_all = ssm.scan_chunked(x, dt, a, b, c, s0, chunk=8)
    _, s_nine = ssm.scan_chunked(x[:9], dt[:9], a, b[:9], c[:9], s0, chunk=8)
    np.testing.assert_allclose(s_all, s_nine, rtol=1e-5, atol=1e-5)


def test_the_update_kernel_is_the_recurrence_in_place():
    """`state_update`, the Mosaic kernel in interpret mode, on the
    PACKED state (4 heads of a group side by side, N before them):
    lanes read their own slot, a snapshot's, or start from zero; each
    writes its slot of the one layer and nothing else moves."""
    layers, slots, r, k = 2, 5, 3, 4
    x, dt, a, b, c, _ = _scan_inputs(r, seed=11)
    plain = jax.random.normal(jax.random.key(12), (layers, slots, 8, 4, 8))
    s_all = ssm.to_packed(plain, k)
    assert s_all.shape == (layers, slots, 2, 8, 16)
    np.testing.assert_array_equal(ssm.from_packed(s_all, k), plain)
    src, dst = jnp.asarray([1, 4, 0]), jnp.asarray([1, 2, 0])
    zero = jnp.asarray([False, False, True])
    y, out = ssm.state_update(s_all, jnp.int32(1), src, dst, zero, x, dt, a,
                              b, c, interpret=True)
    s0 = jnp.where(zero[:, None, None, None], 0.0, plain[1, src])
    want_y, want_s = ssm.scan_step(x, dt, a, b, c, s0)
    np.testing.assert_allclose(y[:2], want_y[:2], rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(y[2]))      # nobody's lane: skipped
    np.testing.assert_allclose(ssm.from_packed(out, k)[1, dst[:2]],
                               want_s[:2], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(out[0], s_all[0])
    np.testing.assert_array_equal(out[1, 3:], s_all[1, 3:])
    # a lane that starts from zero, in a slot of its own
    y, out = ssm.state_update(s_all, jnp.int32(0), jnp.asarray([3]),
                              jnp.asarray([3]), jnp.asarray([True]), x[2:],
                              dt[2:], a, b[2:], c[2:], interpret=True)
    np.testing.assert_allclose(y, want_y[2:], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ssm.from_packed(out, k)[0, 3], want_s[2],
                               rtol=1e-6, atol=1e-6)


def test_the_plan_finds_lanes_and_tail_rows_from_the_maps():
    """Two prefill lanes (3 and 5 rows, padded to 8 each) and three
    decode rows, one of them idle: every row's sequence, where a lane
    starts from zero, from its slot or from a snapshot, and where one
    saves."""
    maps = np.zeros((3, 16), np.int32)
    maps[:, 3] = (1, 0, 0)       # lane A writes block 3, position 0 on
    maps[:, 5] = (2, 9, 7)       # lane B: loads snapshot 7, saves to 9
    maps[:, 6] = (3, 0, 0)       # a decode lane
    maps[:, 8] = (4, 11, 0)      # a decode lane at a block's last slot
    ws = np.zeros((19,), np.int32)
    pos = np.zeros((19,), np.int32)
    ws[0:3], pos[0:3] = 3 * BS + np.arange(3), np.arange(3)
    ws[8:12], pos[8:12] = 5 * BS + np.arange(4), 16 + np.arange(4)
    ws[16], pos[16] = 6 * BS + 1, 41
    ws[17], pos[17] = 0, 0       # idle
    ws[18], pos[18] = 8 * BS + 3, 23
    plan = ssm.plan_rows(jnp.asarray(ws), jnp.asarray(pos),
                         jnp.asarray(maps), BS, lanes=2, lane_rows=8,
                         tail=3)
    assert plan.lead == 16
    assert plan.dst.tolist() == [1, 2] and plan.length.tolist() == [3, 4]
    assert plan.zero.tolist() == [True, False]
    assert plan.src.tolist() == [1, 7] and plan.save.tolist() == [0, 9]
    assert plan.rows_out[0].tolist() == [0, 1, 2] + [16] * 5
    assert plan.rows_out[1].tolist() == [8, 9, 10, 11] + [16] * 4
    assert plan.t_dst.tolist() == [3, 0, 4]
    assert plan.t_src.tolist() == [3, 0, 4]
    assert plan.t_save.tolist() == [0, 0, 11]


# -- (b) through the runner -----------------------------------------------------
@pytest.mark.parametrize("chunk", [16, 7])
def test_chunked_prefill_then_decode_equals_the_reference(eng, chunk):
    # chunks of 7 run across the boundaries: nothing is saved there
    tokens = ids(70, seed=chunk)
    saves = eng.block_manager.snapshot_saves
    rows, cached, table = serve(eng, tokens, 53, chunk, reuse=False)
    assert cached == 0
    assert_rows(rows, reference(eng.runner.params, tokens))
    # boundaries at 16, 32, 48 by aligned chunks and at 64 by a decode
    # lane; chunks of 7 save none in prefill (49 | 56 run across 48...)
    assert eng.block_manager.snapshot_saves - saves == (
        4 if chunk == 16 else 1)
    eng.block_manager.free(table)
    assert eng.block_manager.state_slots_in_use == 0


def test_the_tree_and_the_state_group_are_what_the_counts_say(eng):
    r = eng.runner
    assert isinstance(eng.block_manager, StateBlockManager)
    assert MC.units() == (("EM", 3, 0, 0), ("*", 1, 0, 3))
    (experts, mixer), (attn,) = r.params["segments"]
    assert experts["w_up"].shape == (3, 8, 16, 24)
    assert experts["w_lat_in"].shape == (3, 32, 16)
    assert experts["ws_up"].shape == (3, 32, 48)
    assert "w_gate" not in experts and "ws_gate" not in experts
    assert mixer["w_in"].shape == (3, 32, 64 + 96 + 8)
    assert mixer["conv_w"].shape == (3, 4, 96)
    assert attn["wq"].shape == (1, 32, 64)
    assert sum(a.size for a in jax.tree.leaves(r.params)) == MC.num_params()
    slots = 1 + r.num_state_slots + r.num_snapshots
    assert (r.num_state_slots, r.num_snapshots) == (4, 12)
    # 8 heads of 8 in 2 groups: 4 heads of a group side by side
    assert r.k_cache["ssm"]["s"].shape == (3, slots, 2, 8, 32)
    assert r.k_cache["ssm"]["conv"].shape == (3, slots, 3, 96)
    state = sum(a.nbytes for a in jax.tree.leaves(r.k_cache["ssm"]))
    assert state == slots * MC.state_bytes_per_seq(4)
    assert eng._layer_group_stats()["ssm_stats"]["snapshots_resident"] == (
        eng.block_manager.snapshots_resident)


def test_the_family_builds_the_tree_the_program_serves(eng):
    mine = jax.eval_shape(
        lambda k: family.init_params(MC, k, jnp.float32), jax.random.key(0))
    theirs = jax.eval_shape(
        lambda k: layer_groups.init_params(MC, k, jnp.float32),
        jax.random.key(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.leaves(mine) == jax.tree.leaves(theirs)


def test_a_hit_is_restored_from_a_snapshot_and_one_without_is_cut_back(eng):
    """A sequence served and freed; a second that shares its first 39
    tokens hits at the deepest boundary under them (32) and starts from
    the snapshot there; after that snapshot is gone a third is cut back
    to 16, and with none left a fourth to nothing: all serve the cold
    run's log-probabilities, and the cut-back tokens are counted."""
    e, bm = eng, eng.block_manager
    first = ids(50, seed=21)
    rows, cached, table = serve(e, first, 50, CHUNK, reuse=False)
    bm.free(table)
    was = (bm.snapshot_restores, bm.cutback_tokens)
    second = first[:39] + ids(20, seed=22)
    rows, cached, table2 = serve(e, second, 50, CHUNK)
    assert cached == 32
    assert (bm.snapshot_restores, bm.cutback_tokens) == (
        was[0] + 1, was[1] + 4)                  # 36 hashed, 32 granted
    assert_rows(rows, reference(e.runner.params, second))
    bm.free(table2)
    # the snapshot at 32 goes: the hit ends at 16
    bm._drop_snapshot(bm.snapshots[bm.blocks[table[7]].block_hash])
    third = first[:39] + ids(20, seed=23)
    assert bm.match_prefix(third[:50])[1] == 16
    rows, cached, table3 = serve(e, third, 50, CHUNK)
    assert cached == 16 and bm.cutback_tokens == was[1] + 4 + 20
    assert_rows(rows, reference(e.runner.params, third))
    bm.free(table3)
    # the third recomputed 16..32 and saved the boundary again
    assert bm.blocks[table3[7]].block_hash in bm.snapshots or (
        bm.blocks[table[7]].block_hash in bm.snapshots)
    for slot in list(bm._snap_hash):
        bm._drop_snapshot(slot)
    fourth = first[:39] + ids(20, seed=24)
    rows, cached, table4 = serve(e, fourth, 50, CHUNK)
    assert cached == 0
    assert_rows(rows, reference(e.runner.params, fourth))
    bm.free(table4)


# -- (c) through the engine -----------------------------------------------------
def greedy(e, prompts: dict, max_tokens=10, late=None):
    """Serve `prompts` ({id: ids}; `late` ids are admitted at step 4)
    greedily -> {id: RequestOutput}."""
    sp = SamplingParams(max_tokens=max_tokens, temperature=0.0,
                        ignore_eos=True)
    late = late or ()
    for rid, p in prompts.items():
        if rid not in late:
            e.add_request(rid, prompt_token_ids=p, sampling_params=sp)
    done = {}
    for step in range(400):
        if step == 4:
            for rid in late:
                e.add_request(rid, prompt_token_ids=prompts[rid],
                              sampling_params=sp)
        for o in e.step():
            if o.finished:
                done[o.request_id] = o
        if len(done) == len(prompts):
            break
    return done


def assert_greedy(e, prompts, done):
    for rid, prompt in prompts.items():
        got = list(done[rid].token_ids)
        ref = reference(e.runner.params, prompt + got)
        want = [int(np.argmax(ref[len(prompt) - 1 + i]))
                for i in range(len(got))]
        assert got == want, rid


def test_k_fused_decode_steps_are_k_single_ones():
    prompts = {"a": ids(37, seed=41), "b": ids(22, seed=42)}
    fused = engine(num_scheduler_steps=4)
    one = greedy(engine(num_scheduler_steps=1), prompts, max_tokens=12)
    four = greedy(fused, prompts, max_tokens=12)
    for rid in prompts:
        assert list(one[rid].token_ids) == list(four[rid].token_ids)
    assert_greedy(fused, prompts, four)


def test_sequences_packed_in_one_round_do_not_leak(kernel_eng):
    """Ragged-rows prefill groups, fused decode rounds and lane-typed
    mixed rounds: the second and third requests are admitted while the
    first decodes; the third shares the first's 32 leading tokens
    through the prefix cache and a snapshot. Every sequence generates
    what the reference does alone."""
    e = kernel_eng
    before = e.runner.moe_stats()
    a = ids(41, seed=31)
    prompts = {"a": a, "b": ids(27, seed=32), "c": a[:35] + ids(9, seed=33)}
    done = greedy(e, prompts, late=("b", "c"))
    assert done["c"].num_cached_tokens == 32
    assert_greedy(e, prompts, done)
    jax.block_until_ready(list(e.runner._stats_pending))
    routed, local, active = (
        x - y for x, y in zip(e.runner.moe_stats(), before))
    rows = (41 + 9) + (27 + 9) + (44 - 32 + 9)
    assert routed == 3 * 4 * rows       # 3 routed layers, top-4
    assert 0 < local < routed and active > 0
    stats = e._layer_group_stats()["ssm_stats"]
    assert stats["snapshot_restores"] >= 1
    assert stats["lane_layer_steps"] > 0
    assert stats["state_slots_in_use"] == 0


def test_a_preempted_sequence_recomputes_and_a_full_pool_evicts(monkeypatch):
    """Six blocks more than the prompts need: the second sequence is
    preempted when the pool runs out, gives its state slot back and
    recomputes; a snapshot pool of 2 slots (one a lane) evicts."""
    from production_stack_tpu.engine.model_runner import ModelRunner

    monkeypatch.setattr(ModelRunner, "SNAPSHOTS_A_LANE", 1)
    e = engine(num_kv_blocks=30, max_num_seqs=2)
    prompts = {"a": ids(40, seed=51), "b": ids(40, seed=52)}
    done = greedy(e, prompts, max_tokens=24)
    assert e._preemptions_total > 0
    assert_greedy(e, prompts, done)
    bm = e.block_manager
    assert bm.state_slots_in_use == 0
    assert bm.snapshot_evictions > 0
    assert bm.snapshots_resident <= bm.num_snapshots == 2
    assert not bm._pending and not any(bm._pins.values())


# -- (d) the block manager alone ------------------------------------------------
def manager(num_snapshots=4, num_blocks=256, slots=2):
    return StateBlockManager(num_blocks, BS, True, num_state_slots=slots,
                             num_snapshots=num_snapshots, interval_blocks=4)


def play(bm, tokens, n_out=0, chunk=CHUNK):
    table, cached = bm.allocate_prompt(tokens)
    n, done = len(tokens), cached // BS
    prev = bm.blocks[table[done - 1]].block_hash if done else 0
    tokens = tokens + [7] * n_out

    def register(upto):
        nonlocal prev, done
        for i in range(done, upto // BS):
            prev = bm.register_block(
                prev, tuple(tokens[i * BS:(i + 1) * BS]), table[i])
            bm.note_saved(table, i)
        done = max(done, upto // BS)

    start = cached
    while start < n:
        end = min(start + chunk, n)
        bm.prepare_chunk(table, start, end)
        register(end)
        start = end
    for pos in range(n, n + n_out):
        assert bm.ensure_capacity(pos + 1, table)
        register(pos + 1)
    bm.free(table)
    return cached, table


def test_a_sequence_owns_a_slot_and_admission_waits_for_one():
    bm = manager(slots=1)
    t1, _ = bm.allocate_prompt(ids(10, seed=1))
    assert t1.slot == 1 and bm.state_slots_in_use == 1
    assert all(bm.maps[0, b] == 1 for b in t1)
    assert bm.allocate_prompt(ids(10, seed=2)) is None
    bm.free(t1)
    t2, _ = bm.allocate_prompt(ids(10, seed=2))
    assert t2.slot == 1
    bm.free(t2)


def test_passed_snapshots_go_first_and_a_shared_end_is_kept():
    """A sequence's own earlier boundaries go before any sequence's
    deepest one; the end of a shared prompt, where two prompts' hits
    ended, is no trail."""
    bm = manager(num_snapshots=5)
    doc = ids(34, seed=4)                 # boundaries at 16 and 32
    play(bm, doc)
    assert bm.snapshots_resident == 2 and len(bm._trail) == 1
    for seed in (5, 6):                   # two sessions start on it
        cached, _ = play(bm, doc + ids(20, seed=seed))   # and save 48
        assert cached == 32
    # 16 (passed), 32 (two hits ended there), two sessions' 48s
    assert bm.snapshots_resident == 4 and len(bm._trail) == 1
    play(bm, ids(70, seed=7))             # 4 boundaries of somebody else's
    cached, _ = play(bm, doc + ids(20, seed=8))
    assert cached == 32 and bm.snapshot_evictions > 0


def test_a_snapshot_goes_with_its_block_and_pins_hold():
    bm = manager(num_snapshots=4, num_blocks=14)
    first = ids(36, seed=9)               # 9 blocks of 13
    play(bm, first)
    assert bm.snapshots_resident == 2
    table, cached = bm.allocate_prompt(first[:34] + ids(6, seed=10))
    assert cached == 32 and table.load and bm._pins[table.load] == 1
    assert bm.maps[2, table[8]] == table.load
    bm.prepare_chunk(table, 32, 40)
    bm.free(table)
    assert not any(bm._pins.values())
    assert bm.maps[2, table[8]] == 0
    # another prompt takes every block: the cached ones are evicted and
    # their snapshots go with them
    play(bm, ids(50, seed=11))
    assert bm.match_prefix(first)[1] == 0
    assert set(bm._snap_hash.values()) == set(bm.snapshots)


# -- (e) the expert layer -------------------------------------------------------
def test_the_ranks_routed_parts_add_up_to_the_uncut_layer():
    """Four ranks, each with its slice of 16 experts, through the latent
    projections: their routed parts sum to what one engine with all 16
    computes; the shared expert and everything else are counted once."""
    k = jax.random.split(jax.random.key(5), 7)
    n, h, lat, f, e = 9, 32, 16, 24, 16
    x = jax.random.normal(k[0], (n, h))
    v = x @ (jax.random.normal(k[1], (h, lat)) * h ** -0.5)
    router = jax.random.normal(k[2], (h, e))
    bias = 0.1 * jax.random.normal(k[3], (e,))
    w_up = jax.random.normal(k[4], (e, lat, f)) * lat ** -0.5
    w_down = jax.random.normal(k[5], (e, f, lat)) * f ** -0.5
    w_b = jax.random.normal(k[6], (lat, h)) * lat ** -0.5
    kw = dict(top_k=4, scoring="sigmoid", renorm=True, scale=2.5,
              act="relu2", expert_x=v)
    whole, st = moe.routed_experts(x, router, bias, None, w_up, w_down,
                                   first_expert=0, **kw)
    parts = [moe.routed_experts(
        x, router, bias, None, w_up[r * 4:r * 4 + 4],
        w_down[r * 4:r * 4 + 4], first_expert=r * 4, **kw)
        for r in range(4)]
    np.testing.assert_allclose(sum(p[0] for p in parts) @ w_b, whole @ w_b,
                               rtol=1e-5, atol=1e-5)
    assert sum(int(p[1][1]) for p in parts) == int(st[1]) == n * 4
    # and the layer is the reference's
    s = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(s + bias, 4)
    w = jnp.take_along_axis(s, chosen, 1)
    w = w / w.sum(-1, keepdims=True) * 2.5
    want = sum(
        jnp.sum(jnp.where(chosen == j, w, 0.0), -1)[:, None]
        * (jnp.square(jax.nn.relu(v @ w_up[j])) @ w_down[j])
        for j in range(e))
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows", [16, 48])
def test_the_ungated_kernel_is_the_ragged_dots(rows):
    """`expert_ffn` without a gate matrix, the Mosaic kernel in
    interpret mode against the ragged dots, at an f that only 128-lane
    tiles divide (384 = 3 x 128)."""
    k = jax.random.split(jax.random.key(rows), 3)
    d, f, e = 128, 384, 6
    xs = jax.random.normal(k[0], (rows, d), jnp.float32)
    w_up = jax.random.normal(k[1], (2 * e, d, f)) * d ** -0.5
    w_down = jax.random.normal(k[2], (2 * e, f, d)) * f ** -0.5
    sizes = jnp.asarray([3, 0, 5, 1, 0, 4], jnp.int32)
    args = (xs, None, w_up, w_down, sizes, 2, e)
    want = ef._plain(*args, act="relu2")
    got = ef.expert_ffn(*args, interpret=True, act="relu2")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[:2])) and not np.any(
        np.asarray(got[15:]))


# -- (f) the reader and its refusals --------------------------------------------
HF = {
    "model_type": "nemotron_h", "vocab_size": 384, "hidden_size": 32,
    "num_hidden_layers": 7, "hybrid_override_pattern": "EMEMEM*",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 8, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "mlp_hidden_act": "relu2", "n_routed_experts": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 24,
    "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
    "max_position_embeddings": 256, "rope_theta": 10000,
    "use_conv_bias": True, "n_group": 1, "topk_group": 1,
    "ep_size": 2, "ep_rank": 0, "num_nextn_predict_layers": 1,
    "mtp_hybrid_override_pattern": "*E",
}


def read(tmp_path, **changes):
    with open(tmp_path / "config.json", "w") as f:
        json.dump({**HF, **changes}, f)
    return mcfg.from_hf_config(str(tmp_path), name=MC.name)


def test_the_reader_builds_the_preset(tmp_path):
    assert read(tmp_path) == MC


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("mamba_proj_bias", True),
    ("use_bias", True), ("mlp_bias", True),
    ("moe_shared_expert_overlap", True), ("attention_bias", True),
    ("use_conv_bias", False), ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"), ("norm_topk_prob", False),
    ("sliding_window", 128),
])
def test_what_has_no_code_path_is_refused_by_name(tmp_path, key, value):
    with pytest.raises(ValueError, match=key):
        read(tmp_path, **{key: value})


def test_a_plain_mlp_block_and_a_wrong_pattern_are_refused(tmp_path):
    with pytest.raises(ValueError, match="plain-MLP"):
        read(tmp_path, hybrid_override_pattern="EMEM-M*")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        read(tmp_path, hybrid_override_pattern="EMEM*")
    with pytest.raises(ValueError, match="mamba_num_heads"):
        read(tmp_path, mamba_head_dim=16)


@pytest.mark.parametrize("kw,named", [
    (dict(num_speculative_tokens=2), "--num-speculative-tokens"),
    (dict(enable_lora=True), "--enable-lora"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(pipeline_parallel_size=2), "--pipeline-parallel-size"),
    (dict(cpu_offload_bytes=1 << 20), "KV offload tiers"),
    (dict(kv_role="prefill"), "PD transfer"),
])
def test_what_a_recurrent_state_cannot_be_served_with_is_refused(kw, named):
    with pytest.raises(ValueError, match="recurrent state") as err:
        engine(**kw)
    assert named in str(err.value)


def test_embeddings_are_refused_as_for_every_layer_group_model(eng):
    # the embed program runs outside the block manager: no state slot
    with pytest.raises(NotImplementedError, match="layer groups"):
        eng.embed_one("no slot for this")


def test_the_units_are_the_cover_with_the_fewest_traced_bodies():
    def units(pattern):
        return [(u, c) for u, c, _, _ in dataclasses.replace(
            MC, block_pattern=pattern, num_layers=len(pattern),
            layer_kinds=(0,) * pattern.count("*")).units()]

    assert units("EMEMEMEMEM*") == [("EM", 5), ("*", 1)]
    assert sum(len(u) for u, _ in units("MEMEMEM*EME")) == 7
    assert units("MMMM") == [("M", 4)]
    published = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                 "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    cover = units(published)
    assert "".join(u * c for u, c in cover) == published
    assert sum(len(u) for u, _ in cover) <= 30

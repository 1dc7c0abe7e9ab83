"""A stack of single-sublayer blocks with gated delta-rule linear
attention (KDA, `ops/kda.py`) beside latent attention without positions:
the matrix state a head in the state group, the query projected
directly, a gated dense MLP and gated routed experts at the model's own
width, against the plain reference of the kimi_linear family
(benchmarks/chip/families/kimi_linear.py::forward_logprobs: a sequential
scan over the tokens, un-absorbed attention, nothing of the program
imported), at the tiny widths of `pst-tiny-kimi-debug`: K-KEKE*EKE, 4
heads of 8 key and 16 value dims, 4 taps, chunks of 8 rows under prompts
of 40-70, a 32-dim latent row + 8 shared key dims, 16 experts top-4
(rank 0 of 2 holds 8) of width 24. Blocks of 4 tokens, prefill chunks of
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
from production_stack_tpu.models.config import TINY_KIMI_DEBUG as MC
from production_stack_tpu.ops import kda, moe

TOL = 2e-4
BS = 4
CHUNK = 16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "kimi_linear_family_for_layer_group_tests",
    os.path.join(ROOT, "benchmarks", "chip", "families", "kimi_linear.py"))
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
    """ONE engine a module (eight traced bodies a program: building it
    twice is a minute of the tests' clock): the programs the chip runs
    (ragged rows, the fused decode round of 4 steps), the Pallas walk in
    interpret mode; the runner-level tests drive its single prefill and
    decode programs."""
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


# -- (a) the recurrence ---------------------------------------------------------
def _rule_inputs(t, seed, strongest=False):
    """q, k normed, v, log-decays, beta, a state that is not zero. With
    `strongest` the decays are the initialisation's strongest: A = 16
    times a softplus of order one, exp(-16) a row and channel, under
    which exp(G_r) / exp(G_i) overflows float32 within a chunk."""
    k = jax.random.split(jax.random.key(seed), 6)
    h, kd, vd = 4, 8, 16
    g = -jax.nn.softplus(jax.random.normal(k[3], (t, h, kd)))
    return (kda.l2_norm(jax.random.normal(k[0], (t, h, kd))) * kd ** -0.5,
            kda.l2_norm(jax.random.normal(k[1], (t, h, kd))),
            jax.random.normal(k[2], (t, h, vd)),
            16.0 * g if strongest else g,
            jax.nn.sigmoid(jax.random.normal(k[4], (t, h))),
            jax.random.normal(k[5], (h, kd, vd)))


def _recurrence(q, k, v, g, beta, s0):
    def token(s, x):
        o, s = kda.scan_step(*(a[None] for a in x), s[None])
        return s[0], o[0]

    s, o = jax.lax.scan(token, s0, (q, k, v, g, beta))
    return o, s


@pytest.mark.parametrize("strongest", [False, True])
@pytest.mark.parametrize("t", [1, 7, 8, 13, 29])
def test_the_chunked_form_is_the_recurrence_from_a_state_that_is_not_zero(
        t, strongest):
    args = _rule_inputs(t, seed=t, strongest=strongest)
    o, s_end = kda.scan_chunked(*args, chunk=8)
    want_o, want_s = _recurrence(*args)
    assert np.all(np.isfinite(np.asarray(o)))
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_end, want_s, rtol=1e-4, atol=1e-4)


def test_the_quotient_of_cumulative_decays_would_overflow():
    """Why every exp(G_r - G_i) is one exponential of a difference: at
    the strongest decays exp(-G) within a chunk of 8 is not finite."""
    g = _rule_inputs(8, seed=8, strongest=True)[3]
    assert not np.all(np.isfinite(np.asarray(
        jnp.exp(-jnp.cumsum(g, axis=0)))))


def test_rows_that_are_no_tokens_pass_the_state_unchanged():
    q, k, v, g, beta, s0 = _rule_inputs(13, seed=3)
    g, beta = g.at[9:].set(0.0), beta.at[9:].set(0.0)
    _, s_all = kda.scan_chunked(q, k, v, g, beta, s0, chunk=8)
    _, s_nine = kda.scan_chunked(q[:9], k[:9], v[:9], g[:9], beta[:9], s0,
                                 chunk=8)
    np.testing.assert_allclose(s_all, s_nine, rtol=1e-5, atol=1e-5)


def test_the_inverse_of_a_unit_lower_triangle_is_exact():
    a = jnp.tril(jax.random.normal(jax.random.key(2), (3, 16, 16)), -1)
    t = kda.unit_lower_inverse(a)
    np.testing.assert_allclose(
        jnp.matmul(jnp.eye(16) + a, t), jnp.broadcast_to(jnp.eye(16), a.shape),
        rtol=1e-4, atol=1e-4)


def test_the_update_kernel_is_the_recurrence_in_place():
    """`state_update`, the Mosaic kernel in interpret mode: lanes read
    their own slot, a snapshot's, or start from zero; each writes its
    slot of the one layer and nothing else moves."""
    layers, slots, r = 2, 5, 3
    q, k, v, g, beta, _ = _rule_inputs(r, seed=11)
    s_all = jax.random.normal(jax.random.key(12), (layers, slots, 4, 8, 16))
    src, dst = jnp.asarray([1, 4, 0]), jnp.asarray([1, 2, 0])
    zero = jnp.asarray([False, False, True])
    y, out = kda.state_update(s_all, jnp.int32(1), src, dst, zero, q, k, v,
                              g, beta, interpret=True)
    s0 = jnp.where(zero[:, None, None, None], 0.0, s_all[1, src])
    want_y, want_s = kda.scan_step(q, k, v, g, beta, s0)
    np.testing.assert_allclose(y[:2], want_y[:2], rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(y[2]))      # nobody's lane: skipped
    np.testing.assert_allclose(out[1, dst[:2]], want_s[:2], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(out[0], s_all[0])
    np.testing.assert_array_equal(out[1, 3:], s_all[1, 3:])
    # a lane that starts from zero, in a slot of its own
    y, out = kda.state_update(
        s_all, jnp.int32(0), jnp.asarray([3]), jnp.asarray([3]),
        jnp.asarray([True]), q[2:], k[2:], v[2:], g[2:], beta[2:],
        interpret=True)
    np.testing.assert_allclose(y, want_y[2:], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[0, 3], want_s[2], rtol=1e-6, atol=1e-6)


# -- (b) through the runner -----------------------------------------------------
@pytest.mark.parametrize("chunk", [16, 7])
def test_chunked_prefill_then_decode_equals_the_reference(eng, chunk):
    # chunks of 7 run across the boundaries: nothing is saved there
    tokens = ids(70, seed=chunk)
    saves = eng.block_manager.snapshot_saves
    rows, cached, table = serve(eng, tokens, 53, chunk, reuse=False)
    assert cached == 0
    assert_rows(rows, reference(eng.runner.params, tokens))
    assert eng.block_manager.snapshot_saves - saves == (
        4 if chunk == 16 else 1)
    eng.block_manager.free(table)
    assert eng.block_manager.state_slots_in_use == 0


def test_the_tree_and_the_state_group_are_what_the_counts_say(eng):
    r = eng.runner
    assert isinstance(eng.block_manager, StateBlockManager)
    # four kinds of block against a best cover of eight bodies: one
    # stack a kind, walked under one scan that switches on the letter
    assert "".join(u * c for u, c, _, _ in MC.units()) == "K-KEKE*EKE"
    assert sum(len(u) for u, _, _, _ in MC.units()) == 8 and MC.switched
    assert [(u, c) for u, c, _, _ in MC.tree_units()] == [
        ("K", 4), ("-", 1), ("E", 4), ("*", 1)]
    (mixer,), (dense,), (experts,), (attn,) = r.params["segments"]
    # [v | k | q | f_a | g_a | beta]: one product a row
    assert mixer["w_in"].shape == (4, 32, 4 * 16 + 2 * 4 * 8 + 2 * 8 + 4)
    assert mixer["conv_w"].shape == (4, 4, 128)
    assert mixer["w_fb"].shape == (4, 8, 32)
    assert mixer["dt_bias"].shape == (4, 32) and mixer["A_log"].shape == (4, 4)
    assert experts["w_gate"].shape == (4, 8, 32, 24)
    assert experts["ws_gate"].shape == (4, 32, 24)
    assert attn["wq"].shape == (1, 32, 4 * 24) and "w_dq" not in attn
    assert attn["w_dkv"].shape == (1, 32, 40)
    assert dense["w_gate"].shape == (1, 32, 64)
    assert sum(a.size for a in jax.tree.leaves(r.params)) == MC.num_params()
    slots = 1 + r.num_state_slots + r.num_snapshots
    assert (r.num_state_slots, r.num_snapshots) == (4, 12)
    # a head's (K, V) matrix: keys on the sublanes, values on the lanes
    assert r.k_cache["ssm"]["s"].shape == (4, slots, 4, 8, 16)
    assert r.k_cache["ssm"]["conv"].shape == (4, slots, 3, 128)
    state = sum(a.nbytes for a in jax.tree.leaves(r.k_cache["ssm"]))
    assert state == slots * MC.state_bytes_per_seq(4)
    # ONE latent cache array, no V array
    assert r.k_cache["g"][0].shape[:2] == (1, 1)
    assert r.k_cache["g"][0].shape[-1] == 40 and r.v_cache["g"] == (None,)


def test_the_family_builds_the_tree_the_program_serves():
    mine = jax.eval_shape(
        lambda k: family.init_params(MC, k, jnp.float32), jax.random.key(0))
    theirs = jax.eval_shape(
        lambda k: layer_groups.init_params(MC, k, jnp.float32),
        jax.random.key(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.leaves(mine) == jax.tree.leaves(theirs)


@pytest.mark.parametrize("preset", ["TINY_KIMI_DEBUG", "TINY_NEMOTRON_DEBUG"])
def test_the_counts_are_the_built_tree(preset):
    mc = getattr(mcfg, preset)
    tree = jax.eval_shape(
        lambda k: layer_groups.init_params(mc, k, jnp.bfloat16),
        jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == mc.num_params()
    assert mc.num_params() == mc.vocab_size * mc.hidden_size * 2 + (
        mc.hidden_size + sum(mc.block_params(c) for c in mc.block_pattern))


def test_a_hit_is_restored_from_a_snapshot_and_one_without_is_cut_back(eng):
    """A sequence served and freed; a second that shares its first 39
    tokens hits at the deepest boundary under them (32) and starts from
    the snapshot there; after that snapshot is gone a third is cut back
    to 16: both serve the cold run's log-probabilities, and the cut-back
    tokens are counted."""
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


def test_k_fused_decode_steps_are_k_single_ones(eng):
    """The fused round of 4 steps generates what single decode steps,
    teacher-forced on its tokens through the runner, would choose."""
    prompts = {"a": ids(37, seed=41), "b": ids(22, seed=42)}
    four = greedy(eng, prompts, max_tokens=12)
    assert_greedy(eng, prompts, four)
    for rid, prompt in prompts.items():
        got = list(four[rid].token_ids)
        rows, _, table = serve(eng, prompt + got, len(prompt), CHUNK,
                               reuse=False)
        assert got == [int(np.argmax(rows[len(prompt) - 1 + i]))
                       for i in range(len(got))]
        eng.block_manager.free(table)
    stats = eng._layer_group_stats()["ssm_stats"]
    # at most two live lanes a call of the update kernel
    assert stats["update_calls"] > 0
    assert stats["lane_layer_steps"] <= 2 * stats["update_calls"]


def test_sequences_packed_in_one_round_do_not_leak(eng):
    """Ragged-rows prefill groups, fused decode rounds and lane-typed
    mixed rounds: the second and third requests are admitted while the
    first decodes; the third shares the first's 32 leading tokens
    through the prefix cache and a snapshot. Every sequence generates
    what the reference does alone."""
    e = eng
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
    assert routed == 4 * 4 * rows       # 4 routed layers, top-4
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


# -- (d) the layers alone -------------------------------------------------------
def test_the_ranks_routed_parts_add_up_to_the_uncut_layer():
    """Two ranks, each with its half of 16 gated experts at the model's
    own width: their routed parts sum to what one engine with all 16
    computes, which is the reference's layer; the shared expert, the
    mixers and the dense MLP are every rank's alike and counted once."""
    k = jax.random.split(jax.random.key(5), 6)
    n, h, f, e = 9, 32, 24, 16
    x = jax.random.normal(k[0], (n, h))
    router = jax.random.normal(k[1], (h, e))
    bias = 0.1 * jax.random.normal(k[2], (e,))
    w_gate = jax.random.normal(k[3], (e, h, f)) * h ** -0.5
    w_up = jax.random.normal(k[4], (e, h, f)) * h ** -0.5
    w_down = jax.random.normal(k[5], (e, f, h)) * f ** -0.5
    kw = dict(top_k=4, scoring="sigmoid", renorm=True, scale=2.446)
    whole, st = moe.routed_experts(x, router, bias, w_gate, w_up, w_down,
                                   first_expert=0, **kw)
    parts = [moe.routed_experts(
        x, router, bias, w_gate[r * 8:r * 8 + 8], w_up[r * 8:r * 8 + 8],
        w_down[r * 8:r * 8 + 8], first_expert=r * 8, **kw)
        for r in range(2)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole,
                               rtol=1e-5, atol=1e-5)
    assert sum(int(p[1][1]) for p in parts) == int(st[1]) == n * 4
    s = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(s + bias, 4)
    w = jnp.take_along_axis(s, chosen, 1)
    w = w / w.sum(-1, keepdims=True) * 2.446
    want = sum(
        jnp.sum(jnp.where(chosen == j, w, 0.0), -1)[:, None]
        * ((jax.nn.silu(x @ w_gate[j]) * (x @ w_up[j])) @ w_down[j])
        for j in range(e))
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q_lora_rank", [0, 12])
def test_latent_attention_without_positions_is_the_unabsorbed_form(
        q_lora_rank):
    """`_latent_qkv` with no cos / sin caches [c; k_r] as projected and
    scores the absorbed query against it: the un-absorbed softmax over
    every head's own keys and values. With `q_lora_rank` 0 the query is
    one direct projection."""
    cfg = dataclasses.replace(MC, q_lora_rank=q_lora_rank)
    ak = cfg.kinds[0]
    n, h, nq, dk, dv = 11, 32, 4, 24, 16
    lat, rot, nope = 32, 8, 16
    k = jax.random.split(jax.random.key(7), 7)
    x = jax.random.normal(k[0], (n, h))
    lp = {"w_dkv": jax.random.normal(k[1], (h, lat + rot)) * h ** -0.5,
          "kv_norm": 1.0 + 0.1 * jax.random.normal(k[2], (lat,)),
          "w_ukv": jax.random.normal(k[3], (lat, nq * (nope + dv)))
          * lat ** -0.5}
    if q_lora_rank:
        lp |= {"w_dq": jax.random.normal(k[4], (h, q_lora_rank)) * h ** -0.5,
               "q_norm": jnp.ones((q_lora_rank,)),
               "w_uq": jax.random.normal(k[5], (q_lora_rank, nq * dk))
               * q_lora_rank ** -0.5}
        cq = x @ lp["w_dq"]
        cq = cq * jax.lax.rsqrt(jnp.mean(cq * cq, -1, keepdims=True) + 1e-5)
        q = (cq @ lp["w_uq"]).reshape(n, nq, dk)
    else:
        lp["wq"] = jax.random.normal(k[4], (h, nq * dk)) * h ** -0.5
        q = (x @ lp["wq"]).reshape(n, nq, dk)
    kc = jnp.zeros((1, 1, 16, lat + rot))
    slots = 1 + jnp.arange(n)
    q_abs, kc, w_uv = layer_groups._latent_qkv(
        cfg, ak, x, lp, kc, 0, slots, None, None, jnp.float32)
    rows = kc[0, 0, slots]
    mask = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.einsum("thl,sl->ths", q_abs, rows) * dk ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
    got = jnp.einsum("ths,sl,lhd->thd", p, rows[:, :lat], w_uv)
    # the un-absorbed form
    ckv = x @ lp["w_dkv"]
    c = ckv[:, :lat] * jax.lax.rsqrt(
        jnp.mean(ckv[:, :lat] ** 2, -1, keepdims=True) + 1e-5) * lp["kv_norm"]
    np.testing.assert_allclose(rows[:, lat:], ckv[:, lat:], rtol=1e-6)
    kv = (c @ lp["w_ukv"]).reshape(n, nq, nope + dv)
    s = (jnp.einsum("thd,shd->ths", q[..., :nope], kv[..., :nope])
         + jnp.einsum("thd,sd->ths", q[..., nope:], ckv[:, lat:])
         ) * dk ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
    want = jnp.einsum("ths,shd->thd", p, kv[..., nope:])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- (e) the one scan over a switched pattern -----------------------------------
def _one_step(r):
    """One decode step of the module's runner over its `max_num_seqs`
    lanes: what `_build_decode` jits, nothing donated, traced anew."""
    attn = r._decode_attn_closure()
    b = r.config.max_num_seqs

    def step(params, kc, vc, tokens, positions, write_slots, tables, ctx):
        kc, vc = r._enter_caches(kc, vc)
        return r._forward(
            MC, params, tokens, positions, kc, vc, write_slots,
            lambda q, l, k, v, spec=None: attn(
                q, l, k, v, tables=tables, context_lens=ctx, spec=spec),
            logits_rows=jnp.arange(b), **r._state_kw(0, 0, b))

    return jax.jit(step)


@pytest.mark.parametrize("shape,turned", [
    ((4, 2304, 12576), True),    # the KDA in-projections: 98.25 tiles
    ((1, 2304, 576), True),      # the latent down-projection: 4.5
    ((4, 4096, 2304), False),    # whole tiles both ways
    ((4, 2304, 256), False),
    ((4, 100, 12576), False),    # neither way whole: as written
    ((4, 2304), False),          # a stack of vectors
])
def test_a_stack_is_turned_where_the_chip_holds_it_turned(shape, turned):
    assert layer_groups._stored_turned(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16)) == turned


@pytest.mark.parametrize("case,prompts,lanes", [
    ("two_lanes", (37, 22), (0, 1)),
    # a lane BETWEEN two sequences holds none: slot 0, context 0
    ("an_empty_lane_between", (37, 22), (0, 2)),
    # positions 47 and 31 end blocks that end at a boundary (16 tokens):
    # the step saves both states to snapshot slots
    ("across_a_snapshot_boundary", (47, 31), (1, 3)),
])
def test_a_decode_step_is_the_same_with_a_stack_turned_and_as_written(
        eng, monkeypatch, case, prompts, lanes):
    """`forward_blocks` hands a loop of the scan a stack of matrices as
    the chip holds it and turns the block's slice back: the same
    product. At a tile of 8 the tiny preset's in-projections (32 x 148)
    are turned as the cell's are at 128; logits, the latent cache, the
    state pool, the convolution's rows and the routed layers' counters
    are what the stacks as written give."""
    r, bm = eng.runner, eng.block_manager
    b, pages = r.config.max_num_seqs, 16
    tokens = np.zeros(b, np.int32)
    positions, slots, ctx = (np.zeros(b, np.int32) for _ in range(3))
    tables = np.zeros((b, pages), np.int32)
    held = []
    for n, lane in zip(prompts, lanes):
        toks = ids(n + 1, seed=100 + n)
        _, _, table = serve(eng, toks[:n], n, CHUNK, reuse=False)
        assert bm.ensure_capacity(n + 1, table)
        held.append(table)
        tokens[lane], positions[lane], ctx[lane] = toks[n], n, n + 1
        slots[lane] = table[n // BS] * BS + n % BS
        tables[lane, :len(table)] = table
    saves = [int(bm.maps[1, t[n // BS]]) for t, n in zip(held, prompts)]
    assert all(saves) == (case == "across_a_snapshot_boundary")
    args = (r.params, r.k_cache, r.v_cache, *map(jnp.asarray, (
        tokens, positions, slots, tables, ctx)))
    stacks = r.params["segments"][0][0]          # the K kind's
    out = []
    for tile, which in ((1 << 30, []), (8, ["w_in"])):
        monkeypatch.setattr(layer_groups, "LANES", tile)
        assert [k for k, a in sorted(stacks.items())
                if layer_groups._stored_turned(a)] == which
        out.append(_one_step(r)(*args))
    written, turned = out
    for got, want in zip(jax.tree.leaves(turned), jax.tree.leaves(written)):
        # the CPU sums a product against a turned matrix in another
        # order: float32 rounding, no more
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    logits, kc, _ = turned
    # and the step did something: the rows' pairs were routed, each
    # sequence's state moved, a boundary's snapshot was written
    assert int(kc["stats"][0]) == 4 * 4 * len(prompts)
    before = r.k_cache["ssm"]["s"]
    for table, slot in zip(held, saves):
        assert np.any(np.asarray(kc["ssm"]["s"][:, table.slot])
                      != np.asarray(before[:, table.slot]))
        if slot:
            np.testing.assert_array_equal(
                kc["ssm"]["s"][:, slot], kc["ssm"]["s"][:, table.slot])
    assert np.all(np.isfinite(np.asarray(logits)[list(lanes)]))
    for table in held:
        bm.free(table)
    assert bm.state_slots_in_use == 0


def _under(jaxpr, path=()):
    """{a block kind's mark: the loops and branches it sits under, the
    shortest such path}: K by the scope `kda_step`, E by
    `shared_expert`, * by the paged attention kernel."""
    found: dict[str, tuple] = {}

    def note(mark, path):
        if mark not in found or len(path) < len(found[mark]):
            found[mark] = path

    for eqn in jaxpr.eqns:
        name, scopes = eqn.primitive.name, str(eqn.source_info.name_stack)
        if name == "pallas_call":
            note("*", path)
        for mark, scope in (("K", "kda_step"), ("E", "shared_expert")):
            if scope in scopes:
                note(mark, path)
        here = path + ((name,) if name in ("scan", "while", "cond") else ())
        for sub in jax.core.jaxprs_in_params(eqn.params):
            for mark, p in _under(sub, here).items():
                note(mark, p)
    return found


def _traced(r, monkeypatch, build, n_packed):
    """The jaxpr of a fresh step program and what the model's code was
    asked for while it was traced, in order: a letter a block body
    (K a delta-rule mixer, - a dense MLP, E routed experts with their
    shared expert, * latent attention) and the number of chunked KDA
    bodies."""
    calls = []

    def noting(mod, name, mark):
        real = getattr(mod, name)

        def wrapper(*a, **kw):
            calls.append(mark)
            return real(*a, **kw)

        monkeypatch.setattr(mod, name, wrapper)

    noting(kda, "mixer", "K")
    noting(kda, "scan_chunked", "c")
    noting(layer_groups, "routed_experts", "E")
    noting(layer_groups, "swiglu", "s")
    noting(layer_groups, "_latent_qkv", "*")

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    jaxpr = build().trace(
        spec(r.params), spec(r.k_cache), spec(r.v_cache),
        jax.ShapeDtypeStruct((n_packed,), jnp.int32)).jaxpr
    monkeypatch.undo()
    order = "".join(calls)
    return jaxpr, order.replace("c", "").replace("Es", "E").replace(
        "s", "-"), order.count("c")


def test_every_program_walks_the_blocks_under_the_one_scan(eng, monkeypatch):
    """Each kind of block is traced ONCE a forward in every kind of
    program (what set-up pays follows the kinds, not the pattern), under
    the one scan over the pattern: a loop of one turn or none around a
    kind that stands more than once, a branch around one that stands
    once, and in a turn the kinds that stand once FIRST (behind the
    others' loops the compiler fetched the latent block's `wo` ahead of
    its branch in every turn). A program with prefill rows holds ONE
    chunked KDA body."""
    r = eng.runner
    b, k = r.config.max_num_seqs, 4
    n_dec = r._decode_pack_layout(b, 64, False, stop_cap=0)[1]
    turn = ("while", "scan", "while")
    scanned = {"K": turn, "E": turn, "*": ("while", "scan", "cond")}
    for kw in ({}, {"want_logprobs": True}):
        jaxpr, order, chunked = _traced(
            r, monkeypatch,
            lambda: r._build_decode_multi(b, 64, k, stop_cap=0, **kw), n_dec)
        assert (order, chunked) == ("-*KE", 0)
        assert _under(jaxpr) == scanned
    jaxpr, order, chunked = _traced(
        r, monkeypatch,
        lambda: r._build_ragged_rows(32, 64, b, 64, k, stop_cap=0),
        sum(r._ragged_rows_pack_sizes(32, 64, b, 64, False, stop_cap=0)))
    # the step with the prefill rows, then the loop's step
    assert (order, chunked) == ("-*KE-*KE", 1)
    assert _under(jaxpr) == {c: p[1:] for c, p in scanned.items()}
    jaxpr, order, chunked = _traced(
        r, monkeypatch, lambda: r._build_prefill_rows(32, 64),
        r._rows_prefill_pack_layout(32, 64)[1])
    assert (order, chunked) == ("-*KE", 1)
    # no one-token rows behind the chunks: no `kda_step`
    assert _under(jaxpr) == {c: scanned[c][1:] for c in "E*"}


# -- (f) the reader and its refusals --------------------------------------------
HF = {
    "model_type": "kimi_linear", "vocab_size": 384, "hidden_size": 32,
    "intermediate_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 9,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": None, "mla_use_nope": True,
    "rope_scaling": None, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "hidden_act": "silu", "model_max_length": 256,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
        "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 24, "num_experts": 16,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "use_grouped_topk": True, "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "ep_size": 2, "ep_rank": 0,
}


def read(tmp_path, **changes):
    with open(tmp_path / "config.json", "w") as f:
        json.dump({**HF, **changes}, f)
    return mcfg.from_hf_config(str(tmp_path), name=MC.name)


def test_the_reader_builds_the_preset_but_for_the_value_width(tmp_path):
    # the published family has K = V; the preset keeps them apart so
    # that a transposed state shows
    assert read(tmp_path) == dataclasses.replace(
        MC, ssm_head_dim=8, ssm_chunk=16)


@pytest.mark.parametrize("key,value", [
    ("num_expert_group", 2), ("topk_group", 2),
    ("num_nextn_predict_layers", 1), ("moe_layer_freq", 2),
    ("hidden_act", "gelu"), ("moe_router_activation_func", "softmax"),
    ("q_lora_rank", 768), ("attention_bias", True),
    ("mla_use_nope", False),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
])
def test_what_has_no_code_path_is_refused_by_name(tmp_path, key, value):
    with pytest.raises(ValueError, match=key):
        read(tmp_path, **{key: value})


def test_layer_lists_that_do_not_share_out_the_layers_are_refused(tmp_path):
    lin = HF["linear_attn_config"]
    with pytest.raises(ValueError, match="kda_layers"):
        read(tmp_path, linear_attn_config={**lin, "kda_layers": [1, 2, 3]})
    with pytest.raises(ValueError, match="full_attn_layers"):
        read(tmp_path, linear_attn_config={
            **lin, "kda_layers": [1, 2, 3, 4, 5], "full_attn_layers": []})


def test_two_kinds_of_recurrence_in_one_stack_are_refused():
    with pytest.raises(ValueError, match="state-space .M. and delta-rule"):
        dataclasses.replace(MC, block_pattern="K-MEKE*EKE")
    with pytest.raises(ValueError, match="block_pattern"):
        dataclasses.replace(MC, intermediate_size=0)
    with pytest.raises(ValueError, match="block_pattern"):
        dataclasses.replace(MC, ssm_groups=2)


@pytest.mark.parametrize("kw,named", [
    (dict(num_speculative_tokens=2), "--num-speculative-tokens"),
    (dict(enable_lora=True), "--enable-lora"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(cpu_offload_bytes=1 << 20), "KV offload tiers"),
    (dict(kv_role="prefill"), "PD transfer"),
])
def test_what_a_recurrent_state_cannot_be_served_with_is_refused(kw, named):
    with pytest.raises(ValueError, match="recurrent state") as err:
        engine(**kw)
    assert named in str(err.value)


def test_the_units_keep_a_period_in_few_traced_bodies():
    def units(layers):
        p = "".join(("*" if i % 4 == 0 else "K") + ("-" if i == 1 else "E")
                    for i in range(1, layers + 1))
        return p, [(u, c) for u, c, _, _ in dataclasses.replace(
            MC, block_pattern=p, num_layers=layers,
            layer_kinds=(0,) * p.count("*")).units()]

    p, cover = units(27)     # the published depth
    assert "".join(u * c for u, c in cover) == p
    assert sum(len(u) for u, _ in cover) <= 14
    # and walked block by block it traces its four kinds once each
    mc = dataclasses.replace(MC, block_pattern=p, num_layers=27,
                             layer_kinds=(0,) * p.count("*"))
    assert mc.switched and [(u, c) for u, c, _, _ in mc.tree_units()] == [
        ("K", 21), ("-", 1), ("E", 26), ("*", 6)]
    assert not mcfg.TINY_NEMOTRON_DEBUG.switched

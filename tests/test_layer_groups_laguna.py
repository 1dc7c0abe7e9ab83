"""Query heads, rotary scheme and YaRN by layer kind, the per-head output
gate and softmax routing beside a shared expert in models/layer_groups.py
against the plain reference of the laguna family
(benchmarks/chip/families/laguna.py::forward_logprobs, which imports
nothing of the program), at the tiny widths of `pst-tiny-laguna-debug`:
layers 0-4's shape (full, window, window, window, full; layer 0 dense),
6 query heads on the full kind and 8 on the window kind over 2 kv heads,
half of a head rotated under YaRN with the factor on cos and sin against
the whole head plain, 16 softmax experts top-4 times 2.5 beside a shared
one, window 12 = three blocks of 4 with contexts of 40-80.

TOLERANCE 2e-4 on float32 log-probabilities, as tests/test_layer_groups.py
states it for logits: both sides compute in float32 and differ in the
order of sums. A wrong term moves them by 1e-2 to whole units.
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

from moved_block_map import fused_rounds_across_a_moved_map
from production_stack_tpu.engine.block_manager import WindowedBlockManager
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.models import config as mcfg
from production_stack_tpu.models import layer_groups
from production_stack_tpu.models.config import TINY_LAGUNA_DEBUG as MC
from production_stack_tpu.ops import attention as xla_attn
from production_stack_tpu.ops import expert_ffn as ef
from production_stack_tpu.ops import layers as ops_layers

TOL = 2e-4
BS = 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

_spec = importlib.util.spec_from_file_location(
    "laguna_family_for_layer_group_tests",
    os.path.join(ROOT, "benchmarks", "chip", "families", "laguna.py"))
family = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(family)


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


@pytest.fixture(scope="module")
def kernel_eng():
    """The programs the chip runs, the Pallas walk in interpret mode at
    GQA groups of 3 and 4 in one program."""
    e = engine(attention_impl="pallas", num_scheduler_steps=4)
    assert e.runner.ragged_kernel
    return e


def ids(n: int, seed: int = 0) -> list[int]:
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, MC.vocab_size - 4, n)]


def reference(params, tokens, cfg=MC) -> np.ndarray:
    """The family's log-probabilities at every position."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(family.forward_logprobs(
            cfg, params, jnp.asarray(tokens, jnp.int32),
            jnp.arange(len(tokens))))


def logprobs(logits) -> np.ndarray:
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits), -1))


def serve(e: LLMEngine, tokens, n_prompt: int, chunk: int, reuse=True):
    """tests/test_layer_groups.py::serve: chunked prefill, then decode
    teacher-forced, through the runner and the block manager of two
    pools. -> ({position: log-probabilities}, cached tokens, table)."""
    r, bm = e.runner, e.block_manager
    table, cached = bm.allocate_prompt(tokens[:n_prompt], reuse_cache=reuse)
    rows, start, prev_hash, done = {}, cached, 0, 0
    while start < n_prompt:
        end = min(start + chunk, n_prompt)
        bm.prepare_chunk(table, start, end)
        _, logits = r.prefill(tokens[start:end], start, table, end)
        rows[end - 1] = logprobs(logits)
        start = end
        for i in range(done, end // BS):
            prev_hash = bm.register_block(
                prev_hash, tuple(tokens[i * BS:(i + 1) * BS]), table[i])
        done = end // BS
    for pos in range(n_prompt, len(tokens)):
        assert bm.ensure_capacity(pos + 1, table)
        bm.release_behind(table, pos)
        logits = r.decode([tokens[pos]], [pos], [table], [pos + 1])
        rows[pos] = logprobs(logits[0])
    return rows, cached, table


def assert_rows(rows: dict, ref: np.ndarray, tol: float = TOL) -> None:
    for pos, row in rows.items():
        np.testing.assert_allclose(row, ref[pos], rtol=tol, atol=tol,
                                   err_msg=f"position {pos}")


# -- (a) through both cache groups ---------------------------------------------
@pytest.mark.parametrize("chunk", [16, 7])
def test_chunked_prefill_then_decode_equals_the_reference(eng, chunk):
    # 80 tokens: past YaRN's original 64 positions, six windows long
    tokens = ids(80, seed=chunk)
    rows, cached, table = serve(eng, tokens, 66, chunk, reuse=False)
    assert cached == 0
    assert_rows(rows, reference(eng.runner.params, tokens))
    eng.block_manager.free(table)


def test_the_cache_groups_have_the_kv_heads_and_the_tree_the_query_heads(
        eng):
    r = eng.runner
    kf, kw = r.k_cache["g"]
    vf, vw = r.v_cache["g"]
    # both groups 2 kv heads x 16; the window group a smaller pool
    assert kf.shape == vf.shape == (2, 2, 128 * BS, 16)
    assert kw.shape == vw.shape == (3, 2, r.num_window_blocks * BS, 16)
    assert type(eng.block_manager).__name__ == "WindowedBlockManager"
    full0, window, full1 = r.params["segments"]
    assert full0["wq"].shape == (1, 64, 6 * 16)
    assert window["wq"].shape == (3, 64, 8 * 16)
    assert window["wo"].shape == (3, 8 * 16, 64)
    assert full1["w_head_gate"].shape == (1, 64, 6)
    assert window["w_head_gate"].shape == (3, 64, 8)
    assert "router" not in full0 and full1["router"].shape == (1, 64, 16)
    assert sum(a.size for a in jax.tree.leaves(r.params)) == MC.num_params()
    stats = eng._layer_group_stats()
    assert set(stats["attn_context_by_kind"]) == {"full", "window"}
    assert stats["prefix_window_cutback_blocks"] == tuple(
        eng.block_manager.prefix_cutback)


def test_a_returning_sequence_hits_and_one_whose_twins_are_gone_is_cut_back(
        kernel_eng):
    """On the kernel path: a sequence served and freed, a second that
    shares its first 36 tokens (nine blocks, the three window blocks at
    the hit's end still resident), and a third after the window group
    lost a twin inside that window: its hit ends earlier, it serves the
    same log-probabilities, and the cut is counted."""
    e = kernel_eng
    bm = e.block_manager
    first = ids(40, seed=7)
    rows, cached, table = serve(e, first, 40, 16)
    assert cached == 0
    assert_rows(rows, reference(e.runner.params, first))
    bm.free(table)
    before = list(bm.prefix_cutback)
    second = first[:36] + ids(14, seed=8)
    rows, cached, table2 = serve(e, second, 44, 16)
    assert cached == 36
    assert bm.prefix_cutback == [before[0], before[1] + 1]
    assert_rows(rows, reference(e.runner.params, second))
    bm.free(table2)
    # a hit of 9 blocks needs the twins of blocks 6, 7, 8 (the query at
    # 36 reads keys 25..36); without block 7's it may end after block 6
    # (the query at 28 reads keys 17..28: blocks 4, 5, 6)
    bm._drop_twin(table[7])
    third = first[:36] + ids(14, seed=9)
    matched, n = bm.match_prefix(third[:44])
    assert n == 28 and len(matched) == 7
    rows, cached, table3 = serve(e, third, 44, 16)
    assert cached == 28
    assert bm.prefix_cutback == [before[0] + 2, before[1] + 2]
    assert_rows(rows, reference(e.runner.params, third))
    bm.free(table3)


def test_fused_rounds_across_a_moved_block_map_are_the_single_steps(
        kernel_eng):
    """Three fused rounds of four steps over lanes 0, 2 and (from the
    second) 3, GQA groups of 3 and 4 in each program: between two
    rounds the map lets pages go and takes new ones, a returning
    session's among them; each round maps its tables once, with the map
    of its own dispatch, and the tokens are the single-step path's
    (tests/moved_block_map.py). A lane that holds no sequence maps to
    the null block only."""
    fused_rounds_across_a_moved_map(kernel_eng, serve, ids)


def test_the_engine_serves_mixed_rounds_with_a_prefix_hit(kernel_eng):
    """Ragged-rows prefill groups, fused decode rounds and lane-typed
    mixed rounds with both query widths in each program: the second
    request is admitted while the first decodes and shares its first 24
    tokens through the prefix cache. The routed layers' counters count
    this model's pairs, the per-kind counters cut the window kind to
    ITS window."""
    e = kernel_eng
    before = e.runner.moe_stats()
    ctx_before = [c[0] for c in e.runner.attn_context_by_kind]
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
    jax.block_until_ready(list(e.runner._stats_pending))
    routed, local, active = (
        x - y for x, y in zip(e.runner.moe_stats(), before))
    # 4 routed layers, 4 experts a token; every expert is held here
    rows = 41 + 9 + (len(b) - 24) + 9
    assert routed == local == 4 * 4 * rows
    assert 0 < active <= 4 * 16 * 40
    full, window = (c[0] - was for c, was in zip(
        e.runner.attn_context_by_kind, ctx_before))
    assert 0 < window < full


def test_the_window_kinds_counter_is_cut_to_its_own_window():
    """The per-kind counter of a model whose window is 512 cuts a lane's
    context to 512, whatever another model's window is."""
    mc = mcfg._register(dataclasses.replace(
        MC, name="pst-tiny-laguna-w512", max_model_len=2048,
        attn_kinds=(MC.attn_kinds[0], dataclasses.replace(
            MC.attn_kinds[1], window=512))))
    try:
        r = engine(model=mc.name, block_size=32, num_kv_blocks=64).runner
        assert r._kind_windows == [None, 512]
        r._note_attn_context(decode_lens=[100, 600, 1000], steps=2,
                             prefill_lens=[300, 900])
        full, window = (c[0] for c in r.attn_context_by_kind)
        assert full == 2 * 1700 + 3 + 1200
        assert window == (100 + 101) + 4 * 512 + 300 + 512
    finally:
        mcfg._PRESETS.pop(mc.name)


# -- (a2) the window pool under sessions that come back -------------------------
def manager(num_window_blocks, window=12, num_blocks=256):
    return WindowedBlockManager(num_blocks, BS, True, window=window,
                                num_window_blocks=num_window_blocks)


def play(bm, tokens, n_out=0, chunk=16):
    """One sequence through the manager alone, as the engine drives it:
    the prompt in chunks, each chunk's full blocks registered when it is
    applied, then `n_out` decode positions. -> (cached tokens, table)."""
    table, cached = bm.allocate_prompt(tokens)
    n, prev, done = len(tokens), 0, 0
    tokens = tokens + [7] * n_out

    def register(upto):
        nonlocal prev, done
        for i in range(done, upto // BS):
            prev = bm.register_block(
                prev, tuple(tokens[i * BS:(i + 1) * BS]), table[i])
        done = max(done, upto // BS)

    register(cached)
    start = cached
    while start < n:
        end = min(start + chunk, n)
        bm.prepare_chunk(table, start, end)
        register(end)
        start = end
    for pos in range(n, n + n_out):
        assert bm.ensure_capacity(pos + 1, table)
        bm.release_behind(table, pos)
        register(pos + 1)
    bm.free(table)
    return cached, table


def test_a_long_prefill_recycles_trails_and_evicts_nobodys_end():
    """Twins a sequence passed over go before any twin a sequence ended
    on: after a prompt of 60 blocks through a pool of 24, the end of the
    sequence before it is still where a returning session needs it."""
    bm = manager(24)
    first = ids(40, seed=1)
    _, table = play(bm, first)
    # blocks 0-4 lay behind the last chunk's window, 5-9 it ended on
    assert len(bm._wtrail) == 5 and len(bm._wevictable) == 5
    play(bm, ids(240, seed=2))            # 60 blocks, alone
    assert len(bm._wevictable) == 5 + 7 and len(bm._wtrail) == 23 - 12
    assert all(bm.block_map[b] for b in table[5:10])
    cached, _ = play(bm, first + ids(9, seed=3))
    assert cached == 40 and bm.prefix_cutback == [0, 1]


def test_the_end_of_a_shared_document_is_learned_and_kept():
    """Where the prefix hits of two prompts ended, a twin that is passed
    over counts as an end: the shared document's last window survives
    prefills that recycle every trail."""
    bm = manager(24)
    doc = ids(64, seed=4)
    play(bm, doc + ids(3, seed=5))        # the document, primed
    for seed in (6, 7):                   # two sessions start on it
        cached, table = play(bm, doc + ids(30, seed=seed))
        assert cached == 64
    assert [bm._tail_ends[b] for b in table[13:16]] == [2, 2, 2]
    play(bm, ids(400, seed=8))            # 100 blocks of somebody else's
    cached, _ = play(bm, doc + ids(30, seed=9))
    assert cached == 64 and bm.prefix_cutback[0] == 0


def test_a_hit_that_was_cut_back_heals_for_the_next_prompt():
    """A cut costs one prefill, not one a prompt: the recomputed blocks
    take the hashes over from the cached copies that have no twins."""
    bm = manager(24)
    doc = ids(64, seed=10)
    _, first = play(bm, doc + ids(3, seed=11))
    for b in first[:16]:                  # every twin of the document
        bm._drop_twin(b)                  # is gone from the pool
    cached, second = play(bm, doc + ids(30, seed=12))
    assert cached == 0 and bm.prefix_cutback == [16, 1]
    # the recomputed copies are the cached blocks now, and keep twins
    # where the hit ended (a cut there counted twice)
    assert [bm.cached_blocks[bm.blocks[b].block_hash] for b in second[:16]
            ] == second[:16]
    assert all(bm.blocks[b].block_hash is None for b in first[:16])
    cached, third = play(bm, doc + ids(30, seed=13))
    assert cached == 64 and third[:16] == second[:16]
    assert bm.prefix_cutback == [16, 2]


# -- (b) every term is seen -----------------------------------------------------
def plain_logprobs(cfg, params, tokens, misread_scale=False) -> np.ndarray:
    """layer_groups.forward over one contiguous cache per kind (row =
    position), the XLA attention: the model's own code, no runner.
    `misread_scale`: the full kind's factor on cos and sin taken off
    them and squared onto its softmax scale (DeepSeek's reading)."""
    t = len(tokens)
    pos = jnp.arange(t, dtype=jnp.int32)
    counts = [cfg.layer_kinds.count(i) for i in range(len(cfg.attn_kinds))]
    kc = {"g": tuple(
        jnp.zeros((counts[i], ak.num_kv_heads, t + BS, cfg.head_dim))
        for i, ak in enumerate(cfg.attn_kinds)),
        "map": jnp.arange(t // BS + 2, dtype=jnp.int32),
        "stats": jnp.zeros((layer_groups.N_STATS,), jnp.int32)}
    vc = {"g": tuple(
        jnp.zeros((counts[i], ak.num_kv_heads, t + BS, cfg.v_dim))
        for i, ak in enumerate(cfg.attn_kinds))}
    factor = MC.attn_kinds[0].rope_factor

    def attn(q, l, k, v, spec):
        scale = cfg.attn_scale
        if misread_scale and spec.window is None:
            scale *= factor ** 2
        return xla_attn.context_attention_prefill(
            q, k[l].swapaxes(0, 1)[BS:], v[l].swapaxes(0, 1)[BS:], pos,
            jnp.int32(t), scale, window=spec.window, sink=spec.sink)

    logits, _, _ = layer_groups.forward(
        cfg, params, jnp.asarray(tokens, jnp.int32), pos, kc, vc, pos + BS,
        attn, logits_rows=pos, block_size=BS)
    return logprobs(logits)


def _with_kinds(**by_kind):
    """MC with fields of its kinds replaced: name -> (kind 0's, kind 1's)."""
    return dataclasses.replace(MC, attn_kinds=tuple(
        dataclasses.replace(ak, **{n: v[i] for n, v in by_kind.items()})
        for i, ak in enumerate(MC.attn_kinds)))


def test_the_models_own_code_equals_the_reference():
    params = layer_groups.init_params(MC, jax.random.key(4), jnp.float32)
    tokens = ids(80, seed=13)
    np.testing.assert_allclose(plain_logprobs(MC, params, tokens),
                               reference(params, tokens), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("term", [
    "gate", "factor moved to the scale", "factor", "yarn blend",
    "rotary width swapped", "window of another size", "scaling factor",
    "shared expert", "renormalisation", "window"])
def test_a_dropped_or_misread_term_fails_the_comparison(term):
    """The program serving the model with one term dropped or read
    another way, against the reference of the true model."""
    params = layer_groups.init_params(MC, jax.random.key(4), jnp.float32)
    tokens = ids(80, seed=13)
    cfg, served, kw = MC, params, {}
    if term == "gate":
        cfg = dataclasses.replace(MC, head_gate=False)
    elif term == "factor moved to the scale":
        cfg = _with_kinds(rope_factor=(1.0, None))
        kw = {"misread_scale": True}
    elif term == "factor":
        cfg = _with_kinds(rope_factor=(1.0, None))
    elif term == "yarn blend":
        cfg = _with_kinds(rope_yarn=(None, None))
    elif term == "rotary width swapped":
        cfg = _with_kinds(rotary_dim=(16, 8))
    elif term == "window of another size":
        cfg = _with_kinds(window=(None, 24))
    elif term == "scaling factor":
        cfg = dataclasses.replace(MC, routed_scaling=1.0)
    elif term == "shared expert":
        served = {**params, "segments": [
            {k: (jnp.zeros_like(v) if k == "ws_down" else v)
             for k, v in seg.items()} for seg in params["segments"]]}
    elif term == "renormalisation":
        cfg = dataclasses.replace(MC, router_renorm=False)
    else:
        cfg = _with_kinds(window=(None, None))
    diff = np.abs(plain_logprobs(cfg, served, tokens, **kw)
                  - reference(params, tokens)).max()
    assert diff > 50 * TOL, (term, diff)


def test_the_factor_multiplies_cos_and_sin_and_not_the_softmax_scale():
    ak = MC.kinds[0]
    assert MC.attn_scale == MC.head_dim ** -0.5
    cos, sin = ops_layers.rope_cos_sin(
        jnp.arange(3), ak.rotary_dim, ak.rope_theta, ak.rope_yarn,
        ak.rope_factor)
    assert cos.shape == (3, 8)
    assert float(cos[0, 0]) == pytest.approx(0.1 * np.log(4.0) + 1.0)
    plain, _ = ops_layers.rope_cos_sin(
        jnp.arange(3), ak.rotary_dim, ak.rope_theta, ak.rope_yarn, 1.0)
    np.testing.assert_allclose(np.asarray(cos),
                               np.asarray(plain) * ak.rope_factor, rtol=1e-6)
    # two of the four frequencies lie on YaRN's ramp at these widths
    got = np.asarray(ops_layers.yarn_inv_freq(8, ak.rope_theta, ak.rope_yarn))
    base = 1.0 / (ak.rope_theta ** (np.arange(0, 8, 2) / 8))
    ratio = got / base
    assert ratio[0] == pytest.approx(1.0) and ratio[-1] == pytest.approx(
        0.25)
    assert ((ratio[1:3] < 0.999) & (ratio[1:3] > 0.2501)).all()
    # a model-wide YaRN with the softmax's mscale stays the model's
    with pytest.raises(ValueError, match="softmax scale is one a model"):
        _with_kinds(rope_yarn=(mcfg.YarnScaling(
            factor=4.0, original_max_position=64, mscale_all_dim=1.0),
            None))


# -- (c) the experts at 256 a layer ----------------------------------------------
@pytest.mark.parametrize("m,live,base", [(64, 6, 1), (512, 256, 2)])
def test_expert_ffn_at_256_experts_equals_the_plain_form(m, live, base):
    """`expert_ffn` in interpret mode at e_loc 256 in a stack of 3 (768
    groups): a decode step's few rows over a handful of experts, and a
    pass of a prefill chunk that touches most of them."""
    e_loc, d, f, top_k = 256, 128, 128, 8
    rng = np.random.default_rng(m)
    counts = np.zeros(e_loc, np.int32)
    for _ in range(live):
        counts[rng.choice(e_loc, top_k, replace=False)] += 1
    skip = 3
    counts = np.minimum(counts, 2)
    while counts.sum() > m - skip:          # a pass holds m rows
        counts[np.flatnonzero(counts)[-1]] -= 1
    keys = jax.random.split(jax.random.key(m), 4)
    xs = jax.random.normal(keys[0], (m, d), jnp.float32)
    wg, wu, wd = ((0.1 * jax.random.normal(k, (3 * e_loc, *s))) for k, s in
                  zip(keys[1:], [(d, f), (d, f), (f, d)]))
    sizes = jnp.asarray(counts)
    args = (xs, wg, wu, wd, sizes, jnp.int32(skip), jnp.int32(base * e_loc))
    want = ef._plain(*args)
    got = ef.expert_ffn(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    used = skip + int(counts.sum())
    assert not np.asarray(got)[:skip].any()
    assert not np.asarray(got)[used:].any()
    assert np.abs(np.asarray(got)[skip:used]).min(axis=1).max() > 0


# -- (d) the configuration's path -------------------------------------------------
def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(json.loads(ln) for ln in f if '"Laguna-XS.2"' in ln)


def test_from_hf_config_reads_the_catalogs_keys(tmp_path):
    row = _catalog_row()
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    mc = mcfg.from_hf_config(str(tmp_path), name="laguna-catalog")
    assert mc.layer_groups and mc.num_layers == 40 and mc.head_gate
    full, window = mc.attn_kinds
    assert full == mcfg.AttnKind(
        num_kv_heads=8, rope_theta=5e5, num_heads=48, rotary_dim=64,
        rope_yarn=mcfg.YarnScaling(factor=64.0, original_max_position=4096,
                                   beta_fast=64.0, beta_slow=1.0),
        rope_factor=1.4158883083359672)
    assert window == mcfg.AttnKind(
        num_kv_heads=8, rope_theta=1e4, window=512, num_heads=64,
        rotary_dim=128)
    assert mc.kinds == mc.attn_kinds  # nothing left to the model
    assert mc.layer_kinds == (0, 1, 1, 1) * 10
    assert mc.segments()[:3] == (
        (0, False, 1, 0), (1, True, 3, 0), (0, True, 1, 1))
    assert len(mc.segments()) == 20
    assert (mc.hidden_size, mc.num_heads, mc.head_dim, mc.v_dim,
            mc.intermediate_size, mc.moe_intermediate_size) == (
        2048, 48, 128, 128, 8192, 512)
    assert (mc.dense_layers, mc.router_experts, mc.local_experts,
            mc.num_experts_per_tok, mc.shared_experts, mc.routed_scaling,
            mc.router_scoring, mc.router_bias, mc.router_renorm) == (
        1, 256, 256, 8, 1, 2.5, "softmax", False, True)
    assert mc.attn_scale == 128 ** -0.5 and mc.rope_yarn is None
    assert (mc.vocab_size, mc.max_model_len, mc.rms_norm_eps,
            mc.tie_word_embeddings) == (100352, 262144, 1e-6, False)
    # the published 33.4 B, every one in the tree layer_groups builds
    assert mc.num_params() / 1e9 == pytest.approx(33.44, abs=0.01)


@pytest.mark.parametrize("over,says", [
    ({"gating": "element-wise"}, "gating"),
    ({"gating_types": ["per_head", "elementwise"]}, "gating"),
    ({"sliding_window": [512, 1024]}, "sliding_window"),
    ({"layer_types": ["sliding_attention", "full_attention"] * 20},
     "window layer first"),
    ({"layer_types": ["full_attention", "linear_attention"] * 20},
     "linear_attention"),
    ({"num_attention_heads_per_layer": [48, 64, 72, 64] * 10},
     "one count a layer type"),
    ({"mlp_layer_types": ["dense", "sparse", "dense"] + ["sparse"] * 37},
     "dense MLP after"),
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_apply_router_weight_on_input": True}, "router_weight_on_input"),
    ({"n_group": 8}, "n_group"),
    ({"shared_expert_intermediate_size": 700}, "no\n? ?multiple"),
])
def test_what_is_not_served_is_refused_by_name(tmp_path, over, says):
    row = _catalog_row()
    (tmp_path / "config.json").write_text(
        json.dumps({**row["config"], **over}))
    with pytest.raises(ValueError, match=says):
        mcfg.from_hf_config(str(tmp_path))


def test_the_other_spelling_of_the_gate_and_no_gate_at_all(tmp_path):
    row = _catalog_row()
    for gating, want in (("per-head", True), (False, False)):
        (tmp_path / "config.json").write_text(json.dumps(
            {**row["config"], "gating": gating,
             "gating_types": ["per_head"] * 40}))
        assert mcfg.from_hf_config(str(tmp_path)).head_gate is want


def test_kinds_fill_in_what_a_kind_leaves_to_the_model():
    """MiMo's and Xing4's kinds say none of the new fields and get the
    model-wide values they had before the fields moved."""
    for cfg in (mcfg.TINY_GROUPS_DEBUG, mcfg.TINY_LATENT_DEBUG):
        for raw, ak in zip(cfg.attn_kinds, cfg.kinds):
            assert (raw.num_heads, raw.rotary_dim, raw.rope_yarn,
                    raw.rope_factor) == (None, None, None, None)
            assert (ak.num_heads, ak.rotary_dim, ak.rope_yarn,
                    ak.rope_factor) == (
                cfg.num_heads, cfg.rope_dim, cfg.rope_yarn, None)
    assert [k.num_heads for k in MC.kinds] == [6, 8]
    with pytest.raises(ValueError, match="head_gate needs"):
        dataclasses.replace(mcfg.TINY_DEBUG, head_gate=True)


def test_what_has_no_code_path_is_refused_by_name():
    with pytest.raises(ValueError, match="enable-lora.*num-speculative"):
        engine(enable_lora=True, num_speculative_tokens=2)
    with pytest.raises(ValueError, match="tensor-parallel"):
        engine(tensor_parallel_size=2)
    two = dataclasses.replace(MC, attn_kinds=(
        MC.attn_kinds[0], MC.attn_kinds[1],
        dataclasses.replace(MC.attn_kinds[1], window=24)))
    with pytest.raises(ValueError, match="at most one windowed kind"):
        layer_groups.mapped_kind(two)

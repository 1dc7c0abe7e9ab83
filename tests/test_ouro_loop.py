"""A looped stack (`ModelConfig.ut_steps` > 1, `model_type: ouro`)
through the step programs and the paged cache, against the benchmark's
plain reference (benchmarks/chip/families/ouro.py: float32, no cache,
each pass attending over its own keys and values), at the tiny widths of
`pst-tiny-loop-debug`: two layers run three times a token, six cache
layers, every gain seeded away from 1, a non-zero gate.

TOLERANCE 2e-4 on float32 log-softmaxed logits: both sides compute in
float32, so what separates them is the order of sums (the served path
attends chunk by chunk through the cache and gathers padded contexts;
the reference holds one dense mask), a few float32 roundings on logits
of magnitude ~1. A wrong cache slot or a dropped norm moves them by 1e-2
to whole units (the tests below and tests/chip_benchmark/
test_chipbench_ouro.py show each).
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

from production_stack_tpu.engine.block_manager import hash_block
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.models import config as mcfg
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import TINY_LOOP_DEBUG as MC

TOL = 2e-4
BS = 4
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _family():
    path = os.path.join(ROOT, "benchmarks", "chip", "families", "ouro.py")
    spec = importlib.util.spec_from_file_location("ouro_family", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


family = _family()


def seeded_params(seed: int = 11):
    return jax.jit(lambda k: family.init_params(MC, k, jnp.float32))(
        jax.random.key(seed))


def engine(**kw) -> LLMEngine:
    cfg = dict(
        model=MC.name, tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=BS, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, seed=3,
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg), params=seeded_params())


@pytest.fixture(scope="module")
def eng():
    return engine()


def ids(n: int, seed: int = 0) -> list[int]:
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, MC.vocab_size - 4, n)]


def reference(params, tokens, **kw) -> np.ndarray:
    """(t, vocab) reference log-probabilities at every position."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(family.forward_logprobs(
            MC, params, jnp.asarray(tokens, jnp.int32),
            jnp.arange(len(tokens)), **kw))


def serve(e: LLMEngine, tokens: list[int], n_prompt: int, chunk: int,
          reuse: bool = True):
    """Prefill `tokens[:n_prompt]` in chunks of `chunk` and decode the
    rest teacher-forced, through the runner's programs and the engine's
    block manager. Returns ({position: logits row}, cached tokens, the
    table): the last row of every chunk and every decode row."""
    r, bm = e.runner, e.block_manager
    table, cached = bm.allocate_prompt(tokens[:n_prompt], reuse_cache=reuse)
    rows, start, prev_hash, done = {}, cached, 0, cached // BS
    for i in range(done):
        prev_hash = hash_block(
            prev_hash, tuple(tokens[i * BS:(i + 1) * BS]))
    while start < n_prompt:
        end = min(start + chunk, n_prompt)
        _, logits = r.prefill(tokens[start:end], start, table, end)
        rows[end - 1] = np.asarray(logits)
        start = end
        for i in range(done, end // BS):
            prev_hash = bm.register_block(
                prev_hash, tuple(tokens[i * BS:(i + 1) * BS]), table[i])
        done = end // BS
    for pos in range(n_prompt, len(tokens)):
        assert bm.ensure_capacity(pos + 1, table)
        logits = r.decode([tokens[pos]], [pos], [table], [pos + 1])
        rows[pos] = np.asarray(logits[0])
    return rows, cached, table


def assert_rows(rows: dict, ref: np.ndarray, tol: float = TOL) -> None:
    for pos, row in rows.items():
        np.testing.assert_allclose(
            np.asarray(jax.nn.log_softmax(row)), ref[pos], rtol=tol,
            atol=tol, err_msg=f"position {pos}")


def worst(rows: dict, ref: np.ndarray) -> float:
    return max(float(np.abs(np.asarray(jax.nn.log_softmax(row))
                            - ref[pos]).max())
               for pos, row in rows.items())


# -- the configuration -----------------------------------------------------
def test_the_cache_holds_a_layer_for_every_layer_and_pass(eng):
    r = eng.runner
    assert (MC.ut_steps, MC.num_layers, MC.cache_layers) == (3, 2, 6)
    assert r.k_cache.shape == (6, MC.num_kv_heads, 128 * BS, MC.head_dim)
    assert r.v_cache.shape == r.k_cache.shape


def test_the_looped_stack_is_counted_once():
    """`num_params()` counts what is held: the stack once, its four
    gains a layer, the gate; `_resolve_num_blocks` reserves that, and
    sizes a block by `cache_layers`."""
    params = llama.init_params(MC, jax.random.key(0), jnp.float32)
    held = sum(a.size for a in jax.tree.leaves(params))
    assert MC.num_params() == held
    once = dataclasses.replace(MC, ut_steps=1)
    assert once.num_params() == held
    plain = dataclasses.replace(once, sandwich_norm=False, exit_gate=False)
    h = MC.hidden_size
    assert held - plain.num_params() == MC.num_layers * 2 * h + h + 1
    want = jax.eval_shape(
        lambda k: family.init_params(MC, k, jnp.float32), jax.random.key(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


def test_blocks_are_sized_by_the_cache_layers():
    """With no `--num-kv-blocks` the pool is what the memory holds at
    2 x cache_layers x block x heads x head_dim a block: a model of
    three passes gets a third of the blocks of the same stack run
    once (a context long enough that the cap on blocks does not bind)."""
    def blocks(mc):
        mcfg._register(mc)
        try:
            return LLMEngine(EngineConfig(
                model=mc.name, tokenizer="byte", dtype="float32",
                cache_dtype="float32", block_size=BS, max_num_seqs=4096,
                max_prefill_chunk=16, hbm_utilization=0.002,
            )).runner.num_blocks
        finally:
            mcfg._PRESETS.pop(mc.name)

    long_ctx = dict(max_model_len=65536)
    looped = blocks(dataclasses.replace(MC, name="t-loop3", **long_ctx))
    once = blocks(dataclasses.replace(MC, name="t-loop1", ut_steps=1,
                                      **long_ctx))
    assert once // looped == 3 and looped > 100


OURO_TINY = {
    "architectures": ["OuroForCausalLM"], "model_type": "ouro",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 384, "max_position_embeddings": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "layer_types": ["full_attention"] * 4, "max_window_layers": 4,
    "total_ut_steps": 3, "early_exit_threshold": 1,
}


def _from_file(tmp_path, **changed):
    (tmp_path / "config.json").write_text(
        json.dumps({**OURO_TINY, **changed}))
    return mcfg.from_hf_config(str(tmp_path), name=MC.name)


def test_an_ouro_config_json_becomes_a_looped_stack(tmp_path):
    assert _from_file(tmp_path) == MC


@pytest.mark.parametrize("changed, name", [
    (dict(early_exit_threshold=0.9), "early_exit_threshold=0.9"),
    (dict(use_sliding_window=True), "use_sliding_window=true"),
    (dict(layer_types=["sliding_attention"] * 2), "layer_types"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
])
def test_what_is_not_served_is_refused_by_name(tmp_path, changed, name):
    with pytest.raises(ValueError, match=name):
        _from_file(tmp_path, **changed)


@pytest.mark.parametrize("kw, name", [
    (dict(enable_lora=True), "--enable-lora"),
    (dict(num_speculative_tokens=2), "--num-speculative-tokens"),
    (dict(long_prefill_threshold=64, context_parallel_size=2),
     "ring prefill lane"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(pipeline_parallel_size=2), "--pipeline-parallel-size"),
])
def test_features_that_run_the_layers_once_are_refused_by_name(kw, name):
    with pytest.raises(ValueError, match="looped stack.*" + name):
        engine(**kw)


def test_a_looped_stack_of_layer_groups_is_no_config():
    with pytest.raises(ValueError, match="ut_steps"):
        dataclasses.replace(mcfg.TINY_GROUPS_DEBUG, ut_steps=2)


# -- one pass: the dense program, unchanged --------------------------------
def test_a_stack_that_runs_once_traces_no_pass_loop():
    """At `ut_steps` 1 without the output norms `llama.forward` is the
    dense forward: ONE scan (the layers), three norms in the text (two
    in the layer body, the final one); and the jaxpr does not
    depend on the new fields' being there. (That it is the parent's
    jaxpr letter for letter was checked by hand on both trees: PERF.md,
    Findings PR 38.)"""
    dense = mcfg.TINY_DEBUG
    assert (dense.ut_steps, dense.sandwich_norm, dense.exit_gate,
            dense.cache_layers) == (1, False, False, dense.num_layers)
    params = llama.init_params(dense, jax.random.key(0), jnp.float32)
    assert "exit_gate_w" not in params
    assert "attn_out_norm" not in params["layers"]
    n = 8
    kc = jnp.zeros((dense.num_layers, dense.num_kv_heads, 16,
                    dense.head_dim))
    pos = jnp.arange(n)

    def attn(q, l, k, v):
        return q

    def fwd(cfg):
        return str(jax.make_jaxpr(lambda p, kc, vc: llama.forward(
            cfg, p, pos, pos, kc, vc, pos, attn, pos[-1:]))(
                params, kc, kc))

    text = fwd(dense)
    assert text.count(" scan[") == 1 and "while" not in text
    assert text.count("rsqrt") == 3
    looped = fwd(dataclasses.replace(
        MC, tie_word_embeddings=True, rms_norm_eps=dense.rms_norm_eps,
        ut_steps=1, sandwich_norm=False, exit_gate=False))
    assert looped == text
    params3 = llama.init_params(MC, jax.random.key(0), jnp.float32)
    kc3 = jnp.zeros((MC.cache_layers,) + kc.shape[1:])
    text3 = str(jax.make_jaxpr(lambda p, kc, vc: llama.forward(
        MC, p, pos, pos, kc, vc, pos, attn, pos[-1:]))(params3, kc3, kc3))
    assert text3.count(" scan[") == 2 and text3.count("rsqrt") == 5


# -- (a) prefill in chunks, then decode, through the paged cache -----------
@pytest.mark.parametrize("chunk", [16, 7])
def test_chunked_prefill_then_decode_equals_the_reference(eng, chunk):
    tokens = ids(58, seed=chunk)
    rows, cached, table = serve(eng, tokens, 45, chunk, reuse=False)
    assert cached == 0
    assert_rows(rows, reference(eng.runner.params, tokens))
    eng.block_manager.free(table)


# -- (b) a prefix-cache hit against the cold prompt ------------------------
def test_a_prefix_hit_serves_the_cold_prompts_logits_exactly():
    """The hit leaves the prompt's last chunk [36, 40) to compute, the
    chunk the cold prompt ended with: the same program over the same
    values in all six cache layers, so the difference is 0.0."""
    e = engine()
    prompt = ids(40, seed=7)
    cold, cached, table = serve(e, prompt, 40, 12)
    assert cached == 0 and sorted(cold) == [11, 23, 35, 39]
    e.block_manager.free(table)
    hit, cached, table = serve(e, prompt, 40, 12)
    assert cached == 36 and sorted(hit) == [39]
    assert float(np.abs(hit[39] - cold[39]).max()) == 0.0
    assert_rows(hit, reference(e.runner.params, prompt))


# -- (c) the cache slot t * L + l ------------------------------------------
def test_one_cache_shared_among_the_passes_would_differ(eng):
    """The reference with ONE slot a layer shared among the passes (a
    second chunk reading, in every pass, what the first chunk's LAST
    pass wrote) is far from the served logits, which equal the true
    reference: the slot `t * L + l` is tested, not assumed."""
    tokens = ids(48, seed=3)
    rows, _, table = serve(eng, tokens, 48, 16, reuse=False)
    eng.block_manager.free(table)
    later = {p: r for p, r in rows.items() if p >= 16}
    assert_rows(rows, reference(eng.runner.params, tokens))
    shared = reference(eng.runner.params, tokens, shared_cache_from=16)
    assert worst(later, shared) > 100 * TOL
    # the first chunk has no earlier rows to share: alike there
    assert_rows({15: rows[15]}, shared)


# -- (d) the programs the chip runs ----------------------------------------
def test_the_kernel_path_serves_mixed_rounds_like_the_reference():
    """Ragged-rows prefill groups, fused decode rounds and lane-typed
    mixed rounds with the Pallas walk in interpret mode: two requests,
    the second admitted while the first decodes and sharing its first
    24 tokens through the prefix cache. Then the counters: the passes of
    every dispatched forward, and the exit distribution of exactly the
    rows that were sampled for a sequence."""
    e = engine(attention_impl="pallas", num_scheduler_steps=4)
    r = e.runner
    assert r.ragged_kernel and not isinstance(r.k_cache, dict)
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
        ref = reference(r.params, prompt + got)
        want = [int(np.argmax(ref[len(prompt) - 1 + i]))
                for i in range(len(got))]
        assert got == want, rid
    assert not isinstance(r.k_cache, dict)      # nothing stays in the cache
    jax.block_until_ready(list(r._stats_pending))
    mass = r.loop_exit_mass()
    assert len(mass) == MC.ut_steps and r.loop_passes % MC.ut_steps == 0
    assert r.loop_passes >= MC.ut_steps * 10
    stats = e.stats()
    assert stats.loop_passes_total == r.loop_passes
    assert stats.loop_exit_mass == mass
    # every row sampled for a lane that holds a sequence carries a mass
    # of 1 over the passes; lanes that hold no sequence and padding rows
    # carry none. a: chunks ending at 16, 32, 41 and 9 decoded steps
    # fed back; b: 24 cached, chunks ending at 40, 43 and 9 steps; and
    # what a round of 4 fused steps runs past a request's last token
    # (3 each: sampled on the device, thrown away by the host)
    assert sum(mass) == pytest.approx(round(sum(mass)), abs=1e-3)
    assert 3 + 9 + 2 + 9 <= round(sum(mass)) <= 3 + 9 + 2 + 9 + 6
    # and it is the reference's distribution: its mean exit pass over
    # the rows that chose each request's tokens (the counter also holds
    # the chunks' other last rows and the rows run past the last token,
    # so the means agree to a tenth of a pass, not to rounding)
    with jax.default_matmul_precision("highest"):
        want = sum(np.asarray(family.exit_distribution(
            MC, r.params, jnp.asarray(p + list(done[rid].token_ids)),
            jnp.arange(len(p) - 1, len(p) + 9))).sum(1)
            for rid, p in (("a", a), ("b", b)))

    def mean(m):
        return sum((t + 1) * x for t, x in enumerate(m)) / sum(m)

    assert mean(mass) == pytest.approx(mean(want), abs=0.2)
    assert 1.0 < mean(mass) < MC.ut_steps


def test_the_exit_mass_of_one_prefill_is_the_references(eng):
    """One chunk, one sampled row: the program's counters for that
    program alone are the reference's exit distribution at that row."""
    r = eng.runner
    tokens = ids(16, seed=12)
    jax.block_until_ready(list(r._stats_pending))
    before = r.loop_exit_mass()
    passes = r.loop_passes
    _, _, table = serve(eng, tokens, 16, 16, reuse=False)
    eng.block_manager.free(table)
    jax.block_until_ready(list(r._stats_pending))
    got = np.subtract(r.loop_exit_mass(), before)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.exit_distribution(
            MC, r.params, jnp.asarray(tokens), jnp.asarray([15])))[:, 0]
    np.testing.assert_allclose(got, want, atol=TOL)
    assert got.sum() == pytest.approx(1.0, abs=1e-5)
    assert r.loop_passes - passes == MC.ut_steps


def test_the_counters_reach_the_metrics():
    from prometheus_client import CollectorRegistry, generate_latest

    from production_stack_tpu.engine.metrics import EngineMetrics

    e = engine(num_scheduler_steps=4)
    e.generate([ids(20, seed=6)], SamplingParams(
        max_tokens=5, temperature=0.0, ignore_eos=True))
    jax.block_until_ready(list(e.runner._stats_pending))
    reg = CollectorRegistry()
    m = EngineMetrics(MC.name, registry=reg)
    m.update_from_snapshot(e.stats())
    text = generate_latest(reg).decode()
    got = {ln.split(" ")[0]: float(ln.split(" ")[1])
           for ln in text.splitlines() if ln.startswith("tpu:loop_")}
    label = f'model_name="{MC.name}"'
    assert got[f"tpu:loop_passes_total{{{label}}}"] == e.runner.loop_passes
    mass = [got[f'tpu:loop_exit_mass_total{{{label},pass="{t}"}}']
            for t in (1, 2, 3)]
    assert mass == pytest.approx(list(e.runner.loop_exit_mass()))
    assert got[f"tpu:loop_exit_pass_count{{{label}}}"] == pytest.approx(
        sum(mass))
    assert got[f"tpu:loop_exit_pass_sum{{{label}}}"] == pytest.approx(
        mass[0] + 2 * mass[1] + 3 * mass[2])


# -- (e) the cache on the wire: KV tiers and PD transfer -------------------
def test_exported_blocks_carry_every_pass_and_restore_the_logits(eng):
    """The wire format is (2, cache_layers, blocks, kv heads, block,
    head_dim); blocks exported, their slots overwritten and imported
    into OTHER blocks serve the next token exactly as before."""
    r, bm = eng.runner, eng.block_manager
    tokens = ids(33, seed=9)
    rows, _, table = serve(eng, tokens, 32, 16, reuse=False)
    data = r.export_blocks(table[:8])
    assert data.shape == (2, MC.cache_layers, 8, MC.num_kv_heads, BS,
                          MC.head_dim)
    assert bm.ensure_capacity(33, table)
    want = np.asarray(r.decode([tokens[32]], [32], [table], [33])[0])
    r.import_blocks(table[:8], np.zeros_like(data))      # wipe
    wiped = np.asarray(r.decode([tokens[32]], [32], [table], [33])[0])
    assert float(np.abs(wiped - want).max()) > 100 * TOL
    other, _ = bm.allocate_prompt(ids(32, seed=10), reuse_cache=False)
    r.import_blocks(other[:8], data)
    again = np.asarray(r.decode(
        [tokens[32]], [32], [other[:8] + table[8:]], [33])[0])
    np.testing.assert_array_equal(again, want)
    bm.free(table)
    bm.free(other)


def test_the_offload_tier_restores_a_looped_models_blocks():
    """Through the engine: a prompt's blocks go to the CPU tier when the
    pool of 12 blocks evicts them, come back on the next request for
    it (every pass's layers with them), and the answer is the first
    one's."""
    import time

    e = engine(num_kv_blocks=12, max_num_seqs=2, max_prefill_chunk=32,
               cpu_offload_bytes=64 * 2**20)
    try:
        sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        a = ids(24, seed=40)
        first = e.generate([a], sp)[0]
        deadline = time.time() + 5
        while time.time() < deadline and not e.offload.tiers[0].hashes():
            time.sleep(0.01)
        assert e.offload.tiers[0].hashes(), "no blocks offloaded"
        for i in range(4):
            e.generate([ids(24, seed=41 + i)], sp)
        hits = e.block_manager.prefix_hits
        again = e.generate([a], sp)[0]
        assert e.block_manager.prefix_hits - hits >= 16
        assert again.token_ids == first.token_ids
        assert e.offload.hits > 0
        ref = reference(e.runner.params, a + list(first.token_ids))
        assert list(first.token_ids) == [
            int(np.argmax(ref[len(a) - 1 + i])) for i in range(4)]
    finally:
        e.shutdown()

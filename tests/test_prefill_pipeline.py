"""Pipelined prefill (fused h2d buffer + cold-prompt chunk chaining)
vs the serial per-array upload path.

The pipeline is a pure transport/scheduling optimisation: sampled
tokens and KV cache CONTENTS must be bit-identical to the serial path
(`prefill_pipeline=False`, the `--no-prefill-pipeline` escape hatch) on
every prefill shape — single-sequence, packed cross-sequence groups,
multi-chunk prompts, prefix-cache resume tails, and LoRA-slotted
requests. Chaining and lane-typed rounds may legitimately order
decode/prefill rounds otherwise than the serial engine, so physical
block ids can differ between the two engines under load; the cache
comparison is therefore per-CONTENT (cached-block hash -> slot data), which pins the
logical KV while staying layout-agnostic. Single-sequence runs have a
deterministic layout and compare the raw caches whole."""

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.sampling_params import SamplingParams


def cfg(**overrides) -> EngineConfig:
    kwargs = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=4, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, seed=0,
    )
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def engine_pair(**overrides):
    return (
        LLMEngine(cfg(prefill_pipeline=True, **overrides)),
        LLMEngine(cfg(prefill_pipeline=False, **overrides)),
    )


def cached_kv_by_hash(engine):
    """Logical KV state: cached-block hash -> (k_block, v_block)."""
    k = np.asarray(engine.runner.k_cache)
    v = np.asarray(engine.runner.v_cache)
    bs = engine.block_manager.block_size
    return {
        h: (k[:, :, bid * bs : (bid + 1) * bs],
            v[:, :, bid * bs : (bid + 1) * bs])
        for h, bid in engine.block_manager.cached_blocks.items()
    }


def assert_logical_kv_equal(e1, e2):
    c1, c2 = cached_kv_by_hash(e1), cached_kv_by_hash(e2)
    assert set(c1) == set(c2) and c1, "cached-block hash sets differ"
    for h in c1:
        np.testing.assert_array_equal(c1[h][0], c2[h][0])
        np.testing.assert_array_equal(c1[h][1], c2[h][1])


# -- runner level -----------------------------------------------------------

def test_runner_packed_buffer_matches_serial():
    """One fused-buffer dispatch == the serial per-array dispatch
    (same token, same logits, same cache), single and packed."""
    r_new = ModelRunner(cfg(prefill_pipeline=True))
    r_old = ModelRunner(cfg(prefill_pipeline=False))
    rng = np.random.RandomState(3)
    ids = rng.randint(0, 384, size=13).tolist()
    tok_n, lg_n = r_new.prefill(ids, 0, [2, 3, 4, 5], len(ids))
    tok_o, lg_o = r_old.prefill(ids, 0, [2, 3, 4, 5], len(ids))
    assert int(np.asarray(tok_n)) == int(np.asarray(tok_o))
    np.testing.assert_array_equal(np.asarray(lg_n), np.asarray(lg_o))

    chunks = [rng.randint(0, 384, size=n).tolist() for n in (7, 16, 3)]
    tables = [[6, 7], [8, 9, 10, 11], [12]]
    out_n = r_new.prefill_batch(chunks, [0, 0, 0], tables,
                                [len(c) for c in chunks])
    out_o = r_old.prefill_batch(chunks, [0, 0, 0], tables,
                                [len(c) for c in chunks])
    np.testing.assert_array_equal(np.asarray(out_n[0]),
                                  np.asarray(out_o[0]))
    np.testing.assert_array_equal(np.asarray(out_n[1]),
                                  np.asarray(out_o[1]))
    np.testing.assert_array_equal(np.asarray(r_new.k_cache),
                                  np.asarray(r_old.k_cache))
    np.testing.assert_array_equal(np.asarray(r_new.v_cache),
                                  np.asarray(r_old.v_cache))


# -- engine level -----------------------------------------------------------

def _prompts(seed=7, sizes=(5, 23, 45, 12)):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 384, size=n).tolist() for n in sizes]


def test_engine_parity_mixed_batch():
    """Packed groups + multi-chunk prompts + interleaved decode:
    tokens and logical KV bit-identical."""
    e_new, e_old = engine_pair()
    out_n = [o.token_ids for o in e_new.generate(_prompts(), greedy(6))]
    out_o = [o.token_ids for o in e_old.generate(_prompts(), greedy(6))]
    assert out_n == out_o
    assert_logical_kv_equal(e_new, e_old)


def test_engine_parity_sampled():
    """Seeded stochastic sampling is key-driven, so the pipeline must
    not shift any sampling key."""
    sp = SamplingParams(max_tokens=8, temperature=0.9, seed=11,
                        ignore_eos=True)
    e_new, e_old = engine_pair()
    out_n = [o.token_ids for o in e_new.generate(_prompts(), sp)]
    out_o = [o.token_ids for o in e_old.generate(_prompts(), sp)]
    assert out_n == out_o


def test_engine_cold_multi_chunk_chains():
    """A lone cold prompt's chunks drain via the chained dispatch (no
    host round-trip between chunks) and stay bit-identical, raw caches
    included (single sequence -> deterministic layout)."""
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, 384, size=61).tolist()  # 4 chunks
    e_new, e_old = engine_pair()
    out_n = e_new.generate([prompt], greedy(5))[0].token_ids
    out_o = e_old.generate([prompt], greedy(5))[0].token_ids
    assert out_n == out_o
    assert e_new._pf_chained_chunks_total >= 3  # chunks 2..4 chained
    assert e_old._pf_chained_chunks_total == 0
    np.testing.assert_array_equal(np.asarray(e_new.runner.k_cache),
                                  np.asarray(e_old.runner.k_cache))
    np.testing.assert_array_equal(np.asarray(e_new.runner.v_cache),
                                  np.asarray(e_old.runner.v_cache))


@pytest.fixture(scope="module")
def long_cold_prompt():
    """A prompt of 11 chunks, more than one step's chain holds, and
    the serial engine's tokens for it."""
    prompt = np.random.RandomState(10).randint(0, 384, size=163).tolist()
    serial = LLMEngine(cfg(prefill_pipeline=False))
    return prompt, serial.generate([prompt], greedy(5))[0].token_ids


@pytest.mark.parametrize("ragged", [True, False])
def test_chain_past_its_cap_continues_next_step(long_cold_prompt, ragged):
    """Chaining is the one way a cold prompt's chunks follow each
    other: past the cap the next step schedules the following chunk and
    chains again, lane-typed rounds or not. Every chunk but the steps'
    first is a chained one, and the tokens are the serial path's."""
    prompt, want = long_cold_prompt
    e = LLMEngine(cfg(ragged_dispatch=ragged))
    cap = e.MAX_CHAINED_PREFILLS
    chunks = -(-len(prompt) // e.config.max_prefill_chunk)
    assert chunks > cap + 1
    e.add_request("cold", prompt_token_ids=prompt,
                  sampling_params=greedy(5))
    e.step()
    assert e._pf_chained_chunks_total == cap
    assert e._seqs["cold"].num_computed_tokens == (
        (cap + 1) * e.config.max_prefill_chunk)
    got = None
    while e.has_unfinished():
        for o in e.step():
            if o.finished:
                got = o.token_ids
    assert got == want
    assert e.stats().prefill_chained_chunks_total == chunks - 2


def test_engine_prefix_cache_resume_tail():
    """Rounds 2+ of a chat session re-prefill only the session tail
    past the cached prefix — the resume-tail chunk must ride the
    pipeline unchanged."""
    rng = np.random.RandomState(13)
    base = rng.randint(0, 384, size=30).tolist()
    e_new, e_old = engine_pair()
    r1_n = e_new.generate([base], greedy(6))[0].token_ids
    r1_o = e_old.generate([base], greedy(6))[0].token_ids
    assert r1_n == r1_o
    # session grows by the answer + the next question, resumes cached
    follow = base + r1_n + rng.randint(0, 384, size=5).tolist()
    r2_n = e_new.generate([follow], greedy(6))[0].token_ids
    r2_o = e_old.generate([follow], greedy(6))[0].token_ids
    assert r2_n == r2_o
    assert e_new.block_manager.prefix_hits > 0
    assert e_old.block_manager.prefix_hits > 0
    assert_logical_kv_equal(e_new, e_old)


def test_engine_parity_lora_slot():
    """LoRA adapters travel OUTSIDE the packed buffer (device-resident
    stacks); a slotted request must still be bit-identical."""
    pytest.importorskip("jax")
    from production_stack_tpu.engine.lora import save_adapter_npz
    from production_stack_tpu.models.config import get_model_config
    import tempfile, os

    mc = get_model_config("pst-tiny-debug")
    rng = np.random.RandomState(21)
    L, h = mc.num_layers, mc.hidden_size
    w = {"scaling": np.float32(0.5)}
    for t, (din, dout) in {"wq": (h, mc.q_size),
                           "wo": (mc.q_size, h)}.items():
        w[f"{t}_A"] = rng.randn(L, din, 2).astype(np.float32) * 0.05
        w[f"{t}_B"] = rng.randn(L, 2, dout).astype(np.float32) * 0.05
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ad.npz")
        save_adapter_npz(path, w)
        kw = dict(enable_lora=True, max_loras=2, max_lora_rank=4)
        e_new, e_old = engine_pair(**kw)
        e_new.load_lora("ad", path)
        e_old.load_lora("ad", path)
        prompts = _prompts(seed=17, sizes=(6, 21))
        outs = []
        for e in (e_new, e_old):
            for i, p in enumerate(prompts):
                e.add_request(f"r{i}", prompt_token_ids=p,
                              sampling_params=greedy(5),
                              lora_name="ad")
            got = {}
            while e.has_unfinished():
                for o in e.step():
                    if o.finished:
                        got[o.request_id] = o.token_ids
            outs.append([got[f"r{i}"] for i in range(len(prompts))])
        assert outs[0] == outs[1]
        assert_logical_kv_equal(e_new, e_old)


def test_phase_timing_and_staging_counters_populate():
    """The /metrics + bench attribution surface: per-phase prefill
    timings accumulate, and a cold prompt served alone moves the
    chain's counter (three chunks after the step's first)."""
    e = LLMEngine(cfg(ragged_dispatch=False))
    e.generate(_prompts(), greedy(4))
    assert e.stats().prefill_chained_chunks_total == 0  # finals, streams
    e.generate(_prompts(seed=9, sizes=(61,)), greedy(4))
    s = e.stats()
    # (seconds, count) per phase of the round (tracing/phases.py) —
    # tpu:engine_phase_*_seconds in /metrics
    for phase in ("schedule", "pack", "dispatch", "fetch", "apply"):
        seconds, count = s.engine_phases[phase]
        assert seconds > 0 and count > 0, phase
    assert s.engine_phases["h2d"][0] >= 0
    assert s.engine_phases["h2d"][1] > 0
    assert s.prefill_chained_chunks_total == 3


def test_no_pipeline_flag_selects_serial_path():
    """--no-prefill-pipeline reaches the engine config and the runner."""
    e = LLMEngine(cfg(prefill_pipeline=False))
    assert e.runner.prefill_pipeline is False
    assert e._prefill_pipeline is False
    e2 = LLMEngine(cfg())
    assert e2.runner.prefill_pipeline is True

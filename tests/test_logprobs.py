"""OpenAI logprobs support: per-token chosen logprob + top-N
alternatives, computed on device inside the fused multi-step scan (one
fetch) and host-side on the single-step/prefill paths — all paths must
agree on the same values."""

from __future__ import annotations

import asyncio
import json
import math

import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams


def make_engine(**overrides) -> LLMEngine:
    kw = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=64,
        max_num_seqs=2, max_prefill_chunk=16, seed=0,
    )
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


PROMPT = list(range(40, 49))


def run(engine, sp):
    return engine.generate([PROMPT], sp)[0]


def test_logprobs_shape_and_consistency_single_step():
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True,
                        logprobs=3)
    out = run(make_engine(), sp)
    assert out.logprobs is not None
    assert len(out.logprobs) == len(out.token_ids)
    for tok, entry in zip(out.token_ids, out.logprobs):
        assert entry["token_id"] == tok
        assert entry["logprob"] <= 0.0
        tops = entry["top_logprobs"]
        assert len(tops) == 3
        lps = [t["logprob"] for t in tops]
        assert lps == sorted(lps, reverse=True)
        # greedy: the chosen token IS the top candidate
        assert tops[0]["token_id"] == tok
        assert math.isclose(tops[0]["logprob"], entry["logprob"],
                            rel_tol=1e-5, abs_tol=1e-5)


def test_logprobs_multi_step_matches_single_step():
    """The fused K-step on-device logprobs must match the host-side
    single-step values bit-for-bit-ish."""
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True,
                        logprobs=4)
    a = run(make_engine(num_scheduler_steps=1), sp)
    b = run(make_engine(num_scheduler_steps=4), sp)
    assert a.token_ids == b.token_ids
    for ea, eb in zip(a.logprobs, b.logprobs):
        assert math.isclose(ea["logprob"], eb["logprob"], abs_tol=1e-4)
        assert [t["token_id"] for t in ea["top_logprobs"]] == [
            t["token_id"] for t in eb["top_logprobs"]
        ]


def test_logprobs_staged_rounds_match_unstaged():
    """The chained program a staged round dispatches returns the same
    logprob arrays as the round built from host tokens."""
    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True,
                        logprobs=2)
    eng = make_engine(num_scheduler_steps=4)
    a = run(eng, sp)
    assert eng._staged_hits_total >= 2
    b = run(make_engine(num_scheduler_steps=4, prefetch_decode=False), sp)
    assert a.token_ids == b.token_ids
    for ea, eb in zip(a.logprobs, b.logprobs):
        assert math.isclose(ea["logprob"], eb["logprob"], abs_tol=1e-5)


def test_logprobs_off_by_default():
    sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    out = run(make_engine(), sp)
    assert out.logprobs is None


def test_completions_api_logprobs_format():
    """OpenAI completions: logprobs=N -> tokens / token_logprobs /
    top_logprobs arrays."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def scenario():
        srv = EngineServer(EngineConfig(
            model="pst-tiny-debug", tokenizer="byte", dtype="float32",
            cache_dtype="float32", block_size=8, num_kv_blocks=64,
            max_num_seqs=2, max_prefill_chunk=16, seed=0,
        ))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 5, "temperature": 0,
                "ignore_eos": True, "logprobs": 2,
            })
            assert r.status == 200
            lp = (await r.json())["choices"][0]["logprobs"]
            assert lp is not None
            assert len(lp["tokens"]) == 5
            assert len(lp["token_logprobs"]) == 5
            assert all(v <= 0 for v in lp["token_logprobs"])
            assert all(len(d) == 2 for d in lp["top_logprobs"])
            # chat variant: logprobs=true + top_logprobs
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4, "temperature": 0, "ignore_eos": True,
                "logprobs": True, "top_logprobs": 3,
            })
            assert r.status == 200
            content = (await r.json())["choices"][0]["logprobs"]["content"]
            assert len(content) == 4
            for e in content:
                assert e["logprob"] <= 0
                assert len(e["top_logprobs"]) == 3
            # streamed chunks carry logprobs too
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 4, "temperature": 0,
                "ignore_eos": True, "logprobs": 1, "stream": True,
            })
            body = await r.text()
            chunks = [json.loads(ln[6:]) for ln in body.splitlines()
                      if ln.startswith("data: ") and ln != "data: [DONE]"]
            with_lp = [c for c in chunks
                       if c["choices"] and c["choices"][0].get("logprobs")]
            total = sum(len(c["choices"][0]["logprobs"]["tokens"])
                        for c in with_lp)
            assert total == 4
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())


def test_logprobs_with_sampling_contains_chosen():
    """Sampled (non-greedy) tokens: the chosen token's logprob is the
    full-distribution log-softmax value (may rank below top-N)."""
    sp = SamplingParams(max_tokens=6, temperature=1.0, seed=3,
                        ignore_eos=True, logprobs=3)
    out = run(make_engine(num_scheduler_steps=4), sp)
    for tok, entry in zip(out.token_ids, out.logprobs):
        assert entry["token_id"] == tok
        assert np.isfinite(entry["logprob"])


def test_batch_streaming_logprobs():
    """Batch streamed choices carry per-index logprobs chunks."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def scenario():
        srv = EngineServer(EngineConfig(
            model="pst-tiny-debug", tokenizer="byte", dtype="float32",
            cache_dtype="float32", block_size=8, num_kv_blocks=64,
            max_num_seqs=2, max_prefill_chunk=16, seed=0,
        ))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "prompt": ["bb one", "bb two"], "max_tokens": 3,
                "temperature": 0, "ignore_eos": True, "logprobs": 1,
                "stream": True,
            })
            assert r.status == 200
            body = await r.text()
            chunks = [json.loads(ln[6:]) for ln in body.splitlines()
                      if ln.startswith("data: ") and ln != "data: [DONE]"]
            counts = {0: 0, 1: 0}
            for c in chunks:
                for ch in c.get("choices", []):
                    lp = ch.get("logprobs")
                    if lp:
                        counts[ch["index"]] += len(lp["tokens"])
            assert counts == {0: 3, 1: 3}
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())

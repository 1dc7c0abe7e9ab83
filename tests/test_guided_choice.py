"""Structured output via guided_choice (vLLM extension API): the
generation is constrained to exactly one of the given strings by
masking logits to tokens that extend a still-matching choice."""

from __future__ import annotations

import asyncio

from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams


def make_engine(**overrides) -> LLMEngine:
    kw = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=64,
        max_num_seqs=2, max_prefill_chunk=32, seed=0,
    )
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


CHOICES = ["positive", "negative", "neutral"]


def test_output_is_exactly_one_choice():
    eng = make_engine()
    sp = SamplingParams(max_tokens=32, temperature=0.0,
                        guided_choice=CHOICES)
    out = eng.generate(["classify: great product!"], sp)[0]
    assert out.text in CHOICES
    assert out.finish_reason == "stop"


def test_sampled_guided_still_lands_on_a_choice():
    eng = make_engine()
    sp = SamplingParams(max_tokens=32, temperature=1.0, seed=1,
                        guided_choice=CHOICES)
    outs = eng.generate(["a", "b"], sp)
    assert all(o.text in CHOICES for o in outs)


def test_guided_under_multistep_config():
    """K>1 engines must route guided lanes through the single-step
    masked path."""
    eng = make_engine(num_scheduler_steps=4)
    sp = SamplingParams(max_tokens=32, temperature=0.0,
                        guided_choice=["alpha", "beta"])
    out = eng.generate(["pick"], sp)[0]
    assert out.text in ("alpha", "beta")


def test_guided_and_free_lanes_coexist():
    """A guided lane and a free lane decode in the same batch; only the
    guided one is constrained."""
    eng = make_engine()
    sps = [
        SamplingParams(max_tokens=12, temperature=0.0,
                       guided_choice=["yes", "no"]),
        SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True),
    ]
    outs = eng.generate(["q1", "q2"], sps)
    assert outs[0].text in ("yes", "no")
    assert len(outs[1].token_ids) == 12  # unconstrained lane unaffected


def test_prefix_sharing_choices():
    """One choice a prefix of another: BOTH stay reachable — at the
    complete-but-extendable point the model chooses between EOS (stop
    at the short choice) and the extension tokens (review finding r4:
    first-match-wins silently made the longer choice impossible)."""
    eng = make_engine()
    sp = SamplingParams(max_tokens=16, temperature=0.0,
                        guided_choice=["go", "gone"])
    out = eng.generate(["x"], sp)[0]
    assert out.text in ("go", "gone")
    assert out.finish_reason == "stop"
    # force the short choice: make EOS the only allowed continuation by
    # offering choices where the extension path is pruned
    sp2 = SamplingParams(max_tokens=16, temperature=0.0,
                         guided_choice=["go"])
    out2 = eng.generate(["x"], sp2)[0]
    assert out2.text == "go"


def test_api_surface():
    from production_stack_tpu.engine.server import EngineServer

    async def scenario():
        srv = EngineServer(EngineConfig(
            model="pst-tiny-debug", tokenizer="byte", dtype="float32",
            cache_dtype="float32", block_size=8, num_kv_blocks=64,
            max_num_seqs=2, max_prefill_chunk=32, seed=0,
        ))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user",
                              "content": "sentiment of: meh"}],
                "max_tokens": 16, "temperature": 0,
                "guided_choice": CHOICES,
            })
            assert r.status == 200
            data = await r.json()
            assert data["choices"][0]["message"]["content"] in CHOICES
            # validation errors are clean 400s
            r = await client.post("/v1/completions", json={
                "prompt": "x", "guided_choice": [],
            })
            assert r.status == 400
            r = await client.post("/v1/completions", json={
                "prompt": "x", "guided_choice": "notalist",
            })
            assert r.status == 400
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())

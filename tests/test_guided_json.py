"""Structured output via guided_json / guided_regex (vLLM guided
decoding roles, served by outlines/xgrammar-class backends there;
reference: src/vllm_router/services/request_service/request.py forwards
the fields verbatim to its engines). Ours compiles the schema/pattern
to a character-level machine and masks logits through a vocab-trie
product (engine/structured.py) — every completion must PARSE against
the constraint, at any temperature, streaming or not."""

from __future__ import annotations

import asyncio
import json

import pytest
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


SCHEMA = {
    "type": "object",
    "properties": {
        # maxLength bounds the string so a tiny random model cannot
        # babble the whole token budget away inside one value
        "name": {"type": "string", "maxLength": 10},
        "age": {"type": "integer"},
        "mood": {"enum": ["happy", "sad"]},
    },
    "required": ["name", "age", "mood"],
}


def _check(schema, text):
    v = json.loads(text)  # must parse
    if schema is SCHEMA:
        assert set(v) == {"name", "age", "mood"}
        assert isinstance(v["name"], str)
        assert isinstance(v["age"], int)
        assert v["mood"] in ("happy", "sad")
    return v


def test_greedy_output_parses_against_schema():
    eng = make_engine()
    sp = SamplingParams(max_tokens=96, temperature=0.0,
                        guided_json=SCHEMA)
    out = eng.generate(["describe a person"], sp)[0]
    assert out.finish_reason == "stop"
    _check(SCHEMA, out.text)


def test_sampled_output_parses_against_schema():
    eng = make_engine()
    sp = SamplingParams(max_tokens=96, temperature=1.0, seed=7,
                        guided_json=SCHEMA)
    outs = eng.generate(["a", "b"], sp)
    for o in outs:
        _check(SCHEMA, o.text)


def test_guided_json_under_multistep_config():
    """K>1 engines route guided lanes through the single-step masked
    path (the documented guided-vs-multistep cliff)."""
    eng = make_engine(num_scheduler_steps=4)
    sp = SamplingParams(max_tokens=96, temperature=0.0,
                        guided_json=SCHEMA)
    out = eng.generate(["x"], sp)[0]
    _check(SCHEMA, out.text)


def _parses_or_valid_prefix(text, finish_reason, spec):
    """Finished constrained output must parse; a budget-capped one must
    still be a valid PREFIX of the constraint language (the guarantee
    masking provides when max_tokens cuts generation short)."""
    if finish_reason == "stop":
        json.loads(text)
        return
    from production_stack_tpu.engine.structured import get_machine

    m = get_machine("json", spec)
    assert m.step_str(m.initial(), text), text


def test_json_object_any_value():
    """guided_json={} / response_format json_object: any JSON value."""
    eng = make_engine()
    sp = SamplingParams(max_tokens=64, temperature=0.0, guided_json={})
    out = eng.generate(["x"], sp)[0]
    _parses_or_valid_prefix(out.text, out.finish_reason, {})


def test_array_and_number_schema():
    eng = make_engine()
    schema = {"type": "array", "items": {"type": "number"},
              "minItems": 2, "maxItems": 4}
    sp = SamplingParams(max_tokens=64, temperature=0.8, seed=3,
                        guided_json=schema)
    v = json.loads(eng.generate(["x"], sp)[0].text)
    assert isinstance(v, list) and 2 <= len(v) <= 4
    assert all(isinstance(x, (int, float)) for x in v)


def test_recursive_ref_schema():
    eng = make_engine()
    schema = {
        "$defs": {"node": {
            "type": "object",
            "properties": {
                "v": {"type": "integer"},
                "kids": {"type": "array",
                         "items": {"$ref": "#/$defs/node"},
                         "maxItems": 2},
            },
            "required": ["v"],
        }},
        "$ref": "#/$defs/node",
    }
    sp = SamplingParams(max_tokens=96, temperature=0.9, seed=11,
                        guided_json=schema)
    out = eng.generate(["x"], sp)[0]
    _parses_or_valid_prefix(out.text, out.finish_reason, schema)
    if out.finish_reason == "stop":
        assert isinstance(json.loads(out.text)["v"], int)


def test_guided_regex():
    eng = make_engine()
    import re

    sp = SamplingParams(max_tokens=32, temperature=0.0,
                        guided_regex=r"[ab]{3}-\d{2}")
    out = eng.generate(["x"], sp)[0]
    assert re.fullmatch(r"[ab]{3}-\d{2}", out.text), out.text
    assert out.finish_reason == "stop"


def test_guided_regex_sampled():
    eng = make_engine()
    import re

    pat = r"(yes|no|maybe) with p=0\.\d"
    sp = SamplingParams(max_tokens=32, temperature=1.0, seed=5,
                        guided_regex=pat)
    for o in eng.generate(["q1", "q2"], sp):
        assert re.fullmatch(pat, o.text), o.text


def test_mutual_exclusion_and_bad_schema():
    with pytest.raises(ValueError):
        SamplingParams(guided_json={}, guided_regex="a+")
    eng = make_engine()
    with pytest.raises(ValueError):
        eng.generate(["x"], SamplingParams(
            guided_json={"type": "object",
                         "properties": {"a": {"type": "wat"}},
                         "required": ["a"]},
        ))
    with pytest.raises(ValueError):
        eng.generate(["x"], SamplingParams(guided_regex="([a-"))


def test_malformed_schemas_rejected_at_admission():
    """Every malformed construct must raise ValueError at add_request
    (-> HTTP 400), never TypeError/KeyError inside the step loop (which
    would kill the serving thread) — review findings r5."""
    from production_stack_tpu.engine.structured import JsonSchemaMachine

    bad = [
        {"type": "array", "items": False},
        {"type": "array", "items": [{"type": "integer"}]},  # tuple form
        {"$ref": "#/nope"},
        42,
        {"type": "array", "minItems": "2"},
        {"anyOf": []},
        {"type": "object", "properties": {"a": {"type": "wat"}}},
    ]
    for schema in bad:
        with pytest.raises(ValueError):
            JsonSchemaMachine(schema)


def test_properties_implies_object():
    from production_stack_tpu.engine.structured import JsonSchemaMachine

    m = JsonSchemaMachine({"properties": {"a": {"type": "boolean"}},
                           "required": ["a"]})
    st = m.step_str(m.initial(), '{"a":true}')
    assert st and m.accepting(st)


def test_step_failure_fails_requests_not_the_server():
    """An unexpected exception inside engine.step() must fail the
    in-flight requests with finish_reason=error and keep the server
    serving (review finding r5: a dead step-loop thread wedges every
    future request)."""
    from production_stack_tpu.engine.config import EngineConfig
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
            orig_step = srv.engine.engine.step
            calls = {"n": 0}

            def boom():
                calls["n"] += 1
                if calls["n"] == 1:
                    raise RuntimeError("injected step failure")
                return orig_step()

            srv.engine.engine.step = boom
            r = await client.post("/v1/completions", json={
                "prompt": "x", "max_tokens": 4, "temperature": 0,
            })
            # the poisoned request terminates (any clean HTTP status)
            assert r.status in (200, 500)
            # ...and the server still serves the next request
            r2 = await client.post("/v1/completions", json={
                "prompt": "y", "max_tokens": 4, "temperature": 0,
            })
            assert r2.status == 200
            data = await r2.json()
            assert data["usage"]["completion_tokens"] == 4
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())


def test_guided_and_spec_decode_coexist():
    """Spec-enabled engines exclude guided lanes from the verify path
    but must still serve them correctly."""
    eng = make_engine(num_speculative_tokens=4)
    sp = SamplingParams(max_tokens=96, temperature=0.0,
                        guided_json=SCHEMA)
    _check(SCHEMA, eng.generate(["x"], sp)[0].text)


def test_api_surface_guided_json():
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
            # non-streaming chat with guided_json
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "person"}],
                "max_tokens": 96, "temperature": 0,
                "guided_json": SCHEMA,
            })
            assert r.status == 200
            data = await r.json()
            _check(SCHEMA, data["choices"][0]["message"]["content"])

            # OpenAI response_format json_schema spelling
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "person"}],
                "max_tokens": 96, "temperature": 0,
                "response_format": {
                    "type": "json_schema",
                    "json_schema": {"name": "person", "schema": SCHEMA},
                },
            })
            assert r.status == 200
            data = await r.json()
            _check(SCHEMA, data["choices"][0]["message"]["content"])

            # STREAMING chat: concatenated deltas must parse too
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "person"}],
                "max_tokens": 96, "temperature": 0.7, "seed": 2,
                "guided_json": SCHEMA, "stream": True,
            })
            assert r.status == 200
            text = ""
            finish = None
            async for line in r.content:
                line = line.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                chunk = json.loads(line[len("data: "):])
                delta = chunk["choices"][0]["delta"]
                text += delta.get("content", "")
                finish = chunk["choices"][0]["finish_reason"] or finish
            _check(SCHEMA, text)
            assert finish == "stop"

            # completions + guided_regex
            r = await client.post("/v1/completions", json={
                "prompt": "x", "max_tokens": 24, "temperature": 0,
                "guided_regex": r"ab+c",
            })
            assert r.status == 200
            data = await r.json()
            import re

            assert re.fullmatch(r"ab+c", data["choices"][0]["text"])

            # bad schema -> clean 400
            r = await client.post("/v1/completions", json={
                "prompt": "x", "guided_json": {"type": "nope"},
            })
            assert r.status == 400
            r = await client.post("/v1/completions", json={
                "prompt": "x", "guided_regex": 123,
            })
            assert r.status == 400
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())


def test_deeply_nested_json_spec_is_admission_valueerror():
    """RecursionError from cache-key construction (json.loads/dumps
    recurse over the spec BEFORE compile) must surface as the documented
    admission ValueError -> 400, like grammar/regex (code-review r5)."""
    import pytest

    from production_stack_tpu.engine.structured import get_machine

    deep = "[" * 30000 + "1" + "]" * 30000
    with pytest.raises(ValueError, match="nested"):
        get_machine("json", deep)


# -- budget-aware completion steering ---------------------------------------
def test_steering_completes_regex_at_exact_budget():
    """With max_tokens barely above the shortest conforming string, the
    final-token mask must steer off the repeatable construct so the
    stream ends regex-conforming instead of riding 'b' past the budget
    (_steer_allowed / _dist_to_accept)."""
    import re

    eng = make_engine()
    sp = SamplingParams(max_tokens=5, temperature=0.0,
                        guided_regex=r"ab+c")
    text = eng.generate(["x"], sp)[0].text
    assert re.fullmatch(r"ab+c", text), text


def test_steering_parity_k1_vs_k4_near_budget():
    """Guided lanes leave the fused device path inside the steering
    window (near_budget bail), so K=4 output stays bit-identical to the
    K=1 host-masked path AND both complete within budget."""
    import re

    outs = []
    for k in (1, 4):
        eng = make_engine(num_scheduler_steps=k)
        sp = SamplingParams(max_tokens=6, temperature=0.0,
                            guided_regex=r"ab+c")
        outs.append(eng.generate(["x"], sp)[0].text)
    assert outs[0] == outs[1]
    assert re.fullmatch(r"ab+c", outs[0]), outs


def test_steering_gives_up_when_nothing_completes():
    """A budget too small for ANY conforming completion must not crash
    or empty the mask: steering returns None and the unsteered
    constraint masks apply (output is a conforming PREFIX)."""
    eng = make_engine()
    sp = SamplingParams(max_tokens=2, temperature=0.0,
                        guided_regex=r"abbbbbc")
    text = eng.generate(["x"], sp)[0].text
    assert "abbbbbc".startswith(text) and text, text

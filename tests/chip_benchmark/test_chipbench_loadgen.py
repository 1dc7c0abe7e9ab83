"""The benchmark's traffic generator and its arithmetic (CPU, no server
process, no chip)."""

import asyncio
import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                     "chip")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


loadgen = _load("loadgen")


def traffic(name, **over):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        t = json.load(f)
    if t["loop"] == "open":
        t["rate_rps"] = 3.0
    return {**t, **over}


@pytest.mark.parametrize("name", ["chat-sys2k", "batch-fewshot2k"])
def test_same_seed_same_requests(name):
    a = loadgen.build_plan(traffic(name), 3000000019, 10)
    b = loadgen.build_plan(traffic(name), 3000000019, 10)
    assert a.fingerprint() == b.fingerprint()


@pytest.mark.parametrize("name", ["chat-sys2k", "batch-fewshot2k"])
def test_other_seed_other_requests_same_work(name):
    a = loadgen.build_plan(traffic(name), 1, 10)
    b = loadgen.build_plan(traffic(name), 2, 10)
    assert a.fingerprint() != b.fingerprint()
    # the same multiset of sizes and gaps, in another order
    assert len(a.turns) == len(b.turns)
    if name == "batch-fewshot2k":
        # a closed loop deals one pool of sizes to its clients (the
        # first request of each is cut short to spread their phases)
        assert sorted(len(t.user_text) for t in a.turns) == sorted(
            len(t.user_text) for t in b.turns)
        return
    wa = [t for t in a.turns if t.phase == "window"]
    wb = [t for t in b.turns if t.phase == "window"]
    assert len(wa) == len(wb) == 30
    assert [t.max_tokens for t in wa] != [t.max_tokens for t in wb]
    assert sorted(t.max_tokens for t in wa) == sorted(
        t.max_tokens for t in wb)
    assert sorted(len(t.user_text) for t in wa) == sorted(
        len(t.user_text) for t in wb)
    assert wa[-1].due_s == pytest.approx(wb[-1].due_s)
    gaps = lambda w: sorted(round(y.due_s - x.due_s, 9)  # noqa: E731
                            for x, y in zip(w, w[1:]))
    assert gaps(wa)[1:] == pytest.approx(gaps(wb)[1:], abs=0.2)


@pytest.mark.parametrize("n_blocks", [1, 4, 10])
def test_stratified_order_keeps_the_values_and_spreads_them(n_blocks):
    import random

    vals = list(range(120))
    out = loadgen.stratified_order(list(vals), n_blocks, random.Random(3))
    assert sorted(out) == vals and out != vals
    size = len(vals) // n_blocks
    for j in range(n_blocks):
        block = sorted(out[j * size:(j + 1) * size])
        # one value out of every n_blocks consecutive ones
        assert [v // n_blocks for v in block] == list(range(size))
    # dealt forwards and backwards in turn, the blocks' sums are alike
    sums = [sum(out[j * size:(j + 1) * size]) for j in range(n_blocks)]
    assert max(sums) - min(sums) <= n_blocks
    # one block is the plain shuffle the generator had before
    if n_blocks == 1:
        ref = list(vals)
        random.Random(3).shuffle(ref)
        assert out == ref


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_stratified_window_offers_the_same_load_in_every_stretch(seed):
    """With `stratify_seconds` every stretch of the window holds about
    the same arrivals and the same tokens to generate, whatever the
    seed; without it a seed can pile them up."""
    t = traffic("chat-sys2k", stratify_seconds=5)
    assert "stratify_seconds" in traffic("chat-sys2k")
    plan = loadgen.build_plan(t, seed, 50)
    warm = t["setup"]["warm_seconds"]
    win = [x for x in plan.turns if x.phase == "window"]
    assert len(win) == 150
    per = [[x for x in win if warm + 5 * j <= x.due_s < warm + 5 * (j + 1)]
           for j in range(10)]
    mean_out = sum(x.max_tokens for x in win) / 10
    for block in per:
        assert 9 <= len(block) <= 21
    # tokens asked for by 15 consecutive arrivals: a systematic sample
    for j in range(10):
        block = win[15 * j:15 * (j + 1)]
        assert abs(sum(x.max_tokens for x in block) - mean_out) \
            < 0.02 * mean_out
    # every seed's session pool starts with the same histories
    other = loadgen.build_plan(t, seed + 1, 50)
    size = lambda s: loadgen.prompt_tokens(s.messages)  # noqa: E731
    assert sorted(map(size, plan.sessions)) == sorted(
        map(size, other.sessions))
    assert sorted(map(size, plan.spares)) == sorted(
        map(size, other.spares))


def test_open_loop_schedule_fills_warm_and_window():
    t = traffic("chat-sys2k")
    plan = loadgen.build_plan(t, 5, 20)
    warm = t["setup"]["warm_seconds"]
    assert len(plan.turns) == round(3.0 * (warm + 20))
    assert all(x.due_s < y.due_s for x, y in zip(plan.turns, plan.turns[1:]))
    assert plan.turns[-1].due_s < warm + 20
    n_window = sum(t.phase == "window" for t in plan.turns)
    assert n_window == 60
    assert len(plan.setup_turns) == t["session_pool"] == len(plan.sessions)


@pytest.mark.parametrize("name", ["chat-sys2k", "batch-fewshot2k"])
def test_every_context_stays_in_one_context_bucket(name):
    """The traffic files promise that every prefill chunk ends past
    token 2048 (the shared prefix alone is longer) and that no context
    passes 4096: the program buckets contexts by powers of two, and
    set-up warms the buckets above the shared prefix only (no list of
    the program's buckets is kept in a traffic file)."""
    t = traffic(name)
    plan = loadgen.build_plan(t, 11, 30)
    assert plan.prefixes and "warm_programs" not in t
    assert t["shared_prefix_tokens"] > 2048
    for pre in plan.prefixes:
        assert loadgen.prompt_tokens(
            [{"role": "system", "content": pre}]) - 14 > 2048 + 32
    biggest = max(len(x.user_text) for x in plan.turns)
    longest = max(x.max_tokens for x in plan.turns)
    if name == "batch-fewshot2k":
        n = loadgen.prompt_tokens([
            {"role": "system", "content": plan.prefixes[0]},
            {"role": "user", "content": "x" * biggest}])
        assert n + longest + 8 <= 4096
        return
    for s in plan.sessions + plan.spares:
        n = loadgen.prompt_tokens(s.messages)
        assert s.messages[0]["content"] in plan.prefixes
        # a fresh session's first turn always fits
        assert (n + loadgen.message_tokens("user", "x" * biggest)
                + longest + 8 <= t["history"]["retire_context_tokens"])
    assert t["history"]["retire_context_tokens"] + 8 <= 4096


def test_prompt_tokens_is_the_byte_tokenizers_count():
    from production_stack_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    msgs = [{"role": "system", "content": "abc def"},
            {"role": "user", "content": "hello"},
            {"role": "assistant", "content": "xy"},
            {"role": "user", "content": "again"}]
    assert loadgen.prompt_tokens(msgs) == len(
        tok.encode(tok.apply_chat_template(msgs)))


def test_latency_runs_from_the_due_instant():
    rec = loadgen.Record(idx=0, phase="window", due=10.0, sent=10.5,
                         first=11.0)
    assert loadgen.ttft_ms(rec) == pytest.approx(1000.0)
    assert loadgen.late_ms(rec) == pytest.approx(500.0)
    closed = loadgen.Record(idx=0, phase="window", due=None, sent=10.5,
                            first=11.0)
    assert loadgen.ttft_ms(closed) == pytest.approx(500.0)
    assert loadgen.late_ms(closed) is None
    assert loadgen.ttft_ms(loadgen.Record(idx=0, phase="window")) is None


@pytest.mark.parametrize("p,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                    (100, 5.0), (25, 2.0)])
def test_percentile(p, want):
    assert loadgen.percentile([5.0, 1.0, 4.0, 2.0, 3.0], p) == \
        pytest.approx(want)


def test_percentile_of_nothing_and_supported_percentile():
    assert loadgen.percentile([], 95) is None
    assert loadgen.supported_percentile(200) == pytest.approx(95.0)
    assert loadgen.supported_percentile(10) == 0.0


def test_token_gaps_and_rate():
    rec = loadgen.Record(idx=0, phase="window",
                         events=[(1.0, 8), (1.1, 8), (1.35, 1)])
    gaps = loadgen.token_gaps_ms(rec)
    assert len(gaps) == 16            # 17 tokens, 16 gaps
    assert gaps.count(0.0) == 14
    assert sorted(g for g in gaps if g) == pytest.approx([100.0, 250.0])
    other = loadgen.Record(idx=1, phase="warm", events=[(0.5, 4), (2.0, 4)])
    assert loadgen.tokens_in_window([rec, other], 1.0, 2.0) == 17
    assert loadgen.tokens_in_window([rec, other], 0.0, 3.0) == 25


def test_request_time_and_time_per_output_token():
    rec = loadgen.Record(idx=0, phase="window", due=1.0, sent=1.0,
                         first=1.5, tokens=17,
                         events=[(1.5, 8), (1.7, 8), (2.3, 1)])
    assert loadgen.request_ms(rec) == pytest.approx(1300.0)
    assert loadgen.tpot_ms(rec) == pytest.approx(800.0 / 16)
    one = loadgen.Record(idx=0, phase="window", due=1.0, first=1.5,
                         tokens=1, events=[(1.5, 1)])
    assert loadgen.tpot_ms(one) is None
    assert loadgen.request_ms(loadgen.Record(idx=0, phase="w")) is None
    # normalized latency: the whole of the request over its tokens
    assert loadgen.norm_latency_ms(rec) == pytest.approx(1300.0 / 17)
    assert loadgen.norm_latency_ms(one) == pytest.approx(500.0)
    assert loadgen.norm_latency_ms(loadgen.Record(idx=0, phase="w")) is None
    assert loadgen.mean([1.0, 2.0, 6.0]) == pytest.approx(3.0)
    assert loadgen.mean([]) is None


def test_record_ok_needs_all_of_it():
    good = dict(idx=0, phase="window", status=200, done=True, tokens=5,
                max_tokens=5, usage_completion=5, finish_reason="length")
    assert loadgen.Record(**good).ok()
    for bad in ({"status": 500}, {"done": False}, {"tokens": 4},
                {"usage_completion": 4}, {"error": "x"},
                {"finish_reason": "stop"}):
        assert not loadgen.Record(**{**good, **bad}).ok()


def test_driver_reads_a_stream_and_grows_the_session():
    """One request against an in-process SSE handler: the role chunk is
    not a token, an event's length is its token count, usage and [DONE]
    are checked, and the answer joins the session's history."""
    from aiohttp import web

    seen = {}

    async def handler(request):
        body = await request.json()
        seen["body"] = body
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)

        async def send(obj):
            await resp.write(b"data: " + json.dumps(obj).encode() + b"\n\n")
        await send({"choices": [{"delta": {"role": "assistant"}}]})
        await send({"choices": [{"delta": {"content": "\U00010041" * 3}}]})
        await asyncio.sleep(0.05)
        await send({"choices": [{"delta": {"content": "\U00010042" * 2}}]})
        await send({"choices": [{"delta": {}, "finish_reason": "length"}]})
        await send({"choices": [], "usage": {
            "prompt_tokens": loadgen.prompt_tokens(body["messages"]),
            "completion_tokens": 5}})
        await resp.write(b"data: [DONE]\n\n")
        return resp

    async def go():
        app = web.Application()
        app.router.add_post("/v1/chat/completions", handler)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        t = traffic("chat-sys2k", session_pool=1, prefix_variants=1)
        plan = loadgen.build_plan(t, 1, 1)
        plan.setup_turns[0].max_tokens = 5
        drv = loadgen.Driver(plan, port, "m")
        recs = await drv.run_setup()
        await runner.cleanup()
        return plan, recs

    plan, recs = asyncio.run(go())
    prime, rec = recs     # the shared prefix alone first, then turn 0
    assert prime.max_tokens == 1 and not prime.ok()   # 5 tokens came
    assert rec.ok(), rec.as_json()
    assert [n for _, n in rec.events] == [3, 2]
    assert rec.first == rec.events[0][0]
    assert seen["body"]["stream"] is True and "logprobs" not in seen["body"]
    assert plan.sessions[0].messages[-1] == {
        "role": "assistant", "content": "\U00010041" * 3 + "\U00010042" * 2}
    assert not plan.sessions[0].in_flight

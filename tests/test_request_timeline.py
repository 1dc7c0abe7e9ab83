"""Engine request-lifecycle timeline e2e (CPU).

A chunked + preempted request runs through AsyncLLMEngine and its
timeline must attribute TTFT into enqueue -> admit (queue-wait) ->
prefill-chunk(s) -> first-token -> finish with monotonically ordered
events, all sharing the trace id the router span propagated via
`traceparent`; the exported `engine_request` span is a child of the
router span. Also pins: preempt/resume events + stall accounting, the
/debug/requests endpoint shape, and zero recording when disabled."""

from __future__ import annotations

import asyncio

import numpy as np

from production_stack_tpu import tracing as T
from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams


def _config(**overrides) -> EngineConfig:
    kwargs = dict(
        model="pst-tiny-debug",
        tokenizer="byte",
        dtype="float32",
        cache_dtype="float32",
        block_size=8,
        num_kv_blocks=128,
        max_num_seqs=4,
        max_prefill_chunk=8,  # 17-token prompts take 3 chunks
        num_scheduler_steps=1,
        seed=0,
    )
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def _prompt(n: int, seed: int = 3) -> list[int]:
    rng = np.random.RandomState(seed)
    return rng.randint(0, 384, size=n).tolist()


def _names(tl: dict) -> list[str]:
    return [e["name"] for e in tl["events"]]


async def _drain(engine: AsyncLLMEngine, request_id: str, prompt, sp,
                 traceparent=None, priority=0):
    final = None
    async for out in engine.generate(
        request_id, prompt_token_ids=prompt, sampling_params=sp,
        traceparent=traceparent, priority=priority,
    ):
        final = out
    return final


def test_async_engine_timeline_chunked_preempted_shared_trace():
    async def run():
        # pool sized so A (17 prompt + 40 gen = 8 blocks) + B exhaust
        # blocks mid-decode; priority policy makes the victim
        # DETERMINISTIC: B (priority 1) is always evicted, never A, so
        # A's timeline stays a clean 3-chunk prefill while B records
        # preempt -> resume
        eng = AsyncLLMEngine(_config(
            num_kv_blocks=12, tracing_exporter="memory",
            scheduling_policy="priority",
        ))
        eng.start(asyncio.get_running_loop())
        try:
            # the "router": a proxy span whose traceparent rides the
            # request into the engine
            router_tracer = T.RequestTracer("memory")
            router_span = router_tracer.start_span("proxy_request")

            sp_a = SamplingParams(
                max_tokens=40, temperature=0.0, ignore_eos=True
            )
            sp_b = SamplingParams(
                max_tokens=40, temperature=0.0, ignore_eos=True
            )
            task_a = asyncio.ensure_future(_drain(
                eng, "req-a", _prompt(17, 3), sp_a,
                traceparent=router_span.traceparent,
            ))
            await asyncio.sleep(0.05)  # A admitted first
            task_b = asyncio.ensure_future(_drain(
                eng, "req-b", _prompt(17, 4), sp_b, priority=1,
            ))
            out_a, out_b = await asyncio.gather(task_a, task_b)
            router_tracer.finish(router_span)

            assert out_a.finished and out_b.finished
            assert len(out_a.token_ids) == 40
            assert len(out_b.token_ids) == 40

            recorder = eng.timeline
            by_id = {tl["request_id"]: tl
                     for tl in recorder.snapshot(limit=16)}
            tl_a, tl_b = by_id["req-a"], by_id["req-b"]

            # -- A: chunked lifecycle, shared trace id -----------------
            names = _names(tl_a)
            assert names[0] == "enqueue"
            assert names[-1] == "finish"
            for marker in ("admit", "prefill_chunk", "first_token"):
                assert marker in names, f"missing {marker}: {names}"
            # 17-token prompt at chunk 8 -> 3 prefill chunks, the last
            # flagged; chunk events carry the chained flag
            chunks = [e for e in tl_a["events"]
                      if e["name"] == "prefill_chunk"]
            assert len(chunks) == 3
            assert [c["attributes"]["chunk_len"] for c in chunks] == \
                [8, 8, 1]
            assert [c["attributes"]["last"] for c in chunks] == \
                [False, False, True]
            for c in chunks:
                assert "chained" in c["attributes"]
            # strict event order (enqueue -> ... -> finish) on the
            # monotonic clock
            rels = [e["t_rel_s"] for e in tl_a["events"]]
            assert rels == sorted(rels)
            assert (names.index("enqueue") < names.index("admit")
                    < names.index("prefill_chunk")
                    < names.index("first_token")
                    < names.index("finish"))
            # TTFT attribution: admit carries queue-wait, first_token
            # carries ttft, and both are consistent with event order
            admit = next(e for e in tl_a["events"] if e["name"] == "admit")
            ft = next(e for e in tl_a["events"]
                      if e["name"] == "first_token")
            assert admit["attributes"]["queue_wait_s"] >= 0
            assert ft["attributes"]["ttft_s"] >= 0
            # trace id shared with the router span end-to-end
            assert tl_a["trace_id"] == router_span.trace_id
            assert tl_a["parent_span_id"] == router_span.span_id
            for e in tl_a["events"]:
                pass  # events live inside the timeline: one trace id

            # -- engine span: child of the router span -----------------
            eng_spans = [s for s in eng.tracer.spans
                         if s.attributes.get("request_id") == "req-a"]
            assert eng_spans, "engine_request span not exported"
            es = eng_spans[-1]
            assert es.name == "engine_request"
            assert es.trace_id == router_span.trace_id
            assert es.parent_span_id == router_span.span_id
            assert es.duration_s is not None and es.duration_s >= 0
            assert [n for n, _, _ in es.events][0] == "enqueue"

            # -- B: preempted + resumed, stall accounted ---------------
            names_b = _names(tl_b)
            assert "preempt" in names_b and "resume" in names_b
            assert names_b.index("preempt") < names_b.index("resume")
            resume = next(e for e in tl_b["events"]
                          if e["name"] == "resume")
            assert resume["attributes"]["stall_s"] > 0
            assert out_b.metrics.num_preemptions >= 1
            assert out_b.metrics.preempt_stall_s > 0
            assert out_b.metrics.admitted_time is not None
            # B started its own trace (no traceparent supplied)
            assert tl_b["trace_id"] != tl_a["trace_id"]
        finally:
            eng.shutdown()

    asyncio.run(run())


def test_timeline_decode_rounds_sampled_not_per_token():
    engine = LLMEngine(_config(num_scheduler_steps=1))
    sp = SamplingParams(max_tokens=48, temperature=0.0, ignore_eos=True)
    (out,) = engine.generate([_prompt(9)], sp)
    assert out.finished
    (tl,) = [t for t in engine.timeline.snapshot(limit=8)
             if t["request_id"] == "gen-0"]
    ticks = [e for e in tl["events"] if e["name"] == "decode_round"]
    # 47 decode rounds after the first token -> sampled every
    # DECODE_EVENT_EVERY, far fewer events than tokens (the finishing
    # round is covered by the finish event, not a decode tick)
    assert 0 < len(ticks) <= 48 // T.DECODE_EVENT_EVERY
    assert tl["decode_rounds"] == 46


def test_timeline_disabled_records_nothing():
    engine = LLMEngine(_config(request_timeline=False))
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    (out,) = engine.generate([_prompt(9)], sp)
    assert out.finished
    assert engine.timeline.enabled is False
    assert engine.timeline.snapshot() == []
    # queue-wait metrics still populate (they ride RequestMetrics, not
    # the timeline)
    assert out.metrics.admitted_time is not None


def test_timeline_abort_finishes_entry():
    engine = LLMEngine(_config())
    sp = SamplingParams(max_tokens=64, temperature=0.0, ignore_eos=True)
    engine.add_request("victim", prompt_token_ids=_prompt(9),
                       sampling_params=sp)
    engine.step()
    assert engine.abort_request("victim")
    tls = {t["request_id"]: t for t in engine.timeline.snapshot()}
    assert tls["victim"]["finished"] is True
    assert tls["victim"]["finish_reason"] == "abort"


def test_engine_server_honors_and_echoes_request_id():
    """Real EngineServer: a router-supplied x-request-id becomes the
    engine-side request id (response id + echoed header + timeline key)
    and the propagated traceparent links the engine timeline to the
    router's trace; a malformed id falls back to a generated one."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def run():
        srv = EngineServer(_config(
            num_kv_blocks=64, max_num_seqs=2, max_prefill_chunk=16,
        ))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            router_trace, router_span = "ab" * 16, "cd" * 8
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hello", "max_tokens": 3,
                      "temperature": 0, "ignore_eos": True},
                headers={
                    "x-request-id": "router-req-7",
                    "traceparent": T.format_traceparent(
                        router_trace, router_span
                    ),
                },
            )
            assert r.status == 200
            assert r.headers["x-request-id"] == "router-req-7"
            assert (await r.json())["id"] == "router-req-7"
            dbg = await (await client.get("/debug/requests")).json()
            (tl,) = [t for t in dbg["requests"]
                     if t["request_id"] == "router-req-7"]
            assert tl["trace_id"] == router_trace
            assert tl["parent_span_id"] == router_span
            assert tl["finished"] is True

            # malformed id: rejected, fresh id generated and echoed
            r2 = await client.post(
                "/v1/completions",
                json={"prompt": "hello", "max_tokens": 2,
                      "temperature": 0, "ignore_eos": True},
                headers={"x-request-id": "bad id with spaces"},
            )
            assert r2.status == 200
            rid2 = r2.headers["x-request-id"]
            assert rid2.startswith("cmpl-")
            assert (await r2.json())["id"] == rid2
        finally:
            await client.close()

    asyncio.run(run())


def test_request_identity_deconflicts_inflight_ids():
    """A router/client-supplied x-request-id that is still IN FLIGHT
    (timeout retry with a stable id) must fall back to a fresh id and
    be SERVED, not 400 on the engine's duplicate-id guard; multi-choice
    retries collide on the `-c0` sub-id and fall back too."""
    from production_stack_tpu.engine.server import EngineServer

    class _Req:
        def __init__(self, headers):
            self.headers = headers

    class _Eng:
        # note c3: sub-ids other than -c0 may be the surviving ones
        inflight = {"busy-id", "multi-id-c3"}

        def has_request(self, rid):
            return rid in self.inflight

        def has_request_prefix(self, rid):
            return any(k.startswith(f"{rid}-c") for k in self.inflight)

    srv = EngineServer.__new__(EngineServer)
    srv.engine = _Eng()

    rid, _ = srv._request_identity(_Req({"x-request-id": "fresh-id"}),
                                   "cmpl")
    assert rid == "fresh-id"
    rid, _ = srv._request_identity(_Req({"x-request-id": "busy-id"}),
                                   "cmpl")
    assert rid != "busy-id" and rid.startswith("cmpl-")
    rid, _ = srv._request_identity(_Req({"x-request-id": "multi-id"}),
                                   "cmpl")
    assert rid != "multi-id" and rid.startswith("cmpl-")


def test_debug_requests_endpoint_shape():
    """/debug/requests serves the recorder ring (stubbed server, same
    idiom as test_rerank_score)."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    engine = LLMEngine(_config(max_prefill_chunk=16))
    sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    engine.generate([_prompt(9)], sp)

    srv = EngineServer.__new__(EngineServer)
    srv.config = engine.config
    srv.model_name = "pst-tiny-debug"
    srv.lora_adapters = {}
    srv._stats_task = None

    class _Eng:
        timeline = engine.timeline
        tracer = engine.tracer

    srv.engine = _Eng()
    srv.app = srv._build_app()

    async def run():
        srv.app.on_startup.clear()  # stub engine has no step loop
        srv.app.on_cleanup.clear()
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            r = await client.get("/debug/requests")
            assert r.status == 200
            data = await r.json()
            assert data["enabled"] is True
            (tl,) = data["requests"]
            assert tl["request_id"] == "gen-0"
            assert _names(tl)[0] == "enqueue"
            assert _names(tl)[-1] == "finish"
            # bad limit falls back instead of 500ing
            r2 = await client.get("/debug/requests?limit=bogus")
            assert r2.status == 200
        finally:
            await client.close()

    asyncio.run(run())

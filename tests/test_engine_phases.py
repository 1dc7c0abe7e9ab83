"""The engine's round seen from inside (tracing/phases.py): phase spans
in the profiler's own trace, the counters of /metrics, and the names the
programs and kernels carry. All on the CPU at the tiny debug widths."""

import ast
import asyncio
import glob
import inspect
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from prometheus_client import CollectorRegistry, generate_latest

from production_stack_tpu.engine import model_runner
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.metrics import EngineMetrics
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.ops import pallas_attention
from production_stack_tpu.tracing import ENGINE_PHASES, phases

STEP_PHASES = ("schedule", "pack", "h2d", "dispatch", "fetch", "apply")


def cfg(**overrides) -> EngineConfig:
    kwargs = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=4, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, num_scheduler_steps=4,
        adaptive_decode_k=False, seed=0,
    )
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def prompt(n, seed=3):
    return np.random.RandomState(seed).randint(0, 384, size=n).tolist()


def drive(e, late_at=None):
    """Step until nothing is left; at step `late_at` request "b" is
    admitted while the first request decodes, so that its prefill rides
    a ragged round. Returns the kinds of the steps taken."""
    kinds = []
    while e.has_unfinished():
        if len(kinds) == late_at:
            e.add_request("b", prompt_token_ids=prompt(9, seed=5),
                          sampling_params=greedy(6))
        e.step()
        kinds.append(e.last_step_kind)
    return kinds


# -- (a) spans on the profiler's clock ---------------------------------------
def host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "server.")):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return out


def test_phases_are_leaves_of_engine_step_in_the_profilers_trace(tmp_path):
    e = LLMEngine(cfg())
    jax.profiler.start_trace(str(tmp_path))
    try:
        # a cold two-chunk prefill, decode rounds chained on staged
        # buffers, and a second request whose prefill rides a ragged
        # round beside the first one's decode lanes
        e.add_request("a", prompt_token_ids=prompt(21),
                      sampling_params=greedy(24))
        kinds = drive(e, late_at=4)
    finally:
        jax.profiler.stop_trace()
    assert {"prefill", "ragged", "decode"} <= set(kinds)
    events = host_events(str(tmp_path))
    steps = [ev for ev in events if ev[0] == "engine.step"]
    assert len(steps) == len(kinds)
    names = {ev[0] for ev in events}
    assert {"engine." + p for p in STEP_PHASES} <= names
    assert "engine.build" in names          # cold: every program built
    # every phase lies inside one engine.step; a build inside a dispatch
    for name, s, t, _ in events:
        if name in ("engine.step", "engine.idle", "engine.deliver"):
            continue
        assert any(s0 <= s and t <= t1 for _, s0, t1, _ in steps), name
    dispatches = [ev for ev in events if ev[0] == "engine.dispatch"]
    for name, s, t, stats in events:
        if name == "engine.build":
            assert stats["kind"] in model_runner.PROGRAM_KINDS
            assert any(s0 <= s and t <= t1 for _, s0, t1, _ in dispatches)
    # engine.step carries the round's number and kind
    tagged = [st[3] for st in steps if "round" in st[3]]
    assert len(tagged) == len(steps)
    assert [t["round"] for t in tagged] == sorted(t["round"] for t in tagged)
    assert {t["kind"] for t in tagged} == {"prefill", "ragged", "decode"}
    ragged = next(t for t in tagged if t["kind"] == "ragged")
    assert ragged["k"] == 4 and ragged["lanes"] == 1 and ragged["rows"] == 9
    # a timeline event joins the span it fell in by the round's number
    timelines = {t["request_id"]: t for t in e.timeline.snapshot()}
    joined = 0
    for tl in timelines.values():
        base = None
        for ev in tl["events"]:
            rnd = (ev.get("attributes") or {}).get("engine_round")
            if rnd is None:
                continue
            # a step that chains several prefill rounds carries the
            # number of its first: the span is the last one at or
            # below the event's round
            step = [st for st in steps if st[3]["round"] <= rnd][-1]
            if ev["name"] == "prefill_chunk" and ev["attributes"].get(
                    "ragged"):
                assert step[3]["kind"] == "ragged"
            if ev["name"] == "decode_round":
                assert step[3]["kind"] in ("decode", "ragged")
            joined += 1
            base = rnd if base is None else base
            assert rnd >= base          # rounds only grow along a request
    assert joined >= 5
    first = [ev for ev in timelines["b"]["events"]
             if ev["name"] == "first_token"]
    assert first[0]["attributes"]["engine_round"] == ragged["round"]


def test_no_profiler_session_no_annotation_object(monkeypatch):
    """With the profiler off a span is a counter and nothing else: the
    helper makes no annotation, on any path of the round."""
    made = []

    class Recording(phases.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(phases, "TraceAnnotation", Recording)
    assert not phases.profiling()
    e = LLMEngine(cfg())
    e.add_request("a", prompt_token_ids=prompt(21),
                  sampling_params=greedy(12))
    drive(e, late_at=4)
    assert made == []
    assert phases.annotation("engine.build", kind="x") is phases.NO_SPAN
    counts = e.phases.counts()
    assert all(counts[p] > 0 for p in STEP_PHASES)


# -- (b) names -------------------------------------------------------------
def test_every_builder_jits_under_a_kind_and_counts_it_under_the_same():
    tree = ast.parse(inspect.getsource(model_runner))
    jitted, counted, bare = set(), set(), []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = ast.unparse(node.func)
            if callee == "jit_program":
                jitted.add(node.args[0].value)
            elif callee == "self._note_compile":
                counted.add(node.args[0].value)
            elif callee == "jax.jit" and fn.name.startswith("_build"):
                bare.append(fn.name)
    kinds = model_runner.PROGRAM_KINDS
    assert len(set(kinds)) == len(kinds)
    assert jitted == counted == set(kinds)
    assert not bare


@pytest.mark.parametrize("attention_impl,pipeline", [
    ("pallas", True), ("xla", True), ("xla", False)])
def test_programs_lower_under_the_name_of_their_kind(
        attention_impl, pipeline):
    # the rows programs, the lane-mix programs, the per-array uploads
    lowered = []

    def listen(event, duration, fun_name=None, **_):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        e = LLMEngine(cfg(attention_impl=attention_impl,
                          prefill_pipeline=pipeline))
        e.add_request("a", prompt_token_ids=prompt(21),
                      sampling_params=greedy(12))
        e.add_request("c", prompt_token_ids=prompt(7, seed=9),
                      sampling_params=greedy(5))
        drive(e, late_at=3)
        e.embed_one("hello")
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    built = set(e.runner.compile_events)
    assert len(built) >= 3 and "embed" in built
    assert built <= set(model_runner.PROGRAM_KINDS)
    # the lowering event names a module `jit(<name>)`; the HLO module
    # and the trace's XLA Modules line read `jit_<name>`
    assert {f"jit({k})" for k in built} <= set(lowered)
    assert not {n for n in lowered if "step" in n}


def test_the_three_kernels_are_named_in_the_pallas_call():
    """Each public kernel function stages ONE pallas_call that carries
    the function's own name: the name its operations have in a device
    trace, which the benchmark's readers match."""
    from jax._src import core

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)

    q = jnp.zeros((8, 4, 128))
    cache = jnp.zeros((1, 2, 64, 128))
    layer = jnp.int32(0)
    table = jnp.zeros((8, 2), jnp.int32)
    kw = dict(block_size=8, scale=1.0)
    staged = {
        "ragged_paged_attention": lambda f: f(
            q, cache, cache, layer, table, jnp.asarray([0, 1], jnp.int32),
            jnp.asarray([[0, 0, 8, 0]], jnp.int32), **kw),
        "paged_prefill_attention": lambda f: f(
            q, cache, cache, layer, table[0], jnp.int32(0), **kw),
        "paged_decode_attention": lambda f: f(
            q, cache, cache, layer, table, jnp.ones((8,), jnp.int32), **kw),
    }
    for name, call in staged.items():
        fn = getattr(pallas_attention, name)
        jaxpr = jax.make_jaxpr(lambda: call(fn))().jaxpr
        assert list(pallas_calls(jaxpr)) == [name]


# -- (c) counters ------------------------------------------------------------
def test_attn_context_tokens_equals_the_hand_count_and_phases_count():
    e = LLMEngine(cfg())
    e.add_request("a", prompt_token_ids=prompt(10),
                  sampling_params=greedy(9))
    e.add_request("b", prompt_token_ids=prompt(6, seed=5),
                  sampling_params=greedy(9))
    e.step()                    # both prompts in one packed prefill
    assert e.last_step_kind == "prefill"
    assert tuple(e.runner.attn_context_tokens) == (10 + 6, 1)
    tokens0, rounds0 = e.runner.attn_context_tokens
    dispatch0 = e.phases.counts()["dispatch"]
    e.step()                    # two lanes, K=4: contexts 11.. and 7..
    assert e.last_step_kind == "decode"
    hand = sum(11 + i for i in range(4)) + sum(7 + i for i in range(4))
    assert e.runner.attn_context_tokens[0] - tokens0 == hand
    assert e.runner.attn_context_tokens[1] - rounds0 == 1
    assert e.phases.counts()["dispatch"] - dispatch0 == 1
    snap = e.stats()
    assert snap.attn_context_tokens == tuple(e.runner.attn_context_tokens)
    assert set(snap.engine_phases) == set(ENGINE_PHASES)
    assert set(snap.program_stages) == {"trace", "lower", "compile"}
    assert all(n > 0 and s > 0 for s, n in snap.program_stages.values())


def test_decode_lane_steps_count_the_lanes_that_hold_no_sequence():
    """tpu:decode_lane_steps / tpu:decode_idle_lane_steps: lanes x fused
    steps of every dispatched round's decode rows, and those of lanes
    the pack shipped with context 0 (zero-row segments of the walk);
    a prefill round adds nothing."""
    e = LLMEngine(cfg())
    lanes = e.config.max_num_seqs
    assert lanes > 2
    e.add_request("a", prompt_token_ids=prompt(10),
                  sampling_params=greedy(9))
    e.add_request("b", prompt_token_ids=prompt(6, seed=5),
                  sampling_params=greedy(9))
    e.step()
    assert e.last_step_kind == "prefill"
    assert e.runner.decode_lane_steps == [0, 0]
    e.step()                    # two live lanes, K=4
    assert e.last_step_kind == "decode"
    assert e.runner.decode_lane_steps == [4 * lanes, 4 * (lanes - 2)]
    assert e.stats().decode_lane_steps == (4 * lanes, 4 * (lanes - 2))
    reg = CollectorRegistry()
    metrics = EngineMetrics("m", registry=reg)
    metrics.update_from_snapshot(e.stats())
    text = generate_latest(reg).decode()
    for name, value in (("decode_lane_steps", 4.0 * lanes),
                        ("decode_idle_lane_steps", 4.0 * (lanes - 2))):
        assert f'tpu:{name}_total{{model_name="m"}} {value}' in text, name


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_sampler_steps_reach_metrics(temperature):
    """tpu:sampler_steps / tpu:sampler_window_steps: one evaluation for a
    prefill round's first-token rows, K for a fused decode round; in the
    window count where a row of the round has a temperature > 0."""
    e = LLMEngine(cfg())
    e.add_request("a", prompt_token_ids=prompt(10),
                  sampling_params=SamplingParams(
                      max_tokens=9, temperature=temperature, seed=3,
                      ignore_eos=True))
    e.step()
    assert e.last_step_kind == "prefill"
    assert e.runner.sampler_steps == [1, 1 if temperature else 0]
    e.step()                    # K=4
    assert e.last_step_kind == "decode"
    window = 5 if temperature else 0
    assert e.stats().sampler_steps == (5, window)
    reg = CollectorRegistry()
    metrics = EngineMetrics("m", registry=reg)
    metrics.update_from_snapshot(e.stats())
    text = generate_latest(reg).decode()
    for name, value in (("sampler_steps", 5.0),
                        ("sampler_window_steps", float(window))):
        assert f'tpu:{name}_total{{model_name="m"}} {value}' in text, name


def test_sliding_window_bounds_the_attention_context_count():
    r = LLMEngine(cfg()).runner
    r.model_config = type("MC", (), {"sliding_window": 8})()
    r._note_attn_context([6, 20], 4, [30])
    assert r.attn_context_tokens == [6 + 7 + 8 + 8 + 4 * 8 + 8, 1]


def test_program_listeners_install_once():
    before = len(jax._src.monitoring._event_duration_secs_listeners)
    phases.install_program_listeners()
    LLMEngine(cfg())
    assert len(jax._src.monitoring._event_duration_secs_listeners) == before


def _benchmark_samples():
    """Every engine sample name that a new layer-metric file reads."""
    import json

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "chip", "layer_metrics")
    names = set()
    for path in glob.glob(os.path.join(here, "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("scrape") != "engine":
            continue
        for key in ("numerator", "denominator", "samples"):
            names.update(n for n in spec.get(key, ())
                         if n.startswith("tpu:"))
    return names


def test_server_exposes_what_the_benchmark_reads_and_counts_lock_waits():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def scrape(client):
        text = await (await client.get("/metrics")).text()
        out = {}
        for line in text.splitlines():
            if line and line[0] != "#":
                head, _, value = line.rpartition(" ")
                out[head.partition("{")[0]] = float(value)
        return out

    async def run():
        srv = EngineServer(cfg(num_kv_blocks=64, max_num_seqs=2))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            before = await scrape(client)
            assert not [n for n in before if re.match(
                r"tpu:prefill_(prep|h2d|dispatch|fetch)_seconds", n)]

            async def stream():
                r = await client.post("/v1/completions", json={
                    "prompt": "hello there", "max_tokens": 6,
                    "temperature": 0, "ignore_eos": True,
                    # a chunk per token whatever the byte tokenizer
                    # renders of a random model's ids
                    "logprobs": 1, "stream": True})
                assert r.status == 200
                return await r.text()

            # hold the engine lock while a request arrives: its wait is
            # the admission's, on the event loop
            lock = srv.engine._lock
            await asyncio.get_running_loop().run_in_executor(
                None, lock.acquire)
            # released from another thread: the loop itself will be
            # standing in the acquire
            threading.Timer(0.25, lock.release).start()
            body = await stream()
            assert "[DONE]" in body
            after = await scrape(client)
            wanted = _benchmark_samples()
            assert len(wanted) >= 14
            assert wanted <= set(after), sorted(wanted - set(after))
            d = {k: after[k] - before.get(k, 0.0) for k in after}
            assert d["tpu:admit_lock_wait_seconds_count"] == 1
            assert d["tpu:admit_lock_wait_seconds_sum"] >= 0.2
            assert (d["tpu:event_loop_lock_wait_seconds_sum"]
                    >= d["tpu:admit_lock_wait_seconds_sum"])
            assert d["tpu:event_loop_lock_wait_seconds_count"] >= 2
            assert d["tpu:server_ttft_seconds_count"] == 1
            assert d["tpu:server_ttft_seconds_sum"] >= 0.2
            # one dispatch observation per step program dispatched, one
            # attention-context observation per round
            rounds = d["tpu:engine_phase_dispatch_seconds_count"]
            assert rounds >= 2
            assert d["tpu:attn_context_tokens_count"] == rounds
            assert d["tpu:attn_context_tokens_sum"] > 0
            for p in STEP_PHASES:
                assert d[f"tpu:engine_phase_{p}_seconds_count"] > 0, p
            assert after["tpu:program_trace_seconds_sum"] > 0
            assert after["tpu:program_compile_seconds_count"] > 0
        finally:
            await client.close()

    asyncio.run(run())

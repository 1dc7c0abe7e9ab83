"""The engine's round seen from inside (tracing/phases.py): phase spans
in the profiler's own trace, the counters of /metrics, and the names the
programs and kernels carry. All on the CPU at the tiny debug widths."""

import ast
import asyncio
import glob
import inspect
import os
import json
import re
import threading
import time

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
from production_stack_tpu.tracing import ENGINE_PHASES, HOST_PHASES, phases

STEP_PHASES = ("schedule", "pack", "h2d", "dispatch", "fetch", "apply")
# the hand-over's annotations: the step thread's, the event loop's
HANDOVER_SPANS = ("engine.lock_wait", "server.admit", "server.deliver",
                  "server.send")


def cfg(**overrides) -> EngineConfig:
    kwargs = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=4, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, num_scheduler_steps=4,
        seed=0,
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
        for i, line in enumerate(plane.lines):      # a line is a thread
            for ev in line.events:
                if ev.name.startswith(("engine.", "server.")):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats), (plane.name, i)))
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
    for name, s, t, _, _ in events:
        if name in ("engine.step", "engine.idle", "engine.deliver"):
            continue
        assert any(s0 <= s and t <= t1 for _, s0, t1, _, _ in steps), name
    dispatches = [ev for ev in events if ev[0] == "engine.dispatch"]
    for name, s, t, stats, _ in events:
        if name == "engine.build":
            assert stats["kind"] in model_runner.PROGRAM_KINDS
            assert any(s0 <= s and t <= t1
                       for _, s0, t1, _, _ in dispatches)
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

    # nor at the sites of the hand-over: the step loop's wait for the
    # lock, the admission under it, the loop's delivery, a chunk's send
    async def body(srv, client):
        chunks = await stream_chunks(client, "/v1/completions")
        assert len(chunks) >= 2
        step, loop = srv.engine.engine.phases, srv.engine.loop_phases
        assert step.counts()["lock_wait"] > 0
        assert loop.counts()["deliver"] > 0
        assert loop.counts()["send"] == len(chunks)
        assert loop.counts()["admit_lock_wait"] == 1

    with_server(body)
    assert made == []


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
    # the step carries TWO dispatched rounds: the one it fetched, and
    # its staged successor (contexts 15.. and 11..), which started at
    # that fetch's return and is in flight when the step returns
    assert e._inflight is not None and e._early_dispatch_total == 1
    hand = sum(11 + i for i in range(8)) + sum(7 + i for i in range(8))
    assert e.runner.attn_context_tokens[0] - tokens0 == hand
    assert e.runner.attn_context_tokens[1] - rounds0 == 2
    assert e.phases.counts()["dispatch"] - dispatch0 == 2
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
    e.step()                    # two live lanes, K=4, and the round
    # after it, dispatched at this one's fetch: two rounds' worth
    assert e.last_step_kind == "decode"
    assert e.stats().decode_early_dispatch_total == 1
    assert e.runner.decode_lane_steps == [8 * lanes, 8 * (lanes - 2)]
    assert e.stats().decode_lane_steps == (8 * lanes, 8 * (lanes - 2))
    reg = CollectorRegistry()
    metrics = EngineMetrics("m", registry=reg)
    metrics.update_from_snapshot(e.stats())
    text = generate_latest(reg).decode()
    for name, value in (("decode_lane_steps", 8.0 * lanes),
                        ("decode_idle_lane_steps", 8.0 * (lanes - 2))):
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
    e.step()                    # K=4, and the round started at its fetch
    assert e.last_step_kind == "decode"
    window = 9 if temperature else 0
    assert e.stats().sampler_steps == (9, window)
    reg = CollectorRegistry()
    metrics = EngineMetrics("m", registry=reg)
    metrics.update_from_snapshot(e.stats())
    text = generate_latest(reg).decode()
    for name, value in (("sampler_steps", 9.0),
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


async def scrape(client):
    text = await (await client.get("/metrics")).text()
    out = {}
    for line in text.splitlines():
        if line and line[0] != "#":
            head, _, value = line.rpartition(" ")
            out[head.partition("{")[0]] = float(value)
    return out


def with_server(body, timeout=240.0):
    """`await body(srv, client)` against an EngineServer at the tiny
    widths, within `timeout` seconds, compiles included."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def run():
        srv = EngineServer(cfg(num_kv_blocks=64, max_num_seqs=2))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            await asyncio.wait_for(body(srv, client), timeout)
        finally:
            await client.close()

    asyncio.run(run())


async def stream_chunks(client, path, **extra):
    """One streamed request of 6 tokens; its CONTENT chunks (those
    `EngineServer._stream_chunk` made) as the client got them."""
    chat = path.endswith("/chat/completions")
    body = {"max_tokens": 6, "temperature": 0, "ignore_eos": True,
            "stream": True, **extra}
    if chat:
        # a chunk per engine output whatever the byte tokenizer renders
        # of a random model's ids
        body.update(messages=[{"role": "user", "content": "hello there"}],
                    logprobs=True, top_logprobs=1)
    else:
        body.update(prompt="hello there", logprobs=1)
    r = await client.post(path, json=body)
    assert r.status == 200
    text = await r.text()
    assert "[DONE]" in text
    chunks = [json.loads(line[6:]) for line in text.splitlines()
              if line.startswith("data: {")]
    if chat:
        return [c for c in chunks
                if "content" in c["choices"][0]["delta"]]
    return [c for c in chunks if c["choices"][0]["finish_reason"] is None]


def test_server_exposes_what_the_benchmark_reads_and_counts_lock_waits():
    async def run(srv, client):
        before = await scrape(client)
        assert not [n for n in before if re.match(
            r"tpu:prefill_(prep|h2d|dispatch|fetch)_seconds", n)]

        # hold the engine lock while a request arrives: its wait is
        # the admission's, on the event loop
        lock = srv.engine._lock
        await asyncio.get_running_loop().run_in_executor(
            None, lock.acquire)
        # released from another thread: the loop itself will be
        # standing in the acquire
        threading.Timer(0.25, lock.release).start()
        assert await stream_chunks(client, "/v1/completions")
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

    with_server(run)


# -- (d) the hand-over between the step thread and the event loop ------------
def test_offcpu_tells_a_phase_that_waits_from_one_that_works():
    """Wall less `time.thread_time()`: a phase that sleeps 20 ms stood
    20 ms without running, one that spins 20 ms ran them; a name
    outside `offcpu=` pays for no second clock and keeps none."""
    def spin_offcpu():
        timer = phases.PhaseTimer(("spins",), "t.", offcpu=("spins",))
        with timer.span("spins"):
            until = time.perf_counter() + 0.02
            while time.perf_counter() < until:
                pass
        (wall, n), (off, m) = (timer.pairs()["spins"],
                               timer.offcpu_pairs()["spins"])
        # two clocks: a span that never left the CPU reads near 0,
        # on either side
        assert n == m == 1 and wall >= 0.02 and -0.002 <= off <= wall
        return off

    # a spin the machine's other work pre-empts reads high: best of 5
    assert min(spin_offcpu() for _ in range(5)) < 0.005
    timer = phases.PhaseTimer(("sleeps", "plain"), "t.", offcpu=("sleeps",))
    with timer.span("sleeps"):
        time.sleep(0.02)
    with timer.span("plain"):
        time.sleep(0.001)
    assert set(timer.offcpu_pairs()) == {"sleeps"}
    wall, n = timer.pairs()["sleeps"]
    off, m = timer.offcpu_pairs()["sleeps"]
    assert n == m == 1 and 0.018 <= off <= wall < 0.2
    assert timer.totals["plain"][2] == 0.0
    assert timer.ended("plain") >= timer.ended("sleeps") > 0.0
    timer.observe("plain", 0.5)
    assert timer.pairs()["plain"] == (pytest.approx(0.5, abs=0.1), 2)
    # the engine's timer measures it for the host-work phases alone
    e = LLMEngine(cfg())
    e.add_request("a", prompt_token_ids=prompt(10),
                  sampling_params=greedy(5))
    drive(e)
    snap = e.stats()
    assert set(snap.engine_phases_offcpu) == set(HOST_PHASES)
    assert not set(HOST_PHASES) & {"fetch", "idle", "lock_wait"}
    for p, (off, n) in snap.engine_phases_offcpu.items():
        wall, count = snap.engine_phases[p]
        assert n == count and -0.1 * wall - 1e-4 <= off <= wall, p
    reg = CollectorRegistry()
    metrics = EngineMetrics("m", registry=reg)
    metrics.update_from_snapshot(snap)
    text = generate_latest(reg).decode()
    for p in ENGINE_PHASES:
        exported = f"tpu:engine_phase_{p}_offcpu_seconds_sum" in text
        assert exported == (p in HOST_PHASES), p


def test_lock_wait_counts_what_another_thread_held_the_lock():
    """`engine.lock_wait`: the step thread's acquire of `_lock`. ~0 while
    nobody else wants the lock; at least the 50 ms of a 100 ms hold by
    another thread (the idle step loop is back at the lock within 20)."""
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine

    async def run():
        eng = AsyncLLMEngine(cfg())
        eng.start(asyncio.get_running_loop())
        try:
            timer = eng.engine.phases
            await asyncio.sleep(0.2)
            s0, n0 = timer.pairs()["lock_wait"]
            assert n0 >= 3 and s0 < 0.02

            def hold():
                with eng._lock:
                    time.sleep(0.1)

            await asyncio.wait_for(
                asyncio.get_running_loop().run_in_executor(None, hold), 10)
            await asyncio.sleep(0.1)
            s1, n1 = timer.pairs()["lock_wait"]
            assert n1 > n0 and 0.05 <= s1 - s0 <= 0.5
            assert timer.pairs()["deliver"] == (0.0, 0)
        finally:
            eng.shutdown()
        assert not eng._thread.is_alive()

    asyncio.run(asyncio.wait_for(run(), 120))


def test_a_blocked_loop_shows_as_the_delivery_callbacks_wait():
    """`tpu:deliver_pickup_seconds`: the loop stands still for 500 ms
    from the instant a request is admitted; its one round is fetched
    and handed over meanwhile, and the callback waits out the rest."""
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine

    async def one_token(eng, rid, seed):
        outs = [out async for out in eng.generate(
            rid, prompt_token_ids=prompt(9, seed=seed),
            sampling_params=greedy(1))]
        assert outs[-1].finished and len(outs[-1].token_ids) == 1
        return outs

    async def run():
        eng = AsyncLLMEngine(cfg())
        eng.start(asyncio.get_running_loop())
        try:
            loop_phases = eng.loop_phases
            # the same shapes once, so that nothing compiles below
            await asyncio.wait_for(one_token(eng, "warm", 5), 120)
            s0, n0 = loop_phases.pairs()["deliver_pickup"]
            assert n0 >= 1
            task = asyncio.ensure_future(one_token(eng, "late", 7))
            await asyncio.sleep(0)      # runs `generate` to its queue
            assert eng.has_request("late")
            t0 = time.perf_counter()
            time.sleep(0.5)             # the loop, blocked
            blocked = time.perf_counter() - t0
            (out,) = await asyncio.wait_for(task, 30)
            s1, n1 = loop_phases.pairs()["deliver_pickup"]
            assert n1 - n0 == 1
            assert 0.05 <= s1 - s0 <= blocked
            # the round's fetch closed inside the block too: the stamp
            # its output carries is the step thread's reading
            assert t0 < out.t_fetched < t0 + blocked
            assert eng.engine.phases.ended("fetch") == out.t_fetched
            assert loop_phases.counts()["deliver"] == n1
        finally:
            eng.shutdown()

    asyncio.run(asyncio.wait_for(run(), 240))


@pytest.mark.parametrize("writer", ["completion", "chat", "multi-choice"])
def test_every_content_chunk_is_a_token_delivery_and_a_send(writer):
    """`tpu:token_delivery_seconds` / `tpu:server_send_seconds`: one
    observation a content chunk written, on each stream writer, each
    between 0 and the time the request took (a chunk whose round ends
    while the loop stands behind a compiling step waits seconds)."""
    path, extra = {
        "completion": ("/v1/completions", {}),
        "chat": ("/v1/chat/completions", {}),
        "multi-choice": ("/v1/completions", {"n": 2}),
    }[writer]

    async def body(srv, client):
        before = await scrape(client)
        t0 = time.perf_counter()
        chunks = await stream_chunks(client, path, **extra)
        took = time.perf_counter() - t0
        for _ in range(40):
            after = await scrape(client)
            d = {k: after[k] - before.get(k, 0.0) for k in after}
            # the step thread closes its `deliver` span AFTER it has
            # queued the callback: this loop may have run the callback
            # and the scrape before that thread ran again
            if (d["tpu:deliver_pickup_seconds_count"]
                    <= d["tpu:engine_phase_deliver_seconds_count"]):
                break
            await asyncio.sleep(0.025)
        if writer == "multi-choice":
            assert {c["choices"][0]["index"] for c in chunks} == {0, 1}
        assert len(chunks) >= 2
        for pair in ("tpu:token_delivery_seconds",
                     "tpu:server_send_seconds"):
            assert d[pair + "_count"] == len(chunks), pair
            assert 0.0 < d[pair + "_sum"] < took * len(chunks), pair
        # a chunk is written after it was taken off its queue
        assert (d["tpu:token_delivery_seconds_sum"]
                > d["tpu:server_send_seconds_sum"])
        # one pick-up and one delivery a round handed over
        rounds = d["tpu:deliver_pickup_seconds_count"]
        assert 1 <= rounds <= d["tpu:engine_phase_deliver_seconds_count"]
        assert d["tpu:server_deliver_seconds_count"] == rounds
        assert 0.0 < d["tpu:deliver_pickup_seconds_sum"] < took * rounds
        assert d["tpu:engine_phase_lock_wait_seconds_count"] >= rounds
        assert d["tpu:server_ttft_seconds_count"] == 1

    with_server(body)


def test_metrics_serves_the_loops_pairs_though_the_scrape_waits_for_the_lock():
    """The loop-side pairs are the loop's own: `/metrics` reads them
    after `stats()` has let the engine lock go, so a scrape that stood
    250 ms behind another thread's hold serves them whole."""
    loop_side = ("tpu:deliver_pickup_seconds", "tpu:token_delivery_seconds",
                 "tpu:server_send_seconds", "tpu:server_deliver_seconds")

    async def body(srv, client):
        chunks = await stream_chunks(client, "/v1/chat/completions")
        quiet = await scrape(client)
        lock = srv.engine._lock
        await asyncio.get_running_loop().run_in_executor(None, lock.acquire)
        threading.Timer(0.25, lock.release).start()
        held = await asyncio.wait_for(scrape(client), 30)
        assert (held["tpu:event_loop_lock_wait_seconds_sum"]
                - quiet["tpu:event_loop_lock_wait_seconds_sum"]) >= 0.2
        for pair in loop_side:
            assert held[pair + "_count"] == quiet[pair + "_count"] > 0
            assert held[pair + "_sum"] == quiet[pair + "_sum"] > 0.0
        assert held["tpu:token_delivery_seconds_count"] == len(chunks)
        # and the snapshot's copy is made without the lock
        assert not lock.locked()
        assert set(srv.engine.stats().loop_phases) == set(phases.LOOP_PHASES)

    with_server(body)


def test_the_hand_over_is_in_the_profilers_trace_on_its_threads(tmp_path):
    """Inside a profiler session: `engine.lock_wait` on the step
    thread, a leaf OUTSIDE every `engine.step`; `server.admit`,
    `server.deliver` and `server.send` on the event loop's thread."""
    async def body(srv, client):
        await stream_chunks(client, "/v1/completions")      # compiles
        jax.profiler.start_trace(str(tmp_path))
        try:
            chunks = await stream_chunks(client, "/v1/completions")
        finally:
            jax.profiler.stop_trace()
        assert len(chunks) >= 2

    with_server(body)
    events = host_events(str(tmp_path))
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    assert set(HANDOVER_SPANS) <= set(by_name)
    (step_thread,) = {ev[4] for ev in by_name["engine.step"]}
    (loop_thread,) = {ev[4] for ev in by_name["server.admit_lock_wait"]}
    assert step_thread != loop_thread
    steps = by_name["engine.step"]
    for _, s, t, _, thread in by_name["engine.lock_wait"]:
        assert thread == step_thread
        assert not any(s < t1 and s0 < t for _, s0, t1, _, _ in steps)
    for name in ("server.admit", "server.deliver", "server.send"):
        assert {ev[4] for ev in by_name[name]} == {loop_thread}, name
    # the admission's annotation starts where its wait for the lock ends
    (_, _, wait_end, _, _), = by_name["server.admit_lock_wait"]
    (_, admit_start, _, _, _), = by_name["server.admit"]
    assert 0 <= admit_start - wait_end < 50_000_000
    assert len(by_name["server.send"]) >= 2

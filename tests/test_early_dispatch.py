"""A staged round starts when the fetch returns (`LLMEngine.
_starts_at_fetch`): where round N's fetched arrays show that the
stage's prediction holds, round N+1 is dispatched BEFORE round N's
tokens are applied and is in flight when `step()` returns. It changes
WHEN a program starts, never which program, buffer or chained tokens:
every stream equals `prefetch_decode=False`'s, an arrival or an abort
that stands at the caller's lock still gets in before the next round
is chosen, and nothing a round in flight can write is freed under it.

ONE engine with the stage (inside an `AsyncLLMEngine` that most cases
never start) and one without serve every case; `max_model_len` sits
with tests/test_staged_decode.py's engine of that limit, the mesh and
the greedy / sampled parity with counted hits with
tests/test_multistep.py."""

import asyncio
import contextlib
import threading
import time

import numpy as np
import pytest
from prometheus_client import CollectorRegistry

from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.metrics import EngineMetrics
from production_stack_tpu.engine.sampling_params import SamplingParams

K = 4


def config(prefetch: bool, **overrides) -> EngineConfig:
    kwargs = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, num_scheduler_steps=K,
        prefetch_decode=prefetch, seed=0,
    )
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


@pytest.fixture(scope="module")
def served() -> AsyncLLMEngine:
    return AsyncLLMEngine(config(True))


@pytest.fixture(scope="module")
def on(served) -> LLMEngine:
    return served.engine


@pytest.fixture(scope="module")
def off() -> LLMEngine:
    return LLMEngine(config(False))


def prompts(seed: int, lengths=(5, 19, 11)) -> list[list[int]]:
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 384, size=n).tolist() for n in lengths]


def serve(eng: LLMEngine, ps, sps, tag: str) -> list[tuple]:
    """Every request's (tokens, finish reason, log-probabilities), and
    on the engine the longest run of steps that each left a round in
    flight (`eng.run_in_flight`)."""
    if not isinstance(sps, list):
        sps = [sps] * len(ps)
    for i, (p, sp) in enumerate(zip(ps, sps)):
        eng.add_request(f"{tag}-{i}", prompt_token_ids=p, sampling_params=sp)
    finals, run, eng.run_in_flight = {}, 0, 0
    while eng.has_unfinished():
        for out in eng.step():
            if out.finished:
                finals[out.request_id] = out
        run = run + 1 if eng._inflight is not None else 0
        eng.run_in_flight = max(eng.run_in_flight, run)
    outs = [finals[f"{tag}-{i}"] for i in range(len(ps))]
    return [(o.token_ids, o.finish_reason, o.logprobs) for o in outs]


def step_until_in_flight(eng: LLMEngine) -> list:
    outs = []
    for _ in range(20):
        outs.extend(eng.step())
        if eng._inflight is not None:
            return outs
    raise AssertionError("no round started at a fetch in 20 steps")


def finish(eng: LLMEngine, outs=()) -> dict:
    outs = list(outs)
    while eng.has_unfinished():
        outs.extend(eng.step())
    return {o.request_id: o for o in outs if o.finished}


SAMPLING = {
    "greedy": [dict(temperature=0.0)] * 3,
    "seeded": [dict(temperature=0.8, seed=7 + i) for i in range(3)],
    "mixed": [dict(temperature=0.0, logprobs=2),
              dict(temperature=0.9, top_p=0.8, seed=3),
              dict(temperature=0.7, top_k=20, seed=4, logprobs=1)],
}


@pytest.mark.parametrize("sampling", list(SAMPLING))
def test_streams_equal_those_without_a_stage(on, off, sampling):
    """Tokens, finish reasons and log-probabilities equal
    `prefetch_decode=False`'s, with at least three rounds in a row
    started at the fetch before them."""
    sps = [SamplingParams(max_tokens=41, ignore_eos=True, **kw)
           for kw in SAMPLING[sampling]]
    ps = prompts(21)
    early = on._early_dispatch_total
    assert serve(on, ps, sps, sampling) == serve(off, ps, sps, sampling)
    assert on.run_in_flight >= 3
    assert on._early_dispatch_total - early >= on.run_in_flight
    assert off._early_dispatch_total == 0 and off.run_in_flight == 0


def first_token_round(eng: LLMEngine, at_the_lock: bool) -> int:
    """Rounds from an arrival to its first token, the arrival standing
    at the caller's lock when a round's fetch returns (what
    `AsyncLLMEngine._at_the_lock` shows the engine) or coming after it."""
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    a, b = prompts(33, (9, 13))
    eng.add_request("first", prompt_token_ids=a, sampling_params=sp)
    for _ in range(4):
        eng.step()
    stage = eng.runner.stage_decode_multi

    def arrives_under_the_round(*args, **kw):
        # the round is on the device and its successor being staged:
        # the arrival takes its place at the lock
        eng.callers_waiting += at_the_lock
        return stage(*args, **kw)

    eng.runner.stage_decode_multi = arrives_under_the_round
    try:
        eng.step()
    finally:
        eng.runner.stage_decode_multi = stage
    # the step's end: the lock is free and the arrival gets in
    eng.add_request("late", prompt_token_ids=b, sampling_params=sp)
    eng.callers_waiting = 0
    arrived = eng._round - (eng._inflight is not None)  # the last fetched
    while True:
        if any(o.request_id == "late" and o.token_ids for o in eng.step()):
            break
    rounds = eng._round - arrived - (eng._inflight is not None)
    finals = finish(eng)
    assert len(finals["first"].token_ids) == 40
    return rounds, finals["late"].token_ids


def test_an_arrival_at_the_lock_rides_the_very_next_round(on, off):
    """An arrival that stands at the lock when the fetch returns stops
    the early start: its first token comes from the round it would
    have come from without a stage. One that comes after the early
    start waits that round out: the one more round, and no more."""
    parent, tokens = first_token_round(off, True)
    early = on._early_dispatch_total
    assert first_token_round(on, True) == (parent, tokens)
    assert on._early_dispatch_total > early  # before and after it
    assert first_token_round(on, False) == (parent + 1, tokens)


def pool(eng: LLMEngine) -> tuple:
    bm = eng.block_manager
    return bm.free_epoch, sorted(bm.free_blocks), len(bm.evictable)


@pytest.mark.parametrize("gone", [["b"], ["a", "b"]], ids=["one", "all"])
def test_abort_with_a_round_in_flight_keeps_its_blocks(on, off, gone):
    """A sequence aborted while a round that holds it is on the device
    keeps its blocks until that round's fetch: nothing is freed, no
    output is made for it, its tokens of the round are dropped and
    counted, and whoever stays goes on to the stream it has alone."""
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    ps = dict(zip("ab", prompts(45, (7, 12))))
    for rid, p in ps.items():
        on.add_request(rid, prompt_token_ids=p, sampling_params=sp)
    outs = step_until_in_flight(on)
    before, dropped = pool(on), on._decode_overshoot_tokens_total
    finished = on.stats().requests_finished_total
    for rid in gone:
        assert on.abort_request(rid) and not on.has_request(rid)
    assert pool(on) == before and on.has_unfinished()
    outs.extend(on.step())  # the fetch of the round in flight
    assert on._inflight is None  # and nothing started behind it
    assert pool(on)[0] == before[0] + len(gone)
    assert (on._decode_overshoot_tokens_total - dropped
            == K * len(gone))
    finals = finish(on, outs)
    assert sorted(finals) == sorted(set(ps) - set(gone))
    assert (on.stats().requests_finished_total - finished == len(finals))
    for rid, out in finals.items():
        (alone,) = serve(off, [ps[rid]], sp, "alone")
        assert (out.token_ids, out.finish_reason) == alone[:2]


def test_a_stop_id_inside_a_round_starts_nothing_early(on, off):
    """The round in which a lane's stop id fires is fetched with its
    successor staged: the fetched arrays refuse the early start, the
    stage is a counted miss, the stream ends at the unstaged engine's
    token and the other lanes go on (and start early again)."""
    ps = prompts(57)
    free = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True)
    probe = serve(off, ps, free, "probe")
    # a token whose first occurrence in one stream is well into it,
    # and that the other streams never make
    s, i = next((s, i) for i in range(24, 13, -1)
                for s, (ids, *_) in enumerate(probe)
                if ids.index(ids[i]) == i and not any(
                    ids[i] in other for other, *_ in probe
                    if other is not ids))
    sp = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True,
                        stop_token_ids=[probe[s][0][i]])
    misses, early = on._staged_misses_total, on._early_dispatch_total
    got = serve(on, ps, sp, "stop")
    assert [g[0] for j, g in enumerate(got) if j != s] == [
        p[0] for j, p in enumerate(probe) if j != s]
    assert got[s][0] == probe[s][0][:i + 1] and got[s][1] == "stop"
    assert on._staged_misses_total - misses >= 1
    assert on._early_dispatch_total - early >= 3


def test_a_stop_string_takes_the_round_the_parent_took(on, off):
    """A rule only the host can evaluate, after rendering: no round of
    a batch that carries one starts early; its stages are taken at the
    next step, as they were."""
    ps = prompts(63)
    sp = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True,
                        stop=["\x00never\x00"])
    hits, early = on._staged_hits_total, on._early_dispatch_total
    assert serve(on, ps, sp, "str") == serve(off, ps, sp, "str")
    assert on._early_dispatch_total == early and on.run_in_flight == 0
    assert on._staged_hits_total - hits >= 3


def test_a_lane_ends_inside_the_early_round_by_its_budget(on, off):
    """With device stops the stage ships each lane's budget less K, so
    a lane whose last tokens fall INSIDE the staged round rides it: the
    round that ends the 4k+2 lane started early, froze that lane on
    the device, and the other lanes went on."""
    ps = prompts(71)
    sps = [SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
           for n in (4 * K + 2, 9 * K, 9 * K)]
    ended, early_rounds = [], set()
    finishing, dispatch = on._finish_decode_round, on._dispatch_decode
    seen = [on._early_dispatch_total]

    def finished(rnd, **kw):
        out = finishing(rnd, **kw)
        if any(s.finished for s in rnd["seqs"]):
            ended.append(rnd["round"])
        return out

    def started(*a, **kw):
        rnd = dispatch(*a, **kw)
        if on._early_dispatch_total > seen[0]:
            seen[0] = on._early_dispatch_total
            early_rounds.add(rnd["round"])
        return rnd

    on._finish_decode_round, on._dispatch_decode = finished, started
    try:
        got = serve(on, ps, sps, "budget")
    finally:
        del on._finish_decode_round, on._dispatch_decode
    assert got == serve(off, ps, sps, "budget")
    assert [len(t) for t, *_ in got] == [4 * K + 2, 9 * K, 9 * K]
    # the round that ended the short lane had started at a fetch; the
    # one behind it (another lane set) was chosen by a schedule, and
    # the rounds after that start early again
    assert ended[0] in early_rounds and ended[0] + 1 not in early_rounds
    assert max(early_rounds) > ended[0] + 1


def test_shutdown_drains_the_round_in_flight(on):
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    on.add_request("held", prompt_token_ids=prompts(81)[0],
                   sampling_params=sp)
    step_until_in_flight(on)
    before = len(on._seqs["held"].generated_token_ids)
    on.shutdown()
    assert on._inflight is None
    assert len(on._seqs["held"].generated_token_ids) == before + K
    assert len(finish(on)["held"].token_ids) == 40


def test_the_counter_beside_the_rounds(on):
    """`tpu:decode_early_dispatch` counts what `tpu:decode_rounds`
    counts, for the rounds that started at a fetch."""
    s0 = on.stats()
    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    serve(on, prompts(91), sp, "count")
    s1 = on.stats()
    early = s1.decode_early_dispatch_total - s0.decode_early_dispatch_total
    rounds = s1.decode_rounds_total - s0.decode_rounds_total
    assert 3 <= early < rounds
    assert s1.decode_early_dispatch_total == on._early_dispatch_total
    reg = CollectorRegistry()
    metrics = EngineMetrics("m", registry=reg)
    metrics.update_from_snapshot(s0)
    metrics.update_from_snapshot(s1)
    assert reg.get_sample_value(
        "tpu:decode_early_dispatch_total", {"model_name": "m"}) == (
            s1.decode_early_dispatch_total)
    assert reg.get_sample_value(
        "tpu:decode_rounds_total", {"model_name": "m"}) == (
            s1.decode_rounds_total)


def test_what_refuses_a_stage_refuses_the_early_start(on, off):
    """No second list of conditions: with penalties nothing is staged,
    so nothing starts early (a mesh: tests/test_multistep.py::
    test_tp_multistep_parity)."""
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True,
                        repetition_penalty=1.3)
    ps = prompts(97)[:2]
    early = on._early_dispatch_total
    assert serve(on, ps, sp, "pen") == serve(off, ps, sp, "pen")
    assert on._early_dispatch_total == early and on.run_in_flight == 0


def test_the_loop_counts_who_stands_at_the_lock(served):
    """`generate` and `abort` show themselves to the engine while they
    wait for the lock the step holds (`LLMEngine.callers_waiting`)."""
    eng = served.engine
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()

    def until(cond, what):
        deadline = time.monotonic() + 20
        while not cond():
            assert time.monotonic() < deadline, what
            time.sleep(0.002)

    try:
        served._lock.acquire()  # a step holds it
        try:
            fut = asyncio.run_coroutine_threadsafe(
                served.abort("nobody"), loop)
            until(lambda: eng.callers_waiting == 1, "no caller counted")
        finally:
            served._lock.release()
        assert fut.result(timeout=20) is False
        assert eng.callers_waiting == 0
    finally:
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=20)
        loop.close()
    assert not thread.is_alive()


def test_sleep_drains_the_round_in_flight_and_wakes_to_the_same_stream(
        served, off):
    """A round that started at a fetch is fetched and applied before
    the pause, nothing is dispatched behind it, and after `wake_up` the
    stream goes on to what it is without a stage. (Last: it starts the
    shared engine's step thread, and shuts the engine down.)"""
    eng = served.engine
    sp = SamplingParams(max_tokens=4000, temperature=0.0, ignore_eos=True)
    (p,) = prompts(103, (9,))

    async def run():
        served.start(asyncio.get_running_loop())
        outs = []

        async def stream():
            async for out in served.generate(
                    "sleeper", prompt_token_ids=p, sampling_params=sp):
                outs.append(out)

        task = asyncio.ensure_future(stream())
        early = eng._early_dispatch_total
        while eng._early_dispatch_total < early + 3:
            await asyncio.sleep(0.001)
        served.sleep()
        await asyncio.sleep(0.3)
        assert eng._inflight is None
        rounds = eng._round
        await asyncio.sleep(0.2)
        assert eng._round == rounds and not task.done()
        n = len(eng._seqs["sleeper"].generated_token_ids)
        assert n % K == 1  # whole rounds applied, none half way
        served.wake_up()
        seq = eng._seqs["sleeper"]
        while len(seq.generated_token_ids) < n + 5 * K:
            await asyncio.sleep(0.001)
        with served._lock:  # between two steps
            got = list(seq.generated_token_ids)
        task.cancel()  # the abort of a stream: `generate`'s way out
        with contextlib.suppress(asyncio.CancelledError):
            await task
        assert not eng.has_request("sleeper")
        return got

    try:
        got = asyncio.run(asyncio.wait_for(run(), 120))
    finally:
        served.shutdown()
    assert not served._thread.is_alive()
    sp_ref = SamplingParams(
        max_tokens=len(got), temperature=0.0, ignore_eos=True)
    ((want, *_),) = serve(off, [p], sp_ref, "ref")
    assert got == want[:len(got)] and len(got) >= 6 * K

"""Async facade over LLMEngine for the HTTP server.

The engine step loop (device dispatch) runs on a dedicated thread so the
asyncio event loop stays responsive for streaming; per-request outputs are
delivered to asyncio queues via call_soon_threadsafe. This mirrors the
process shape of the reference's engines (uvicorn front + engine core), minus
GPUs: on TPU the device work is already async (XLA dispatch returns before
compute finishes), so one runner thread saturates the chip.
"""

from __future__ import annotations

import asyncio
import contextlib
import threading
import time
from collections.abc import AsyncIterator, Iterator

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.outputs import (
    EngineStatsSnapshot,
    RequestOutput,
)
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.engine.sequence import PromptIds
from production_stack_tpu.tracing import phases
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


class EngineSleepingError(RuntimeError):
    pass


class AsyncLLMEngine:
    def __init__(self, config: EngineConfig, params: dict | None = None):
        self.config = config
        self.engine = LLMEngine(config, params=params)
        self._loop: asyncio.AbstractEventLoop | None = None
        # the step thread's _fail_inflight iterates these under the lock;
        # loop-side writes hold it too, except the GIL-atomic single-op
        # reads/pops on hot paths (suppressed with rationale in place)
        self._streams: dict[str, asyncio.Queue] = {}  # guarded by: self._lock
        self._lock = threading.Lock()
        # The step thread holds `_lock` for the whole of engine.step(),
        # which ends in the fetch of the round in flight; `generate`,
        # `abort` and `stats` take it ON THE EVENT LOOP, so each such
        # acquire can stop every SSE stream of the replica for up to a
        # round. These spans count that wait by site (seconds, count):
        # tpu:event_loop_lock_wait_seconds (all sites) and tpu:admit_
        # lock_wait_seconds (`generate` alone, once per request), and
        # `server.<site>` in a profiler trace. The same timer holds the
        # loop's side of a round's hand-over (`_deliver` here, the
        # stream writers of server.py); the step thread's side is
        # `lock_wait` and `deliver` of the engine's own timer.
        self.loop_phases = phases.PhaseTimer(phases.LOOP_PHASES, "server.")
        self._wake = threading.Event()
        self._stopped = False
        self._thread = threading.Thread(
            target=self._step_loop, name="engine-step-loop", daemon=True
        )
        # sleep/wake lifecycle (reference parity: engine /sleep /wake_up,
        # reference: src/vllm_router/service_discovery.py:414-441)
        self.sleeping = False
        self.sleep_level = 0

    # -- lifecycle ---------------------------------------------------------
    def start(self, loop: asyncio.AbstractEventLoop | None = None) -> None:
        self._loop = loop or asyncio.get_event_loop()
        self._thread.start()

    def shutdown(self) -> None:
        self._stopped = True
        self._wake.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5)
        self.engine.shutdown()

    # -- step loop thread --------------------------------------------------
    def _step_loop(self) -> None:
        logger.info("engine step loop started")
        engine_phases = self.engine.phases
        while not self._stopped:
            if self.sleeping:
                self._wake.wait(timeout=0.1)
                self._wake.clear()
                continue
            try:
                with engine_phases.span("lock_wait") as wait, self._lock:
                    wait.stop()  # held: what follows is the round
                    # `sleep` drains the round in flight under this
                    # lock: a step that waited it out starts nothing
                    busy = (not self.sleeping
                            and self.engine.has_unfinished())
                    outputs = self.engine.step() if busy else []
                t_fetched = engine_phases.ended("fetch")
            except Exception:  # noqa: BLE001 — a step failure must fail
                # the in-flight REQUESTS, not the serving thread: a dead
                # step loop wedges every current and future request
                logger.exception(
                    "engine step failed; aborting in-flight requests"
                )
                outputs = self._fail_inflight()
                busy = True
                t_fetched = 0.0  # no round stands behind these outputs
                # if the engine state is corrupt enough that aborts
                # also fail, has_unfinished() can stay true forever —
                # backoff bounds the retry/log rate instead of pegging
                # the thread in a no-sleep exception loop
                # audited for stackcheck's blocking-async rule: _step_loop
                # runs on the dedicated engine-step thread (self._thread),
                # never the event loop, so a blocking backoff is the
                # intent (the rule only scans async defs; no directive
                # needed — this note is the audit trail)
                time.sleep(0.5)
            if outputs and self._loop is not None:
                with engine_phases.span("deliver"):
                    self._loop.call_soon_threadsafe(
                        self._deliver, outputs, t_fetched,
                        time.perf_counter())
            if not busy:
                with engine_phases.span("idle"):
                    self._wake.wait(timeout=0.02)
                self._wake.clear()

    def _fail_inflight(self) -> list[RequestOutput]:
        """Abort every engine request and emit finished error outputs so
        waiting streams terminate instead of hanging forever."""
        from production_stack_tpu.engine.sequence import RequestMetrics

        outs: list[RequestOutput] = []
        with self._lock:
            try:
                # a round in flight goes first: an abort under it would
                # only be deferred to its fetch
                self.engine.drain_round()
            except Exception:  # noqa: BLE001 — state may be corrupt
                logger.exception("draining the round in flight failed")
            for request_id in list(self._streams):
                try:
                    self.engine.abort_request(request_id)
                except Exception:  # noqa: BLE001 — state may be corrupt
                    logger.exception("abort failed for %s", request_id)
                outs.append(RequestOutput(
                    request_id=request_id,
                    prompt_token_ids=[],
                    token_ids=[],
                    new_token_ids=[],
                    text="",
                    delta_text="",
                    finished=True,
                    finish_reason="error",
                    metrics=RequestMetrics(arrival_time=time.time()),
                ))
        return outs

    def _deliver(self, outputs: list[RequestOutput], t_fetched: float,
                 t_handed: float) -> None:
        """On the event loop: a round's outputs onto their requests'
        queues, each stamped with the `perf_counter()` reading that
        closed the round's fetch (`t_fetched`). `t_handed` is the
        reading the step thread took as it queued this callback: how
        long a READY callback waited is how far the loop is behind."""
        self.loop_phases.observe(
            "deliver_pickup", time.perf_counter() - t_handed)
        with self.loop_phases.span("deliver"):
            for out in outputs:
                out.t_fetched = t_fetched
                # stackcheck: disable=guarded-by-lock — loop-thread
                # dict.get is GIL-atomic and _fail_inflight snapshots
                # via list(); taking the lock here would stall delivery
                # behind the next engine.step (the step thread holds it
                # for the whole step)
                q = self._streams.get(out.request_id)
                if q is not None:
                    q.put_nowait(out)

    @contextlib.contextmanager
    def _at_the_lock(self) -> Iterator[None]:
        """Around the wait for `_lock` of a caller that changes what
        the next round holds (an arrival, an abort): while it stands
        there the engine can see it (`LLMEngine.callers_waiting`; the
        loop thread is the count's one writer), so the step that holds
        the lock dispatches no round past it, and it gets in at that
        step's end, before the next round is chosen."""
        self.engine.callers_waiting += 1
        try:
            yield
        finally:
            self.engine.callers_waiting -= 1

    # -- request API -------------------------------------------------------
    async def generate(
        self,
        request_id: str,
        prompt: str | None = None,
        prompt_token_ids: list[int] | None = None,
        sampling_params: SamplingParams | None = None,
        lora_name: str | None = None,
        priority: int = 0,
        traceparent: str | None = None,
    ) -> AsyncIterator[RequestOutput]:
        if self.sleeping:
            raise EngineSleepingError("engine is sleeping")
        if prompt_token_ids is not None:
            # what grows with the prompt is done out here: the step
            # thread wants this lock (and the GIL) for its next round
            prompt_token_ids = PromptIds.of(prompt_token_ids)
        q: asyncio.Queue[RequestOutput] = asyncio.Queue()
        finished = False
        try:
            with self._at_the_lock(), \
                    self.loop_phases.span("admit_lock_wait") as wait, \
                    self._lock:
                wait.stop()  # the wait is over: the lock is held
                # in a trace, the step thread's `engine.lock_wait`
                # stands over this: an admission holds the lock
                with phases.annotation("server.admit"):
                    self._streams[request_id] = q
                    self.engine.add_request(
                        request_id,
                        prompt=prompt,
                        prompt_token_ids=prompt_token_ids,
                        sampling_params=sampling_params,
                        arrival_time=time.time(),
                        lora_name=lora_name,
                        priority=priority,
                        traceparent=traceparent,
                    )
            self._wake.set()
            while True:
                out = await q.get()
                finished = out.finished
                yield out
                if finished:
                    break
        finally:
            # stackcheck: disable=guarded-by-lock — loop-thread dict.pop is
            # GIL-atomic vs _fail_inflight's list() snapshot; taking the
            # lock on every NORMAL completion would stall the event loop
            # behind the step thread's full engine.step
            self._streams.pop(request_id, None)
            if not finished:
                with self._at_the_lock(), \
                        self.loop_phases.span("abort_lock_wait") as wait, \
                        self._lock:
                    wait.stop()
                    self.engine.abort_request(request_id)

    async def abort(self, request_id: str) -> bool:
        with self._at_the_lock(), \
                self.loop_phases.span("abort_lock_wait") as wait, self._lock:
            wait.stop()
            return self.engine.abort_request(request_id)

    def has_request(self, request_id: str) -> bool:
        return self.engine.has_request(request_id)

    def has_request_prefix(self, request_id: str) -> bool:
        return self.engine.has_request_prefix(request_id)

    # -- introspection -----------------------------------------------------
    def stats(self) -> EngineStatsSnapshot:
        with self.loop_phases.span("stats_lock_wait") as wait, self._lock:
            wait.stop()
            snap = self.engine.stats()
        # the loop's own pairs, read by the loop thread itself: no lock
        snap.loop_phases = self.loop_phases.pairs()
        return snap

    def drain_kv_observations(self) -> tuple[list[float], list[float]]:
        """KV export/restore histogram observations since the last
        drain. Lock-free: the underlying deque pops are GIL-atomic vs
        the step/worker threads' appends."""
        return self.engine.drain_kv_observations()

    def drain_ragged_observations(self) -> list[int]:
        """Ragged lane-mix observations (tpu:ragged_lane_mix) since the
        last drain. Lock-free: same GIL-atomic deque contract."""
        return self.engine.drain_ragged_observations()

    @property
    def tokenizer(self):
        return self.engine.tokenizer

    @property
    def timeline(self):
        """Per-request lifecycle recorder (tracing.TimelineRecorder)."""
        return self.engine.timeline

    @property
    def long_prefill(self):
        """Long-prefill ring manager (None = lane off) — the server's
        /v1/models card advertises sp capability from it."""
        return self.engine.long_prefill

    @property
    def tracer(self):
        """Engine-side span tracer (tracing.RequestTracer)."""
        return self.engine.tracer

    # -- sleep / wake ------------------------------------------------------
    def sleep(self, level: int = 1) -> None:
        """Pause serving. Level 1 keeps weights; level 2 is a deep sleep
        (the KV cache is dropped either way once in-flight work drains)."""
        self.sleeping = True
        self.sleep_level = level
        with self._lock:
            # a round that started at the last fetch's return is
            # fetched and applied before the pause; the step loop
            # dispatches nothing behind it
            outputs = self.engine.drain_round()
        if outputs and self._loop is not None:
            self._loop.call_soon_threadsafe(
                self._deliver, outputs, 0.0, time.perf_counter())
        logger.info("engine going to sleep (level %d)", level)

    def wake_up(self) -> None:
        self.sleeping = False
        self.sleep_level = 0
        self._wake.set()
        logger.info("engine woke up")

    def is_sleeping(self) -> bool:
        return self.sleeping

"""Distributed request tracing shared by router and engine.

- ``context``: W3C `traceparent` encode/parse + `x-request-id` hygiene.
- ``spans``: the span model, pluggable exporters (log / memory /
  OTLP-shape / none), Sentry init.
- ``timeline``: the engine's per-request lifecycle timeline (enqueue →
  admit → prefill chunks → first token → sampled decode rounds →
  preempt/resume → finish) feeding `/debug/requests` and the
  `engine_request` span.

- ``phases``: the engine round's phase spans, on the profiler's clock
  and in `/metrics` (imports jax: engine only, not re-exported here;
  the phase NAMES below are jax-free for `engine/metrics.py`).

See ``production_stack_tpu/tracing/README.md`` for the end-to-end flow
and how to read a timeline when triaging a TTFT regression.
"""

from production_stack_tpu.tracing.context import (
    REQUEST_ID_HEADER,
    TRACEPARENT_HEADER,
    SpanContext,
    format_traceparent,
    parse_traceparent,
    valid_request_id,
)
from production_stack_tpu.tracing.spans import (
    EXPORTERS,
    OTLP_FLUSH_INTERVAL_S,
    RequestTracer,
    Span,
    init_sentry,
    log_otlp_payload,
    noop_tracer,
    otlp_flush_loop,
    otlp_payload,
    span_to_otlp,
)
from production_stack_tpu.tracing.timeline import (
    DECODE_EVENT_EVERY,
    NULL_RECORDER,
    RequestTimeline,
    TimelineRecorder,
    debug_requests_payload,
)

# The step thread's phases, in the order of a round; `idle`, `deliver`
# and `lock_wait` (its acquire of AsyncLLMEngine._lock) belong to
# AsyncLLMEngine._step_loop, outside `engine.step`.
ENGINE_PHASES = (
    "schedule", "pack", "h2d", "dispatch", "fetch", "apply", "idle",
    "deliver", "lock_wait",
)
# Those that are host work: what a span of one did not RUN (wall less
# the thread's CPU time) is the interpreter's or a lock's, not the
# phase's. `fetch`, `idle` and `lock_wait` are waits by design.
HOST_PHASES = ("schedule", "pack", "h2d", "dispatch", "apply", "deliver")
# The event-loop thread's waits for AsyncLLMEngine._lock, by site, and
# its side of a round's hand-over: spans `deliver` (the callback that
# queues a round's outputs) and `send` (an output taken off its queue
# -> its chunk written), and the pairs `deliver_pickup` (callback
# queued -> run) and `token_delivery` (round fetched -> chunk written).
LOCK_WAITS = ("admit_lock_wait", "abort_lock_wait", "stats_lock_wait")
LOOP_PHASES = LOCK_WAITS + (
    "deliver", "send", "deliver_pickup", "token_delivery",
)

__all__ = [
    "DECODE_EVENT_EVERY",
    "ENGINE_PHASES",
    "EXPORTERS",
    "HOST_PHASES",
    "LOCK_WAITS",
    "LOOP_PHASES",
    "NULL_RECORDER",
    "OTLP_FLUSH_INTERVAL_S",
    "REQUEST_ID_HEADER",
    "RequestTimeline",
    "RequestTracer",
    "Span",
    "SpanContext",
    "TRACEPARENT_HEADER",
    "TimelineRecorder",
    "debug_requests_payload",
    "format_traceparent",
    "init_sentry",
    "log_otlp_payload",
    "noop_tracer",
    "otlp_flush_loop",
    "otlp_payload",
    "parse_traceparent",
    "span_to_otlp",
    "valid_request_id",
]

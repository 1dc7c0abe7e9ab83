"""Phase spans of the engine's round, on the profiler's clock.

One helper for every boundary of the round. ``with timer.span("pack"):``

* adds the elapsed ``time.perf_counter()`` seconds and one observation
  to the timer's (seconds, count) pair of that phase — the source of
  the ``tpu:engine_phase_*_seconds`` samples and of the request
  timeline's ``group_phase_s`` attribute;
* while a ``jax.profiler`` session is active, also writes the span as
  ``engine.pack`` into the profiler's OWN trace (``TraceAnnotation``),
  on the same clock as the device planes, so that the reducer of a
  trace can name an idle gap of the device by what the host was doing.

With no session active a span costs one atomic check in the profiler
(``TraceAnnotation.is_enabled``) and two clock reads (two more, of the
thread's CPU clock, for a phase that keeps its off-CPU time, below): no
annotation object is made, no name or attribute is formatted. Callers on the
round's path hand an annotation only integers they already hold, behind
``if phases.profiling():``.

A wall-clock span cannot tell work from waiting: where another thread
holds the interpreter, a phase's seconds grow though the phase did
nothing more. A timer built with ``offcpu=`` names therefore also reads
``time.thread_time()`` at both ends of a span of those names and adds
wall less CPU to a third number of the phase: the seconds the thread
stood inside the phase without running.

The engine's phases are leaves of one ``engine.step`` annotation per
``LLMEngine.step`` call and are kept to a few milliseconds each: a
reducer that names a gap by the shortest host event over its midpoint
then finds the phase, not the step.

This module is imported by the engine only (it imports ``jax``); the
router's half of ``tracing/`` stays free of it.
"""

from __future__ import annotations

import time

from jax import monitoring
from jax.profiler import TraceAnnotation

from production_stack_tpu.tracing import (  # noqa: F401
    ENGINE_PHASES,
    HOST_PHASES,
    LOCK_WAITS,
    LOOP_PHASES,
)

# one atomic load in the profiler's C++; True only inside a session
profiling = TraceAnnotation.is_enabled


class _NoSpan:
    """The span that is none: what `annotation` returns outside a
    profiler session, so call sites need no branch of their own."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _Annotation:
    """A trace annotation that starts where its `with` block does (a
    `TraceAnnotation` starts the clock where it is constructed)."""

    __slots__ = ("_name", "_attrs", "_ann")

    def __init__(self, name: str, attrs: dict):
        self._name, self._attrs = name, attrs

    def __enter__(self):
        self._ann = TraceAnnotation(self._name, **self._attrs)
        return self._ann.__enter__()

    def __exit__(self, *exc):
        return self._ann.__exit__(*exc)


def annotation(name: str, **attrs):
    """A bare trace annotation (no counter) to hold in a `with` block,
    `NO_SPAN` outside a profiler session. The keyword dictionary is
    built either way: on the round's path guard the call with
    `profiling()`."""
    if profiling():
        return _Annotation(name, attrs)
    return NO_SPAN


class _Span:
    __slots__ = ("_cell", "_label", "_offcpu", "_t0", "_c0", "_ann")

    def __init__(self, cell: list, label: str, offcpu: bool):
        self._cell = cell
        self._label = label
        self._offcpu = offcpu
        self._ann = None

    def __enter__(self):
        if profiling():
            self._ann = TraceAnnotation(self._label)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        if self._offcpu:
            # inside the wall-clock reads: the CPU interval lies within
            # the wall interval. They are two clocks all the same: a
            # phase that never left the CPU reads within a few percent
            # of its wall seconds of 0, on either side
            self._c0 = time.thread_time()
        return self

    def stop(self) -> None:
        """End the span before its `with` block does (a wait that is
        over once the lock it waited for is held); `__exit__` then adds
        nothing more."""
        if self._cell is None:
            return
        cell, self._cell = self._cell, None
        if self._offcpu:
            cpu = time.thread_time() - self._c0
        end = cell[3] = time.perf_counter()
        wall = end - self._t0
        cell[0] += wall
        cell[1] += 1
        if self._offcpu:
            cell[2] += wall - cpu
        if self._ann is not None:
            self._ann.__exit__(None, None, None)

    def __exit__(self, *exc):
        self.stop()
        return False


class PhaseTimer:
    """(seconds, count) per phase name, fed by `span` (or `observe`,
    for a duration the caller took itself). Each name is written by one
    thread; readers on other threads take the pair as it stands (the
    pair of one name may be one observation apart). For the names in
    `offcpu` a span also costs two `time.thread_time()` reads and keeps
    the seconds its thread did not run."""

    def __init__(self, names: tuple[str, ...], prefix: str,
                 offcpu: tuple[str, ...] = ()):
        # [seconds, count, seconds off the CPU, perf_counter() reading
        # that closed the last span]
        self.totals: dict[str, list] = {
            n: [0.0, 0, 0.0, 0.0] for n in names}
        self._labels = {n: prefix + n for n in names}
        self._offcpu = frozenset(offcpu)

    def span(self, name: str) -> _Span:
        return _Span(self.totals[name], self._labels[name],
                     name in self._offcpu)

    def observe(self, name: str, seconds: float) -> None:
        cell = self.totals[name]
        cell[0] += seconds
        cell[1] += 1

    def ended(self, name: str) -> float:
        """The `time.perf_counter()` reading that closed the last span
        of `name` (0.0 before the first)."""
        return self.totals[name][3]

    def seconds(self) -> dict[str, float]:
        return {n: c[0] for n, c in self.totals.items()}

    def counts(self) -> dict[str, int]:
        return {n: c[1] for n, c in self.totals.items()}

    def pairs(self) -> dict[str, tuple[float, int]]:
        return {n: (c[0], c[1]) for n, c in self.totals.items()}

    def offcpu_pairs(self) -> dict[str, tuple[float, int]]:
        """(seconds not run, spans) of the phases that measure it."""
        return {n: (self.totals[n][2], self.totals[n][1])
                for n in self.totals if n in self._offcpu}

    def delta(self, since: dict[str, float]) -> dict[str, float]:
        """Seconds spent per phase since `since` (a `seconds()` copy),
        phases that did not run left out."""
        return {
            n: round(c[0] - since.get(n, 0.0), 6)
            for n, c in self.totals.items()
            if c[0] - since.get(n, 0.0) > 0.0
        }


# -- the stages of building a program, from jax's own monitoring events --
# (seconds, count) of every jaxpr trace, lowering to MLIR and backend
# compile (or retrieval from the persistent cache, which jax times under
# the same event) of this PROCESS, and the persistent cache's hits. The
# listeners are process-wide as jax's registry is; tests build many
# engines, so installing is idempotent.
_STAGE_TOTALS: dict[str, list] = {
    "trace": [0.0, 0], "lower": [0.0, 0], "compile": [0.0, 0],
}
PROGRAM_CACHE_HITS = [0]
# matched by suffix, so that a jax that moves the prefix still reports
_STAGE_OF_SUFFIX = (
    ("jaxpr_trace_duration", "trace"),
    ("jaxpr_to_mlir_module_duration", "lower"),
    ("backend_compile_duration", "compile"),
)
_installed = False


def _on_duration(event: str, duration_secs: float, **_) -> None:
    for suffix, stage in _STAGE_OF_SUFFIX:
        if event.endswith(suffix):
            cell = _STAGE_TOTALS[stage]
            cell[0] += duration_secs
            cell[1] += 1
            return


def _on_event(event: str, **_) -> None:
    if event.endswith("compilation_cache/cache_hits"):
        PROGRAM_CACHE_HITS[0] += 1


def install_program_listeners() -> None:
    global _installed
    if _installed:
        return
    _installed = True
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def program_stage_pairs() -> dict[str, tuple[float, int]]:
    return {k: (c[0], c[1]) for k, c in _STAGE_TOTALS.items()}

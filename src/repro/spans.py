"""Host spans recorded inside the program.

``FleetProgram.run`` and the layers it calls open a :func:`span` at each
layer boundary (sharding, scoring, tape build, replay), or are wrapped
whole by :func:`spanned`.  By default nothing is recorded: ``span`` reads
one module-level variable and returns a shared null context.

Inside :func:`recording` every span is kept as a :class:`Span` and also
opened as a ``jax.profiler.TraceAnnotation`` of the same name, so it lands
in a profiler trace on the device events' clock.  While recording, the
JAX compile stages (tracing, lowering to MLIR, backend compile, which
includes persistent-cache loads) are kept as :class:`Compile` events.

    with spans.recording() as rec:
        FleetProgram(...).run(trace)
    rec.spans, rec.counts, rec.compiles

A span opened with no span open on its thread starts a new *run*: every
span under it shares that run's number, so one ``FleetProgram.run`` call
is one run.  :func:`count` keeps a named number (a :class:`Count`) under
the innermost open span in the same way; outside :func:`recording` it
does nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Callable, ContextManager, Iterator, TypeVar

import jax

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
CACHE_HITS = "/jax/compilation_cache/cache_hits"

T = TypeVar("T")
F = TypeVar("F", bound=Callable)


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed span.  ``t0``/``t1`` are ``time.perf_counter_ns()``,
    ``cpu0``/``cpu1`` the opening thread's ``time.thread_time_ns()``."""

    name: str
    parent: str | None
    run: int
    t0: int
    t1: int
    cpu0: int
    cpu1: int


@dataclasses.dataclass(frozen=True)
class Compile:
    """One JAX compile stage: ``stage`` is a value of
    :data:`COMPILE_EVENTS`; ``t1`` is ``time.perf_counter_ns()`` when JAX
    reported it, so the stage ran over ``[t1 - seconds, t1]``.  Stages of
    an inner jitted function can nest inside its caller's."""

    stage: str
    fun_name: str
    seconds: float
    t1: int


@dataclasses.dataclass(frozen=True)
class Count:
    """One :func:`count` call: ``span`` is the innermost span open on the
    calling thread (``None`` outside every span), ``run`` its run."""

    name: str
    value: int
    span: str | None
    run: int | None


class Recorder:
    """What one :func:`recording` block kept: the closed spans in the
    order they closed, the counts, the compile stages, and the count of
    persistent compile-cache hits."""

    def __init__(self) -> None:
        self.spans: list[Span] = []
        self.counts: list[Count] = []
        self.compiles: list[Compile] = []
        self.cache_hits = 0
        self._runs = 0
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list[tuple[str, int]]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _next_run(self) -> int:
        with self._lock:
            self._runs += 1
            return self._runs - 1

    def _on_duration(self, event: str, secs: float, **kw: object) -> None:
        stage = COMPILE_EVENTS.get(event)
        if stage is not None:
            self.compiles.append(
                Compile(stage, str(kw.get("fun_name", "")), secs, time.perf_counter_ns()))

    def _on_event(self, event: str, **kw: object) -> None:
        if event == CACHE_HITS:
            with self._lock:
                self.cache_hits += 1


class _Open:
    """A span being recorded (the context manager :func:`span` returns
    while recording)."""

    __slots__ = ("rec", "name", "annotation", "parent", "run", "t0", "cpu0")

    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self) -> None:
        stack = self.rec._stack()
        if stack:
            self.parent, self.run = stack[-1]
        else:
            self.parent, self.run = None, self.rec._next_run()
        stack.append((self.name, self.run))
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.cpu0 = time.thread_time_ns()
        self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc: object) -> None:
        t1 = time.perf_counter_ns()
        cpu1 = time.thread_time_ns()
        self.annotation.__exit__(*exc)
        self.rec._stack().pop()
        self.rec.spans.append(
            Span(self.name, self.parent, self.run, self.t0, t1, self.cpu0, cpu1))


_NULL = contextlib.nullcontext()
_active: Recorder | None = None


def span(name: str) -> ContextManager[None]:
    """A context manager that records the block as span ``name`` while
    recording, and does nothing otherwise."""

    rec = _active
    if rec is None:
        return _NULL
    return _Open(rec, name)


def spanned(name: str) -> Callable[[F], F]:
    """Decorate a function so that each call is recorded as span
    ``name`` while recording."""

    def wrap(fn: F) -> F:
        @functools.wraps(fn)
        def call(*args, **kwargs):
            rec = _active
            if rec is None:
                return fn(*args, **kwargs)
            with _Open(rec, name):
                return fn(*args, **kwargs)

        return call  # type: ignore[return-value]

    return wrap


def count(name: str, value: int) -> None:
    """Keep ``value`` as count ``name`` while recording; nothing otherwise.
    The caller passes a number it already holds on the host, so a count
    adds no device synchronisation."""

    rec = _active
    if rec is None:
        return
    stack = rec._stack()
    span_name, run = stack[-1] if stack else (None, None)
    rec.counts.append(Count(name, int(value), span_name, run))


def total(counts: list[Count], name: str) -> int:
    """The sum of the counts named ``name``."""

    return sum(c.value for c in counts if c.name == name)


def wait(x: T) -> T:
    """``jax.block_until_ready(x)`` while recording, so that a span ends
    when the device work it dispatched has; ``x`` unchanged otherwise."""

    return jax.block_until_ready(x) if _active is not None else x


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Record spans and compile stages for the block."""

    global _active
    if _active is not None:
        raise RuntimeError("spans are already being recorded")
    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec._on_duration)
    jax.monitoring.register_event_listener(rec._on_event)
    _active = rec
    try:
        yield rec
    finally:
        _active = None
        jax.monitoring.unregister_event_listener(rec._on_event)
        jax.monitoring.unregister_event_duration_listener(rec._on_duration)

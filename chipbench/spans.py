"""Host spans around the calls ``FleetProgram.run`` makes into each layer.

The program carries no spans of its own, so in a traced run the benchmark
wraps the module attributes ``FleetProgram.run`` looks up: each wrapper
records ``(name, start, end, job)`` on the host clock and opens a
``jax.profiler.TraceAnnotation`` of the same name, so the spans also land
in the profiler's trace on the device events' clock.  ``unwrap`` puts the
originals back.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    job: int


class Spans:
    def __init__(self):
        self.spans: list[Span] = []
        self.counters: dict[str, float] = {}
        self.job = -1
        self._restore: list[tuple[object, str, object]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append(Span(name, t0, time.perf_counter(), self.job))

    def wrap(self, owner, attr: str, name: str, count=None) -> None:
        """Record a span around every call of ``owner.attr``; ``count``,
        if given, maps the call's arguments to counter increments."""

        original = getattr(owner, attr)

        def wrapper(*args, **kwargs):
            if count is not None and self.job >= 0:
                for k, v in count(*args, **kwargs).items():
                    self.counters[k] = self.counters.get(k, 0) + v
            with self.span(name):
                return original(*args, **kwargs)

        self._restore.append((owner, attr, original))
        setattr(owner, attr, wrapper)

    def wrap_program(self) -> None:
        """Wrap the layer entries of ``FleetProgram.run``."""

        from repro.core import engine_device, fleet

        self.wrap(fleet.FleetProgram, "run", "fleet_run")
        self.wrap(fleet.FleetProgram, "shard", "shard")
        self.wrap(fleet, "compute_stream_scores", "score", count=_score_bytes)
        self.wrap(engine_device, "build_events", "build_events")
        self.wrap(engine_device, "stack_events", "stack_events")
        self.wrap(engine_device, "replay_lanes", "replay_lanes")

    def unwrap(self) -> None:
        while self._restore:
            owner, attr, original = self._restore.pop()
            setattr(owner, attr, original)

    def total(self, *names: str) -> float:
        """Seconds spent in spans of these names, over all window jobs."""

        return sum(s.end - s.start for s in self.spans
                   if s.name in names and s.job >= 0)


def _score_bytes(batch, stream_len, *args, **kwargs) -> dict[str, float]:
    """Bytes the scoring work moves at least, from its shapes: every
    request's offset and size read once as int64 from the padded
    ``(streams, stream_len)`` matrix, and each stream's three 8-byte
    results written once."""

    streams = -(-batch.num_requests // stream_len)
    return {"score_bytes": streams * stream_len * 16 + streams * 24}

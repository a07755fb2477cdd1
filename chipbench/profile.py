"""Reduction of a profiler trace to device busy time, per-program device
time and idle gaps attributed to host spans.

``read_xplane`` pulls three event lists out of the ``.xplane.pb`` that
``jax.profiler`` writes: device operations, device programs (XLA
modules) of the first device plane, and the host spans the benchmark
opened with ``TraceAnnotation``.  ``reduce_events`` is pure arithmetic on
such lists, so it is tested on a synthetic trace
(``chipbench/tests/test_profile.py``).  An event is ``(name, start_ns,
end_ns)``; all lists share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re

Event = tuple[str, float, float]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""

    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def program_name(module: str) -> str:
    """``jit__replay_program(123)`` -> ``jit__replay_program``."""

    return re.sub(r"\(.*\)$", "", module).strip()


def reduce_events(ops: list[Event], modules: list[Event], spans: list[Event],
                  window: tuple[float, float]) -> dict:
    """Busy and idle time of the device over ``window``.

    Busy is the union of the operation intervals (the program intervals
    where the trace has no operations).  Each idle gap is charged to the
    innermost host span open at its middle, or to ``"no span"``.
    """

    lo, hi = window
    ops, modules = _clip(ops, lo, hi), _clip(modules, lo, hi)
    busy = union([(s, e) for _, s, e in (ops or modules)])
    busy_ns = sum(e - s for s, e in busy)
    programs: dict[str, float] = {}
    for n, s, e in modules:
        key = program_name(n)
        programs[key] = programs.get(key, 0.0) + (e - s) / 1e9
    op_time: dict[str, float] = {}
    for n, s, e in ops:
        op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
    idle: dict[str, float] = {}
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        open_ = [(ss, -(se - ss), n) for n, ss, se in spans if ss <= mid < se]
        who = max(open_)[2] if open_ else "no span"
        idle[who] = idle.get(who, 0.0) + (e - s) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "programs": programs,
        "ops": op_time,
        "idle": idle,
    }


def program_seconds(trace: dict, name: str) -> float:
    """Device seconds of the programs whose name holds ``name``.  A trace
    with a device plane runs every program of the timed path, so a name
    that matches nothing means the reader no longer knows the program:
    that is an error, not a metric left out."""

    secs = sum(v for k, v in trace["programs"].items() if name in k)
    if not secs:
        raise LookupError(f"no device program named like {name!r} in the trace; "
                          f"programs found: {sorted(trace['programs'])}")
    return secs


def read_xplane(logdir: str, span_names: set[str]) -> tuple[list[Event], list[Event], list[Event]]:
    """``(ops, modules, spans)`` from the newest trace under ``logdir``.

    Device events come from the first ``/device:`` plane; an empty list
    means the trace holds no device plane (a CPU rehearsal)."""

    import jax

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops: list[Event] = []
    modules: list[Event] = []
    spans: list[Event] = []
    device_done = False
    for plane in data.planes:
        if plane.name.startswith("/device:") and not device_done:
            lines = {ln.name: ln for ln in plane.lines}
            for line, out in (("XLA Ops", ops), ("XLA Modules", modules)):
                if line in lines:
                    device_done = True
                    out.extend((ev.name, ev.start_ns, ev.end_ns) for ev in lines[line].events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in span_names:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return ops, modules, spans

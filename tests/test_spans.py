"""Spans the program records itself (``repro.spans``), on a
small ``FleetProgram`` run on the CPU."""

import threading
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import FleetProgram, TraceBatch, ior
from repro.core.workloads import MiB

STREAM_LEN = 16
NODES = 3


@pytest.fixture(scope="module")
def batch():
    w = ior("strided", 8, total_bytes=64 * MiB, seed=5)
    return TraceBatch.from_items(list(w.trace))


def program():
    return FleetProgram(num_nodes=NODES, schemes=("orangefs", "ssdup"),
                        policy="range-offset", stream_len=STREAM_LEN,
                        score_backend="jnp", ssd_capacity=8 * MiB)


def fields(results):
    return {(s, i): r for s, fr in results.items() for i, r in enumerate(fr.node_results)}


def test_off_records_nothing_and_opens_no_annotation(batch, monkeypatch):
    opened = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            opened.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    assert spans._active is None
    program().run(batch)
    assert opened == []
    with spans.span("x") as s:
        assert s is None
    x = object()
    assert spans.wait(x) is x
    with spans.recording() as rec:
        with spans.span("x"):
            pass
    assert len(opened) == 1 and [s.name for s in rec.spans] == ["x"]


def test_recording_changes_no_result(batch):
    off = fields(program().run(batch))
    with spans.recording():
        on = fields(program().run(batch))
    assert off == on


@pytest.fixture(scope="module")
def recorded_runs(batch):
    with spans.recording() as rec:
        program().run(batch)
        program().run(batch)
    return rec


def test_spans_nest_within_their_run(recorded_runs):
    rec = recorded_runs
    roots = [s for s in rec.spans if s.parent is None]
    assert [s.name for s in roots] == ["fleet.run", "fleet.run"]
    assert sorted(s.run for s in roots) == [0, 1]
    for s in rec.spans:
        assert s.t0 <= s.t1 and s.cpu0 <= s.cpu1
        if s.parent is None:
            continue
        assert any(p.name == s.parent and p.run == s.run and p.t0 <= s.t0 and s.t1 <= p.t1
                   for p in rec.spans), s
    names = {s.name for s in rec.spans}
    assert {"fleet.shard", "score", "score.matrix", "score.upload", "score.run",
            "score.readback", "tape.build", "tape.anchors", "tape.anchors.upload",
            "tape.anchors.run", "tape.anchors.readback", "tape.xmerge", "tape.fill",
            "tape.stack", "fleet.lanes",
            "replay", "replay.upload", "replay.run", "replay.readback",
            "fleet.assemble"} <= names


def test_children_of_a_run_fit_inside_it(recorded_runs):
    for root in (s for s in recorded_runs.spans if s.name == "fleet.run"):
        kids = [s for s in recorded_runs.spans if s.run == root.run and s.parent == "fleet.run"]
        assert kids and sum(s.t1 - s.t0 for s in kids) <= root.t1 - root.t0


def test_one_score_and_tape_span_per_shard(recorded_runs, batch):
    """Every shard is scored in the run's one score span (one device
    dispatch) and lowered to a tape in a tape span of its own."""

    shards = [s for s in program().shard(batch) if s.num_requests]
    assert len(shards) == NODES
    for run in (0, 1):
        names = [s.name for s in recorded_runs.spans if s.run == run]
        assert names.count("score") == names.count("score.run") == 1
        assert names.count("tape.build") == len(shards)
        assert names.count("replay") == names.count("tape.stack") == 1


def test_spanned_keeps_the_function():
    @spans.spanned("f")
    def f(a, b=2):
        """doc"""
        return a + b

    assert f.__name__ == "f" and f.__doc__ == "doc"
    assert f(1) == 3
    with spans.recording() as rec:
        assert f(1, b=5) == 6
        with pytest.raises(TypeError):
            f()
    assert [s.name for s in rec.spans] == ["f", "f"]


def test_compiles_recorded_on_first_run_only(batch):
    jax.clear_caches()
    with spans.recording() as rec:
        program().run(batch)
        first = list(rec.compiles)
        t_second = time.perf_counter_ns()
        program().run(batch)
    assert any("_replay_program" in c.fun_name for c in first)
    assert {c.stage for c in first} >= {"jaxpr_trace", "backend_compile"}
    assert [c for c in rec.compiles if c.t1 >= t_second] == []
    # unregistered on exit: a compile now is seen by nobody
    n = len(rec.compiles)
    jax.jit(lambda x: x * 3 + 1)(np.arange(7))
    assert len(rec.compiles) == n


def test_second_thread_has_its_own_stack():
    seen = {}

    def other():
        with spans.span("worker"):
            with spans.span("worker.inner"):
                pass
        seen["done"] = True

    with spans.recording() as rec:
        with spans.span("main"):
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=30)
            with spans.span("main.inner"):
                pass
    assert not t.is_alive() and seen["done"]
    by = {s.name: s for s in rec.spans}
    assert by["worker"].parent is None and by["worker.inner"].parent == "worker"
    assert by["main.inner"].parent == "main"
    assert by["worker"].run != by["main"].run
    assert by["worker.inner"].run == by["worker"].run


def test_recording_does_not_nest():
    with spans.recording():
        with pytest.raises(RuntimeError):
            with spans.recording():
                pass
    assert spans._active is None

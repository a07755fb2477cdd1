"""The trace reduction and the scoring roofline's bytes, on small
synthetic inputs."""

import pytest

from chipbench import profile
from chipbench.spans import _score_bytes


def test_union_merges_overlaps():
    assert profile.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_busy_programs_and_idle():
    ops = [("fusion.1", 10, 20), ("sort.2", 15, 30), ("fusion.1", 60, 70), ("x", 200, 300)]
    modules = [("jit__stream_stats64(7)", 10, 30), ("jit__replay_program(9)", 60, 70)]
    spans = [("job", 0, 100), ("score", 5, 35), ("build_events", 35, 55), ("replay_lanes", 55, 100)]
    r = profile.reduce_events(ops, modules, spans, window=(0, 100))
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)  # [10, 30] and [60, 70]; x is outside
    assert r["programs"] == pytest.approx({"jit__stream_stats64": 20e-9, "jit__replay_program": 10e-9})
    assert r["ops"]["fusion.1"] == pytest.approx(20e-9)
    # gaps [0,10] -> score, [30,60] -> build_events (mid 45), [70,100] -> replay_lanes
    assert r["idle"] == pytest.approx({"score": 10e-9, "build_events": 30e-9, "replay_lanes": 30e-9})


def test_reduce_without_ops_uses_programs():
    r = profile.reduce_events([], [("jit_f", 0, 40)], [], window=(0, 100))
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["idle"] == pytest.approx({"no span": 60e-9})


def test_program_name():
    assert profile.program_name("jit__replay_program(123)") == "jit__replay_program"


class _Batch:
    def __init__(self, n):
        self.num_requests = n


@pytest.mark.parametrize("n,streams", [(16384, 128), (16383, 128), (129, 2)])
def test_score_bytes_from_shapes(n, streams):
    got = _score_bytes(_Batch(n), 128)
    assert got == {"score_bytes": streams * 128 * 16 + streams * 24}


def test_program_seconds_finds_or_refuses():
    trace = {"programs": {"jit__replay_program": 0.25, "jit__stream_stats64": 0.5}}
    assert profile.program_seconds(trace, "_replay_program") == 0.25
    with pytest.raises(LookupError, match="jit__replay_program"):
        profile.program_seconds(trace, "_renamed_program")

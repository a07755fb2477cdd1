"""Scoring a trace's shards in one call (``TraceShards``).

``FleetProgram.run`` scores every shard of a job with one call of
``compute_stream_scores``, one device dispatch on the device backends.
Each shard's part of the result must equal that shard scored alone and
the numpy oracle, field for field: stream grouping restarts at every
shard, each shard's trailing partial is padded on its own, and the rows
that round the stacked matrix up to its fixed shape score nothing.
"""

import math
import os
import sys

import numpy as np
import pytest

from repro import spans
from repro.core import (
    FleetProgram,
    StreamScores,
    TraceBatch,
    TraceShards,
    compute_stream_scores,
    fleet,
    ior,
    random_factor,
)
from repro.core.random_factor import stream_stats_batch_np
from repro.core.workloads import MiB

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.spans import Spans  # noqa: E402

L = 16
FIELDS = ("rf_sum", "percentage", "seek_distance", "nbytes", "offset_sum")


def _batch(offs, sizes) -> TraceBatch:
    offs = np.asarray(offs, dtype=np.int64)
    return TraceBatch(
        offsets=offs,
        sizes=np.asarray(sizes, dtype=np.int64),
        file_ids=np.zeros(offs.size, dtype=np.int64),
        app_ids=np.zeros(offs.size, dtype=np.int64),
        times=np.zeros(offs.size, dtype=np.float64),
        gap_positions=np.zeros(0, dtype=np.int64),
        gap_seconds=np.zeros(0, dtype=np.float64),
    )


def _random_shard(rng, n, hi) -> TraceBatch:
    sizes = rng.choice([512, 4096, 47008, 65536], size=n)
    offs = rng.integers(0, hi, size=n) // 512 * 512
    # a sequential run, so that some streams merge requests
    run = min(n, 2 * L)
    offs[:run] = 4096 * np.arange(run)
    sizes[:run] = 4096
    return _batch(offs, sizes)


def _ragged(rng):
    return [_random_shard(rng, n, 1 << 30) for n in (3 * L + 5, L + 1, 2 * L - 1, 7)]


def _with_empty(rng):
    return [_random_shard(rng, 2 * L + 3, 1 << 30), _batch([], []),
            _random_shard(rng, L, 1 << 30), _batch([], [])]


def _ties(rng):
    """Many requests share an offset and differ in size: the seek
    distance then depends on the tie order, which must be arrival order."""

    def shard(n):
        return _batch(rng.choice([0, 4096, 8192, 1 << 20], size=n),
                      rng.choice([512, 4096, 8192, 12288], size=n))

    return [shard(2 * L + 9), shard(L - 3), shard(L)]


def _huge(rng):
    return [_random_shard(rng, n, 1 << 40) for n in (2 * L + 2, L, 9)]


CASES = {"ragged": _ragged, "empty_shard": _with_empty, "ties": _ties,
         "beyond_int32": _huge}


def _score(x, backend):
    return compute_stream_scores(x, L, backend=backend, interpret=backend == "pallas")


@pytest.mark.parametrize("backend", ["numpy", "jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_one_call_equals_each_shard_alone(case, backend):
    shards = CASES[case](np.random.default_rng(sorted(CASES).index(case)))
    job = TraceShards(tuple(shards))
    if backend == "pallas" and case == "beyond_int32":
        with pytest.raises(ValueError, match="backend='jnp'"):
            _score(job, backend)
        return
    whole = _score(job, backend)
    assert len(whole) == sum(s.num_streams(L) for s in shards)
    parts = whole.split(job.stream_counts(L))
    assert len(parts) == len(shards)
    for shard, got in zip(shards, parts):
        alone = _score(shard, backend)
        oracle = compute_stream_scores(shard, L, backend="numpy")
        for field in FIELDS:
            np.testing.assert_array_equal(getattr(got, field), getattr(alone, field),
                                          err_msg=f"{case}/{backend}: {field}")
            np.testing.assert_array_equal(getattr(got, field), getattr(oracle, field),
                                          err_msg=f"{case}/{backend}: {field}")
        assert got.backend == alone.backend and got.stream_len == L
        # the host oracle on the shard's unpadded full windows
        m = shard.num_requests // L
        if m:
            rf, pct, dist = stream_stats_batch_np(
                shard.offsets[:m * L].reshape(m, L), shard.sizes[:m * L].reshape(m, L))
            np.testing.assert_array_equal(got.rf_sum[:m], rf)
            np.testing.assert_array_equal(got.percentage[:m], pct)
            np.testing.assert_array_equal(got.seek_distance[:m], dist)


def test_ties_keep_arrival_order():
    """Offsets ``[0, 0, 10]`` with sizes ``[10, 5, 1]``: sorted in arrival
    order among the tie, the residuals are -10 and +5 (2 seeks, distance
    15); the other order would give -5 and 0."""

    job = TraceShards((_batch([0, 0, 10], [10, 5, 1]), _batch([10, 0, 0], [1, 10, 5])))
    for backend in ("numpy", "jnp", "pallas"):
        s = _score(job, backend)
        np.testing.assert_array_equal(s.rf_sum, [2, 2])
        np.testing.assert_array_equal(s.seek_distance, [15, 15])


def test_matrix_shape_depends_only_on_request_and_shard_counts():
    """However the requests fall, the stacked matrix has the rows the
    worst split needs, and the rows past the streams are neutral."""

    rng = np.random.default_rng(3)
    total, n_shards = 10 * L + 3, 4
    for cut in ([0, 0, 0, total], [L, L, L, total - 3 * L],
                [L - 1, L + 1, 2 * L - 1, total - 4 * L + 1]):
        shards = [_random_shard(rng, n, 1 << 30) for n in cut]
        offs, szs, lens = TraceShards(tuple(shards)).padded_stream_matrix(L)
        assert offs.shape == szs.shape == ((total + n_shards * (L - 1)) // L, L)
        streams = sum(s.num_streams(L) for s in shards)
        assert (lens[:streams] > 0).all() and (lens[streams:] == 0).all()
        assert not offs[streams:].any() and not szs[streams:].any()
        rf, _, dist = stream_stats_batch_np(offs[streams:], szs[streams:])
        assert not rf.any() and not dist.any()
    # one trace is its own stream count, with no neutral rows
    one = _random_shard(rng, total, 1 << 30)
    assert one.padded_stream_matrix(L)[0].shape == (one.num_streams(L), L)


def test_split_refuses_counts_that_do_not_cover_the_scores():
    s = compute_stream_scores(_batch(np.arange(40) * 4096, [4096] * 40), L)
    assert isinstance(s, StreamScores) and len(s) == 3
    with pytest.raises(ValueError, match="split"):
        s.split([1, 1])
    assert [len(p) for p in s.split([0, 2, 1])] == [0, 2, 1]


# -- FleetProgram ------------------------------------------------------

NODES = 4


@pytest.fixture(scope="module")
def trace():
    w = ior("strided", 8, total_bytes=48 * MiB, request_size=47008, seed=9)
    return TraceBatch.from_items(list(w.trace))


def program():
    return FleetProgram(num_nodes=NODES, schemes=("orangefs", "ssdup"),
                        policy="range-offset", stream_len=L, score_backend="jnp",
                        ssd_capacity=4 * MiB, ssd="ftl")


def _per_shard(x, stream_len, backend="numpy", interpret=False):
    """Score each shard on its own and join the results: the scoring
    ``FleetProgram.run`` made before it scored a job in one call."""

    parts = [compute_stream_scores(s, stream_len, backend=backend, interpret=interpret)
             for s in x.shards]
    return StreamScores(
        **{f: np.concatenate([getattr(p, f) for p in parts]) for f in FIELDS},
        stream_len=stream_len, backend=parts[0].backend)


def test_fleet_program_results_equal_per_shard_scoring(trace, monkeypatch):
    assert all(s.num_requests % L for s in program().shard(trace))  # ragged tails
    one_call = program().run(trace)
    monkeypatch.setattr(fleet, "compute_stream_scores", _per_shard)
    per_shard = program().run(trace)
    assert one_call.keys() == per_shard.keys()
    for scheme in one_call:
        assert one_call[scheme].node_results == per_shard[scheme].node_results
    assert one_call["ssdup"].bytes_to_ssd > 0


def test_fleet_program_scores_in_one_call_and_one_dispatch(trace, monkeypatch):
    calls, dispatched = [], []
    original = fleet.compute_stream_scores
    program64 = random_factor._stream_stats64

    def counted(x, *args, **kwargs):
        calls.append(x)
        return original(x, *args, **kwargs)

    def counted64(offs, szs):
        dispatched.append(offs.shape)
        return program64(offs, szs)

    monkeypatch.setattr(fleet, "compute_stream_scores", counted)
    monkeypatch.setattr(random_factor, "_stream_stats64", counted64)
    with spans.recording() as rec:
        program().run(trace)
    assert len(calls) == 1 and calls[0].num_requests == trace.num_requests
    # one device program over every shard's rows, the matrix's fixed shape
    assert dispatched == [((trace.num_requests + NODES * (L - 1)) // L, L)]
    assert [s.name for s in rec.spans].count("score.run") == 1


def test_benchmark_span_wrappers_still_read_the_job(trace):
    """The benchmark's wrappers (``chipbench.spans``) see one ``score``
    span per job and count the job's scoring bytes from its request
    count."""

    rec = Spans()
    rec.wrap_program()
    try:
        rec.job = 0
        program().run(trace)
    finally:
        rec.unwrap()
    names = [s.name for s in rec.spans]
    assert names.count("score") == 1 and names.count("fleet_run") == 1
    assert names.count("build_events") == NODES
    streams = math.ceil(trace.num_requests / L)
    assert rec.counters["score_bytes"] == streams * L * 16 + streams * 24

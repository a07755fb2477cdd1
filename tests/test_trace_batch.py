"""TraceBatch round-trips + batched stream scoring vs the scalar oracle."""

import numpy as np
import pytest

from repro.core import (
    Gap,
    Request,
    StreamGrouper,
    TraceBatch,
    compute_stream_scores,
    ior,
    stream_percentage,
)
from repro.core.random_factor import (
    random_factor_sum,
    sorted_seek_distance,
    stream_stats_batch64,
    stream_stats_batch_np,
)
from repro.core.workloads import MiB


def random_trace(n, seed=0, max_offset=1 << 30):
    rng = np.random.default_rng(seed)
    return [
        Request(
            offset=int(rng.integers(0, max_offset)),
            size=int(rng.integers(1, 1 << 20)),
            file_id=int(rng.integers(0, 4)),
            app_id=int(rng.integers(0, 3)),
            time=float(i) * 1e-4,
        )
        for i, n_ in enumerate(range(n))
    ]


class TestTraceBatchRoundTrip:
    def test_requests_round_trip(self):
        trace = random_trace(333)
        batch = TraceBatch.from_requests(trace)
        assert batch.num_requests == 333
        assert batch.total_bytes == sum(r.size for r in trace)
        assert batch.to_requests() == trace

    def test_items_round_trip_with_gaps(self):
        items = [Gap(2.0), Request(0, 10), Request(10, 10), Gap(1.5),
                 Request(100, 10), Gap(3.0)]
        batch = TraceBatch.from_items(items)
        assert batch.num_gaps == 3
        assert batch.gap_seconds_total == pytest.approx(6.5)
        assert batch.to_items() == items

    def test_workload_round_trip(self):
        w = ior("strided", 16, total_bytes=64 * MiB)
        batch = TraceBatch.from_requests(w.trace)
        assert tuple(batch.to_requests()) == w.trace

    def test_select_remaps_gap_positions(self):
        items = [Request(0, 1), Gap(1.0), Request(10, 1), Request(20, 1)]
        batch = TraceBatch.from_items(items)
        sub = batch.select(np.array([0, 2]))
        # gap preceded request 1; locally it precedes selected request 1
        assert sub.to_items() == [Request(0, 1), Gap(1.0), Request(20, 1)]

    def test_shard_partitions_without_loss(self):
        batch = TraceBatch.from_requests(random_trace(1000))
        assignment = np.arange(1000) % 3
        shards = batch.shard(assignment, 3)
        assert sum(s.num_requests for s in shards) == 1000
        assert sum(s.total_bytes for s in shards) == batch.total_bytes


COLUMNS = ("offsets", "sizes", "file_ids", "app_ids", "times",
           "gap_positions", "gap_seconds")


def gapped_batch(n, gap_positions, seed=0):
    rng = np.random.default_rng(seed)
    return TraceBatch(
        offsets=rng.integers(0, 1 << 40, n, dtype=np.int64),
        sizes=rng.integers(1, 1 << 21, n, dtype=np.int64),
        file_ids=rng.integers(0, 160, n, dtype=np.int64),
        app_ids=rng.integers(0, 4, n, dtype=np.int64),
        times=np.sort(rng.random(n)),
        gap_positions=np.asarray(gap_positions, dtype=np.int64),
        gap_seconds=rng.random(len(gap_positions)),
    )


def shard_case(kind):
    """``(batch, assignment, num_nodes)`` for one partition case."""

    rng = np.random.default_rng(11)
    n = 2000
    gaps = [0, 0, 700, 700, 700, 1333, n, n]  # ends, middle, shared positions
    if kind == "empty_trace":
        return gapped_batch(0, [0, 0]), np.zeros(0, np.int64), 4
    if kind.startswith("random_"):
        nodes = int(kind.split("_")[1])
        return gapped_batch(n, gaps), rng.integers(0, nodes, n), nodes
    if kind == "one_node_idle":
        a = rng.integers(0, 8, n)
        a[a == 5] = 6
        return gapped_batch(n, gaps), a, 8
    if kind == "changes_every_request":
        a = np.arange(n) % 64
        assert np.all(np.diff(a) != 0)
        return gapped_batch(n, gaps), a, 64
    if kind == "long_runs":
        return gapped_batch(n, gaps), np.repeat(rng.integers(0, 64, 8), n // 8), 64
    raise KeyError(kind)


class TestShardPartition:
    """``TraceBatch.shard`` is the definition it replaced: per node, the
    requests at ``np.nonzero(assignment == node)`` in arrival order, with
    every gap replicated and placed as ``select`` places it."""

    @pytest.mark.parametrize("kind", [
        "random_1", "random_3", "random_64", "random_300", "random_70000",
        "one_node_idle", "empty_trace", "changes_every_request", "long_runs",
    ])
    def test_shard_equals_per_node_select(self, kind):
        batch, assignment, num_nodes = shard_case(kind)
        shards = batch.shard(assignment, num_nodes)
        assert len(shards) == num_nodes
        present = set(np.unique(assignment).tolist())
        nobody = batch.select(np.zeros(0, np.int64))  # every absent node's
        for node, shard in enumerate(shards):
            want = (
                batch.select(np.nonzero(assignment == node)[0])
                if node in present else nobody
            )
            for name in COLUMNS:
                got, ref = getattr(shard, name), getattr(want, name)
                assert got.dtype == ref.dtype, (node, name)
                assert np.array_equal(got, ref), (node, name)
        if kind == "one_node_idle":
            idle = shards[5]
            assert idle.num_requests == 0
            assert idle.num_gaps == batch.num_gaps
            assert not np.any(idle.gap_positions)

    def test_shard_columns_are_read_only(self):
        batch, assignment, num_nodes = shard_case("changes_every_request")
        shards = batch.shard(assignment, num_nodes)
        before = shards[1].offsets.copy()
        for name in COLUMNS[:5]:
            with pytest.raises(ValueError, match="read-only"):
                getattr(shards[0], name)[-1] = 0
        with pytest.raises(ValueError, match="read-only"):
            shards[0].sizes += 1
        np.testing.assert_array_equal(shards[1].offsets, before)
        # the gaps are each shard's own
        shards[0].gap_seconds[0] = -1.0
        assert shards[1].gap_seconds[0] == batch.gap_seconds[0] >= 0.0

    @pytest.mark.parametrize("assignment", [[0, 1, 3], [-1, 0, 1], [0, 1]])
    def test_shard_rejects_bad_assignment(self, assignment):
        batch = gapped_batch(3, [1])
        match = "length" if len(assignment) != 3 else "out of range"
        with pytest.raises(ValueError, match=match):
            batch.shard(np.asarray(assignment), 3)


class TestBatchedScoresMatchScalar:
    @pytest.mark.parametrize("stream_len", [32, 128])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_numpy_backend_is_bit_exact(self, stream_len, seed):
        trace = random_trace(stream_len * 7 + 13, seed=seed)
        scores = compute_stream_scores(trace, stream_len, backend="numpy")
        grouper = StreamGrouper(stream_len)
        streams = list(grouper.push_many(trace))
        tail = grouper.flush()
        if tail is not None:
            streams.append(tail)
        assert len(scores) == len(streams)
        for i, s in enumerate(streams):
            offs = [r.offset for r in s]
            szs = [r.size for r in s]
            assert scores.rf_sum[i] == random_factor_sum(offs, szs)
            assert scores.percentage[i] == stream_percentage(s)  # bit-exact
            assert scores.seek_distance[i] == sorted_seek_distance(s)
            assert scores.nbytes[i] == sum(szs)

    def test_jnp_backend_matches_numpy(self):
        jax = pytest.importorskip("jax")
        del jax
        rng = np.random.default_rng(3)
        offs = rng.integers(0, 1 << 30, size=(37, 128)).astype(np.int64)
        szs = rng.integers(1, 1 << 20, size=(37, 128)).astype(np.int64)
        # int64 lanes: exact seek counts and distances; the percentage
        # (compute_stream_scores divides it on the host) within one ulp
        rf_np, pct_np, dist_np = stream_stats_batch_np(offs, szs)
        rf_j, pct_j, dist_j = stream_stats_batch64(offs, szs)
        np.testing.assert_array_equal(rf_np, np.asarray(rf_j))
        np.testing.assert_array_max_ulp(pct_np, np.asarray(pct_j), maxulp=1)
        np.testing.assert_array_equal(dist_np, np.asarray(dist_j))

    @pytest.mark.parametrize("backend", ["cuda", "pallas"])
    def test_unknown_backend_rejected(self, backend):
        with pytest.raises(ValueError, match=r"one of \('numpy', 'jnp'\)"):
            compute_stream_scores(random_trace(10), backend=backend)

    def test_gaps_do_not_split_streams(self):
        """Gap markers must not flush a partial window (StreamGrouper rule)."""

        trace = random_trace(100)
        gapped = trace[:50] + [Gap(5.0)] + trace[50:]
        a = compute_stream_scores(trace, stream_len=64)
        b = compute_stream_scores(gapped, stream_len=64)
        np.testing.assert_array_equal(a.rf_sum, b.rf_sum)
        np.testing.assert_array_equal(a.seek_distance, b.seek_distance)

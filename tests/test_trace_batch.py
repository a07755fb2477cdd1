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
    stream_stats_batch,
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
        rf_np, pct_np, dist_np = stream_stats_batch_np(offs, szs)
        rf_j, pct_j, dist_j = stream_stats_batch(offs, szs)
        np.testing.assert_array_equal(rf_np, np.asarray(rf_j))
        np.testing.assert_allclose(pct_np, np.asarray(pct_j), atol=1e-6)
        # distance is float32-accumulated on device (int32 would wrap)
        np.testing.assert_allclose(dist_np, np.asarray(dist_j), rtol=1e-6)

    def test_pallas_backend_matches_numpy(self):
        pytest.importorskip("jax")
        trace = random_trace(128 * 5, seed=4)
        s_np = compute_stream_scores(trace, backend="numpy")
        s_pl = compute_stream_scores(trace, backend="pallas", interpret=True)
        np.testing.assert_array_equal(s_np.rf_sum, s_pl.rf_sum)
        np.testing.assert_array_equal(s_np.percentage, s_pl.percentage)
        np.testing.assert_array_equal(s_np.seek_distance, s_pl.seek_distance)

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="backend"):
            compute_stream_scores(random_trace(10), backend="cuda")

    def test_gaps_do_not_split_streams(self):
        """Gap markers must not flush a partial window (StreamGrouper rule)."""

        trace = random_trace(100)
        gapped = trace[:50] + [Gap(5.0)] + trace[50:]
        a = compute_stream_scores(trace, stream_len=64)
        b = compute_stream_scores(gapped, stream_len=64)
        np.testing.assert_array_equal(a.rf_sum, b.rf_sum)
        np.testing.assert_array_equal(a.seek_distance, b.seek_distance)

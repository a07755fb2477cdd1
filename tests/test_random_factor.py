"""Unit + property tests for the random-factor detector (paper Section 2.2)."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic no-shrink fallback, same API surface
    from _hypothesis_fallback import given, settings, st

from repro.core import (
    Request,
    StreamGrouper,
    random_factor_sum,
    random_percentage,
    stream_percentage,
)
from repro.core.random_factor import stream_stats_batch64, stream_stats_batch_np

REQ = 256 * 1024


class TestRandomFactorScalar:
    def test_fully_sequential_is_zero(self):
        offs = np.arange(128) * REQ
        assert random_factor_sum(offs, REQ) == 0
        assert random_percentage(offs, REQ) == 0.0

    def test_sorted_out_of_order_arrivals_still_sequential(self):
        # paper Fig. 4: arrival order is irrelevant, only sorted gaps count
        rng = np.random.default_rng(0)
        offs = rng.permutation(np.arange(128)) * REQ
        assert random_factor_sum(offs, REQ) == 0

    def test_fully_random_is_max(self):
        # huge strides: every sorted-adjacent pair leaves a gap
        offs = np.arange(128) * (10 * REQ)
        assert random_factor_sum(offs, REQ) == 127
        assert random_percentage(offs, REQ) == pytest.approx(1.0)

    def test_paper_fig4_example(self):
        # items #2,#3 contiguous after sorting (RF 0); #4 -> #7 gap (RF 1)
        offs = np.array([2, 3, 4, 7]) * REQ
        # pairs after sort: (2,3)=0, (3,4)=0, (4,7)=1
        assert random_factor_sum(offs, REQ) == 1

    def test_strided_half(self):
        # every second request present: all gaps = 2*REQ -> all random
        offs = np.arange(0, 256, 2) * REQ
        assert random_percentage(offs, REQ) == pytest.approx(1.0)

    def test_variable_sizes(self):
        # contiguity must use each request's own size
        offs = [0, 100, 300]
        sizes = [100, 200, 50]
        assert random_factor_sum(offs, sizes) == 0
        sizes = [100, 100, 50]
        assert random_factor_sum(offs, sizes) == 1

    def test_single_and_empty(self):
        assert random_factor_sum([], REQ) == 0
        assert random_factor_sum([42], REQ) == 0
        assert random_percentage([42], REQ) == 0.0


class TestBatchOracleAgreement:
    """The exact device program must agree with the numpy batch oracle
    bit for bit, and both with the scalar path."""

    @pytest.mark.parametrize("n", [2, 16, 128, 256])
    def test_agreement_random(self, n):
        rng = np.random.default_rng(n)
        offs = rng.integers(0, 1 << 20, size=(8, n)) * 7
        sizes = np.full((8, n), 7, np.int64)
        batch = np.asarray(stream_stats_batch64(offs, sizes)[0])
        np.testing.assert_array_equal(batch, stream_stats_batch_np(offs, sizes)[0])
        for i in range(8):
            assert batch[i] == random_factor_sum(offs[i], sizes[i])

    def test_percentage_batch(self):
        offs = np.arange(128, dtype=np.int64)[None, :] * 7
        out = np.asarray(stream_stats_batch64(offs, 7)[1])
        assert out.shape == (1,)
        assert out[0] == 0.0
        np.testing.assert_array_equal(out, stream_stats_batch_np(offs, 7)[1])


@settings(max_examples=200, deadline=None)
@given(
    offsets=st.lists(st.integers(0, 1 << 30), min_size=2, max_size=128),
    size=st.integers(1, 1 << 20),
)
def test_property_rf_bounds_and_sort_invariance(offsets, size):
    """0 <= S <= N-1; permuting arrivals never changes S (sorting first)."""

    offs = np.asarray(offsets, dtype=np.int64)
    s = random_factor_sum(offs, size)
    assert 0 <= s <= len(offs) - 1
    rng = np.random.default_rng(1)
    assert random_factor_sum(rng.permutation(offs), size) == s
    p = random_percentage(offs, size)
    assert 0.0 <= p <= 1.0


@settings(max_examples=100, deadline=None)
@given(st.integers(2, 64), st.integers(1, 100))
def test_property_contiguous_run_is_zero(n, size):
    offs = np.arange(n, dtype=np.int64) * size
    assert random_factor_sum(offs, size) == 0


class TestStreamGrouper:
    def test_groups_of_stream_len(self):
        g = StreamGrouper(4)
        out = list(g.push_many(Request(i, 1) for i in range(10)))
        assert [len(s) for s in out] == [4, 4]
        assert g.pending == 2
        tail = g.flush()
        assert len(tail) == 2
        assert g.flush() is None
        assert g.streams_emitted == 3

    def test_rejects_tiny_stream_len(self):
        with pytest.raises(ValueError):
            StreamGrouper(1)

    def test_stream_percentage_of_requests(self):
        stream = [Request(i * 10, 10) for i in range(16)]
        assert stream_percentage(stream) == 0.0
        stream = [Request(i * 30, 10) for i in range(16)]
        assert stream_percentage(stream) == 1.0

"""The tape's seek anchors, computed by the device program in
``engine_device.build_events``, against the numpy oracle
(``repro.testing.anchors``): every ``pf_*``, ``wf_*``, ``wn_*`` and
``hddt_*`` field of every stream event bit for bit."""

import numpy as np
import pytest

from repro.core import FleetProgram, TraceBatch, compute_stream_scores
from repro.core import engine_device as ed
from repro.core.device_model import HDDModel
from repro.testing import anchors as oracle
from repro.testing import golden
from repro.testing.traces import GOLDEN_WORKLOADS, golden_trace

HDD = HDDModel()
A = ed.SUFFIX_ANCHORS


def expected_fields(batch, stream_len):
    """The anchor columns of a shard's stream events, from the oracle."""

    bounds = batch.stream_bounds(stream_len)
    sc = compute_stream_scores(batch, stream_len)
    suffix = oracle._suffix_hdd_anchors(batch, bounds, HDD)
    suffix[:, 0] = (sc.rf_sum.astype(np.float64) * HDD.seek_time
                    + sc.seek_distance.astype(np.float64) * HDD.seek_dist_coeff
                    + sc.nbytes / HDD.seq_bw)
    pf = oracle._prefix_seek_anchors(batch, bounds)
    wf, wn = oracle._window_seek_anchors(batch, bounds)
    out = {}
    for j in range(A + 1):
        out[f"hddt_{j}"] = suffix[:, j]
        out[f"pf_{j}"] = pf[:, j]
    for i in range(ed.N_WINDOWS):
        out[f"wf_{i}"] = wf[:, i]
        out[f"wn_{i}"] = wn[:, i]
    return out


def assert_tape_matches_oracle(batch, stream_len=128):
    scores = compute_stream_scores(batch, stream_len)
    tape = ed.build_events(batch, scores, stream_len=stream_len, hdd=HDD)
    want = expected_fields(batch, stream_len) if batch.num_requests else {}
    streams = ~tape["is_gap"]
    assert streams.sum() == len(scores)
    for k in ed._EVENT_FIELDS:
        if k.startswith(("hddt_", "pf_", "wf_", "wn_")):
            got = tape[k][streams]
            assert got.dtype == np.float64, k
            assert np.array_equal(got, want.get(k, np.zeros(0))), k


def make_batch(offsets, sizes, files, gaps=()):
    n = len(offsets)
    return TraceBatch(
        offsets=np.asarray(offsets, dtype=np.int64),
        sizes=np.asarray(sizes, dtype=np.int64),
        file_ids=np.asarray(files, dtype=np.int64),
        app_ids=np.zeros(n, dtype=np.int64), times=np.zeros(n),
        gap_positions=np.asarray([p for p, _ in gaps], dtype=np.int64),
        gap_seconds=np.asarray([s for _, s in gaps], dtype=np.float64),
    )


def random_batch(seed, n, files=3, slots=64, block=4096):
    """Offsets on a coarse grid (many duplicates and contiguous runs),
    zero-size requests among them, several files per stream."""

    rng = np.random.default_rng(seed)
    return make_batch(
        rng.integers(0, slots, n) * block,
        rng.choice([0, block, 2 * block, 3 * block + 512], n),
        rng.integers(0, files, n),
        gaps=[(int(rng.integers(0, n + 1)), 1.5)] if n else (),
    )


def ior_easy_shard(ranks=16, segments=1024, transfer=2 << 20):
    """A cell-shaped shard at test size: file-per-process sequential
    transfers, ranks interleaved in arrival order."""

    k = np.arange(ranks * segments)
    return make_batch((k // ranks) * transfer, np.full(k.size, transfer), k % ranks)


@pytest.mark.parametrize("workload", sorted(GOLDEN_WORKLOADS))
@pytest.mark.parametrize("policy", golden.FIXTURE_POLICIES)
def test_golden_shards_match_oracle(workload, policy):
    prog = FleetProgram(num_nodes=golden.FIXTURE_NODES, schemes=("orangefs",),
                        policy=policy)
    for shard in prog.shard(golden_trace(workload)):
        assert_tape_matches_oracle(shard)


def test_cell_shaped_shard_matches_oracle():
    batch = ior_easy_shard()
    assert len(batch.stream_bounds(128)) - 1 == 128
    assert_tape_matches_oracle(batch)


@pytest.mark.parametrize("seed,n", [(1, 1000), (2, 4096), (3, 700), (4, 129)])
def test_random_shards_match_oracle(seed, n):
    assert_tape_matches_oracle(random_batch(seed, n))


def test_overwrites_and_zero_sizes_match_oracle():
    rng = np.random.default_rng(9)
    n = 640
    # few distinct offsets: every stream rewrites the same extents
    batch = make_batch(rng.integers(0, 4, n) * 8192,
                       np.where(rng.random(n) < 0.3, 0, 8192),
                       rng.integers(0, 2, n))
    assert_tape_matches_oracle(batch)


@pytest.mark.parametrize("n", [0, 1, 2, 128, 129, 257])
def test_short_and_partial_streams_match_oracle(n):
    """An empty shard, single-request streams and partial trailing
    streams of one or more requests."""

    assert_tape_matches_oracle(random_batch(n + 11, n))


def test_gaps_only_shard_has_no_stream_events():
    batch = make_batch([], [], [], gaps=[(0, 2.0), (0, 1.0)])
    scores = compute_stream_scores(batch, 128)
    tape = ed.build_events(batch, scores, stream_len=128, hdd=HDD)
    assert tape["is_gap"].all() and len(tape["is_gap"]) == 2


@pytest.mark.parametrize("stream_len", [16, 48, 256])
def test_other_stream_lengths_match_oracle(stream_len):
    assert_tape_matches_oracle(random_batch(stream_len, 20 * stream_len + 5),
                               stream_len=stream_len)
    assert_tape_matches_oracle(ior_easy_shard(segments=64), stream_len=stream_len)


def test_large_offsets_and_sums_match_oracle():
    """Offsets near 2**40, as a multi-TiB shared file has them, and
    per-stream byte and ``|residual|`` sums past 2**32."""

    rng = np.random.default_rng(5)
    n = 1500
    batch = make_batch((1 << 40) + rng.integers(0, 1 << 20, n) * 47008,
                       rng.choice([47008, 1 << 26], n), np.zeros(n))
    sums = np.add.reduceat(batch.sizes, batch.stream_bounds(128)[:-1])
    assert sums.max() > 1 << 32
    assert_tape_matches_oracle(batch)


def test_one_compile_per_shape_bucket():
    """Shards whose stream counts round up to one power of two share one
    compiled program; a stream count past it compiles a second."""

    stream_len = 24  # a row width no other test uses
    before = ed._tape_anchors64._cache_size()
    for streams in (9, 12, 16):
        assert_tape_matches_oracle(random_batch(streams, streams * stream_len - 3),
                                   stream_len=stream_len)
    assert ed._tape_anchors64._cache_size() == before + 1
    assert_tape_matches_oracle(random_batch(17, 17 * stream_len), stream_len=stream_len)
    assert ed._tape_anchors64._cache_size() == before + 2

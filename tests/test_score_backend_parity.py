"""Scoring-backend parity: ``jnp`` vs the numpy oracle.

``compute_stream_scores`` has two backends; the numpy path is the
int64 bit-exact oracle.  The ``jnp`` backend runs in the scoped 64-bit
mode (int64 lanes, float64 division) and must be BIT-EXACT on every
field at any offset magnitude, the benchmark cells' included.  It
scores the trailing partial stream on device via the score-neutral
padded row (``TraceBatch.padded_stream_matrix``).
"""

import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import TraceBatch, compute_stream_scores, ior, mixed, relabel
from repro.core.workloads import MiB

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, os.fspath(ROOT))

from chipbench import ior as cell_ior  # noqa: E402

STREAM_LEN = 128


def _nontrivial_batch(tail: int = 0) -> TraceBatch:
    """Mixed-pattern trace: sequential, random and strided phases
    interleaved, offsets spanning several files.  ``tail`` trims requests
    to leave a ragged final stream."""

    apps = [
        relabel(ior("segmented-contiguous", 8, total_bytes=48 * MiB, seed=11),
                app_id=0, file_id=0),
        relabel(ior("segmented-random", 8, total_bytes=48 * MiB, seed=12),
                app_id=1, file_id=1),
        relabel(ior("strided", 16, total_bytes=48 * MiB, seed=13),
                app_id=2, file_id=2),
    ]
    items = list(mixed(*apps, burst_requests=64).trace)
    if tail:
        items = items[:-tail]
    return TraceBatch.from_items(items)


@pytest.fixture(scope="module")
def batch():
    return _nontrivial_batch()


@pytest.fixture(scope="module")
def ragged_batch():
    return _nontrivial_batch(tail=37)


def _score(batch, backend):
    return compute_stream_scores(batch, STREAM_LEN, backend=backend)


def _assert_parity(batch, backend):
    oracle = compute_stream_scores(batch, STREAM_LEN, backend="numpy")
    scores = _score(batch, backend)
    assert len(scores) == len(oracle)
    # every statistic is integer counting or an exact int64 sum, and the
    # percentage divides host-side in float64 for every backend —
    # bit-exact, including the padded trailing partial
    for field in ("rf_sum", "percentage", "seek_distance", "nbytes",
                  "offset_sum"):
        np.testing.assert_array_equal(
            getattr(scores, field), getattr(oracle, field),
            err_msg=f"{backend}: {field} diverged from numpy oracle")


@pytest.mark.parametrize("backend", ["jnp"])
def test_backend_matches_oracle(batch, backend):
    _assert_parity(batch, backend)


@pytest.mark.parametrize("backend", ["jnp"])
def test_backend_matches_oracle_ragged_tail(ragged_batch, backend):
    _assert_parity(ragged_batch, backend)


def test_padded_tail_is_score_neutral(ragged_batch):
    """The padded row the device backend scores must carry the tail's exact
    statistics: same rf/dist as the unpadded host scoring of the tail."""

    offs_p, szs_p, lens = ragged_batch.padded_stream_matrix(STREAM_LEN)
    assert offs_p.shape == (len(lens), STREAM_LEN)
    assert lens[-1] < STREAM_LEN  # this fixture really has a partial tail
    assert (lens[:-1] == STREAM_LEN).all()
    # pad block sorts strictly after (or tied with) every real request and
    # contributes zero-size contiguous records
    t = int(lens[-1])
    assert (szs_p[-1, t:] == 0).all()
    assert offs_p[-1, t:].min() >= ragged_batch.offsets[-t:].max()
    from repro.core.random_factor import stream_stats_batch_np

    rf_pad, _, dist_pad = stream_stats_batch_np(offs_p[-1:], szs_p[-1:])
    tail_o = ragged_batch.offsets[len(ragged_batch.offsets) - t:]
    tail_s = ragged_batch.sizes[len(ragged_batch.sizes) - t:]
    rf_true, _, dist_true = stream_stats_batch_np(tail_o[None, :], tail_s[None, :])
    assert rf_pad[0] == rf_true[0]
    assert dist_pad[0] == dist_true[0]


def _batch(columns, n: int) -> TraceBatch:
    return TraceBatch(
        **columns,
        times=np.zeros(n, dtype=np.float64),
        gap_positions=np.zeros(0, dtype=np.int64),
        gap_seconds=np.zeros(0, dtype=np.float64),
    )


def _huge_offset_batch() -> TraceBatch:
    offs = np.array([2**33, 2**33 + 4096, 2**34, 5, 2**31], dtype=np.int64)
    return _batch({"offsets": offs,
                   "sizes": np.full(offs.size, 4096, dtype=np.int64),
                   "file_ids": np.zeros(offs.size, dtype=np.int64),
                   "app_ids": np.zeros(offs.size, dtype=np.int64)}, offs.size)


def _cell_batch(config: str, requests: int = 4 * STREAM_LEN + 37) -> TraceBatch:
    """``requests`` consecutive arrivals of a benchmark configuration's
    first job, up to the one at the layout's largest offset: the cell's
    own offsets at its full scale (``(ranks * segments - 1) * transfer``,
    about 2.2e12 B file per process and 4.9e10 B in one shared file)."""

    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{config}.json").read_text())
    t = cell_ior.ior_trace(cfg, 0, 0)
    end = int(np.argmax(t["offsets"])) + 1
    start = max(end - requests, 0)
    batch = _batch({k: t[k][start:end] for k in ("offsets", "sizes", "file_ids", "app_ids")},
                   end - start)
    top = (int(cfg["ranks"]) * int(cfg["segments"]) - 1) * int(cfg["transfer_bytes"])
    assert int(batch.offsets.max()) == top > np.iinfo(np.int32).max
    return batch


# the ids name the device backend, then the trace
@pytest.mark.parametrize("make", [
    pytest.param(_huge_offset_batch, id="jnp"),
    pytest.param(lambda: _cell_batch("ior_easy_ssdup"), id="jnp-ior_easy_ssdup"),
    pytest.param(lambda: _cell_batch("ior_hard_ssdup"), id="jnp-ior_hard_ssdup"),
])
def test_huge_offsets_stay_exact(make):
    """Offsets beyond int32 — up to the benchmark cells' own — stay exact
    on jnp's x64 lanes."""

    batch = make()
    oracle = compute_stream_scores(batch, STREAM_LEN, backend="numpy")
    scores = _score(batch, "jnp")
    np.testing.assert_array_equal(scores.rf_sum, oracle.rf_sum)
    np.testing.assert_array_equal(scores.percentage, oracle.percentage)
    np.testing.assert_array_equal(scores.seek_distance, oracle.seek_distance)


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_scores_name_the_path_that_ran(batch, backend):
    scores = compute_stream_scores(batch, STREAM_LEN, backend=backend)
    assert scores.backend == backend


def test_routing_decisions_identical_across_backends(batch):
    """End-to-end: percentages from the device backend must induce the
    same redirector decisions as the oracle (fp noise must stay far from
    any threshold boundary on this trace)."""

    from repro.core import IONodeSimulator

    results = {}
    for backend in ("numpy", "jnp"):
        scores = _score(batch, backend)
        sim = IONodeSimulator(scheme="ssdup+",
                              ssd_capacity=batch.total_bytes // 2)
        r = sim.run(batch, scores=scores)
        results[backend] = (r.bytes_to_ssd, r.bytes_to_hdd_direct, r.flushes)
    assert results["jnp"] == results["numpy"]

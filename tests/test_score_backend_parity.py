"""Scoring-backend parity: ``jnp`` and ``pallas`` vs the numpy oracle.

``compute_stream_scores`` has three backends; the numpy path is the
int64 bit-exact oracle.  The ``jnp`` backend runs in the scoped 64-bit
mode (int64 lanes, float64 division) and must be BIT-EXACT on every
field at any offset magnitude.  The ``pallas`` backend keeps the fused
kernel's int32 lanes and is bit-exact inside them (here in the Pallas
interpreter; ``chip_smoke.py`` runs it compiled on the TPU).  Both
backends score the trailing partial stream on device via the
score-neutral padded row (``TraceBatch.padded_stream_matrix``), and a
trace whose offsets overflow the kernel's int32 lanes is refused, never
scored elsewhere.
"""

import jax
import numpy as np
import pytest

from repro.core import TraceBatch, compute_stream_scores, ior, mixed, relabel
from repro.core.workloads import MiB

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
    batch = TraceBatch.from_items(items)
    # keep offsets inside the pallas kernel's int32 lanes (the refusal
    # beyond them is tested separately)
    assert int(batch.offsets.max()) < np.iinfo(np.int32).max
    return batch


@pytest.fixture(scope="module")
def batch():
    return _nontrivial_batch()


@pytest.fixture(scope="module")
def ragged_batch():
    return _nontrivial_batch(tail=37)


def _score(batch, backend):
    """Score on ``backend``; the kernel runs in the Pallas interpreter
    because these tests run on the CPU."""

    return compute_stream_scores(batch, STREAM_LEN, backend=backend,
                                 interpret=backend == "pallas")


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


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_backend_matches_oracle(batch, backend):
    _assert_parity(batch, backend)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_backend_matches_oracle_ragged_tail(ragged_batch, backend):
    _assert_parity(ragged_batch, backend)


def test_padded_tail_is_score_neutral(ragged_batch):
    """The padded row the device backends score must carry the tail's exact
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


def _huge_offset_batch() -> TraceBatch:
    offs = np.array([2**33, 2**33 + 4096, 2**34, 5, 2**31], dtype=np.int64)
    return TraceBatch(
        offsets=offs,
        sizes=np.full(offs.size, 4096, dtype=np.int64),
        file_ids=np.zeros(offs.size, dtype=np.int64),
        app_ids=np.zeros(offs.size, dtype=np.int64),
        times=np.zeros(offs.size, dtype=np.float64),
        gap_positions=np.zeros(0, dtype=np.int64),
        gap_seconds=np.zeros(0, dtype=np.float64),
    )


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_huge_offsets_stay_exact(backend):
    """Offsets beyond int32: jnp's x64 lanes handle them natively; pallas
    must refuse them — naming the exact device backend — rather than
    truncate into wrong seek counts or score them elsewhere."""

    batch = _huge_offset_batch()
    if backend == "pallas":
        with pytest.raises(ValueError, match="backend='jnp'"):
            _score(batch, backend)
        return
    oracle = compute_stream_scores(batch, STREAM_LEN, backend="numpy")
    scores = _score(batch, backend)
    np.testing.assert_array_equal(scores.rf_sum, oracle.rf_sum)
    np.testing.assert_array_equal(scores.percentage, oracle.percentage)
    np.testing.assert_array_equal(scores.seek_distance, oracle.seek_distance)


@pytest.mark.parametrize("backend,interpret,ran", [
    ("numpy", False, "numpy"),
    ("jnp", False, "jnp"),
    ("pallas", True, "pallas-interpret"),
])
def test_scores_name_the_path_that_ran(batch, backend, interpret, ran):
    scores = compute_stream_scores(batch, STREAM_LEN, backend=backend,
                                   interpret=interpret)
    assert scores.backend == ran


def test_compiled_pallas_never_falls_back_off_tpu(batch):
    """Without a TPU the compiled kernel cannot run; the call must fail
    instead of quietly interpreting or scoring on the host."""

    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached: the compiled kernel runs here")
    with pytest.raises(ValueError, match="interpret"):
        compute_stream_scores(batch, STREAM_LEN, backend="pallas")


def test_routing_decisions_identical_across_backends(batch):
    """End-to-end: percentages from the device backends must induce the
    same redirector decisions as the oracle (fp noise must stay far from
    any threshold boundary on this trace)."""

    from repro.core import IONodeSimulator

    results = {}
    for backend in ("numpy", "jnp", "pallas"):
        scores = _score(batch, backend)
        sim = IONodeSimulator(scheme="ssdup+",
                              ssd_capacity=batch.total_bytes // 2)
        r = sim.run(batch, scores=scores)
        results[backend] = (r.bytes_to_ssd, r.bytes_to_hdd_direct, r.flushes)
    assert results["jnp"] == results["numpy"]
    assert results["pallas"] == results["numpy"]

"""Deterministic golden-trace protocol.

A golden fixture must be reproducible from nothing but this module: each
named workload is rebuilt from fixed seeds through the public workload
generators, so a fixture file only stores the *name* plus a content
fingerprint of the materialized trace.  At replay time the fingerprint is
checked first — if the trace itself drifted (a NumPy RNG stream change, a
workload-generator edit), the diff reporter says so instead of blaming
the replay engine.

Workloads are sized so a full fixture replay (4 schemes x 2 policies,
4-node fleet) stays well under a second: golden tests run in the fast
suite on every push.
"""

from __future__ import annotations

import hashlib

import numpy as np

from repro.core import Gap, TraceBatch, ior, mixed, relabel
from repro.core.workloads import MiB


def _mixed_burst() -> TraceBatch:
    """The fleet benchmark's 4-app recipe at 1/8 scale (256 MiB).

    Same composition as ``benchmarks.bench_fleet.bench_scaling`` — one
    sequential app, two segmented-random, one strided — bursty arrival
    interleave, so golden replays exercise the exact trace family where
    the 8-16 node anomaly lives.
    """

    per_app = 64 * MiB
    apps = [
        relabel(ior("segmented-contiguous", 8, total_bytes=per_app, seed=1),
                app_id=0, file_id=0),
        relabel(ior("segmented-random", 8, total_bytes=per_app, seed=2),
                app_id=1, file_id=1),
        relabel(ior("strided", 32, total_bytes=per_app, seed=3),
                app_id=2, file_id=2),
        relabel(ior("segmented-random", 16, total_bytes=per_app, seed=4),
                app_id=3, file_id=3),
    ]
    return TraceBatch.from_items(mixed(*apps, burst_requests=256).trace)


def _strided_gaps() -> TraceBatch:
    """Strided + random phases separated by compute gaps, ragged tail.

    Covers the paths the mixed burst does not: ``Gap`` replication across
    shards, the compute-gap flush drain, a partial final stream (37
    requests trimmed off the strided phase), and the end-of-trace drain
    after a trailing gap.
    """

    w1 = relabel(ior("strided", 32, total_bytes=96 * MiB, seed=5),
                 app_id=0, file_id=0)
    w2 = relabel(ior("segmented-random", 8, total_bytes=64 * MiB, seed=6),
                 app_id=1, file_id=1)
    items = list(w1.trace)[:-37]
    items.append(Gap(2.0))
    items.extend(w2.trace)
    items.append(Gap(5.0))
    return TraceBatch.from_items(items)


GOLDEN_WORKLOADS = {
    "mixed-burst": _mixed_burst,
    "strided-gaps": _strided_gaps,
}


def golden_trace(name: str) -> TraceBatch:
    """Materialize a named canonical trace (deterministic)."""

    try:
        build = GOLDEN_WORKLOADS[name]
    except KeyError:
        raise ValueError(
            f"unknown golden workload {name!r}; "
            f"choose from {sorted(GOLDEN_WORKLOADS)}"
        ) from None
    return build()


def replay_trace(n_requests: int, seed: int = 0,
                 offset_limit: int = 1 << 38) -> TraceBatch:
    """The replay benchmarks' random-heavy multi-app trace.

    64 KiB writes at uniform offsets in ``[0, offset_limit)`` over 16
    files from 8 apps, with one 30 s compute gap halfway through; drawn
    in bulk from ``seed``.
    """

    rng = np.random.default_rng(seed)
    return TraceBatch(
        offsets=rng.integers(0, offset_limit, size=n_requests).astype(np.int64),
        sizes=np.full(n_requests, 64 << 10, dtype=np.int64),
        file_ids=rng.integers(0, 16, size=n_requests).astype(np.int64),
        app_ids=rng.integers(0, 8, size=n_requests).astype(np.int64),
        times=np.zeros(n_requests),
        gap_positions=np.asarray([n_requests // 2], dtype=np.int64),
        gap_seconds=np.asarray([30.0]),
    )


def trace_fingerprint(batch: TraceBatch) -> dict:
    """Content fingerprint of a materialized trace.

    The sha256 covers every request column plus the gap schedule, in
    fixed dtypes, so any byte of drift in the generated trace changes it.
    """

    h = hashlib.sha256()
    for arr, dtype in (
        (batch.offsets, np.int64),
        (batch.sizes, np.int64),
        (batch.file_ids, np.int64),
        (batch.app_ids, np.int64),
        (batch.gap_positions, np.int64),
        (batch.gap_seconds, np.float64),
    ):
        h.update(np.ascontiguousarray(arr, dtype=dtype).tobytes())
    return {
        "num_requests": int(batch.num_requests),
        "num_gaps": int(len(batch.gap_positions)),
        "total_bytes": int(batch.total_bytes),
        "sha256": h.hexdigest(),
    }

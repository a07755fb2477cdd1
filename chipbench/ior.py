"""IOR traces as arrays: the benchmark's one traffic generator.

A vectorized copy of the IOR generators of ``repro.core.workloads``
(``_strided_offsets``, ``_segmented_contiguous_offsets``,
``merge_arrivals``): the same per-process offset sequences, the same
random draws in the same order, and the same stable (virtual time,
process) merge, but with no Python object per request.  For equal
arguments it gives the same arrival order as the original
(``chipbench/tests/test_ior.py``).

A configuration names the IOR layout (``pattern``, ``ranks``,
``transfer_bytes``, ``segments``); a job's trace is drawn from
``(seed, job)``.  The offsets of a layout are fixed, and only their
arrival order depends on the draw, so every trace of a configuration
shards into the same shard sizes under ``range-offset``.
"""

from __future__ import annotations

import numpy as np

DT = 1e-4  # arrival spacing of merge_arrivals, seconds


def strided_offsets(ranks: int, segments: int, transfer: int) -> list[np.ndarray]:
    """ior-hard: segment ``s`` of rank ``r`` lands at ``(s*ranks + r)*transfer``."""

    seg = np.arange(segments, dtype=np.int64)
    return [(seg * ranks + r) * transfer for r in range(ranks)]


def file_per_process_offsets(
    ranks: int, segments: int, transfer: int
) -> list[np.ndarray]:
    """ior-easy: rank ``r`` writes its own file sequentially, laid out at
    ``r * segments * transfer`` in the global logical range."""

    block = segments * transfer
    seg = np.arange(segments, dtype=np.int64)
    return [seg * transfer + r * block for r in range(ranks)]


def merge_arrivals(
    per_proc: list[np.ndarray], rng: np.random.Generator, skew: float
) -> tuple[np.ndarray, np.ndarray]:
    """Server-side arrival order of per-process sequences.

    Returns ``(offsets, procs)`` in arrival order.  Each process gets a
    stationary progress offset ``N(0, skew)`` plus per-request jitter
    ``N(0, skew/5)`` and a uniform phase; ``skew == 0`` is a perfect
    round-robin.  Draws are made process by process in the original's
    order, and the merge is the original's stable sort by
    (virtual time, process).
    """

    nproc = len(per_proc)
    times, procs = [], []
    for p, offs in enumerate(per_proc):
        n = len(offs)
        base = np.arange(n, dtype=np.float64)
        if skew > 0:
            base = base + rng.normal(0.0, skew) + rng.normal(0.0, skew * 0.2, n)
            phase = rng.uniform(0, 1)
        else:
            phase = p / max(nproc, 1)
        times.append(base + phase)
        procs.append(np.full(n, p, dtype=np.int64))
    t = np.concatenate(times)
    pr = np.concatenate(procs)
    order = np.lexsort((pr, t))  # stable: ties keep process-major order
    return np.concatenate(per_proc)[order], pr[order]


def ior_trace(layout: dict, seed: int, job: int) -> dict[str, np.ndarray]:
    """One job's trace as columns (``offsets``, ``sizes``, ``file_ids``,
    ``app_ids``, ``times``), drawn from ``(seed, job)``."""

    ranks = int(layout["ranks"])
    segments = int(layout["segments"])
    transfer = int(layout["transfer_bytes"])
    pattern = layout["pattern"]
    if pattern == "strided":
        per_proc = strided_offsets(ranks, segments, transfer)
    elif pattern == "file-per-process":
        per_proc = file_per_process_offsets(ranks, segments, transfer)
    else:
        raise ValueError(f"unknown IOR pattern {pattern!r}")
    rng = np.random.default_rng([int(seed) % 2**64, int(job)])
    offsets, procs = merge_arrivals(per_proc, rng, float(layout["skew"]))
    n = len(offsets)
    file_ids = procs if layout["file_per_process"] else np.zeros(n, np.int64)
    return {
        "offsets": offsets,
        "sizes": np.full(n, transfer, dtype=np.int64),
        "file_ids": file_ids,
        "app_ids": np.zeros(n, dtype=np.int64),
        "times": np.arange(n, dtype=np.float64) * DT,
    }

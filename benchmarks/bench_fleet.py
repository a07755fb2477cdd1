"""Fleet benchmark: batched stream scoring speedup + 1->16 node scaling.

Part 1 — scoring: per-stream scalar NumPy (the seed simulator's hot path:
one ``stream_percentage`` + one ``sorted_seek_distance`` per 128-request
window inside a Python loop) versus the vectorized batched paths
(``numpy`` int64 oracle, one-call ``jnp``, and the ``stream_rf`` Pallas
kernel) on the same >= 4096-stream trace.  The acceptance bar is a >= 5x
speedup for batched over scalar.

Part 2 — fleet scaling: aggregate throughput of the four schemes as the
same mixed workload is sharded over 1 -> 16 I/O nodes (range-offset
policy, per-node SSD shrinking with the shard so total fleet SSD is
constant), the paper's 2-node aggregate generalized.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import Row, timeit
from repro.core import (
    FleetSimulator,
    Request,
    StreamGrouper,
    TraceBatch,
    compute_stream_scores,
    ior,
    mixed,
    relabel,
    stream_percentage,
)
from repro.core.random_factor import sorted_seek_distance
from repro.core.workloads import GiB, MiB

SCORE_STREAMS = 4096
STREAM_LEN = 128


def _scalar_score_all(streams) -> float:
    t0 = time.perf_counter()
    for s in streams:
        stream_percentage(s)
        sorted_seek_distance(s)
    return time.perf_counter() - t0


def bench_scoring(rows: list[Row]) -> None:
    rng = np.random.default_rng(0)
    n = SCORE_STREAMS * STREAM_LEN
    trace = [
        Request(offset=int(o), size=256 * 1024)
        for o in rng.integers(0, 1 << 30, size=n)
    ]
    grouper = StreamGrouper(STREAM_LEN)
    streams = list(grouper.push_many(trace))
    batch = TraceBatch.from_requests(trace)

    print(f"\n-- stream scoring, {SCORE_STREAMS} streams x {STREAM_LEN} reqs --")
    t_scalar = min(_scalar_score_all(streams) for _ in range(3))
    sps = SCORE_STREAMS / t_scalar
    print(f"{'scalar-loop':18s} {t_scalar*1e3:9.1f} ms   {sps:12.0f} streams/s")
    rows.append(Row("fleet_score_scalar", t_scalar * 1e6,
                    f"streams_per_s={sps:.0f}"))

    # off the TPU the kernel runs in the Pallas interpreter, and its row
    # says so (fleet_score_pallas-interpret)
    interpret = jax.default_backend() != "tpu"
    for backend in ("numpy", "jnp", "pallas"):
        def score():
            return compute_stream_scores(batch, STREAM_LEN, backend=backend,
                                         interpret=interpret)

        ran = score().backend  # warmup
        us, _ = timeit(score, repeat=3)
        t = us / 1e6
        speedup = t_scalar / t
        print(f"{'batched-' + ran:18s} {t*1e3:9.1f} ms   "
              f"{SCORE_STREAMS/t:12.0f} streams/s   {speedup:5.1f}x vs scalar")
        rows.append(Row(f"fleet_score_{ran}", us,
                        f"speedup_vs_scalar={speedup:.1f}"))


def bench_scaling(rows: list[Row], total_bytes: int) -> None:
    per_app = max(total_bytes // 4, 64 * MiB)
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
    load = mixed(*apps, burst_requests=512)
    batch = TraceBatch.from_requests(load.trace)
    fleet_ssd = load.total_bytes // 2  # total fleet SSD, split over nodes

    print(f"\n-- fleet scaling, {load.total_bytes / GiB:.1f} GiB mixed load, "
          "range-offset sharding --")
    print(f"{'nodes':>5s} " + "".join(f"{s:>14s}" for s in
                                      ("orangefs", "orangefs-bb", "ssdup",
                                       "ssdup+")) + f" {'imbalance':>10s}")
    for nodes in (1, 2, 4, 8, 16):
        tps = []
        imb = 1.0
        for scheme in ("orangefs", "orangefs-bb", "ssdup", "ssdup+"):
            t0 = time.perf_counter()
            fr = FleetSimulator(
                num_nodes=nodes, scheme=scheme, policy="range-offset",
                ssd_capacity=max(fleet_ssd // nodes, 64 * MiB),
            ).run(batch)
            dt = time.perf_counter() - t0
            tps.append(fr.throughput_mbs)
            imb = fr.load_imbalance
            rows.append(Row(
                f"fleet_{scheme}_{nodes}n", dt * 1e6,
                f"agg_mbs={fr.throughput_mbs:.1f}",
            ))
        print(f"{nodes:5d} " + "".join(f"{t:12.1f} MB/s"[-14:] for t in tps)
              + f" {imb:10.2f}")


def run(total_bytes: int = 2 * GiB) -> list[Row]:
    rows: list[Row] = []
    print("\n== fleet: batched scoring + multi-node scaling ==")
    bench_scoring(rows)
    bench_scaling(rows, total_bytes)
    return rows


if __name__ == "__main__":
    from benchmarks.common import BENCH_BYTES, emit

    emit(run(BENCH_BYTES))

"""Replay benchmark: batched engine speedup + 16-64-node fleet sweep.

Part 1 — replay speedup: one multi-million-request random trace replayed
through ``IONodeSimulator`` twice: the seed configuration (per-request
engine, AVL index — one Python ``pipeline.append`` + pointer-chasing
``insert`` per request) versus the batched engine (vectorized
``append_batch`` + ``ExtentIndex``, whole-stream accounting).  The two
produce bit-identical ``SimResult``\\ s (asserted here); the acceptance
bar is a >= 5x replay-throughput speedup.

Part 2 — fleet sweep: the same trace sharded over 16/32/64 I/O nodes
(range-offset policy, per-node SSD shrinking with the shard), reporting
aggregate throughput, load imbalance, and replay wall time per fleet —
the scale the ROADMAP's fleet layer targets.
"""

from __future__ import annotations

import dataclasses
import time

from benchmarks.common import Row
from repro.core import (
    FleetSimulator,
    IONodeSimulator,
    compute_stream_scores,
)
from repro.core.workloads import GiB, MiB
from repro.testing.traces import replay_trace

DEFAULT_REQUESTS = 1_000_000
FULL_REQUESTS = 4_000_000


def bench_replay_speedup(rows: list[Row], n_requests: int) -> None:
    batch = replay_trace(n_requests)
    scores = compute_stream_scores(batch)
    cap = 8 * GiB
    print(f"\n-- replay engines, {n_requests:,} requests "
          f"({batch.total_bytes / GiB:.0f} GiB logical), ssdup+ --")

    configs = [
        ("per-request+avl", dict(engine="per-request", index_backend="avl")),
        ("per-request+numpy", dict(engine="per-request", index_backend="numpy")),
        ("batched+numpy", dict(engine="batched", index_backend="numpy")),
    ]
    results = {}
    times = {}
    items = None
    for name, kw in configs:
        sim = IONodeSimulator(scheme="ssdup+", ssd_capacity=cap, **kw)
        if kw["engine"] == "per-request":
            if items is None:
                items = batch.to_items()
            trace = items
        else:
            trace = batch
        t0 = time.perf_counter()
        results[name] = sim.run(trace, scores=scores)
        times[name] = time.perf_counter() - t0
        rps = n_requests / times[name]
        speedup = times["per-request+avl"] / times[name]
        print(f"{name:20s} {times[name]:8.2f} s   {rps:12,.0f} req/s   "
              f"{speedup:5.1f}x vs seed")
        rows.append(Row(f"replay_{name.replace('+', '_')}",
                        times[name] * 1e6,
                        f"req_per_s={rps:.0f};speedup={speedup:.1f}"))

    # the speedup must not come from a different answer
    ref = results["per-request+avl"]
    for name, res in results.items():
        for f in dataclasses.fields(ref):
            assert getattr(ref, f.name) == getattr(res, f.name), (
                f"{name} diverged on {f.name}")
    speedup = times["per-request+avl"] / times["batched+numpy"]
    print(f"{'':20s} bit-identical SimResults; batched speedup "
          f"{speedup:.1f}x (bar: >= 5x)")
    assert speedup >= 5.0, f"batched replay speedup {speedup:.2f}x < 5x"


def bench_fleet_sweep(rows: list[Row], n_requests: int) -> None:
    batch = replay_trace(max(n_requests, 1_000_000), seed=1)
    fleet_ssd = batch.total_bytes // 2

    print(f"\n-- fleet sweep, {batch.num_requests:,} requests, "
          "range-offset sharding, ssdup+ --")
    print(f"{'nodes':>5s} {'replay_s':>9s} {'agg MB/s':>10s} "
          f"{'imbalance':>10s} {'ssd_ratio':>10s}")
    for nodes in (16, 32, 64):
        t0 = time.perf_counter()
        fr = FleetSimulator(
            num_nodes=nodes, scheme="ssdup+", policy="range-offset",
            ssd_capacity=max(fleet_ssd // nodes, 64 * MiB),
        ).run(batch)
        dt = time.perf_counter() - t0
        print(f"{nodes:5d} {dt:9.2f} {fr.throughput_mbs:10.1f} "
              f"{fr.load_imbalance:10.2f} {fr.ssd_byte_ratio:10.2f}")
        rows.append(Row(
            f"replay_fleet_{nodes}n", dt * 1e6,
            f"agg_mbs={fr.throughput_mbs:.1f};imbalance={fr.load_imbalance:.2f}",
        ))


def run(total_bytes: int = 2 * GiB) -> list[Row]:
    rows: list[Row] = []
    n = FULL_REQUESTS if total_bytes >= 16 * GiB else DEFAULT_REQUESTS
    print("\n== replay: batched engine speedup + 16-64-node fleet ==")
    bench_replay_speedup(rows, n)
    bench_fleet_sweep(rows, n)
    return rows


if __name__ == "__main__":
    from benchmarks.common import BENCH_BYTES, emit

    emit(run(BENCH_BYTES))

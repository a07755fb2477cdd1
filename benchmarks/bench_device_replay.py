"""Device-resident fleet replay: one jitted sweep vs looped batched numpy.

The acceptance benchmark for ``FleetProgram``: a 64-node fleet replayed
under all four schemes is 256 ``scheme x node`` lane replays.  The
baseline runs them the pre-device way — a Python loop of
``FleetSimulator(engine="batched")`` over schemes, each looping nodes —
while ``FleetProgram`` stacks all 256 lanes and replays them in ONE
``jit(scan(vmap(step)))`` device call.  Acceptance bar: >= 10x
steady-state sweep speedup on the replay-scale trace (the same
million-request random mix ``bench_replay`` uses).

The first call pays the host tape build (2 lexsorts + anchor passes per
shard) plus XLA compile; both amortize — tapes are cached per trace,
the executable per program shape — which is the point of fixing the
program's shape.  Rows:

* ``device_replay_loop_batched``   — the scheme-looped numpy baseline
* ``device_replay_fleet_program``  — FleetProgram steady-state sweep
* ``device_replay_compile``        — first-call cost (tapes + compile)
"""

from __future__ import annotations

import time

from benchmarks.common import Row
from benchmarks.bench_replay import DEFAULT_REQUESTS, FULL_REQUESTS
from repro.core import FleetProgram, FleetSimulator
from repro.core.workloads import GiB, MiB
from repro.testing.traces import replay_trace

NODES = 64
SCHEMES = ("orangefs", "orangefs-bb", "ssdup", "ssdup+")
POLICY = "range-offset"


def run(total_bytes: int = 2 * GiB) -> list[Row]:
    rows: list[Row] = []
    n = FULL_REQUESTS if total_bytes >= 16 * GiB else DEFAULT_REQUESTS
    batch = replay_trace(n)
    cap = max(batch.total_bytes // 2 // NODES, 64 * MiB)
    lanes = NODES * len(SCHEMES)

    print(f"\n-- device fleet replay, {n:,} requests "
          f"({batch.total_bytes / GiB:.0f} GiB logical), {NODES} nodes x "
          f"{len(SCHEMES)} schemes ({lanes} lanes), {POLICY} sharding --")

    # baseline: the pre-device path — Python loop over schemes, each a
    # FleetSimulator Python loop over nodes with the batched numpy engine
    t0 = time.perf_counter()
    loop_results = {
        scheme: FleetSimulator(num_nodes=NODES, scheme=scheme, policy=POLICY,
                               ssd_capacity=cap, engine="batched").run(batch)
        for scheme in SCHEMES
    }
    t_loop = time.perf_counter() - t0
    print(f"{'loop-batched':18s} {t_loop*1e3:9.1f} ms   "
          f"{lanes / t_loop:8.1f} lanes/s")
    rows.append(Row("device_replay_loop_batched", t_loop * 1e6,
                    f"lanes_per_s={lanes / t_loop:.1f}"))

    prog = FleetProgram(num_nodes=NODES, schemes=SCHEMES, policy=POLICY,
                        ssd_capacity=cap)
    t0 = time.perf_counter()
    dev_results = prog.run(batch)  # builds tapes, traces + compiles
    t_compile = time.perf_counter() - t0
    print(f"{'fleet-program(1st)':18s} {t_compile*1e3:9.1f} ms   "
          "(host tape build + XLA compile)")
    rows.append(Row("device_replay_compile", t_compile * 1e6,
                    f"lanes={lanes}"))

    t_dev = None
    for _ in range(3):
        t0 = time.perf_counter()
        dev_results = prog.run(batch)
        dt = time.perf_counter() - t0
        t_dev = dt if t_dev is None else min(t_dev, dt)
    speedup = t_loop / t_dev
    print(f"{'fleet-program':18s} {t_dev*1e3:9.1f} ms   "
          f"{lanes / t_dev:8.1f} lanes/s   {speedup:5.1f}x vs loop "
          "(bar: >= 10x)")
    rows.append(Row("device_replay_fleet_program", t_dev * 1e6,
                    f"speedup_vs_loop={speedup:.1f}"))

    # sanity: the sweep must land on the baseline's aggregate bytes — a
    # speedup over a wrong answer is no speedup
    for scheme in SCHEMES:
        want = sum(r.total_bytes for r in loop_results[scheme].node_results)
        got = sum(r.total_bytes for r in dev_results[scheme].node_results)
        assert got == want, (
            f"{scheme}: device sweep routed {got} bytes, baseline {want}")
    return rows


if __name__ == "__main__":
    run()

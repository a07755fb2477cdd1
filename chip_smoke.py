"""Smoke test of the main path on one TPU: stream scoring and the
device-resident fleet replay, each checked against the numpy oracle.

    python chip_smoke.py [--seed 0]
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --requests 131072 --nodes 8

Every phase runs in this one process, on one chip, through the entry
points a user calls (``compute_stream_scores``, ``FleetProgram.run``):

1. device: platform, kind and count as JAX reports them.
2. scoring: a ``--requests`` replay trace (64 KiB writes, offsets in
   ``[0, 2**38)``, 16 files, 8 apps, one 30 s compute gap) scored with
   ``backend="jnp"`` must equal ``backend="numpy"`` bit for bit.
3. replay: ``FleetProgram`` over ``--nodes`` x 4 schemes, range-offset
   sharding, ``jnp`` scoring, per-node capacity
   ``max(total_bytes // 2 // nodes, 64 MiB)``, once with ``ssd="constant"``
   and once with ``ssd="ftl"``.  Every constant lane must lie within
   ``DEVICE_TOLERANCES`` of ``FleetSimulator(engine="batched")``; the FTL
   sweep is checked on 4 nodes' shards x 4 schemes, because the host FTL
   replay is slow.  The constant sweep runs once more in sanitize mode
   (checkify on the device) and must give the same results.

Times printed along the way are information only.  On success the last
line of stdout is ``{"ok": true, "device": {...}}``.  Any failed check or
error exits non-zero without that line.  Without a TPU the script exits
2 before any work, unless ``--rehearse`` is given for a CPU rehearsal.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.sanitize import sanitizing  # noqa: E402
from repro.core import (  # noqa: E402
    FleetProgram,
    FleetSimulator,
    IONodeSimulator,
    compute_stream_scores,
)
from repro.core.engine_device import DEVICE_TOLERANCES  # noqa: E402
from repro.core.workloads import MiB  # noqa: E402
from repro.runtime import use_compile_cache  # noqa: E402
from repro.testing.golden import (  # noqa: E402
    diff_fleet,
    diff_sim,
    fleet_result_to_dict,
    sim_result_to_dict,
)
from repro.testing.traces import replay_trace  # noqa: E402

SCHEMES = ("orangefs", "orangefs-bb", "ssdup", "ssdup+")
STREAM_LEN = 128
SCORE_FIELDS = ("rf_sum", "percentage", "seek_distance", "nbytes",
                "offset_sum")
FTL_CHECKED_NODES = 4


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _score_mismatches(got, want) -> list[str]:
    return [
        f"{got.backend}: {f} differs from numpy in "
        f"{int(np.sum(getattr(got, f) != getattr(want, f)))} streams"
        for f in SCORE_FIELDS
        if not np.array_equal(getattr(got, f), getattr(want, f))
    ]


def phase_scoring(args, batch) -> list[str]:
    errors = []
    want = compute_stream_scores(batch, STREAM_LEN, backend="numpy")
    got, t_first = _timed(
        lambda: compute_stream_scores(batch, STREAM_LEN, backend="jnp"))
    _, t_steady = _timed(
        lambda: compute_stream_scores(batch, STREAM_LEN, backend="jnp"))
    print(f"scoring jnp: {len(got)} streams, first call {t_first:.3f} s, "
          f"steady {t_steady:.3f} s")
    if got.backend != "jnp":
        errors.append(f"jnp scoring ran as {got.backend!r}")
    return errors + _score_mismatches(got, want)


def _worst(expected: dict, actual: dict, field: str) -> str:
    """The largest relative deviation of one clock field over the lanes."""

    worst, where = 0.0, "-"
    for scheme, fr in actual.items():
        for i, (e, a) in enumerate(zip(expected[scheme]["nodes"],
                                       fr["nodes"])):
            rel = abs(a[field] - e[field]) / max(abs(e[field]), 1e-300)
            if rel > worst:
                worst, where = rel, f"{scheme} node {i}"
    return f"{field} {worst!r} ({where})"


def _report(label, expected: dict, actual: dict) -> list[str]:
    errors = []
    for scheme in SCHEMES:
        errors += [
            f"{label} {scheme} {d}"
            for d in diff_fleet(expected[scheme], actual[scheme],
                                tolerances=DEVICE_TOLERANCES)
        ]
    print(f"{label}: worst relative deviation from the batched oracle: "
          + "; ".join(_worst(expected, actual, f)
                      for f in ("io_seconds", "total_seconds")))
    return errors


def phase_replay(args, batch) -> list[str]:
    errors = []
    nodes = args.nodes
    lanes = nodes * len(SCHEMES)
    cap = max(batch.total_bytes // 2 // nodes, 64 * MiB)

    def program(ssd):
        return FleetProgram(num_nodes=nodes, schemes=SCHEMES,
                            policy="range-offset", score_backend="jnp",
                            ssd_capacity=cap, ssd=ssd)

    prog = program("constant")
    res, t_first = _timed(lambda: prog.run(batch))
    t_steady = min(_timed(lambda: prog.run(batch))[1] for _ in range(3))
    print(f"replay constant: {lanes} lanes, first call {t_first:.3f} s "
          f"(tapes + compile), steady {t_steady:.3f} s with readback, "
          f"{lanes / t_steady:.1f} lanes/s")
    actual = {s: fleet_result_to_dict(fr) for s, fr in res.items()}
    expected = {
        s: fleet_result_to_dict(FleetSimulator(
            num_nodes=nodes, scheme=s, policy="range-offset",
            ssd_capacity=cap, engine="batched").run(batch))
        for s in SCHEMES
    }
    errors += _report("constant", expected, actual)

    with sanitizing(True):
        checked = prog.run(batch)
    if {s: fleet_result_to_dict(fr) for s, fr in checked.items()} != actual:
        errors.append("sanitize mode changed the constant sweep's results")
    else:
        print("replay constant, sanitize mode: checks passed, same results")

    prog = program("ftl")
    res, t_first = _timed(lambda: prog.run(batch))
    t_steady = min(_timed(lambda: prog.run(batch))[1] for _ in range(3))
    print(f"replay ftl: {lanes} lanes, first call {t_first:.3f} s, steady "
          f"{t_steady:.3f} s with readback, {lanes / t_steady:.1f} lanes/s")
    shards = prog.shard(batch)
    picked = np.linspace(0, nodes - 1, FTL_CHECKED_NODES).astype(int)
    for n in sorted(set(picked.tolist())):
        scores = compute_stream_scores(shards[n], STREAM_LEN)
        for s in SCHEMES:
            want = IONodeSimulator(
                scheme=s, ssd_capacity=cap, ssd="ftl", engine="batched"
            ).run(shards[n], scores=scores)
            errors += [
                f"ftl {s} node {n} {d}"
                for d in diff_sim(sim_result_to_dict(want),
                                  sim_result_to_dict(res[s].node_results[n]),
                                  tolerances=DEVICE_TOLERANCES)
            ]
    print(f"replay ftl: checked nodes {sorted(set(picked.tolist()))} "
          "against the batched FTL oracle")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=1 << 20)
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a run without a TPU (CPU rehearsal)")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)  # keep lines if killed

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU found; pass --rehearse for a CPU rehearsal",
              file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache()}")

    batch = replay_trace(args.requests, seed=args.seed)
    failed = []
    for name, phase in (("scoring", phase_scoring), ("replay", phase_replay)):
        t0 = time.perf_counter()
        try:
            errors = phase(args, batch)
        except Exception:
            traceback.print_exc()
            errors = [f"{name} raised"]
        status = "FAILED" if errors else "passed"
        print(f"phase {name}: {status} in {time.perf_counter() - t0:.1f} s")
        for e in errors:
            print(f"  {e}")
        failed += errors
    if failed:
        return 1
    if args.rehearse:
        print("rehearsal passed")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

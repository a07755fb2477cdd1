"""Run every benchmark (one per paper table/figure) and emit the CSV.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fleet,...]
                                            [--check] [--bench-index N]

``--full`` uses the paper's 16 GiB volumes (slow on one core); the default
2 GiB keeps a full sweep short while preserving every trend.

Output artifacts (both written atomically — temp file + rename — and
*merged* by name, so a partial ``--only`` run never truncates results
from suites it did not run):

* ``experiments/bench_results.csv`` — ``name,us_per_call,derived`` rows.
* ``experiments/BENCH_<n>.json`` — the perf-trajectory artifact
  (per-suite timings, speedup vs the previous ``BENCH_<k>.json`` anchor,
  regression flag at +/-15%; see :mod:`repro.testing.perf`).

``--check`` exits nonzero if any suite run this invocation regressed more
than the threshold against the anchor — the CI perf gate.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Sequence

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import (  # noqa: E402
    bench_adaptive,
    bench_checkpoint,
    bench_device_replay,
    bench_fleet,
    bench_ftl,
    bench_hpio,
    bench_kernels,
    bench_overhead,
    bench_patterns,
    bench_pipeline,
    bench_queue,
    bench_replay,
    bench_service,
    bench_shardmap_decode,
    bench_tileio,
)
from benchmarks.common import BENCH_BYTES, PAPER_BYTES, Row  # noqa: E402
from repro.runtime import use_compile_cache  # noqa: E402
from repro.testing import perf  # noqa: E402

SUITES = {
    "patterns": lambda tb: bench_patterns.run(tb),
    "adaptive": lambda tb: bench_adaptive.run(tb),
    "queue": lambda tb: bench_queue.run(tb),
    "pipeline": lambda tb: bench_pipeline.run(tb),
    "hpio": lambda tb: bench_hpio.run(tb),
    "tileio": lambda tb: bench_tileio.run(tb),
    "overhead": lambda tb: bench_overhead.run(),
    "checkpoint": lambda tb: bench_checkpoint.run(),
    "kernels": lambda tb: bench_kernels.run(),
    "shardmap_decode": lambda tb: bench_shardmap_decode.run(),
    "fleet": lambda tb: bench_fleet.run(tb),
    "ftl": lambda tb: bench_ftl.run(tb),
    "replay": lambda tb: bench_replay.run(tb),
    "device_replay": lambda tb: bench_device_replay.run(tb),
    "service": lambda tb: bench_service.run(tb),
}

CSV_PATH = os.path.join("experiments", "bench_results.csv")


def _write_csv(all_rows: list[Row], path: str = CSV_PATH) -> None:
    existing = None
    if os.path.exists(path):
        with open(path) as f:
            existing = f.read()
    perf.atomic_write_text(path, perf.merge_csv(existing, all_rows))


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale 16 GiB volumes")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if any suite run here regressed "
                         "vs the previous BENCH anchor")
    ap.add_argument("--bench-index", type=int, default=perf.CURRENT_INDEX,
                    help="index of the BENCH_<n>.json artifact to write")
    ap.add_argument("--out-dir", default="experiments",
                    help="artifact directory")
    args = ap.parse_args(argv)

    use_compile_cache()
    tb = PAPER_BYTES if args.full else BENCH_BYTES
    names = list(SUITES) if not args.only else args.only.split(",")
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        ap.error(f"unknown suites {unknown}; choose from {list(SUITES)}")

    all_rows: list[Row] = []
    rows_by_suite: dict[str, dict[str, float]] = {}
    t0 = time.time()
    for name in names:
        print(f"\n######## {name} ########", flush=True)
        t1 = time.time()
        rows = SUITES[name](tb)
        all_rows.extend(rows)
        if rows:
            rows_by_suite[name] = {r.name: r.us_per_call for r in rows}
        else:
            # a suite that skipped itself (missing env) must not enter the
            # trajectory as a 0 us entry — that would read as a regression
            print(f"[{name}] skipped (no rows)", flush=True)
        print(f"[{name}] {time.time()-t1:.1f}s", flush=True)

    print("\n######## CSV (name,us_per_call,derived) ########")
    for r in all_rows:
        print(r.csv())
    _write_csv(all_rows, os.path.join(args.out_dir,
                                      os.path.basename(CSV_PATH)))

    bench_path, payload = perf.emit_trajectory(
        rows_by_suite, directory=args.out_dir, index=args.bench_index)
    print(f"\n######## perf trajectory ({bench_path.name}, "
          f"anchor={payload['anchor']}) ########")
    print(perf.format_trajectory(payload))
    print(f"\n[benchmarks] {len(all_rows)} rows in {time.time()-t0:.1f}s "
          f"-> {args.out_dir}/bench_results.csv, {bench_path}")

    if args.check:
        # gate only on the suites actually run this invocation — carried-
        # over entries from a previous partial run are someone else's news
        gated = {n: payload["suites"][n] for n in rows_by_suite}
        problems = perf.check_trajectory(
            {**payload, "suites": gated})
        if problems:
            print("\n[benchmarks] PERF REGRESSION:", file=sys.stderr)
            for p in problems:
                print(f"  {p}", file=sys.stderr)
            return 1
        print("\n[benchmarks] perf gate: ok")
    return 0


def run_all():  # programmatic entry for tests
    return [r for name in SUITES for r in SUITES[name](BENCH_BYTES)]


if __name__ == "__main__":
    sys.exit(main())

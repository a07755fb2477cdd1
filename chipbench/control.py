"""Readings that the limits of ``chipbench/check.py`` are set from.

    python chipbench/control.py --workload ior_easy_ssdup.new_traces --seeds 1,2,3 --control 3 --faults 3

For each seed, in one process on the chip: the cell's warm-up, then
``check.JOBS`` jobs through the timed path (a new ``FleetProgram`` each,
on the traces of ``(seed, 1..JOBS)``), and the comparison a run makes:
every lane of those jobs, replayed by the plain reference, gives the
program's reading of every compared number (the lower readings) and
``check.verdict``'s answer, which has to be correct.

For the first ``--control`` seeds the control takes the program's place:
the same reference computed in float32 where the configuration states
float64, compared in the same way (the upper readings); its verdict has
to be not correct.  For the first ``--faults`` seeds each fault of
``chipbench/faults.py`` is planted in turn and the same jobs are run
again at the cell's own size; each verdict has to be not correct.

``--config`` reads a configuration's file that ``BENCHMARK.json`` has no
cell of, under the traffic mix ``new_traces``.  The benchmark's own runs
never run this.  Prints one JSON line per seed and a summary as the last
line; exits 1 where a verdict is not the one expected.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import check, faults  # noqa: E402
from chipbench.cell import Cell  # noqa: E402
from chipbench.run import build_program  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload", help="a cell of BENCHMARK.json")
    which.add_argument("--config", help="a configuration's name, for a cell BENCHMARK.json lacks")
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the float32 control")
    ap.add_argument("--faults", type=int, default=0,
                    help="how many of the seeds also read each planted fault")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    import jax

    from repro.core.trace import TraceBatch
    from repro.runtime import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU found", file=sys.stderr)
        return 3
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.workload:
        cell = Cell(args.workload)
    else:
        name = f"{args.config}.new_traces"
        cell = Cell(name, {
            "configs": [{"name": args.config, "file": f"chipbench/configs/{args.config}.json"}],
            "workloads": [{"name": name, "config": args.config, "traffic": "new_traces",
                           "chips": 1}]})
    tol, limits = cell.cfg["guarantees"]["tolerances"], cell.cfg["limits"]
    nodes = int(cell.cfg["nodes"])
    seeds = [int(s) for s in args.seeds.split(",")]

    def batch(seed, job):
        return TraceBatch(**cell.trace(seed, job), gap_positions=np.zeros(0, np.int64),
                          gap_seconds=np.zeros(0, np.float64))

    def run_jobs(seed, jobs):
        return {j: build_program(cell).run(batch(seed, j)) for j in jobs}

    def lanes(results, picks):
        return {(j, n, s): check.program_lane(results[j][s].node_results[n])
                for j, n in picks for s in cell.cfg["schemes"]}

    def judge(got, want):
        values = check.numbers(got, want, tol)
        return values, check.verdict(values, limits)[0]

    build_program(cell).run(batch(seeds[0], 0))  # warm-up
    program, control, faulted = {}, {}, {}
    as_expected = True
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        picks = check.draw(seed, list(range(1, check.JOBS + 1)), nodes)
        results = run_jobs(seed, sorted({j for j, _ in picks}))
        got = lanes(results, picks)
        del results
        want = check.reference_lanes(cell, seed, picks)
        values, correct = judge(got, want)
        program[seed] = values
        as_expected &= correct
        line = {"seed": seed, "lanes": len(want), "program": values, "program_correct": correct}
        if i < args.control:
            values, correct = judge(check.reference_lanes(cell, seed, picks, F=np.float32), want)
            control[seed] = values
            as_expected &= not correct
            line.update(control=values, control_correct=correct)
        if i < args.faults:
            for fault, plant in faults.FAULTS.items():
                patch = faults.Patch()
                plant(patch)
                try:
                    results = run_jobs(seed, sorted({j for j, _ in picks}))
                finally:
                    patch.undo()
                values, correct = judge(lanes(results, picks), want)
                del results
                faulted.setdefault(fault, {})[seed] = values
                as_expected &= not correct
                line[fault] = dict(values, correct=correct)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line))
    names = list(limits)
    summary = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "workload": cell.name,
        "limits": limits,
        "lower": {k: max(p[k] for p in program.values()) for k in names},
        "upper": {k: min(c[k] for c in control.values()) for k in names} if control else {},
        "faults_least": {f: {k: min(v[k] for v in by_seed.values()) for k in names}
                         for f, by_seed in faulted.items()},
        "seeds": len(program), "control_seeds": len(control),
        "as_expected": as_expected,
    }
    print(json.dumps(summary))
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())

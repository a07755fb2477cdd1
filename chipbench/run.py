"""Run one benchmark cell once and print its result as the last line.

    python chipbench/run.py --workload ior_easy_ssdup.new_traces --seed 7 --seconds 30 --trace 0

The window drives ``repro.core.FleetProgram(...).run(trace)`` closed
loop, one client: each job builds a new ``FleetProgram`` from the cell's
configuration and replays a trace it has not seen (sharding, stream
scoring, tape build, the device replay, result assembly).  Traces are
drawn from ``(seed, job)`` by a producer thread one job ahead.  Job 0 is
the warm-up and counts as set-up; the window then runs jobs until
``--seconds`` have passed and waits for the last one.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` wraps
the layer calls in spans, records a profiler trace of the window and
reports the per-layer metrics, with the device's busy time and a
breakdown.  Either way, after the window lanes drawn from the seed are
replayed by the plain reference and compared (``chipbench/check.py``);
each compared number is printed beside its limit on the last lines of
standard error and under ``checks`` in the result line.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with status 3 before any work and prints no result; ``--rehearse``
allows a CPU run, whose numbers are no device measurements.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import queue  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import check, profile  # noqa: E402
from chipbench.cell import HERE, Cell, load_benchmark  # noqa: E402
from chipbench.spans import Spans  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Run:
    """What a finished run hands to the metric readers."""

    def __init__(self, cell: Cell, device_kind: str):
        self.cell = cell
        self.device_kind = device_kind
        self.jobs = 0
        self.work = 0
        self.window_s = 0.0
        self.setup_s = 0.0
        self.spans = Spans()
        self.trace: dict | None = None

    def peak(self, key: str) -> float:
        table = json.loads((HERE / "peaks.json").read_text())["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device {self.device_kind!r} in chipbench/peaks.json")
        return float(table[self.device_kind][key])


def build_program(cell: Cell):
    """A new ``FleetProgram`` with every model parameter pinned from the
    configuration's file."""

    from repro.core import FleetProgram
    from repro.core.device_model import HDDModel, IngestLink, InterferenceModel, SSDModel
    from repro.core.ftl import FTLModel

    cfg, m = cell.cfg, cell.cfg["models"]
    cap = cell.capacity()
    if cfg["ssd"] == "ftl":
        f = m["ftl"]
        ssd = FTLModel(
            logical_bytes=cap, page_size=f["page_size"],
            pages_per_block=f["pages_per_block"], n_channels=f["n_channels"],
            overprovision=f["overprovision"],
            t_prog=f["n_channels"] * f["page_size"] / f["nominal_write_bw"],
            t_erase=f["t_erase"], read_bw=f["read_bw"],
            gc_low_blocks=f["gc_low_blocks"], gc_high_blocks=f["gc_high_blocks"])
    elif cfg["ssd"] == "constant":
        ssd = SSDModel(write_bw=m["ssd"]["write_bw"], read_bw=m["ssd"]["read_bw"])
    else:
        raise ValueError(f"unknown ssd model {cfg['ssd']!r}")
    return FleetProgram(
        num_nodes=cfg["nodes"], schemes=tuple(cfg["schemes"]), policy=cfg["policy"],
        stream_len=cfg["stream_len"], score_backend=cfg["score_backend"],
        ssd_capacity=cap, hdd=HDDModel(**m["hdd"]), ssd=ssd,
        link=IngestLink(**m["link"]), interference=InterferenceModel(**m["interference"]),
        flush_gate=cfg["flush_gate"], adaptive_window=cfg["adaptive_window"])


class Producer:
    """Makes the trace of job j+1 while job j runs."""

    def __init__(self, cell: Cell, seed: int, first: int):
        self.cell, self.seed = cell, seed
        self.q: queue.Queue = queue.Queue(maxsize=1)
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._loop, args=(first,), daemon=True)
        self.thread.start()

    def _loop(self, job: int) -> None:
        from repro.core.trace import TraceBatch

        while not self.stop.is_set():
            t = self.cell.trace(self.seed, job)
            batch = TraceBatch(**t, gap_positions=np.zeros(0, np.int64),
                               gap_seconds=np.zeros(0, np.float64))
            while not self.stop.is_set():
                try:
                    self.q.put((job, batch), timeout=0.1)
                    break
                except queue.Full:
                    pass
            job += 1

    def get(self):
        return self.q.get()

    def close(self) -> None:
        self.stop.set()
        self.thread.join(timeout=30)
        if self.thread.is_alive():
            raise RuntimeError("trace producer did not stop")


def reader(name: str):
    return importlib.import_module(f"chipbench.metrics.{name}").read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a run without a TPU (CPU rehearsal; no device numbers)")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    bench = load_benchmark()
    cell = Cell(args.workload, bench)

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(f"device: {json.dumps(device)}")
    if not args.rehearse and (dev.platform != "tpu" or len(devices) < cell.chips):
        print(f"cell {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 3

    from repro.runtime import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event == BACKEND_COMPILE else None)

    run = Run(cell, dev.device_kind)
    lanes_per_job = len(cell.cfg["schemes"])
    producer = Producer(cell, args.seed, first=0)
    results: dict[int, dict] = {}
    waits: list[float] = []
    failed = 0
    profdir = None
    try:
        # set-up: the warm-up job compiles (or loads) every program the
        # window uses, since every trace of a cell has the same shapes
        job, batch = producer.get()
        build_program(cell).run(batch)
        run.setup_s = time.time() - T_START
        print(f"setup: {run.setup_s:.3f} s (warm-up job included)")

        if args.trace:
            run.spans.wrap_program()
            profdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profdir.name, profiler_options=opts)
        t0 = time.perf_counter()
        deadline = t0 + args.seconds
        last = t0
        attempted = 0
        while time.perf_counter() < deadline:
            w0 = time.perf_counter()
            job, batch = producer.get()
            waits.append(time.perf_counter() - w0)
            attempted += 1
            run.spans.job = job
            try:
                with run.spans.span("job"):
                    res = build_program(cell).run(batch)
            except Exception:
                traceback.print_exc()
                failed += 1
                continue
            last = time.perf_counter()
            results[job] = res
            run.jobs += 1
            run.work += batch.num_requests * lanes_per_job
        run.window_s = last - t0
        in_window = sum(t0 <= c <= last for c in compiles)
        if args.trace:
            jax.profiler.stop_trace()
            run.spans.unwrap()
    finally:
        producer.close()
    print(f"window: {run.jobs} jobs in {run.window_s:.3f} s; trace producer kept "
          f"jobs waiting {sum(waits):.3f} s in all (max {max(waits, default=0):.3f} s)")
    print(f"compilations inside the window: {in_window}")

    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    metric_names = [m["name"] for m in bench["per_layer" if args.trace else "end_to_end"]
                    if cell.name in m.get("workloads", [cell.name])]
    breakdown = None
    if args.trace:
        names = {s.name for s in run.spans.spans}
        ops, modules, host = profile.read_xplane(profdir.name, names)
        profdir.cleanup()
        jobs_host = [(s, e) for n, s, e in host if n == "job"]
        if dev.platform == "tpu" and not (ops and modules and jobs_host):
            raise RuntimeError(
                f"the profiler trace lacks what the readers need: {len(ops)} device ops, "
                f"{len(modules)} device programs, {len(jobs_host)} 'job' spans")
        if (ops or modules) and jobs_host:
            window = (min(s for s, _ in jobs_host), max(e for _, e in jobs_host))
            run.trace = profile.reduce_events(ops, modules, host, window)
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            breakdown = {
                k: [[n, v] for n, v in sorted(run.trace[src].items(), key=lambda kv: -kv[1])[:10]]
                for k, src in (("device_ops", "ops" if run.trace["ops"] else "programs"),
                               ("idle_gaps", "idle"))
            }
            print(f"device programs: {json.dumps(run.trace['programs'])}")
    metrics = {}
    for name in metric_names:
        value = reader(name)(run)
        if value is not None:
            unit = next(m["unit"] for m in bench["end_to_end"] + bench["per_layer"]
                        if m["name"] == name)
            metrics[name] = {"value": value, "unit": unit}
    missing = [n for n in metric_names if n not in metrics]
    if dev.platform == "tpu" and missing:
        raise RuntimeError(f"a chip run read nothing for {missing}")
    del run.spans

    # the comparison runs after the window, with the program's state freed
    gc.collect()
    picks = check.draw(args.seed, sorted(results), int(cell.cfg["nodes"]))
    got = {(j, n, s): check.program_lane(results[j][s].node_results[n])
           for j, n in picks for s in cell.cfg["schemes"]}
    results.clear()
    t_ref = time.perf_counter()
    want = check.reference_lanes(cell, args.seed, picks)
    tolerances = cell.cfg["guarantees"]["tolerances"]
    values = check.numbers(got, want, tolerances)
    correct, table = check.verdict(values, cell.cfg["limits"])
    for line in check.offenders(got, want, tolerances):
        print(f"over tolerance: {line}", file=sys.stderr)
    correct = correct and failed == 0 and bool(picks)
    print(f"reference: {len(want)} lanes of jobs {sorted({j for j, _ in picks})} "
          f"in {time.perf_counter() - t_ref:.3f} s")

    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = table
    for k, v in table.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

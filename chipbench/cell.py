"""A benchmark cell, found by name: its configuration and traffic files.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
configuration's file is ``chipbench/configs/<config>.json`` (the path the
``configs`` entry gives) and the mix's is ``chipbench/traffic/<traffic>.json``.
Nothing here knows a cell by name, so a cell is added by adding files and
entries.
"""

from __future__ import annotations

import json
from pathlib import Path

from . import ior

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


class Cell:
    """One configuration under one traffic mix, as ``bench`` names it."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or load_benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"choose from {sorted(cells)}")
        configs = {c["name"]: c["file"] for c in bench["configs"]}
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        self.cfg = json.loads((ROOT / configs[self.spec["config"]]).read_text())
        self.traffic = json.loads(
            (HERE / "traffic" / f"{self.spec['traffic']}.json").read_text())
        self.requests = int(self.cfg["ranks"]) * int(self.cfg["segments"])
        self.total_bytes = self.requests * int(self.cfg["transfer_bytes"])

    def capacity(self) -> int:
        """Per-node SSD bytes: the configuration's share of the mean
        per-node shard bytes."""

        mean_node = self.total_bytes // int(self.cfg["nodes"])
        return int(mean_node * float(self.cfg["ssd_capacity_fraction"]))

    def trace(self, seed: int, job: int) -> dict:
        """The trace job ``job`` replays, its own draw from ``(seed, job)``."""

        return ior.ior_trace(self.cfg, seed, job)

    def lane_config(self) -> dict:
        return dict(self.cfg, ssd_capacity=self.capacity())

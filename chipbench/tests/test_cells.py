"""Every cell of BENCHMARK.json resolves to its files, and the file keeps
the shape the benchmark's contract asks for."""

import importlib
import json
import re

import pytest

from chipbench.cell import HERE, ROOT, Cell, load_benchmark

from .conftest import config_cell

BENCH = load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = Cell(cell)
    assert c.chips == 1
    assert c.traffic["name"] == c.spec["traffic"]
    assert c.capacity() > 0
    for key in ("models", "guarantees", "limits", "schemes", "nodes"):
        assert key in c.cfg
    assert set(c.cfg["limits"]) == {"exact_bytes_off", "tol_used", "hdd_clock_rel"}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_metric_has_a_reader(metric):
    assert callable(importlib.import_module(f"chipbench.metrics.{metric}").read)


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and len(m["layer"]) <= 200
    for c in BENCH["configs"]:
        path = ROOT / c["file"]
        assert path.is_relative_to(HERE) and json.loads(path.read_text())["name"] == c["name"]
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and (HERE / "traffic" / f"{w['traffic']}.json").exists()
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_cell_kept_out_still_resolves_by_its_files():
    """``ior_hard.new_traces`` and ``ior_easy.new_traces`` are out of
    BENCHMARK.json (PERF.md, Open questions): the harness refuses them by
    name, and their configurations' files still make cells for the tests."""

    for name in ("ior_hard.new_traces", "ior_easy.new_traces"):
        with pytest.raises(KeyError):
            Cell(name)
    c = config_cell("ior_hard")
    assert c.cfg["name"] == "ior_hard" and c.chips == 1
    assert c.requests == 160 * 6553 and c.cfg["ssd"] == "ftl"
    easy, stand_in = config_cell("ior_easy"), config_cell("ior_easy_ssdup")
    assert "orangefs-bb" in easy.cfg["schemes"]
    assert stand_in.cfg["schemes"] == [s for s in easy.cfg["schemes"]
                                       if s not in ("orangefs-bb", "ssdup+")]
    # the stand-in differs from ior_easy in its schemes and its description alone
    same = {"name", "source", "deployment", "schemes", "assumed"}
    assert {k: v for k, v in easy.cfg.items() if k not in same} == \
        {k: v for k, v in stand_in.cfg.items() if k not in same}

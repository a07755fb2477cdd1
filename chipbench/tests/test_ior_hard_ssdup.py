"""``ior_hard_ssdup``: IO500 ior-hard through the SSD path, at the cell's
own per-node geometry on the CPU.

160 ranks x 410 segments over 4 nodes gives each node what a node of the
64-node cell gets (16,400 requests, ~770 MB, 4 region flushes of an FTL
SSD).  Before the replay costed each ``ssdup`` region's flush from its
exact sorted union, and each stream's SSD writes from the FTL's own
request-level garbage collection, these lanes broke their stated
tolerances.  On the lanes this file compares, ``tol_used`` read 5.74 on
seed 1 (``peak_ssd_occupancy`` 373,055,488 B against the reference's
348,987,392; ``total_seconds`` 8.81 s against 8.43) and 4.44 on seed 2;
on seed 1's job 1, ``ssdup blocked_seconds`` read 0.0244 s against 0.0,
24,399 times its tolerance.
"""

import numpy as np
import pytest

from chipbench import check, faults
from chipbench.run import build_program
from repro import spans
from repro.core.trace import TraceBatch

from .conftest import config_cell

RANKS, SEGMENTS, NODES = 160, 410, 4


def node_geometry_cell():
    cell = config_cell("ior_hard_ssdup")
    cell.cfg.update(ranks=RANKS, segments=SEGMENTS, nodes=NODES)
    cell.requests = RANKS * SEGMENTS
    cell.total_bytes = cell.requests * int(cell.cfg["transfer_bytes"])
    return cell


def batch(cell, seed, job):
    return TraceBatch(**cell.trace(seed, job), gap_positions=np.zeros(0, np.int64),
                      gap_seconds=np.zeros(0, np.float64))


def lanes(cell, seed, picks):
    got = {}
    for job in sorted({j for j, _ in picks}):
        res = build_program(cell).run(batch(cell, seed, job))
        for j, n in picks:
            if j == job:
                for s in cell.cfg["schemes"]:
                    got[(j, n, s)] = check.program_lane(res[s].node_results[n])
    return got


@pytest.fixture(scope="module")
def compared():
    """Program and reference lanes of the jobs a run of seeds 1 and 2
    would draw, every node under both schemes."""

    cell = node_geometry_cell()
    out = {}
    for seed in (1, 2):
        picks = check.draw(seed, [1, 2, 3], NODES)
        out[seed] = (lanes(cell, seed, picks), check.reference_lanes(cell, seed, picks))
    return cell, out


def test_config_differs_from_ior_hard_in_schemes_and_description_alone():
    """Besides the schemes and the description, only ``tol_used``'s limit
    differs, and only downward: this cell's lanes meet the stated
    tolerances to rounding, so its limit sits between the program's
    reading and the float32 control's (PERF.md)."""

    hard, cell = config_cell("ior_hard"), config_cell("ior_hard_ssdup")
    assert cell.cfg["schemes"] == ["orangefs", "ssdup"]
    same = {"name", "source", "deployment", "schemes", "assumed", "limits", "limits_note"}
    assert {k: v for k, v in hard.cfg.items() if k not in same} == \
        {k: v for k, v in cell.cfg.items() if k not in same}
    assert cell.cfg["limits"] == {**hard.cfg["limits"], "tol_used": 1e-6}
    assert cell.cfg["ssd"] == "ftl" and cell.cfg["reduced"] == ["segments"]
    assert cell.capacity() == 385_054_280


@pytest.mark.parametrize("seed", [1, 2])
def test_node_geometry_is_correct(compared, seed):
    cell, out = compared
    got, want = out[seed]
    tol = cell.cfg["guarantees"]["tolerances"]
    values = check.numbers(got, want, tol)
    correct, table = check.verdict(values, cell.cfg["limits"])
    assert correct, (table, check.offenders(got, want, tol))
    assert values["exact_bytes_off"] == 0 and values["hdd_clock_rel"] <= 1e-12
    assert values["tol_used"] <= 1e-6
    # the SSD path carries the traffic: 98-99% of every node's bytes
    for (_, _, scheme), lane in got.items():
        if scheme == "ssdup":
            assert lane["bytes_to_ssd"] > 0.98 * lane["total_bytes"]
            assert lane["flushes"] == 4


@pytest.mark.parametrize("seed", [1, 2])
def test_float32_control_is_not_correct(compared, seed):
    cell, out = compared
    _, want = out[seed]
    picks = sorted({(j, n) for j, n, _ in want})
    low = check.reference_lanes(cell, seed, picks, F=np.float32)
    values = check.numbers(low, want, cell.cfg["guarantees"]["tolerances"])
    assert not check.verdict(values, cell.cfg["limits"])[0], values


@pytest.mark.parametrize("fault", list(faults.FAULTS))
def test_planted_fault_is_not_correct(compared, fault, monkeypatch):
    cell, out = compared
    _, want = out[1]
    faults.FAULTS[fault](monkeypatch.setattr)
    got = lanes(cell, 1, sorted({(j, n) for j, n, _ in want}))
    values = check.numbers(got, want, cell.cfg["guarantees"]["tolerances"])
    assert not check.verdict(values, cell.cfg["limits"])[0], values


def test_counts_at_node_geometry():
    """Every contiguous pair of the traffic is within the tape's merge
    depth, and the fill loop runs."""

    cell = node_geometry_cell()
    with spans.recording() as rec:
        build_program(cell).run(batch(cell, 1, 1))
    assert spans.total(rec.counts, "tape.xmerge_beyond") == 0
    assert spans.total(rec.counts, "tape.xmerge_pairs") > 0
    assert spans.total(rec.counts, "replay.fill_trips") > 0
    assert {c.span for c in rec.counts} == {"tape.build", "replay"}

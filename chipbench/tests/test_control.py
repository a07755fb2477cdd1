"""The control: the reference computed in float32 where the configuration
states float64, put in the program's place, must come out not correct.
On the chip it is read at the cells' own sizes by ``chipbench/control.py``;
here at the small test size, on the lanes a run would draw."""

import numpy as np
import pytest

from chipbench import check, faults

from .conftest import config_cell, shrink


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
@pytest.mark.parametrize("name", ["ior_hard", "ior_easy", "ior_easy_ssdup"])
def test_float32_control_fails(name, seed):
    cell = shrink(config_cell(name))
    picks = check.draw(seed, [1, 2, 3], cell.cfg["nodes"])
    want = check.reference_lanes(cell, seed, picks)
    low = check.reference_lanes(cell, seed, picks, F=np.float32)
    values = check.numbers(low, want, cell.cfg["guarantees"]["tolerances"])
    correct, table = check.verdict(values, cell.cfg["limits"])
    assert not correct, table
    assert values["hdd_clock_rel"] > cell.cfg["limits"]["hdd_clock_rel"]
    # and the reference against itself is exact
    same = check.numbers(want, want, cell.cfg["guarantees"]["tolerances"])
    assert same == {"exact_bytes_off": 0.0, "tol_used": 0.0, "hdd_clock_rel": 0.0}


def test_control_script_reads_every_verdict(small_cells, monkeypatch, capsys):
    """``chipbench/control.py`` runs the program, the control and each
    planted fault through ``check.verdict``: only the program is correct."""

    import json

    from chipbench import control

    monkeypatch.setattr(control, "Cell", small_cells)
    rc = control.main(["--workload", "ior_easy_ssdup.new_traces", "--seeds", f"{2**31 + 3},8",
                       "--control", "1", "--faults", "1", "--rehearse"])
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0, lines
    first, summary = lines[0], lines[-1]
    assert first["program_correct"] is True and first["control_correct"] is False
    assert all(first[f]["correct"] is False for f in faults.FAULTS)
    assert summary["as_expected"] is True and summary["seeds"] == 2


def test_offenders_name_the_lane_over_tolerance():
    """A run that is not correct names the lane fields over tolerance:
    one stream of 2 MiB transfers moved from the HDD to the SSD."""

    cell = shrink(config_cell("ior_easy_ssdup"))
    picks = check.draw(5, [1, 2], cell.cfg["nodes"])
    want = check.reference_lanes(cell, 5, picks)
    tol = cell.cfg["guarantees"]["tolerances"]
    key = sorted(want)[0]
    got = {k: dict(v) for k, v in want.items()}
    moved = 128 * cell.cfg["transfer_bytes"]
    got[key].update(bytes_to_ssd=got[key]["bytes_to_ssd"] + moved,
                    bytes_to_hdd_direct=got[key]["bytes_to_hdd_direct"] - moved)
    assert check.numbers(got, want, tol)["tol_used"] == 64.0
    lines = check.offenders(got, want, tol)
    assert lines[0] == "2 lane fields over tolerance"
    assert all(f"{key[0]}/{key[1]}/{key[2]}" in x for x in lines[1:]) and len(lines) == 3
    assert check.offenders(want, want, tol) == []

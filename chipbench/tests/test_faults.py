"""A whole run on the CPU (``--rehearse``, small cells), with the timed
path broken underneath (``chipbench/faults.py``): ``correct`` must come
out false for each fault the cells can have, and true with none."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import faults
from chipbench import run as run_mod
from chipbench.cell import HERE, ROOT, load_benchmark


FAULTS = {"none": None, **faults.FAULTS}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", [w["name"] for w in load_benchmark()["workloads"]])
def test_fault_makes_run_incorrect(cell, fault, small_cells, monkeypatch, capsys):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch.setattr)
    rc = run_mod.main(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "0.5",
                       "--trace", "0", "--rehearse"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is (fault == "none"), out["checks"]
    assert list(out)[-1] == "checks" and out["attempted"] >= 1


def test_traced_rehearsal_reports_spans(small_cells, capsys):
    rc = run_mod.main(["--workload", "ior_easy_ssdup.new_traces", "--seed", "17", "--seconds", "0.5",
                       "--trace", "1", "--rehearse"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out
    # the CPU trace has no device plane: only the host spans are read
    assert {"fleet_self_ms", "shard_ms", "score_ms", "tape_ms", "replay_call_ms"} <= set(out["metrics"])


def test_refuses_without_a_tpu(capsys):
    rc = run_mod.main(["--workload", "ior_easy_ssdup.new_traces", "--seed", "1", "--seconds", "1"])
    assert rc == 3
    assert not capsys.readouterr().out.strip().splitlines()[-1].startswith("{")


def test_no_result_without_the_program(tmp_path):
    """A checkout of only BENCHMARK.json and chipbench/ has no program to run."""

    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "ior_easy_ssdup.new_traces",
                        "--seed", "1", "--seconds", "1", "--rehearse"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())

"""Small cells for the CPU tests: the configurations as committed, with
fewer ranks, segments and nodes, so a run fits a test."""

import pytest

from chipbench import cell as cell_mod
from chipbench import run as run_mod

RANKS, SEGMENTS, NODES = 16, 512, 4


def config_cell(config: str, traffic: str = "new_traces"):
    """A cell built straight from a configuration's file, whether or not
    ``BENCHMARK.json`` lists a cell of it."""

    name = f"{config}.{traffic}"
    bench = {"configs": [{"name": config, "file": f"chipbench/configs/{config}.json"}],
             "workloads": [{"name": name, "config": config, "traffic": traffic, "chips": 1}]}
    return cell_mod.Cell(name, bench)


def shrink(cell):
    cell.cfg.update(ranks=RANKS, segments=SEGMENTS, nodes=NODES)
    cell.requests = RANKS * SEGMENTS
    cell.total_bytes = cell.requests * int(cell.cfg["transfer_bytes"])
    return cell


@pytest.fixture
def small_cells(monkeypatch):
    """``chipbench.run`` builds shrunk cells, and keeps the persistent
    compile cache off."""

    import jax

    from repro import runtime

    class Small(cell_mod.Cell):
        def __init__(self, name, bench=None):
            super().__init__(name, bench)
            shrink(self)

    monkeypatch.setattr(run_mod, "Cell", Small)
    monkeypatch.setattr(runtime, "use_compile_cache", lambda: "off (test)")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield Small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)

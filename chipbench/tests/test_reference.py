"""The plain reference replays a lane bit for bit like the repository's
numpy oracle (``FleetSimulator(engine="batched")``), for every scheme, on
both storage models, at full and at an eighth of the cells' SSD share."""

import numpy as np
import pytest

from chipbench import reference
from repro.core import FleetSimulator
from repro.core.ftl import FTLModel
from repro.core.trace import TraceBatch
from repro.testing.golden import sim_result_to_dict

from .conftest import config_cell, shrink


def _oracle(cfg, trace, scheme):
    m = cfg["models"]
    from repro.core.device_model import HDDModel, IngestLink, InterferenceModel, SSDModel

    f = m["ftl"]
    ssd = (FTLModel(logical_bytes=cfg["ssd_capacity"], page_size=f["page_size"],
                    pages_per_block=f["pages_per_block"], n_channels=f["n_channels"],
                    overprovision=f["overprovision"],
                    t_prog=f["n_channels"] * f["page_size"] / f["nominal_write_bw"],
                    t_erase=f["t_erase"], read_bw=f["read_bw"],
                    gc_low_blocks=f["gc_low_blocks"], gc_high_blocks=f["gc_high_blocks"])
           if cfg["ssd"] == "ftl" else SSDModel(**m["ssd"]))
    batch = TraceBatch(**trace, gap_positions=np.zeros(0, np.int64),
                       gap_seconds=np.zeros(0))
    return FleetSimulator(
        num_nodes=cfg["nodes"], scheme=scheme, policy="range-offset",
        ssd_capacity=cfg["ssd_capacity"], hdd=HDDModel(**m["hdd"]), ssd=ssd,
        link=IngestLink(**m["link"]), interference=InterferenceModel(**m["interference"]),
        flush_gate=cfg["flush_gate"], adaptive_window=cfg["adaptive_window"],
        engine="batched").run(batch)


@pytest.mark.parametrize("scale", [1.0, 0.125])
@pytest.mark.parametrize("ssd", ["constant", "ftl"])
@pytest.mark.parametrize("name", ["ior_hard", "ior_easy", "ior_easy_ssdup"])
def test_reference_equals_oracle(name, ssd, scale):
    cell = shrink(config_cell(name))
    cfg = dict(cell.lane_config(), ssd=ssd)
    cfg["ssd_capacity"] = int(cfg["ssd_capacity"] * scale)
    trace = cell.trace(99, 1)
    for scheme in reference.SCHEMES:
        fleet = _oracle(cfg, trace, scheme)
        for node in range(cfg["nodes"]):
            want = sim_result_to_dict(fleet.node_results[node])
            want.pop("metadata_bytes")
            got = reference.replay_lane(scheme, reference.node_shard(trace, node, cfg["nodes"]), cfg)
            assert got == want, (scheme, node)

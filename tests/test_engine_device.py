"""Device-engine parity: ``engine="device"`` vs the numpy oracle matrix.

The device engine (:mod:`repro.core.engine_device`) is stream-granular
where the numpy engines are request-granular, so it carries a documented
per-field accuracy contract instead of bit-exactness.  Every golden
fixture embeds the tolerance table it was verified against
(``device_tolerance``, written by ``repro.testing.golden --write``);
these tests replay the FULL committed matrix — 4 schemes x 2 workloads
x 2 policies x 4 nodes — plus the ``anomaly_16n_straggler`` shard under
``engine="device"`` and assert against the *embedded* contract, so a
tolerance loosening must show up as a reviewable fixture diff, never as
a silent test-side constant bump.

``FleetProgram`` (one jitted sweep over the whole scheme x node lane
matrix) must agree with the per-node ``engine="device"`` dispatch it
batches, and with the stored snapshots under the same tolerances.

Requires jax — the device engine has no host fallback by design (the
numpy engines ARE the fallback, behind the same ``engine=`` switch).
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import FleetProgram, IONodeSimulator, TraceBatch, compute_stream_scores
from repro.core.engine_device import DEVICE_TOLERANCES
from repro.core.random_factor import Request
from repro.testing import golden
from repro.testing.golden import (
    GOLDEN_DIR,
    check_fixture,
    diff_sim,
    fleet_result_to_dict,
    load_fixture,
    replay_fixture,
    sim_result_to_dict,
)
from repro.testing.traces import golden_trace

FIXTURE_FILES = sorted(GOLDEN_DIR.glob("*__*.json"))


@pytest.fixture(scope="module")
def payloads():
    return {p.name: load_fixture(p) for p in FIXTURE_FILES}


def test_every_fixture_embeds_the_tolerance_contract(payloads):
    """Fixtures must carry the table the device replay is judged by."""

    for name, payload in payloads.items():
        tol = payload.get("device_tolerance")
        assert tol, f"{name}: missing device_tolerance metadata"
        assert set(tol) == set(DEVICE_TOLERANCES), name
        for field, (rtol, atol) in DEVICE_TOLERANCES.items():
            assert tuple(tol[field]) == (rtol, atol), (
                f"{name}: embedded tolerance for {field} drifted from "
                "DEVICE_TOLERANCES — regenerate fixtures with --write")


@pytest.mark.parametrize("path", FIXTURE_FILES, ids=lambda p: p.stem)
def test_device_replay_matches_fixture(path, payloads):
    """The whole committed matrix, replayed on device, within contract."""

    payload = payloads[path.name]
    fr = replay_fixture(payload, engine="device")
    diffs = check_fixture(payload, fr,
                          tolerances=payload["device_tolerance"])
    assert diffs == [], f"{path.name} (device):\n" + "\n".join(diffs)


@pytest.mark.parametrize("path", FIXTURE_FILES, ids=lambda p: p.stem)
def test_device_routing_fields_are_exact(path, payloads):
    """Routing and byte accounting for the non-BB schemes is documented
    as timing-independent and EXACT (approximation #5); holding the
    device engine to that stronger claim catches regressions the
    tolerance tiers would mask."""

    payload = payloads[path.name]
    if payload["key"]["scheme"] == "orangefs-bb":
        pytest.skip("plain-BB byte splits are timing-coupled by contract")
    fr = replay_fixture(payload, engine="device")
    actual = fleet_result_to_dict(fr)
    for i, (e, a) in enumerate(zip(payload["result"]["nodes"],
                                   actual["nodes"])):
        for field in ("total_bytes", "bytes_to_ssd", "bytes_to_hdd_direct",
                      "flushes", "peak_ssd_occupancy"):
            assert e[field] == a[field], (
                f"node[{i}].{field}: expected {e[field]}, got {a[field]}")


# -- anomaly fixture ---------------------------------------------------

ANOMALY = GOLDEN_DIR / "anomaly_16n_straggler.json"


@pytest.fixture(scope="module")
def anomaly_payload():
    with open(ANOMALY) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def anomaly_shard(anomaly_payload):
    t = anomaly_payload["trace"]
    return TraceBatch.from_requests([
        Request(offset=o, size=s, file_id=f, app_id=a)
        for o, s, f, a in zip(t["offsets"], t["sizes"],
                              t["file_ids"], t["app_ids"])
    ])


@pytest.mark.parametrize("key,scheme,kwargs", [
    ("orangefs", "orangefs", {}),
    ("ssdup+_gate0.5", "ssdup+", {}),
    ("ssdup+_gate0.75", "ssdup+", {"flush_gate": 0.75}),
])
def test_device_replays_anomaly_fixture(anomaly_payload, anomaly_shard,
                                        key, scheme, kwargs):
    """The straggler shard — the repo's root-caused 8-16 node shortfall —
    must reproduce on device, including the flush-gate sensitivity."""

    node = IONodeSimulator(scheme=scheme, engine="device",
                           ssd_capacity=anomaly_payload["ssd_capacity"],
                           **kwargs)
    scores = (compute_stream_scores(anomaly_shard)
              if scheme != "orangefs" else None)
    result = node.run(anomaly_shard, scores=scores)
    expected = anomaly_payload["expected"][key]["result"]
    diffs = diff_sim(expected, sim_result_to_dict(result),
                     tolerances=anomaly_payload["device_tolerance"])
    assert diffs == [], f"{key} (device):\n" + "\n".join(diffs)


def test_device_reproduces_gate_shortfall(anomaly_payload, anomaly_shard):
    """The device clocks must preserve the anomaly's ORDERING, not just
    its field values: ssdup+ at gate 0.5 loses to plain OrangeFS, and
    raising the gate to 0.75 recovers it."""

    def run(scheme, **kw):
        node = IONodeSimulator(scheme=scheme, engine="device",
                               ssd_capacity=anomaly_payload["ssd_capacity"],
                               **kw)
        scores = (compute_stream_scores(anomaly_shard)
                  if scheme != "orangefs" else None)
        return node.run(anomaly_shard, scores=scores)

    base = run("orangefs")
    plus = run("ssdup+")
    fixed = run("ssdup+", flush_gate=0.75)
    assert plus.io_seconds > base.io_seconds * 1.5
    assert fixed.io_seconds < base.io_seconds


# -- FleetProgram ------------------------------------------------------


def test_fleet_program_matches_fixture_matrix(payloads):
    """One jitted sweep (4 schemes x 4 nodes = 16 lanes) must land every
    scheme's FleetResult inside the same embedded contract the per-node
    device replays satisfy."""

    workload, policy = "mixed-burst", "range-offset"
    batch = golden_trace(workload)
    cap = golden._node_capacity(batch.total_bytes)
    prog = FleetProgram(num_nodes=golden.FIXTURE_NODES,
                        schemes=golden.FIXTURE_SCHEMES,
                        policy=policy, ssd_capacity=cap)
    results = prog.run(batch)
    assert set(results) == set(golden.FIXTURE_SCHEMES)
    for scheme, fr in results.items():
        payload = payloads[golden.fixture_name(scheme, workload, policy)]
        diffs = check_fixture(payload, fr,
                              tolerances=payload["device_tolerance"])
        assert diffs == [], f"FleetProgram {scheme}:\n" + "\n".join(diffs)


@pytest.fixture(scope="module")
def swept():
    """One fused sweep of every scheme over the golden trace's shards,
    per storage backend."""

    batch = golden_trace("strided-gaps")
    cap = golden._node_capacity(batch.total_bytes)
    return batch, cap, {
        ssd: FleetProgram(num_nodes=golden.FIXTURE_NODES, schemes=golden.FIXTURE_SCHEMES,
                          policy="round-robin-app", ssd_capacity=cap,
                          ssd=ssd).run(batch)
        for ssd in ("constant", "ftl")
    }


@pytest.mark.parametrize("ssd", ["constant", "ftl"])
@pytest.mark.parametrize("scheme", golden.FIXTURE_SCHEMES)
def test_fleet_program_equals_per_lane_device_dispatch(swept, scheme, ssd):
    """Batching lanes must not change a single number: the fused sweep
    and N independent ``engine="device"`` runs share one code path, so
    they agree to f64 bit-level on every field."""

    from repro.core import FleetSimulator

    batch, cap, sweeps = swept
    loop = FleetSimulator(num_nodes=golden.FIXTURE_NODES, scheme=scheme,
                          policy="round-robin-app", ssd_capacity=cap,
                          ssd=ssd, engine="device").run(batch)
    a = fleet_result_to_dict(sweeps[ssd][scheme])
    b = fleet_result_to_dict(loop)
    assert a == b, f"{scheme}/{ssd}: fused sweep != per-lane device replay"


def test_plain_bb_cross_stream_merge_routing():
    """Tiled workloads (IOR strided) interleave streams into contiguous
    extents, so a flushed region's sorted union has far fewer seeks than
    the per-stream sum — without the tape's cross-merge correction the
    device underestimates the flush rate ~2x and plain-BB overflow
    routing diverges by whole streams.  Routing must match the oracle
    exactly here, and the clocks must stay inside the contract."""

    from repro.core import ior

    w = ior("strided", 64, total_bytes=1 << 28)
    batch = TraceBatch.from_items(w.trace)
    cap = batch.total_bytes // 2
    oracle = IONodeSimulator(scheme="orangefs-bb", ssd_capacity=cap,
                             engine="batched").run(batch)
    dev = IONodeSimulator(scheme="orangefs-bb", ssd_capacity=cap,
                          engine="device").run(batch)
    assert dev.bytes_to_ssd == oracle.bytes_to_ssd
    assert dev.bytes_to_hdd_direct == oracle.bytes_to_hdd_direct
    assert dev.flushes == oracle.flushes
    assert dev.peak_ssd_occupancy == oracle.peak_ssd_occupancy
    rtol, _ = DEVICE_TOLERANCES["io_seconds"]
    assert abs(dev.io_seconds - oracle.io_seconds) <= rtol * oracle.io_seconds


# -- tolerance-tier mechanics ------------------------------------------


def test_tolerance_tiers_gate_comparison(payloads):
    """The tiered differ: within-tier drift passes, beyond-tier fails,
    and a (0, 0) tier stays bit-exact."""

    import copy

    payload = next(iter(payloads.values()))
    tol = payload["device_tolerance"]
    base = payload["result"]

    drifted = copy.deepcopy(base)
    drifted["nodes"][0]["io_seconds"] *= 1.02  # inside the 5% tier
    assert golden.diff_fleet(base, drifted, tolerances=tol) == []

    broken = copy.deepcopy(base)
    broken["nodes"][0]["io_seconds"] *= 1.2    # far outside
    assert golden.diff_fleet(base, broken, tolerances=tol)

    exact = copy.deepcopy(base)
    exact["nodes"][0]["total_bytes"] += 1      # (0, 0) tier: any drift trips
    assert golden.diff_fleet(base, exact, tolerances=tol)

    # without tolerances the drifted copy is still a divergence
    assert golden.diff_fleet(base, drifted)

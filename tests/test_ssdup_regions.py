"""What the tape knows of an ``ssdup`` lane's SSD path before the replay
(``engine_device._ssdup_regions``): the regions and their flush costs,
each stream's fills and their walls, and the replay that reads them,
against the request-level numpy oracle."""

import numpy as np
import pytest

from repro import spans
from repro.core import FleetProgram, IONodeSimulator, TraceBatch, compute_stream_scores, ior
from repro.core import engine_device as ed
from repro.core.device_model import IngestLink, SSDModel
from repro.core.ftl import FTLModel
from repro.core.workloads import MiB
from repro.testing.traces import golden_trace

LINK = IngestLink()


def make_batch(offsets, sizes, files):
    n = len(offsets)
    return TraceBatch(
        offsets=np.asarray(offsets, dtype=np.int64),
        sizes=np.asarray(sizes, dtype=np.int64),
        file_ids=np.asarray(files, dtype=np.int64),
        app_ids=np.zeros(n, dtype=np.int64), times=np.zeros(n),
        gap_positions=np.zeros(0, dtype=np.int64),
        gap_seconds=np.zeros(0, dtype=np.float64),
    )


def strided(nproc, mib, size=47008, seed=3):
    w = ior("strided", nproc, total_bytes=mib * MiB, request_size=size, seed=seed)
    return TraceBatch.from_items(w.trace)


def regions(batch, cap, ssd=None, static_rand=False, stream_len=128):
    bounds = batch.stream_bounds(stream_len)
    pct = np.asarray(compute_stream_scores(batch, stream_len).percentage)
    order = np.lexsort((batch.offsets, batch.file_ids))
    return ed._ssdup_regions(batch, bounds, pct, order, ed._sort_neighbours(batch, order),
                             cap, static_rand, ssd or SSDModel(), LINK)


@pytest.mark.parametrize("static_rand", [False, True])
def test_route_follows_the_watermarks(static_rand):
    """Hysteresis between 30% and 45%; a stream rides the previous
    stream's decision; equality keeps the current device."""

    pct = np.array([0.5, 0.4, 0.3, 0.29, 0.45, 0.46, 0.3, 0.2, 0.31, 0.45])
    got = ed._ssdup_route(pct, static_rand)
    sr, cur, want = static_rand, False, []
    for p in pct:
        want.append(cur)
        sr = True if p > 0.45 else False if p < 0.30 else sr
        thr = 0.30 if sr else 0.45
        cur = True if p > thr else False if p < thr else cur
    assert got.tolist() == want


def brute_force_table(batch, route, cap):
    """Greedy whole-request regions and each one's flush cost, one
    request at a time: live bytes of the latest copy of each offset, one
    seek per extent start of the (file, offset) order."""

    rows, recs, tail = [], {}, 0

    def close():
        live = seeks = 0
        prev = None
        for (f, o) in sorted(recs):
            s = recs[(f, o)]
            live += s
            seeks += prev is None or prev[0] != f or prev[1] != o
            prev = (f, o + s)
        rows.append((tail, live, seeks))

    for i in np.flatnonzero(route):
        f, o, s = (int(batch.file_ids[i]), int(batch.offsets[i]), int(batch.sizes[i]))
        if tail + s > cap:
            close()
            recs, tail = {}, 0
        recs[(f, o)] = s
        tail += s
    if tail or recs:
        close()
    return np.array(rows, dtype=np.float64)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_region_table_is_each_region_flush_cost(seed):
    """Overwritten offsets, zero sizes and several files, as the
    request-level replay's ``flush_cost`` counts them."""

    rng = np.random.default_rng(seed)
    n = 3000
    batch = make_batch(rng.integers(0, 400, n) * 4096,
                       rng.choice([0, 4096, 8192, 4096 * 3], n),
                       rng.integers(0, 3, n))
    cap = 600 * 1024
    rg, _ = regions(batch, cap, static_rand=True)
    bounds = batch.stream_bounds(128)
    pct = np.asarray(compute_stream_scores(batch, 128).percentage)
    route = np.repeat(ed._ssdup_route(pct, True), np.diff(bounds))
    want = brute_force_table(batch, route, cap)
    cols = [ed.RG_COLUMNS.index(c) for c in ("appended", "live", "seeks")]
    assert len(rg) > 3
    np.testing.assert_array_equal(rg[:, cols], want)


def test_request_larger_than_a_region_leaves_no_rows():
    batch = strided(8, 8)
    rg, sr = regions(batch, 40000)
    assert rg.shape == (0, len(ed.RG_COLUMNS))
    assert (sr[:, ed.SR_COLUMNS.index("k")] == -1).all()


def ftl_times(ftl, batch, route, cap, trim_at):
    """Per-request FTL service times of the SSD-routed requests, written
    region after region into alternating halves; region ``r`` is trimmed
    when ``trim_at(r)`` of the next region's requests are written."""

    idx = np.flatnonzero(route)
    out, tail, region, pending = [], 0, 0, []
    written_in_region = 0
    for i in idx:
        s = int(batch.sizes[i])
        if tail + s > cap:
            pending.append((region, tail))
            region, tail, written_in_region = region + 1, 0, 0
        for r, t in list(pending):
            if r == region - 2 or (r == region - 1 and written_in_region >= trim_at(r)):
                ftl.trim((r % 2) * cap, t)
                pending.remove((r, t))
        out.append(ftl.charge_write(np.array([(region % 2) * cap + tail]), np.array([s]))[0])
        tail += s
        written_in_region += 1
    return np.array(out), idx


@pytest.mark.parametrize("frac", [0.5, 0.3, 0.05])
@pytest.mark.parametrize("trim_after", [0, 700])
def test_ftl_walls_are_the_ftl_model_times(frac, trim_after):
    """Exact where known, whenever the previous region's flush ends; all
    known where the FTL has room."""

    batch = strided(64, 256)
    cap = int(batch.total_bytes * frac)
    half = cap // 2
    bounds = batch.stream_bounds(128)
    pct = np.asarray(compute_stream_scores(batch, 128).percentage)
    route = np.repeat(ed._ssdup_route(pct, False), np.diff(bounds))
    times, idx = ftl_times(FTLModel(logical_bytes=cap), batch, route, half,
                           lambda r: trim_after)
    sz = batch.sizes[idx]
    net = sz / LINK.bw
    order = np.lexsort((batch.offsets, batch.file_ids))
    counts = ed._ssdup_regions(batch, bounds, pct, order, ed._sort_neighbours(batch, order),
                               half, False, SSDModel(), LINK)[0][:, 0].astype(np.int64) // 47008
    reg = np.repeat(np.arange(len(counts)), counts)
    ahead = np.concatenate([np.arange(c) * 47008 for c in counts])
    wall, known = ed._ftl_walls(FTLModel(logical_bytes=cap), (reg % 2) * half + ahead,
                                sz, np.r_[0, np.cumsum(counts)], net)
    assert known == len(sz) if frac >= 0.3 else 0 < known < len(sz)
    assert (times[:known] > net[:known]).any()  # collections that show
    np.testing.assert_array_equal(wall[:known], np.maximum(net, times)[:known])


def test_ftl_walls_stop_where_victims_may_hold_data():
    """An FTL too small to find an empty victim block at every collection
    once two regions are written: streams from the first such collection
    on are not known, those before are."""

    batch = strided(64, 256)
    cap = int(batch.total_bytes * 0.05)
    _, sr = regions(batch, cap // 2, ssd=FTLModel(logical_bytes=cap))
    k = sr[:, ed.SR_COLUMNS.index("k")][sr[:, ed.SR_COLUMNS.index("p")] > 0]
    first = np.flatnonzero(k == -1)[0]
    assert first > 2 and (k[:first] >= 0).all() and (k[first:] == -1).all()


def oracle_and_device(batch, scheme, cap, ssd):
    mk = (lambda: FTLModel(logical_bytes=cap)) if ssd == "ftl" else (lambda: SSDModel())
    o = IONodeSimulator(scheme=scheme, ssd_capacity=cap, engine="batched",
                        ssd=mk()).run(batch)
    d = IONodeSimulator(scheme=scheme, ssd_capacity=cap, engine="device",
                        ssd=mk()).run(batch, scores=compute_stream_scores(batch))
    return o, d


CASES = [
    ("strided64", 0.5, "ftl"), ("strided64", 0.3, "ftl"), ("mixed-burst", 0.3, "ftl"),
    ("strided64", 0.05, "constant"), ("mixed-burst", 0.1, "constant"),
    ("mixed-burst", 0.02, "constant"), ("strided-gaps", 0.02, "constant"),
]


@pytest.mark.parametrize("trace,frac,ssd", CASES)
def test_ssdup_lane_matches_oracle(trace, frac, ssd):
    """Blocking writers, streams spread over several regions, and the
    FTL's collections: every byte and count exactly, every clock to a
    millionth (the request-level replay truncates a flush's progress to
    whole bytes per HDD-routed stream, the replay does not)."""

    batch = strided(64, 256) if trace == "strided64" else golden_trace(trace)
    o, d = oracle_and_device(batch, "ssdup", int(batch.total_bytes * frac), ssd)
    for f in ("bytes_to_ssd", "bytes_to_hdd_direct", "flushes", "peak_ssd_occupancy"):
        assert getattr(d, f) == getattr(o, f), f
    for f in ("blocked_seconds", "io_seconds", "total_seconds"):
        assert getattr(d, f) == pytest.approx(getattr(o, f), rel=1e-6, abs=1e-9), f
    assert o.bytes_to_ssd > 0 and (o.blocked_seconds > 0 or frac >= 0.5)


def test_table_goes_to_ssdup_lanes_alone():
    batch = strided(64, 64)
    tape = ed.build_events(batch, compute_stream_scores(batch), region_cap=8 * MiB)
    assert len(tape["rg"]) > 1
    for scheme in ed.SCHEME_IDS:
        lane = ed.lane_consts(scheme, 16 * MiB, regions=tape["rg"], slots=8)
        listed = lane["rg"][:, ed.RG_COLUMNS.index("appended")] >= 0
        assert listed.any() == (scheme == "ssdup"), scheme
    plain = ed.build_events(batch, compute_stream_scores(batch))
    assert len(plain["rg"]) == 0 and plain["sr"].shape == (len(plain["valid"]), 0)


def replay_one(batch, cap, tape):
    ssd = FTLModel(logical_bytes=cap)
    lanes = ed._stack_lanes([ed.lane_consts("ssdup", cap, ssd=ssd, regions=tape["rg"],
                                            slots=ed._pad_len(len(tape["rg"])))])
    state0 = ed._stack_lanes([ed.initial_lane_state("ssdup", 64, ssd=ssd)])
    return ed.replay_lanes(ed.stack_events([tape]), lanes, state0)


@pytest.mark.parametrize("flip", ["first stream", "first SSD stream"])
def test_a_route_the_table_did_not_take_drops_the_table(flip):
    """Where a stream goes elsewhere than the table's route sends it, the
    lane keeps to the anchor estimates from there on, however alike the
    regions' byte counts look: with the route of the first stream (the
    lane writes the HDD, the table says SSD) or of the first SSD stream
    (the other way round) flipped, the replay is the one without a table,
    bit for bit."""

    batch = strided(64, 256)
    cap = int(batch.total_bytes * 0.3)
    ssd, scores = FTLModel(logical_bytes=cap), compute_stream_scores(batch)
    tape = ed.build_events(batch, scores, ssd=ssd, region_cap=cap // 2)
    plain = ed.build_events(batch, scores, ssd=ssd)
    col = ed.SR_COLUMNS.index("ssd")
    j = 0 if flip == "first stream" else int(np.flatnonzero(tape["sr"][:, col])[0])
    assert len(tape["rg"]) > 2 and tape["sr"][0, col] == 0
    tampered = {**tape, "sr": tape["sr"].copy()}
    tampered["sr"][j, col] = 1 - tampered["sr"][j, col]
    with_table, without, got = (replay_one(batch, cap, t) for t in (tape, plain, tampered))
    assert with_table["io_seconds"][0] != without["io_seconds"][0]
    for k in without:
        np.testing.assert_array_equal(got[k], without[k], err_msg=k)


def spaced(streams, moved, onto):
    """Requests 4096 B long with 4096 B holes, none contiguous, but the
    first request of stream ``moved``, a 2048 B one, continues request
    ``onto``."""

    n = 128 * streams
    offs = np.arange(n, dtype=np.int64) * 8192
    sizes = np.full(n, 4096)
    offs[moved * 128], sizes[moved * 128] = offs[onto] + 4096, 2048
    return make_batch(offs, sizes, np.zeros(n))


def merges(batch):
    order = np.lexsort((batch.offsets, batch.file_ids))
    contig, _ = ed._sort_neighbours(batch, order)
    return ed._cross_stream_merges(batch.stream_bounds(128), order, contig)


def test_merge_depth_follows_the_farthest_pair():
    assert [ed._xmerge_depth(d) for d in (0, 1, 4, 5, 16, 17, 64, 65, 500)] == \
        [4, 4, 4, 16, 16, 64, 64, 64, 64]
    xm, pairs, beyond = merges(spaced(24, moved=20, onto=127))
    assert xm.shape == (24, 64) and (pairs, beyond) == (1, 0)
    assert xm[20, 19] == 1 and xm.sum() == 1
    xm, pairs, beyond = merges(spaced(24, moved=10, onto=127))
    assert xm.shape == (24, 16) and (pairs, beyond) == (1, 0) and xm[10, 9] == 1
    xm, pairs, beyond = merges(spaced(24, moved=2, onto=127))
    assert xm.shape == (24, 4) and (pairs, beyond) == (1, 0) and xm[2, 1] == 1


def test_pairs_beyond_the_deepest_tape_are_counted():
    xm, pairs, beyond = merges(spaced(80, moved=79, onto=0))
    assert xm.shape == (80, ed.XMERGE_MAX_DEPTH) and (pairs, beyond) == (0, 1)
    assert not xm.any()


def tape(events, depth, listed=len(ed.SR_COLUMNS)):
    t = {k: np.zeros(events, dtype=dt) for k, dt in ed._EVENT_FIELDS.items()}
    t["valid"][:] = True
    t["xm"] = np.ones((events, depth))
    t["sr"] = np.ones((events, listed))
    return t


def test_stack_pads_shallower_tapes_with_no_merges():
    out = ed.stack_events([tape(3, 16), tape(2, 32)])
    assert out["xm"].shape == (8, 2, 32) and out["sr"].shape == (8, 2, len(ed.SR_COLUMNS))
    assert out["xm"][:3, 0, :16].all() and not out["xm"][:, 0, 16:].any()
    assert out["xm"][:2, 1].all() and not out["xm"][2:, 1].any()


def test_stack_keeps_fill_columns_only_where_a_tape_lists_fills():
    out = ed.stack_events([tape(3, 4, listed=0), tape(2, 4)])
    assert out["sr"].shape == (8, 2, len(ed.SR_COLUMNS))
    assert not out["sr"][:, 0].any() and out["sr"][:2, 1].all()
    assert ed.stack_events([tape(3, 4, listed=0)] * 2)["sr"].shape == (8, 2, 0)


def test_counts_are_kept_while_recording_only():
    batch = strided(64, 64)
    prog = FleetProgram(num_nodes=2, schemes=("orangefs", "ssdup"),
                        policy="range-offset", ssd_capacity=16 * MiB, ssd="ftl")
    prog.run(batch)
    with spans.recording() as rec:
        FleetProgram(num_nodes=2, schemes=("orangefs", "ssdup"),
                     policy="range-offset", ssd_capacity=16 * MiB, ssd="ftl").run(batch)
    names = [c.name for c in rec.counts]
    assert names.count("tape.xmerge_pairs") == 2 and names.count("tape.xmerge_beyond") == 2
    assert spans.total(rec.counts, "replay.fill_trips") > 0
    assert spans.count("x", 1) is None

"""Plain reference of one fleet lane: a request-at-a-time replay.

This is the yardstick that decides ``correct``.  It imports nothing of
the program under test and takes nothing it made: it shards the job's
trace itself (``range-offset``), scores each 128-request stream itself
(paper Eq. 1), and replays one node under one scheme one request at a
time, with the semantics of the SSDUP+ paper as the repository's numpy
oracle implements them:

* ``orangefs``: every stream to the HDD (CFQ-sorted: seeks, seek
  distance, sequential transfer), capped by the node's ingest link.
* ``orangefs-bb``: everything to the SSD while it has room; once the
  buffer is (nearly) full it flushes, and requests that find it flushing
  go straight to the HDD with their stream peers.
* ``ssdup``: static 45%/30% watermarks with hysteresis route whole
  streams; two SSD regions, one buffering while the other flushes.
* ``ssdup+``: the adaptive threshold (Eq. 2-3 over the last 64 stream
  percentages) and traffic-aware flushing (the flusher pauses while the
  detector reads the traffic as sequential).

Flushes drain at the Eq. 6 rate (residual seeks of the offset-sorted
region plus sequential transfer), shared with foreground HDD writes by
the Eq. 7 interference model.  With ``ssd="ftl"`` each SSD write is
charged by a page-mapped flash translation layer with greedy garbage
collection, and a flushed region is trimmed.

``F`` is the float type every time, rate and percentage is computed in:
``float`` (IEEE double, what the configurations state) for the
reference, ``numpy.float32`` for the lower-precision control.  Byte
counts stay exact integers in both.
"""

from __future__ import annotations

import bisect
from collections import deque

import numpy as np

SCHEMES = ("orangefs", "orangefs-bb", "ssdup", "ssdup+")


# ---------------------------------------------------------------------------
# sharding and stream scores
# ---------------------------------------------------------------------------

def range_offset_nodes(offsets: np.ndarray, nodes: int) -> np.ndarray:
    """Node of each request: the trace's offset span cut into ``nodes``
    equal extents."""

    lo, hi = int(offsets.min()), int(offsets.max())
    extent = max((hi - lo) // nodes + 1, 1)
    return np.minimum((offsets - lo) // extent, nodes - 1)


def stream_stats(offs: np.ndarray, szs: np.ndarray) -> tuple[int, int]:
    """``(seeks, seek_distance)`` of one stream after the offset sort:
    sorted neighbours that are not contiguous each cost a seek, and the
    distance is the sum of their gaps."""

    if len(offs) <= 1:
        return 0, 0
    order = np.argsort(offs, kind="stable")
    so, ss = offs[order], szs[order]
    resid = so[1:] - so[:-1] - ss[:-1]
    return int(np.count_nonzero(resid)), int(np.abs(resid).sum())


# ---------------------------------------------------------------------------
# flash translation layer
# ---------------------------------------------------------------------------

class FTL:
    """Page-mapped FTL: log-structured page allocation over blocks, greedy
    min-valid garbage collection between a low and a high free-block
    watermark, N-channel striped program time."""

    def __init__(self, logical_bytes: int, p: dict, F):
        self.F = F
        self.ps = int(p["page_size"])
        self.ppb = int(p["pages_per_block"])
        self.channels = int(p["n_channels"])
        t_prog = self.channels * self.ps / float(p["nominal_write_bw"])
        self.t_page = F(t_prog / self.channels)
        self.t_erase_ch = F(float(p["t_erase"]) / self.channels)
        self.low = int(p["gc_low_blocks"])
        self.high = int(p["gc_high_blocks"])
        self.logical_bytes = int(logical_bytes)
        n_lp = -(-self.logical_bytes // self.ps)
        lblocks = -(-n_lp // self.ppb)
        spare = max(self.high + 2, int(np.ceil(lblocks * float(p["overprovision"]))))
        self.blocks = lblocks + spare
        self.l2p = np.full(n_lp, -1, dtype=np.int64)
        self.p2l = np.full(self.blocks * self.ppb, -1, dtype=np.int64)
        self.valid = np.zeros(self.blocks, dtype=np.int64)
        self.sealed = np.zeros(self.blocks, dtype=bool)
        self.free = deque(range(1, self.blocks))
        self.open = 0
        self.fp = 0

    def _alloc(self, k: int) -> list[int]:
        out = []
        while len(out) < k:
            if self.fp == self.ppb:
                self.sealed[self.open] = True
                if not self.free:
                    raise RuntimeError("FTL out of physical space")
                self.open = self.free.popleft()
                self.fp = 0
            take = min(self.ppb - self.fp, k - len(out))
            base = self.open * self.ppb + self.fp
            out.extend(range(base, base + take))
            self.fp += take
        return out

    def _invalidate(self, ppns) -> None:
        for q in ppns:
            self.p2l[q] = -1
            self.valid[q // self.ppb] -= 1

    def write(self, lba: int, size: int):
        """Program one request's pages; returns its service time."""

        if size <= 0:
            return self.F(0.0)
        first = lba // self.ps
        count = (lba + size + self.ps - 1) // self.ps - first
        headroom = (self.ppb - self.fp) + (len(self.free) - self.low) * self.ppb
        gc = len(self.free) < self.low or count > headroom
        lpns = range(first, first + count)
        ppns = self._alloc(count)
        stale = [int(self.l2p[lp]) for lp in lpns if self.l2p[lp] >= 0]
        for lp, q in zip(lpns, ppns):
            self.p2l[q] = lp
            self.valid[q // self.ppb] += 1
        self._invalidate(stale)
        for lp, q in zip(lpns, ppns):
            self.l2p[lp] = q
        t = count * self.t_page
        if gc:
            t = t + self._collect()
        return t

    def _collect(self):
        secs = self.F(0.0)
        while len(self.free) < self.high:
            cands = np.flatnonzero(self.sealed)
            if not len(cands):
                break
            victim = int(cands[np.argmin(self.valid[cands])])
            v = int(self.valid[victim])
            if v >= self.ppb:
                break
            if v:
                span = self.p2l[victim * self.ppb:(victim + 1) * self.ppb]
                lps = [int(lp) for lp in span if lp >= 0]
                span[:] = -1
                self.valid[victim] = 0
                for lp, q in zip(lps, self._alloc(v)):
                    self.p2l[q] = lp
                    self.l2p[lp] = q
                    self.valid[q // self.ppb] += 1
                secs = secs + v * self.t_page
            self.sealed[victim] = False
            self.free.append(victim)
            secs = secs + self.t_erase_ch
        return secs

    def trim(self, lba: int, nbytes: int) -> None:
        """Unmap every page wholly inside ``[lba, lba + nbytes)``."""

        first = -(-lba // self.ps)
        last = min(lba + nbytes, self.logical_bytes) // self.ps
        lps = np.arange(first, max(first, last))
        mapped = lps[self.l2p[lps] >= 0]
        stale = self.l2p[mapped]
        self.p2l[stale] = -1
        np.subtract.at(self.valid, stale // self.ppb, 1)
        self.l2p[mapped] = -1


# ---------------------------------------------------------------------------
# SSD buffer: log regions and flush jobs
# ---------------------------------------------------------------------------

class Region:
    """An append-only log region; flushed in (file, offset) order with
    the latest copy of each offset live."""

    def __init__(self, capacity: int, base_lba: int):
        self.capacity = capacity
        self.base_lba = base_lba
        self.tail = 0
        self.recs: list[tuple[int, int, int]] = []  # (file, offset, size)

    def fits(self, size: int) -> bool:
        return self.tail + size <= self.capacity

    def append(self, file_id: int, offset: int, size: int) -> None:
        self.recs.append((file_id, offset, size))
        self.tail += size

    def flush_cost(self) -> tuple[int, int]:
        """``(live bytes, residual seeks)`` of flushing this region."""

        live: dict[tuple[int, int], int] = {}
        for f, o, s in self.recs:
            live[(f, o)] = s  # a rewrite of an offset supersedes
        seeks, nbytes, prev = 0, 0, None
        for (f, o) in sorted(live):
            s = live[(f, o)]
            nbytes += s
            if prev is None or prev[0] != f or o != prev[1]:
                seeks += 1
            prev = (f, o + s)
        return nbytes, seeks

    def reset(self) -> None:
        self.tail = 0
        self.recs = []


class Job:
    def __init__(self, region: Region, nbytes: int, seeks: int, F):
        self.region = region
        self.total = nbytes
        self.seeks = seeks
        self.done = 0
        self.paused = F(0.0)
        self.forced = False

    @property
    def left(self) -> int:
        return self.total - self.done


class Buffer:
    """The node's SSD: two regions (SSDUP, SSDUP+) or one (plain BB)."""

    def __init__(self, capacity: int, two_regions: bool, traffic_aware: bool,
                 gate: float, ftl: FTL | None, F):
        self.F = F
        if two_regions:
            half = capacity // 2
            self.regions = [Region(half, 0), Region(half, half)]
        else:
            self.regions = [Region(capacity, 0)]
        self.single = not two_regions
        self.active = 0
        self.job: Job | None = None
        self.backlog: list[Job] = []
        self.traffic_aware = traffic_aware
        self.gate = gate
        self.ftl = ftl
        self.flushes = 0
        self.paused_total = F(0.0)
        self.last_pct = F(0.0)

    @property
    def buffered(self) -> int:
        return sum(r.tail for r in self.regions)

    def _scheduled(self, region: Region) -> bool:
        return (self.job is not None and self.job.region is region) or any(
            j.region is region for j in self.backlog)

    def _trim(self, region: Region) -> None:
        if self.ftl is not None and region.tail > 0:
            self.ftl.trim(region.base_lba, region.tail)

    def _schedule(self, region: Region) -> None:
        nbytes, seeks = region.flush_cost()
        if nbytes <= 0:
            self._trim(region)
            region.reset()
            return
        job = Job(region, nbytes, seeks, self.F)
        if self.job is None:
            self.job = job
        else:
            self.backlog.append(job)

    def append(self, file_id: int, offset: int, size: int) -> Region | None:
        """Buffer one request; returns the region it landed in, or None
        when the buffer cannot take it now."""

        if self.single:
            region = self.regions[0]
            if self.job is not None:
                return None
            if region.fits(size):
                region.append(file_id, offset, size)
                if region.capacity - region.tail < max(size, region.capacity // 256):
                    self._schedule(region)  # nearly full: flush at once
                    if self.job is not None:
                        self.job.forced = True
                return region
            self._schedule(region)
            if self.job is not None:
                self.job.forced = True
            return None
        region = self.regions[self.active]
        if region.fits(size):
            region.append(file_id, offset, size)
            return region
        standby = self.regions[1 - self.active]
        if standby.tail > 0 or self._scheduled(standby):
            return None
        self._schedule(region)
        self.active = 1 - self.active
        region = self.regions[self.active]
        if not region.fits(size):
            raise ValueError("request larger than an SSD region")
        region.append(file_id, offset, size)
        return region

    def flush_allowed(self) -> bool:
        job = self.job
        if job is None:
            return False
        if job.forced or not self.traffic_aware:
            return True
        return self.last_pct >= self.gate

    def progress(self, nbytes: int) -> None:
        job = self.job
        if job is None or nbytes <= 0:
            return
        job.done += min(nbytes, job.left)
        if job.done >= job.total:
            self._trim(job.region)
            job.region.reset()
            self.flushes += 1
            self.job = self.backlog.pop(0) if self.backlog else None

    def note_pause(self, seconds) -> None:
        if self.job is not None:
            self.job.paused = self.job.paused + seconds
        self.paused_total = self.paused_total + seconds

    def drain(self) -> None:
        for region in self.regions:
            if region.tail > 0 and not self._scheduled(region):
                self._schedule(region)
        for job in ([self.job] if self.job else []) + self.backlog:
            job.forced = True


# ---------------------------------------------------------------------------
# routing policies
# ---------------------------------------------------------------------------

class AdaptiveThreshold:
    """SSDUP+ Eq. 2-3: threshold = sorted list of the recent stream
    percentages at index ``floor((1 - mean) * n)``, the mean taken
    before the new percentage is inserted."""

    def __init__(self, window: int, default, F):
        self.recent: deque = deque(maxlen=window)
        self.window = window
        self.sorted: list = []
        self.threshold = F(default)

    def observe(self, p) -> None:
        avg = sum(self.sorted) / len(self.sorted) if self.sorted else None
        if len(self.recent) == self.window:
            self.sorted.pop(bisect.bisect_left(self.sorted, self.recent[0]))
        self.recent.append(p)
        bisect.insort(self.sorted, p)
        if avg is not None:
            n = len(self.sorted)
            self.threshold = self.sorted[max(0, min(n - 1, int((1.0 - avg) * n)))]


class StaticWatermarks:
    """SSDUP's 45%/30% watermarks with hysteresis."""

    def __init__(self, high, low):
        self.high, self.low = high, low
        self.random = False

    def observe(self, p) -> None:
        if p > self.high:
            self.random = True
        elif p < self.low:
            self.random = False

    @property
    def threshold(self):
        return self.low if self.random else self.high


# ---------------------------------------------------------------------------
# one lane
# ---------------------------------------------------------------------------

def replay_lane(scheme: str, shard: dict[str, np.ndarray], cfg: dict, F=float) -> dict:
    """Replay one node's shard under one scheme; returns the lane's result
    fields (the program's ``SimResult`` fields)."""

    m = cfg["models"]
    seq_bw, seek_time = F(m["hdd"]["seq_bw"]), F(m["hdd"]["seek_time"])
    seek_coeff = F(m["hdd"]["seek_dist_coeff"])
    link_bw = F(m["link"]["bw"])
    phi = F(m["interference"]["phi"])
    slowdown, flush_frac = 2.0 * phi, 1.0 / (2.0 * phi)
    capacity = int(cfg["ssd_capacity"])
    stream_len = int(cfg["stream_len"])
    use_ftl = cfg["ssd"] == "ftl"
    ftl = FTL(capacity, m["ftl"], F) if use_ftl and scheme != "orangefs" else None
    ssd_bw = F(m["ssd"]["write_bw"])
    read_bw = F(m["ftl"]["read_bw"])

    buf = None
    if scheme != "orangefs":
        buf = Buffer(capacity, two_regions=scheme in ("ssdup", "ssdup+"),
                     traffic_aware=scheme == "ssdup+", gate=F(cfg["flush_gate"]),
                     ftl=ftl, F=F)
    policy = None
    if scheme == "ssdup+":
        policy = AdaptiveThreshold(int(cfg["adaptive_window"]), 0.5, F)
    elif scheme == "ssdup":
        policy = StaticWatermarks(F(0.45), F(0.30))
    to_ssd_next = False  # applications start writing the HDD

    clock = F(0.0)
    blocked = F(0.0)
    b_ssd = b_hdd = peak = 0

    def hdd_time(nbytes, seeks, dist):
        return seeks * seek_time + dist * seek_coeff + nbytes / seq_bw

    def rate(job):
        if job.total <= 0:
            return seq_bw
        secs = job.seeks * seek_time + job.total / seq_bw
        if ftl is not None:
            secs = max(secs, job.total / read_bw)
        return job.total / secs

    def advance(dev_dt, nbytes, hdd_fg):
        nonlocal clock
        net = nbytes / link_bw
        flushing = buf is not None and buf.job is not None
        if not flushing or not buf.flush_allowed():
            wall = max(net, dev_dt)
            if flushing:
                buf.note_pause(wall)
            clock = clock + wall
            return
        r = rate(buf.job)
        if hdd_fg:
            wall = max(net, dev_dt * slowdown)
            r = r * flush_frac
        else:
            wall = max(net, dev_dt)
        buf.progress(int(r * wall))
        clock = clock + wall

    offs, szs, fids = shard["offsets"], shard["sizes"], shard["file_ids"]
    n = len(offs)
    for a in range(0, n, stream_len):
        so, ss, sf = offs[a:a + stream_len], szs[a:a + stream_len], fids[a:a + stream_len]
        nbytes = int(ss.sum())
        seeks, dist = stream_stats(so, ss)
        pct = F(seeks) / (len(so) - 1) if len(so) > 1 else F(0.0)
        if scheme == "orangefs":
            advance(hdd_time(nbytes, seeks, dist), nbytes, True)
            b_hdd += nbytes
            continue
        if scheme == "orangefs-bb":
            to_ssd = True
        else:  # Algorithm 1: this stream goes where the last one decided
            to_ssd = to_ssd_next
            policy.observe(pct)
            thr = policy.threshold
            if pct > thr and not to_ssd:
                to_ssd_next = True
            elif pct < thr and to_ssd:
                to_ssd_next = False
        buf.last_pct = pct
        if not to_ssd:
            advance(hdd_time(nbytes, seeks, dist), nbytes, True)
            b_hdd += nbytes
            continue
        over = []
        for i in range(len(so)):
            f, o, s = int(sf[i]), int(so[i]), int(ss[i])
            region = buf.append(f, o, s)
            if region is None:
                if scheme == "orangefs-bb":
                    over.append(i)
                    continue
                # both regions full: the writer waits for the flush
                buf.job.forced = True
                dt = buf.job.left / rate(buf.job)
                buf.progress(buf.job.left)
                clock = clock + dt
                blocked = blocked + dt
                region = buf.append(f, o, s)
                if region is None:
                    raise RuntimeError("append rejected after a full drain")
            if ftl is not None:
                dev = ftl.write(region.base_lba + region.tail - s, s)
            else:
                dev = s / ssd_bw
            advance(dev, s, False)
            b_ssd += s
        if over:
            oo, os_ = so[over], ss[over]
            ob = int(os_.sum())
            o_seeks, o_dist = stream_stats(oo, os_)
            advance(hdd_time(ob, o_seeks, o_dist), ob, True)
            b_hdd += ob
        peak = max(peak, buf.buffered)

    io_seconds = clock
    if buf is not None:
        buf.drain()
        while buf.job is not None:
            clock = clock + buf.job.left / rate(buf.job)
            buf.progress(buf.job.left)
    return {
        "scheme": scheme,
        "total_bytes": b_ssd + b_hdd,
        "per_app_bytes": _per_app(shard),
        "bytes_to_ssd": b_ssd,
        "bytes_to_hdd_direct": b_hdd,
        "flushes": buf.flushes if buf else 0,
        "peak_ssd_occupancy": peak,
        "blocked_seconds": float(blocked),
        "flush_paused_seconds": float(buf.paused_total) if buf else 0.0,
        "io_seconds": float(io_seconds),
        "total_seconds": float(clock),
    }


def _per_app(shard: dict[str, np.ndarray]) -> dict[str, int]:
    apps = shard["app_ids"]
    return {str(int(a)): int(shard["sizes"][apps == a].sum()) for a in np.unique(apps)}


def node_shard(trace: dict[str, np.ndarray], node: int, nodes: int) -> dict[str, np.ndarray]:
    """The requests of ``trace`` that ``node`` serves, in arrival order."""

    keep = range_offset_nodes(trace["offsets"], nodes) == node
    return {k: v[keep] for k, v in trace.items()}

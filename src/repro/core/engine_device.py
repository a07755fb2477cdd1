"""Device-resident replay engine: the batched engine's per-node state
transition as a pure, fixed-shape array program.

:class:`repro.core.simulator.IONodeSimulator` advances one node's replay
through a Python loop over streams; this module re-expresses that
transition as a functional step over a *state struct* (a pytree of
per-lane scalars) driven by ``jax.lax.scan`` over trace *events* and
``jax.vmap`` over *lanes* (node × scheme combinations), so an entire
fleet sweep runs as ONE jitted device call
(:class:`repro.core.fleet.FleetProgram`).

Structure (the state-struct / transition / orchestration split):

* **events** — :func:`build_events` lowers one shard's
  (:class:`~repro.core.trace.TraceBatch`,
  :class:`~repro.core.trace.StreamScores`) pair into a fixed-shape
  struct-of-arrays event tape: one entry per stream or compute gap, in
  the exact interleaving the batched engine uses (a full stream fires
  before a gap marker at its end boundary; the trailing partial stream
  fires after all remaining gaps).  Tapes are padded with ``valid=False``
  entries to a shared power-of-two length so every lane scans the same
  shape (:func:`stack_events`).
* **state** — :func:`initial_lane_state` builds the per-lane state struct
  (clocks, byte counters, region occupancy, the single in-flight flush
  job, the adaptive-threshold window as a circular buffer, routing
  hysteresis bits).  ``threshold_warmup`` is applied on the host through
  the exact scalar policies, then transplanted into the window buffer.
* **transition** — :func:`_event_step` is the pure per-lane step: stream
  routing against the precomputed scores (Eq. 1–3 threshold update +
  Algorithm 1 hysteresis), SSD region fills/swaps/blocks via a bounded
  ``lax.while_loop``, HDD/overflow foreground advances with Eq. 7
  interference, flush-quanta accounting per Eq. 6, and compute-gap
  draining.  All four schemes run the same step, selected by per-lane
  flags, so lanes of different schemes batch into one ``vmap``.
* **orchestration** — :func:`replay_lanes` jits ``scan(vmap(step))`` plus
  the vectorized end-of-trace drain and returns per-lane result arrays;
  :func:`simulate_device` wraps a single lane into a
  :class:`~repro.core.simulator.SimResult` (the ``engine="device"`` path
  of :class:`IONodeSimulator`).

Dtype policy: the engine runs under the scoped 64-bit mode of
:func:`repro.runtime.x64` — clocks/rates in float64, byte counters in
int64 — so the numbers track the numpy oracle at f64 resolution instead
of drifting through float32.

Accuracy contract (vs the bit-exact numpy engines): the device engine is
*stream-granular* where the oracle is request-granular.  The documented
approximations, all bounded and recorded as tolerances in the golden
fixtures (``device_tolerance`` metadata, checked by
``tests/test_engine_device.py``):

1. **Region fills stop on mean-request boundaries.**  The oracle
   appends whole requests (a region takes every request that fits
   entirely; plain BB stops at the eager-trigger request); the device
   reproduces that with the stream's MEAN request size — exact for
   uniform-size streams (the golden traces), byte-fraction approximate
   otherwise.
2. **Flush quanta accumulate in float64.**  The oracle truncates
   ``int(rate * wall)`` per request; the device accumulates
   continuously (≤ 1 byte/request difference), except on the fills
   whose requests it knows (item 3), which it truncates as the oracle
   does.
3. **Eq. 6 residual seeks come from precomputed anchors, not a live
   sort**, except on ``ssdup`` lanes, whose regions the tape knows.
   Under ``ssdup`` what a region holds does not depend on timing
   (routing follows the percentages alone, item 5; a full SSD blocks
   the writer; fills are whole requests), so the tape build lists every
   region that lane fills with its exact flush cost — live bytes and
   the seeks of its sorted union — and each stream's fills with their
   summed per-request walls, the FTL's garbage collection included
   (:func:`_ssdup_regions`).  The replay uses them while every stream
   has gone where the table's route sends it (lane state ``rg_sync``,
   checked stream by stream, so a route that drifts from the table's
   drops the table for the rest of the lane) and its region's ordinal
   and bytes are the ones the tape expects; there the lane's clocks
   match the oracle to rounding.  On an FTL the walls are known up to the first collection
   that might find no empty victim block (one that a region two or more
   back left when it was trimmed); from there on, and on every other
   lane, the estimates below stand.  A region buffering an
   arrival-window of a stream sorts that window ALONE
   (``LogRegion.seek_count_sorted``), which no pro-rated share of the
   whole stream's count reproduces.  The tape build computes per
   stream, exactly and on the device (one jitted program per shard,
   :func:`_tape_anchors64`), (a) PREFIX seek counts at
   ``SUFFIX_ANCHORS + 1`` request quantiles — every plain-BB fill and
   every first two-region fill is prefix-aligned, so those lerp within
   ~2% — and (b) dyadic window anchors (whole/halves/quarters/eighths,
   extent count + distinct-file baseline each) for interior fills,
   picked by nearest scale with linear partial-coverage; overwritten-
   extent dedup is not modeled (flush bytes = appended bytes).  A
   region holding SEVERAL streams sorts their union, so extents
   contiguous across neighbouring streams merge: the tape's per-stream
   cross-merge counts (``xm``, one column per stream distance, see
   :func:`_cross_stream_merges`, a host pass: it sorts across streams)
   are subtracted for partners still in the active region — without
   this a tiled workload's flush rate is underestimated ~2× and plain-BB
   routing diverges.  The depth follows the shard's farthest contiguous
   pair (:data:`XMERGE_MIN_DEPTH`); pairs farther than
   ``XMERGE_MAX_DEPTH`` stay uncorrected (seeks are over-, never
   under-counted) and are counted (``tape.xmerge_beyond``).  The FTL's
   garbage collection is charged per fill from the lane's mean victim
   occupancy.
4. **Plain-BB overflow suffixes are interpolated, not re-scored.**  The
   oracle re-scores an overflowed stream suffix from scratch (a strided
   suffix sorts far worse than its byte share of the whole stream), so
   the tape carries every stream's suffix HDD time at
   ``SUFFIX_ANCHORS + 1`` request-quantile split points (their seek
   counts and sums from the same device program) and the replay lerps
   between them by byte fraction — exact for whole streams (the
   0-split anchor IS the stream's scored time) and at anchor-aligned
   splits, a few percent between anchors.
5. Routing, threshold evolution, and therefore **byte routing for the
   orangefs/ssdup/ssdup+ schemes is timing-independent and exact**;
   plain-BB byte splits are timing-coupled (overflow depends on when a
   flush completes) and carry tolerances.

``metadata_bytes`` is reported as 0, matching the oracle's post-drain
value.  The unbounded adaptive window (``adaptive_window=None``) is not
representable in fixed shape — the device engine requires a finite
window.
"""

from __future__ import annotations

import functools
from typing import Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import checkify

from .. import spans
from ..analysis import sanitize as _sanitize
from ..runtime import x64

from .adaptive import (
    DEFAULT_THRESHOLD,
    AdaptiveThreshold,
    StaticWatermarkThreshold,
)
from .device_model import HDDModel, IngestLink, InterferenceModel, SSDModel
from .random_factor import DEFAULT_STREAM_LEN

SCHEME_IDS = {"orangefs": 0, "orangefs-bb": 1, "ssdup": 2, "ssdup+": 3}

#: Documented comparison tolerances of the device engine vs the numpy
#: oracle, per SimResult field: ``field -> (rtol, atol)``.  Derived from
#: the approximation list in the module docstring; golden fixtures embed
#: this table (``device_tolerance``) at --write time after verifying the
#: device replay satisfies it, and ``tests/test_engine_device.py``
#: asserts against the embedded copy.
DEVICE_TOLERANCES: dict[str, tuple[float, float]] = {
    "total_bytes": (0.0, 0.0),        # conservation: every byte lands
    "per_app_bytes": (0.0, 0.0),      # host-computed, scheme-independent
    "bytes_to_ssd": (0.0, 4 << 20),   # BB overflow split is timing-coupled
    "bytes_to_hdd_direct": (0.0, 4 << 20),
    "metadata_bytes": (0.0, 0.0),     # both report 0 post-drain
    "flushes": (0.0, 2.0),            # BB flush count is timing-coupled
    "peak_ssd_occupancy": (0.0, 4 << 20),
    "blocked_seconds": (0.05, 1e-6),  # Eq. 6 anchor lerp at block time
    "flush_paused_seconds": (0.05, 1e-6),
    "io_seconds": (0.05, 1e-9),       # suffix/seek anchor lerp dominates
    "total_seconds": (0.02, 1e-9),
}

#: Suffix-anchor count: stream suffix HDD times are precomputed at
#: ``round(j * n / SUFFIX_ANCHORS)`` for ``j = 0..SUFFIX_ANCHORS``
#: (anchor 0 = the whole stream, the last anchor = empty suffix).
SUFFIX_ANCHORS = 16

#: Dyadic window scales for Eq. 6 region-seek anchors: every stream is
#: scored whole, in halves, quarters and eighths (1 + 2 + 4 + 8 = 15
#: windows).  A region holding an arrival-window of a stream sorts that
#: window ALONE, so its seek count is NOT a pro-rated share of the whole
#: stream's (a strided stream's window loses the cross-window extent
#: merges); the device picks the scale nearest the fill width and
#: interpolates partial window coverage linearly.
WINDOW_SCALES = 4
N_WINDOWS = (1 << WINDOW_SCALES) - 1

#: Cross-stream merge depth: a flushed region sorts ALL its buffered
#: streams together, so extents that are contiguous ACROSS neighbouring
#: streams merge and cost no seek (``LogRegion.seek_count_sorted``) —
#: on tiled workloads (IOR strided) this collapses per-stream seek sums
#: by an order of magnitude.  The tape carries, per stream, the count of
#: sort-adjacent contiguous pairs it forms with each of its ``D``
#: predecessors (one ``(events, D)`` column, ``xm``); the fill loop
#: subtracts the pairs whose partner stream is (fractionally) in the
#: active region.  ``D`` follows the shard's pair distances: the least
#: of 4, 16 and 64 (``XMERGE_MIN_DEPTH`` times powers of four) that
#: covers the farthest pair, so that traffic whose farthest pair wanders
#: within a bucket keeps one compiled shape.  Pairs beyond
#: ``XMERGE_MAX_DEPTH`` stay uncorrected (seeks are over-, never
#: under-counted) and are counted (``tape.xmerge_beyond``).  IO500
#: ior-easy's farthest pair is 1 stream; ior-hard's 6-7 at 64 nodes,
#: 16 at 4.
XMERGE_MIN_DEPTH = 4
XMERGE_MAX_DEPTH = 64

#: SSDUP's static watermarks (random above ``STATIC_HIGH``, sequential
#: below ``STATIC_LOW``, hysteresis between).
STATIC_HIGH = 0.45
STATIC_LOW = 0.30

_EVENT_FIELDS = {
    "valid": np.bool_,
    "is_gap": np.bool_,
    "gap_sec": np.float64,
    "pct": np.float64,
    "nbytes": np.int64,
    "net_t": np.float64,
    "ssd_w": np.float64,
    "mean_sz": np.float64,
    **{f"hddt_{j}": np.float64 for j in range(SUFFIX_ANCHORS + 1)},
    **{f"pf_{j}": np.float64 for j in range(SUFFIX_ANCHORS + 1)},
    **{f"wf_{i}": np.float64 for i in range(N_WINDOWS)},
    **{f"wn_{i}": np.float64 for i in range(N_WINDOWS)},
    # (events, D): merges with each of the D previous streams
    "xm": np.float64,
    # (events, len(SR_COLUMNS)), or (events, 0) where the tape lists no
    # region: an ssdup lane's fills of the stream
    "sr": np.float64,
}


# ---------------------------------------------------------------------------
# host side: event tapes, lane constants, initial state
# ---------------------------------------------------------------------------


def _xmerge_depth(farthest: int) -> int:
    """Tape depth for a shard whose farthest contiguous pair lies
    ``farthest`` streams apart (see :data:`XMERGE_MIN_DEPTH`)."""

    d = XMERGE_MIN_DEPTH
    while d < min(farthest, XMERGE_MAX_DEPTH):
        d *= 4
    return d


def _sort_neighbours(batch, order: np.ndarray):
    """``(contig, overlap)`` of the shard's ``(file, offset)`` sort
    ``order``: for each element after the first, whether it continues
    its predecessor there (same file, starting at its end), and whether
    any element starts inside its predecessor."""

    so, sf = batch.offsets[order], batch.file_ids[order]
    end = so[:-1] + batch.sizes[order][:-1]
    same = sf[1:] == sf[:-1]
    return same & (so[1:] == end), bool((same & (so[1:] < end)).any())


def _cross_stream_merges(bounds: np.ndarray, order: np.ndarray,
                         contig: np.ndarray):
    """``(xm, pairs, beyond)``: per-stream cross-merge counts
    ``(ns, D)``, the contiguous pairs they cover and those left beyond
    ``D`` (:func:`_xmerge_depth`).

    ``xm[j, d-1]`` = contiguous pairs (``contig``,
    :func:`_sort_neighbours`) stream ``j`` forms with stream ``j - d`` in
    the GLOBAL per-file offset sort ``order`` (each pair assigned to the
    later stream).  For non-overlapping extents a contiguous pair is
    always sort-adjacent — an element between ``p`` and
    ``p.offset + p.size`` would overlap ``p`` — so one global sort
    suffices.  These are exactly the seeks ``LogRegion.seek_count_sorted``
    does NOT pay when both streams sit in the same region, i.e. the gap
    between summing per-stream seek estimates and sorting the region's
    union.
    """

    ns = len(bounds) - 1
    ssid = np.repeat(np.arange(ns, dtype=np.int64), np.diff(bounds))[order]
    i = np.flatnonzero(contig & (ssid[1:] != ssid[:-1]))
    a, b = ssid[i], ssid[i + 1]
    d = np.abs(b - a)
    depth = _xmerge_depth(int(d.max(initial=0)))
    kept = d <= depth
    out = np.bincount(np.maximum(a, b)[kept] * depth + d[kept] - 1,
                      minlength=ns * depth).reshape(ns, depth)
    pairs = int(kept.sum())
    return out.astype(np.float64), pairs, len(d) - pairs


def _shift_right(x, s: int, fill):
    """``x`` moved ``s`` places along the last axis, ``fill`` shifted in."""

    pad = [(0, 0, 0)] * (x.ndim - 1) + [(s, -s, 0)]
    return lax.pad(x, jnp.asarray(fill, x.dtype), pad)


def _masked_prev(mask, idx, end):
    """For every element, the index (-1: none) and the extent end of the
    nearest EARLIER element of its row with ``mask`` set.

    A masked subset of a sorted row keeps the row's order, so the
    element before ``v`` in the subset is the nearest earlier masked one:
    a forward fill, done as a doubling scan of lane shifts and selects
    (``log2(L)`` steps; no gather, which the chip runs slowly).
    """

    p = _shift_right(jnp.where(mask, idx, -1), 1, -1)
    e = _shift_right(jnp.where(mask, end, 0), 1, 0)
    s = 1
    while s < mask.shape[-1]:
        e = jnp.where(p >= 0, e, _shift_right(e, s, 0))
        p = jnp.maximum(p, _shift_right(p, s, -1))
        s *= 2
    return p, e


@jax.jit
def _tape_anchors64(rows):
    """Every stream's prefix, window and suffix seek anchors, one row per
    stream: ``rows`` is ``(4, S, L)`` int64 (offsets, sizes, file ids,
    and 1 where a request is, 0 on padding, which the trailing partial
    stream and the rows past the last stream are; padding sits in no
    mask).  One array, so one upload.

    Returns one ``(S,)``-column int32 array: the rows of
    :data:`_COUNT_BLOCKS`, then the int64 rows of :data:`_SUM_BLOCKS`
    split base ``2**31`` (all high parts, then all low parts), since the
    chip hands an int32 array back in half the time of an int64 one.
    Each family sorts the row alone with arrival position as the last
    key, which reproduces the stable lexsort of the host passes
    (``repro.testing.anchors``), and scores each anchor's masked subset
    of that order by its masked predecessor.
    """

    offs, sizes, files = rows[0], rows[1], rows[2]
    s, l = offs.shape
    idx = lax.broadcasted_iota(jnp.int32, (s, l), 1)
    # requests fill each row from position 0, so the count is the length
    n = jnp.sum(rows[3], axis=1, dtype=jnp.int32, keepdims=True)
    j = jnp.arange(1, SUFFIX_ANCHORS + 1, dtype=jnp.int32)[:, None, None]

    # prefix and window anchors: the (file, offset, arrival) order
    sf, so, sp, ss = lax.sort((files, offs, idx, sizes), dimension=1,
                              num_keys=3)
    # index where each element's file run starts: a masked predecessor
    # at or after it reads the same file
    run0 = lax.cummax(
        jnp.where(sf != _shift_right(sf, 1, -1), idx, 0), axis=1)
    real = sp < n
    # prefix j holds positions [0, round(j * n / A)), integer-exact
    masks = [sp < (j * n + SUFFIX_ANCHORS // 2) // SUFFIX_ANCHORS]
    for scale in range(WINDOW_SCALES):
        w = 1 << scale
        # p's window is the count of k >= 1 with round(k * n / w) <= p
        win = jnp.minimum(((2 * sp + 1) * w - 1) // jnp.maximum(2 * n, 1),
                          w - 1)
        k = jnp.arange(w, dtype=jnp.int32)[:, None, None]
        masks.append(real & (win == k))
    m = jnp.concatenate(masks)
    prev, end = _masked_prev(m, idx, so + ss)
    same = m & (prev >= run0)
    contig = same & (so == end)
    breaks = jnp.sum(m & ~contig, axis=-1, dtype=jnp.int32)
    files_in = jnp.sum(m & ~same, axis=-1, dtype=jnp.int32)

    # suffix anchors 1..A-1: the (offset, arrival) order, files ignored
    so, sp, ss = lax.sort((offs, idx, sizes), dimension=1, num_keys=2)
    m = (sp >= (j[:-1] * n + SUFFIX_ANCHORS // 2) // SUFFIX_ANCHORS) & (sp < n)
    prev, end = _masked_prev(m, idx, so + ss)
    resid = jnp.where(m & (prev >= 0), so - end, 0)
    rf = jnp.sum(resid != 0, axis=-1, dtype=jnp.int32)
    dist = jnp.sum(jnp.abs(resid), axis=-1)
    nb = jnp.sum(jnp.where(m, ss, 0), axis=-1)

    hi, lo = jnp.divmod(jnp.concatenate([dist, nb]), 1 << 31)
    return jnp.concatenate([
        breaks, files_in[SUFFIX_ANCHORS:], rf,
        hi.astype(jnp.int32), lo.astype(jnp.int32),
    ])


#: Row blocks of :func:`_tape_anchors64`'s output, in order: the prefix
#: anchors' extent counts (anchors 1..A), the windows' extent counts
#: and distinct-file baselines, the suffix anchors' (1..A-1)
#: residual-seek counts; then their ``|residual|`` sums and byte sums.
_COUNT_BLOCKS = (
    ("pf", SUFFIX_ANCHORS), ("wf", N_WINDOWS), ("wn", N_WINDOWS),
    ("rf", SUFFIX_ANCHORS - 1),
)
_SUM_BLOCKS = (("dist", SUFFIX_ANCHORS - 1), ("nb", SUFFIX_ANCHORS - 1))


def _dispatch_anchors(batch, stream_len: int):
    """Upload a shard's requests and start :func:`_tape_anchors64` on
    them; returns the output, still on its way back to the host, for
    :func:`_seek_anchors`.  The host is free until then."""

    r = batch.num_requests
    s = _pad_len(-(-r // stream_len))
    rows = np.zeros((4, s * stream_len), dtype=np.int64)
    rows[0, :r] = batch.offsets
    rows[1, :r] = batch.sizes
    rows[2, :r] = batch.file_ids
    rows[3, :r] = 1
    with x64():
        with spans.span("tape.anchors.upload"):
            rows = jax.device_put(rows.reshape(4, s, stream_len))
        with spans.span("tape.anchors.run"):
            out = spans.wait(_tape_anchors64(rows))
    out.copy_to_host_async()
    return out


def _seek_anchors(out, ns: int, hdd) -> tuple[np.ndarray, ...]:
    """``(suffix, pf, wf, wn)``: the Eq. 6 seek anchors of the ``ns``
    streams of a shard with at least one request, as float64 tape
    columns, from the output of :func:`_dispatch_anchors`.

    ``pf[:, j]`` is the extent count (per file: 1 + non-contiguous
    breaks) of the stream's arrival-order prefix of ``round(j * n / A)``
    requests sorted alone; anchor 0 is the empty prefix.  ``wf``/``wn``
    are the extent count and distinct-file count of each dyadic
    arrival-window sorted alone, scale-major
    (``[whole, half0, half1, quarter0..3, eighth0..7]``).
    ``suffix[:, j]`` is the HDD time of the suffix from request
    ``round(j * n / A)`` sorted alone (Eq. 1 seeks + sweep distance +
    sequential time), as the oracle's overflow path scores it; column 0
    (the whole stream) is left for the caller, the empty suffix A is 0.

    Computed by :func:`_tape_anchors64` on the device, one call per
    shard, with the rows padded to a power of two so that shards of
    similar size share one compiled program.  Every count and sum is an
    exact integer there (sums below 2**62); the sums become float64
    here, exactly while a stream's bytes and ``|residual|`` sum stay
    below 2**53.
    """

    with spans.span("tape.anchors.readback"):
        out = np.asarray(out)[:, :ns].astype(np.int64)
    nc = sum(k for _, k in _COUNT_BLOCKS)
    hi, lo = np.split(out[nc:], 2)
    out = np.concatenate([out[:nc], hi * (1 << 31) + lo])
    blocks, i = {}, 0
    for name, k in _COUNT_BLOCKS + _SUM_BLOCKS:
        blocks[name] = out[i:i + k].T
        i += k
    pf = np.zeros((ns, SUFFIX_ANCHORS + 1), dtype=np.float64)
    pf[:, 1:] = blocks["pf"]
    suffix = np.zeros((ns, SUFFIX_ANCHORS + 1), dtype=np.float64)
    # same term order as HDDModel.write_time
    suffix[:, 1:SUFFIX_ANCHORS] = (
        blocks["rf"] * hdd.seek_time
        + blocks["dist"].astype(np.float64) * hdd.seek_dist_coeff
        + blocks["nb"].astype(np.float64) / hdd.seq_bw
    )
    return (suffix, pf, blocks["wf"].astype(np.float64),
            blocks["wn"].astype(np.float64))


def _ssdup_route(pct: np.ndarray, static_rand: bool) -> np.ndarray:
    """Which streams an ``ssdup`` lane sends to the SSD: the static
    watermarks with hysteresis and Algorithm 1, as
    :func:`_observe_and_route` computes them on the device (a stream
    rides the previous stream's decision; applications start on the
    HDD)."""

    route = np.zeros(len(pct), dtype=bool)
    sr, cur = bool(static_rand), False
    for j, p in enumerate(pct.tolist()):
        route[j] = cur
        if p > STATIC_HIGH:
            sr = True
        elif p < STATIC_LOW:
            sr = False
        thr = STATIC_LOW if sr else STATIC_HIGH
        if p > thr:
            cur = True
        elif p < thr:
            cur = False
    return route


#: Columns of an ``ssdup`` lane's region table (lane constant ``rg``,
#: one row per region in fill order; :func:`_ssdup_regions`): the bytes
#: appended to the region, its flush's live bytes and seeks, and its
#: requests' count ``n``, summed foreground walls ``wall`` and the count
#: ``g`` and wall sum ``sw`` of those whose wall is not their stream's
#: ``w0`` (read where one stream fills the region whole).
RG_COLUMNS = ("appended", "live", "seeks", "n", "wall", "g", "sw")
#: Columns of the tape's ``sr`` field: per stream, what an ``ssdup``
#: lane's fills of it are.  ``ssd``: 1 where the table's route sends the
#: stream to the SSD; the replay keeps to the table only while every
#: stream has gone where this says.  ``k``/``tail``: the active region's ordinal
#: and bytes when the stream starts (``k = -1``: not known, the replay
#: estimates); ``p``: the regions its bytes land in, ``k`` to
#: ``k + p - 1``; for its part in the first (``1``) and the last (``l``)
#: of them, the bytes ``b``, and ``n``, ``wall``, ``g``, ``sw`` as in
#: :data:`RG_COLUMNS`; ``w0``: the first request's link time.  A wall is
#: ``max(link, SSD device)`` time, the FTL's garbage collection included.
SR_COLUMNS = ("ssd", "k", "tail", "p", "b1", "n1", "wall1", "g1", "sw1",
              "bl", "nl", "walll", "gl", "swl", "w0")
_RG = {name: i for i, name in enumerate(RG_COLUMNS)}
_SR = {name: i for i, name in enumerate(SR_COLUMNS)}


def _ftl_walls(ssd, lba, sz, starts, net):
    """Foreground walls of an ``ssdup`` lane's SSD writes on a fresh
    :class:`~repro.core.ftl.FTLModel`, request by request as its
    ``charge_write`` serves them, and the index of the first request
    from which they are not known (``len(sz)`` where all are).
    ``starts`` holds each region's first request, then ``len(sz)``.

    The regions are written in turn into alternating halves, so pages
    are allocated in request order, and garbage collection fires where
    the allocated pages pass the low watermark's headroom.  Each
    collection erases up to the high watermark; its cost is known where
    every victim is a block wholly of a region two or more back (those
    are trimmed before the region that reuses their half is written, so
    greedy choice finds them empty): the check keeps a lower bound on
    such blocks, so the walls are exact up to the first collection it
    cannot cover.
    """

    ps, ppb = ssd.page_size, ssd.pages_per_block
    cnt = np.where(sz > 0, (lba + sz + ps - 1) // ps - lba // ps, 0)
    pages = np.cumsum(cnt)
    t = cnt * ssd.t_page
    # empty blocks that each region leaves once trimmed: the blocks
    # wholly inside its run of allocated pages, less those holding the
    # run's first or last page, which may hold a partly covered logical
    # page that the trim keeps
    r_end = pages[starts[1:] - 1]
    r_start = np.r_[0, r_end[:-1]]
    pure = np.maximum(r_end // ppb + (-r_start // ppb)
                      - (r_start % ppb == 0) - (r_end % ppb == 0), 0)
    # a collection at request i erases up to the high mark, so the free
    # pool then depends on i alone: the next fires at the first request
    # past the headroom that i leaves
    blocks = -(-pages // ppb)
    headroom = (ssd.gc_high_blocks - ssd.gc_low_blocks) * ppb
    first = (ssd.free_blocks + 1 - ssd.gc_low_blocks) * ppb
    i = int(pages.searchsorted(first, "right"))
    events = []
    while i < len(sz):
        events.append(i)
        i = int(pages.searchsorted(blocks[i] * ppb + headroom, "right"))
    ev = np.asarray(events, dtype=np.int64)
    # blocks erased so far: after a collection at i, blocks[i] less the
    # blocks the fresh FTL holds above the high mark, less the open one
    after = blocks[ev] + ssd.gc_high_blocks - ssd.free_blocks - 1
    before = np.r_[0, after[:-1]]
    erased = after - before
    ev_reg = np.searchsorted(starts, ev, "right") - 1
    empty = np.r_[0, np.cumsum(pure)][np.maximum(ev_reg - 1, 0)] - before
    bad = np.flatnonzero((erased > empty) | (erased <= 0))
    known = int(ev[bad[0]]) if len(bad) else len(sz)
    ok = ev < known
    t[ev[ok]] += erased[ok] * (ssd.t_erase / ssd.n_channels)
    return np.maximum(net, t), known


def _live_and_seeks(batch, o, ro, r: int):
    """Live bytes and seeks of ``r`` regions whose requests are ``o``
    (in the shard's (file, offset, arrival) order), in regions ``ro``:
    the latest copy of each (file, offset) is live, and each live extent
    that does not continue its predecessor is a seek."""

    # a small-integer key: numpy sorts it stably by radix
    by_region = np.argsort(ro.astype(np.int16 if r < 2**15 else np.int64),
                           kind="stable")
    o, ro = o[by_region], ro[by_region]
    f, off, sz = batch.file_ids[o], batch.offsets[o], batch.sizes[o]
    last = np.ones(len(o), dtype=bool)
    last[:-1] = (ro[1:] != ro[:-1]) | (f[1:] != f[:-1]) | (off[1:] != off[:-1])
    ro, f, off, sz = ro[last], f[last], off[last], sz[last]
    start = np.ones(len(ro), dtype=bool)
    start[1:] = ((ro[1:] != ro[:-1]) | (f[1:] != f[:-1])
                 | (off[1:] != off[:-1] + sz[:-1]))
    return (np.bincount(ro, weights=sz, minlength=r),
            np.bincount(ro[start], minlength=r))


def _ssdup_regions(batch, bounds, pct, order, neighbours, cap: int,
                   static_rand: bool, ssd, link):
    """``(rg, sr)``: the table (:data:`RG_COLUMNS`) of every region an
    ``ssdup`` lane fills, and the ``sr`` columns (:data:`SR_COLUMNS`) of
    every stream.

    Under ``ssdup`` what a region holds does not depend on timing: the
    route follows the percentages alone (:func:`_ssdup_route`), a full
    SSD blocks the writer rather than overflowing, and a region takes
    whole requests in arrival order until the next does not fit.  So the
    regions are known before the replay: each one's flush cost as
    ``Region.flush_cost`` in the request-level replay has it, live bytes
    (the latest copy of each ``(file, offset)``) and one seek per extent
    start of the ``(file, offset)``-sorted union (``order`` is the
    shard's stable ``(file, offset)`` sort, ``neighbours`` what
    :func:`_sort_neighbours` finds in it), and each request's wall.
    No rows where a request is larger than a region.
    """

    ns = len(bounds) - 1
    sr = np.zeros((ns, len(SR_COLUMNS)), dtype=np.float64)
    sr[:, _SR["k"]] = -1
    empty = np.zeros((0, len(RG_COLUMNS)), dtype=np.float64)
    lens = np.diff(bounds)
    route = _ssdup_route(pct, static_rand)
    sr[:, _SR["ssd"]] = route
    to_ssd = route & (lens > 0)
    streams = np.flatnonzero(to_ssd)
    if not len(streams) or cap <= 0:
        return empty, sr
    idx = np.flatnonzero(np.repeat(to_ssd, lens))
    sz = batch.sizes[idx]
    cum = np.cumsum(sz)
    starts, base = [0], 0  # each region's first request, then the end
    while starts[-1] < len(idx):
        e = int(np.searchsorted(cum, base + cap, side="right"))
        if e == starts[-1]:
            return empty, sr
        starts.append(e)
        base = int(cum[e - 1])
    starts = np.asarray(starts)
    r = len(starts) - 1
    reg = np.repeat(np.arange(r), np.diff(starts))
    rg = np.zeros((r, len(RG_COLUMNS)), dtype=np.float64)
    rg[:, _RG["appended"]] = np.diff(np.r_[0, cum[starts[1:] - 1]])

    # each region's flush: per (file, offset)-sorted run of its requests,
    # one seek where a request does not continue its predecessor
    region_of = np.full(batch.num_requests, -1, dtype=np.int64)
    region_of[idx] = reg
    ro = region_of[order]
    contig, overlap = neighbours
    if not overlap:
        # no two extents overlap: every copy is live, and a request's
        # predecessor in its region's order is next to it in the shard's
        # (any request between would overlap one of them)
        cont = contig & (ro[1:] == ro[:-1]) & (ro[1:] >= 0)
        rg[:, _RG["live"]] = rg[:, _RG["appended"]]
        rg[:, _RG["seeks"]] = np.diff(starts) - np.bincount(ro[1:][cont], minlength=r)
    else:
        rg[:, _RG["live"]], rg[:, _RG["seeks"]] = _live_and_seeks(
            batch, order[ro >= 0], ro[ro >= 0], r)

    # each request's bytes ahead of it in its region; its wall
    before = cum - sz
    ahead = before - np.repeat(before[starts[:-1]], np.diff(starts))
    net = sz / link.bw
    if getattr(ssd, "stateful", False):
        if ssd.host_pages:  # a used FTL's state is not the fresh one
            return rg, sr
        wall, known = _ftl_walls(ssd, (reg % 2) * cap + ahead, sz, starts, net)
    else:
        wall, known = np.maximum(net, sz / ssd.write_bw), len(sz)

    # sums over runs of SSD requests: differences of running totals of
    # bytes, requests, walls, and count and walls of those not at w0,
    # kept at the streams' and the regions' first requests
    stop = np.cumsum(lens[streams])
    first = stop - lens[streams]
    w0 = net[first]
    odd = wall != np.repeat(w0, lens[streams])
    cuts = np.union1d(first, starts[:-1])
    run = np.zeros((5, len(cuts) + 1))
    np.cumsum([np.add.reduceat(sz, cuts), np.diff(np.r_[cuts, len(idx)]),
               np.add.reduceat(wall, cuts), np.add.reduceat(odd, cuts, dtype=np.float64),
               np.add.reduceat(np.where(odd, wall, 0.0), cuts)], axis=1, out=run[:, 1:])

    def at(pos):  # running totals before the requests ``pos`` (cuts or the end)
        return run[:, np.searchsorted(cuts, pos)]

    per_region = at(starts[1:]) - at(starts[:-1])
    for name, v in zip(("n", "wall", "g", "sw"), per_region[1:]):
        rg[:, _RG[name]] = v
    # a stream starts in the region the previous SSD request landed in
    k = np.where(first > 0, reg[first - 1], 0)
    tail = np.where(first > 0, ahead[first - 1] + sz[first - 1], 0)
    in_first = at(np.minimum(stop, starts[k + 1])) - at(first)
    in_last = at(stop) - at(np.maximum(first, starts[reg[stop - 1]]))
    cols = {"k": np.where(stop <= known, k, -1), "tail": tail,
            "p": reg[stop - 1] - k + 1, "w0": w0}
    for i, name in enumerate(("b", "n", "wall", "g", "sw")):
        cols[name + "1"], cols[name + "l"] = in_first[i], in_last[i]
    for name, v in cols.items():
        sr[streams, _SR[name]] = v
    return rg, sr


@spans.spanned("tape.build")
def build_events(
    batch,
    scores,
    stream_len: int = DEFAULT_STREAM_LEN,
    hdd: HDDModel | None = None,
    ssd: "SSDModel | object | None" = None,
    link: IngestLink | None = None,
    region_cap: int | None = None,
    static_rand: bool = False,
) -> dict[str, np.ndarray]:
    """Lower one shard into its event tape (struct-of-arrays, length E).

    One event per stream or gap, in the batched engine's firing order.
    All timing inputs the device step needs are precomputed here in
    float64 with the oracle's exact expressions: whole-stream HDD time
    (Eq. 1 seeks + sweep + sequential), network time, the sequential sum
    of per-request SSD walls, and the per-stream score row.

    With ``region_cap`` (an ``ssdup`` lane's region bytes) and the lane's
    initial watermark state ``static_rand``, the tape also carries the
    table of every region that lane fills (``rg``), which
    :func:`lane_consts` hands to ``ssdup`` lanes, and each stream's fills
    on that lane (``sr``; :func:`_ssdup_regions`); without, the table is
    empty and no stream's fills are known.
    """

    hdd = hdd or HDDModel()
    ssd = ssd or SSDModel()
    link = link or IngestLink()

    bounds = batch.stream_bounds(stream_len)
    ns = len(bounds) - 1 if batch.num_requests else 0
    n_req = np.diff(bounds) if ns else np.zeros(0, dtype=np.int64)

    nb = np.asarray(scores.nbytes, dtype=np.int64)
    rf = np.asarray(scores.rf_sum, dtype=np.float64)
    dist = np.asarray(scores.seek_distance, dtype=np.float64)
    pct = np.asarray(scores.percentage, dtype=np.float64)
    if len(nb) != ns:
        raise ValueError(
            f"scores cover {len(nb)} streams but the trace produced {ns}"
        )
    # same association order as HDDModel.write_time / IngestLink.time
    hdd_t = rf * hdd.seek_time + dist * hdd.seek_dist_coeff + nb / hdd.seq_bw
    net_t = nb / link.bw
    if ns:
        # the anchors' round trip to the device runs under the host passes
        # that follow, up to their readback
        with spans.span("tape.anchors"):
            pending = _dispatch_anchors(batch, stream_len)
        with spans.span("tape.xmerge"):
            order = np.lexsort((batch.offsets, batch.file_ids))
            neighbours = _sort_neighbours(batch, order)
            xm, pairs, beyond = _cross_stream_merges(bounds, order, neighbours[0])
        spans.count("tape.xmerge_pairs", pairs)
        spans.count("tape.xmerge_beyond", beyond)
        if region_cap is not None:
            with spans.span("tape.region_seeks"):
                rg, sr = _ssdup_regions(batch, bounds, pct, order, neighbours,
                                        int(region_cap), static_rand, ssd, link)
        w = np.maximum(batch.sizes / link.bw, batch.sizes / ssd.write_bw)
        ssd_w = np.add.reduceat(w, bounds[:-1])
        anchors, pf, wf, wn = _seek_anchors(pending, ns, hdd)
        # anchor 0 (whole stream) comes straight from the scores so the
        # pure-HDD path reproduces the oracle's walls bit-for-bit
        anchors[:, 0] = hdd_t
    else:
        anchors = np.zeros((0, SUFFIX_ANCHORS + 1), dtype=np.float64)
        ssd_w = np.zeros(0, dtype=np.float64)
        wf = np.zeros((0, N_WINDOWS), dtype=np.float64)
        wn = np.zeros((0, N_WINDOWS), dtype=np.float64)
        pf = np.zeros((0, SUFFIX_ANCHORS + 1), dtype=np.float64)
        xm = np.zeros((0, XMERGE_MIN_DEPTH), dtype=np.float64)
    if not ns or region_cap is None:
        rg = np.zeros((0, len(RG_COLUMNS)), dtype=np.float64)
    if not len(rg):  # no stream's fills can be known: no columns
        sr = np.zeros((ns, 0), dtype=np.float64)
    mean_sz = nb / np.maximum(n_req, 1)

    gap_pos = batch.gap_positions
    gap_sec = batch.gap_seconds
    ng = len(gap_pos)

    # the batched engine's interleave: a full stream fires before any gap
    # at its end boundary; the trailing partial stream fires after ALL
    # remaining gaps (see IONodeSimulator._run_batched)
    if ns:
        fire_before = np.where(
            n_req == stream_len, bounds[1:], batch.num_requests + 1
        )
        gaps_before = np.searchsorted(gap_pos, fire_before, side="left")
    else:
        gaps_before = np.zeros(0, dtype=np.int64)

    with spans.span("tape.fill"):
        e = ns + ng
        ev = {k: np.zeros(e, dtype=dt) for k, dt in _EVENT_FIELDS.items()}
        ev["xm"] = np.zeros((e, xm.shape[1]), dtype=np.float64)
        ev["sr"] = np.zeros((e, sr.shape[1]), dtype=np.float64)
        ev["valid"][:] = True
        s_idx = np.arange(ns) + gaps_before
        g_idx = np.arange(ng) + np.searchsorted(
            gaps_before, np.arange(ng), side="right"
        )
        ev["pct"][s_idx] = pct
        ev["nbytes"][s_idx] = nb
        for j in range(SUFFIX_ANCHORS + 1):
            ev[f"hddt_{j}"][s_idx] = anchors[:, j]
            ev[f"pf_{j}"][s_idx] = pf[:, j]
        for i in range(N_WINDOWS):
            ev[f"wf_{i}"][s_idx] = wf[:, i]
            ev[f"wn_{i}"][s_idx] = wn[:, i]
        ev["xm"][s_idx] = xm
        ev["sr"][s_idx] = sr
        ev["net_t"][s_idx] = net_t
        ev["ssd_w"][s_idx] = ssd_w
        ev["mean_sz"][s_idx] = mean_sz
        ev["is_gap"][g_idx] = True
        ev["gap_sec"][g_idx] = gap_sec
    ev["rg"] = rg
    return ev


def _pad_len(n: int) -> int:
    """Shared tape length: next power of two (bounds jit recompiles)."""

    p = 8
    while p < n:
        p *= 2
    return p


def _empty_events(s: int, lanes: int, depth: int = XMERGE_MIN_DEPTH,
                  listed: int = len(SR_COLUMNS)):
    """An all-padding stacked tape: ``(s, lanes)`` per field, with the
    trailing column axis of ``xm`` (``depth``) and ``sr`` (``listed``:
    0 where no tape lists a fill)."""

    out = {k: np.zeros((s, lanes), dtype=dt) for k, dt in _EVENT_FIELDS.items()}
    out["xm"] = np.zeros((s, lanes, depth), dtype=np.float64)
    out["sr"] = np.zeros((s, lanes, listed), dtype=np.float64)
    return out


@spans.spanned("tape.stack")
def stack_events(
    tapes: Sequence[Mapping[str, np.ndarray]], pad_to: int | None = None
) -> dict[str, np.ndarray]:
    """Stack per-lane event tapes into ``(S, L)`` arrays.

    Tapes are right-padded with ``valid=False`` events to ``pad_to``
    (default: the next power of two above the longest tape, so programs
    of similar size share one compiled executable).
    """

    if not tapes:
        raise ValueError("need at least one lane")
    longest = max(len(t["valid"]) for t in tapes)
    s = pad_to if pad_to is not None else _pad_len(longest)
    if s < longest:
        raise ValueError(f"pad_to={s} < longest tape {longest}")
    # a shallower tape's missing merge columns are zeros: no merges; a
    # tape without fill columns lists none (zero rows)
    out = _empty_events(s, len(tapes), max(t["xm"].shape[1] for t in tapes),
                        max(t["sr"].shape[1] for t in tapes))
    for j, t in enumerate(tapes):
        n = len(t["valid"])
        for k in _EVENT_FIELDS:
            if out[k].ndim == 3:
                out[k][:n, j, :t[k].shape[1]] = t[k]
            else:
                out[k][:n, j] = t[k]
    return out


def lane_consts(
    scheme: str,
    ssd_capacity: int,
    flush_gate: float | str = 0.5,
    ssd: object | None = None,
    regions: np.ndarray | None = None,
    slots: int = 1,
) -> dict[str, object]:
    """Per-lane constants (scheme id, region capacity, gate,
    storage-model geometry, region flush table).

    ``flush_gate="device"`` (flush-gate v2) is encoded as the sentinel
    ``gate = -1.0``: the gate then follows the foreground device instead
    of the detector percentage.  A stateful ``ssd`` (FTL) contributes
    its page/GC geometry as ``ftl_*`` constants; stateless lanes get
    inert defaults (``ftl_on=False``) so the jitted step stays one
    program for mixed fleets.

    ``regions`` is a tape's ``ssdup`` region table (``rg`` of
    :func:`build_events` with ``region_cap``); an ``ssdup`` lane takes
    it, padded to ``slots`` rows.  Other lanes, and rows past the
    table, get ``appended = -1``, which no region matches.
    """

    if scheme not in SCHEME_IDS:
        raise ValueError(f"unknown scheme {scheme!r}")
    if isinstance(flush_gate, str):
        if flush_gate != "device":
            raise ValueError(
                f"flush_gate must be a float or 'device', got {flush_gate!r}"
            )
        gate = -1.0
    else:
        gate = float(flush_gate)
    if scheme == "orangefs":
        cap = 0
    elif scheme == "orangefs-bb":
        cap = int(ssd_capacity)
    else:  # two-region pipeline: half the SSD per region
        cap = int(ssd_capacity) // 2
    ftl_on = bool(ssd is not None and getattr(ssd, "stateful", False))
    if ftl_on:
        page = float(ssd.page_size)
        tpp = float(ssd.t_page)
        terase = float(ssd.t_erase / ssd.n_channels)
        ppb = float(ssd.pages_per_block)
        phys = float(ssd.total_pages)
        low = float(ssd.gc_low_blocks * ssd.pages_per_block)
        high = float(ssd.gc_high_blocks * ssd.pages_per_block)
    else:  # inert defaults keep the where()-discarded branch NaN-free
        page, tpp, terase, ppb, phys, low, high = (
            1.0, 0.0, 0.0, 1.0, 1.0, 0.0, 0.0,
        )
    rg = np.zeros((slots, len(RG_COLUMNS)), dtype=np.float64)
    rg[:, _RG["appended"]] = -1
    if regions is not None and scheme == "ssdup":
        n = len(regions)
        if n > slots:
            raise ValueError(f"{n} regions do not fit {slots} slots")
        rg[:n] = regions
    return {
        "rg": rg,
        "scheme": np.int32(SCHEME_IDS[scheme]),
        "cap": np.int64(cap),
        "gate": np.float64(gate),
        "ftl_on": np.bool_(ftl_on),
        "ftl_page": np.float64(page),
        "ftl_tpp": np.float64(tpp),
        "ftl_terase": np.float64(terase),
        "ftl_ppb": np.float64(ppb),
        "ftl_phys": np.float64(phys),
        "ftl_low": np.float64(low),
        "ftl_high": np.float64(high),
    }


def static_rand0(threshold_warmup: Sequence[float] | None) -> bool:
    """An ``ssdup`` lane's initial watermark state after
    ``threshold_warmup`` (the exact host policy's)."""

    if threshold_warmup is None:
        return False
    return bool(StaticWatermarkThreshold().seed(threshold_warmup)._last_random)


def initial_lane_state(
    scheme: str,
    window: int,
    threshold_warmup: Sequence[float] | None = None,
    ssd: object | None = None,
) -> dict[str, np.ndarray]:
    """One lane's initial state struct (numpy; stacked by the caller).

    ``threshold_warmup`` is replayed through the exact host policy
    (:class:`AdaptiveThreshold` / :class:`StaticWatermarkThreshold`) and
    the resulting window/hysteresis state transplanted — bit-identical
    to seeding the oracle's policy.
    """

    if window is None or window < 1:
        raise ValueError(
            "engine='device' needs a finite adaptive window "
            f"(got {window!r}); the unbounded PercentList is host-only"
        )
    win = np.full(window, np.inf, dtype=np.float64)
    win_n = 0
    win_p = 0
    static_rand = False
    if threshold_warmup is not None:
        if scheme == "ssdup+":
            pol = AdaptiveThreshold(window=window)
            pol.seed(threshold_warmup)
            recent = list(pol._recent)  # arrival order, oldest first
            win[: len(recent)] = recent
            win_n = len(recent)
            win_p = len(recent) % window
        elif scheme == "ssdup":
            static_rand = static_rand0(threshold_warmup)
    # FTL occupancy columns mirror the (possibly pre-used) host model
    if ssd is not None and getattr(ssd, "stateful", False):
        ftl_free = float(ssd.free_pages)
        ftl_live = float(ssd.live_pages)
    else:
        ftl_free = 0.0
        ftl_live = 0.0
    return {
        "clock": np.float64(0.0),
        "gap": np.float64(0.0),
        "pause": np.float64(0.0),
        "blocked": np.float64(0.0),
        "b_ssd": np.int64(0),
        "b_hdd": np.int64(0),
        "a_used": np.int64(0),
        "s_used": np.int64(0),
        "peak": np.int64(0),
        "a_fs": np.float64(0.0),
        # regions scheduled for a flush so far: the active region's row
        # of the lane's region flush table
        "rg_n": np.int32(0),
        # every stream so far went where the region table's route sends it
        "rg_sync": np.bool_(True),
        # region-fill loop iterations
        "fill_trips": np.int32(0),
        "j_left": np.float64(0.0),
        "j_rate": np.float64(1.0),  # >0 so where() divisions stay finite
        "j_alive": np.bool_(False),
        "flushes": np.int32(0),
        "win": win,
        "win_n": np.int32(win_n),
        "win_p": np.int32(win_p),
        "static_rand": np.bool_(static_rand),
        "cur_ssd": np.bool_(False),  # paper: apps start writing the HDD
        # FTL lane-state columns (zeros on constant-backend lanes)
        "ftl_free": np.float64(ftl_free),
        "ftl_live": np.float64(ftl_live),
        "ftl_reloc": np.float64(0.0),
    }


def _stack_lanes(dicts: Sequence[Mapping[str, np.ndarray]]) -> dict:
    return {k: np.stack([d[k] for d in dicts]) for k in dicts[0]}


# ---------------------------------------------------------------------------
# device side: the pure per-lane transition
# ---------------------------------------------------------------------------


def _i32(b):
    return b.astype(jnp.int32)


def _observe_and_route(g, lane, st, pct):
    """Threshold observe + Algorithm 1 hysteresis for one stream.

    Returns ``(dev_ssd, allowed, upd)`` — the device serving THIS stream,
    whether the traffic-aware gate lets the flusher run during it, and
    the policy-state updates (applied only on stream events of
    threshold schemes).
    """

    scheme = lane["scheme"]
    is_ofs = scheme == 0
    is_bb = scheme == 1
    is_plus = scheme == 3

    # -- adaptive threshold (Eq. 2/3): avgper over the PRE-insert sorted
    #    window, insert (circular buffer overwrites the oldest entry),
    #    index floor((1-avgper)*n) into the POST-insert sorted window
    win, win_n, win_p = st["win"], st["win_n"], st["win_p"]
    w = win.shape[0]
    pre_sorted = jnp.sort(win)  # +inf pads sort last
    csum = jnp.cumsum(pre_sorted)
    have = win_n > 0
    avg = jnp.where(
        have, csum[jnp.maximum(win_n - 1, 0)] / jnp.maximum(win_n, 1), 0.0
    )
    win2 = win.at[win_p].set(pct)
    n2 = jnp.minimum(win_n + 1, w)
    p2 = (win_p + 1) % w
    post_sorted = jnp.sort(win2)
    idx = jnp.clip(jnp.floor((1.0 - avg) * n2).astype(jnp.int32), 0, n2 - 1)
    adap_thr = jnp.where(have, post_sorted[idx], g["default_thr"])

    # -- static watermarks (SSDUP): hysteresis between high/low
    sr = st["static_rand"]
    sr2 = jnp.where(
        pct > g["static_high"],
        True,
        jnp.where(pct < g["static_low"], False, sr),
    )
    static_thr = jnp.where(sr2, g["static_low"], g["static_high"])

    thr = jnp.where(is_plus, adap_thr, static_thr)

    # -- Algorithm 1: this stream rides the PREVIOUS decision; the new
    #    percentage-vs-threshold comparison steers the NEXT stream
    #    (equality keeps the current device)
    cur = st["cur_ssd"]
    dev_ssd = jnp.where(is_bb, True, jnp.where(is_ofs, False, cur))
    cur2 = jnp.where(pct > thr, True, jnp.where(pct < thr, False, cur))

    # traffic-aware gate (Section 2.4.2): only ssdup+ pauses; BB jobs are
    # forced and ssdup flushes immediately.  gate < 0 is the sentinel for
    # flush_gate="device" (v2): flush exactly while the foreground stream
    # writes the SSD (HDD quiet), pause when it writes the HDD
    allowed = jnp.where(
        is_plus,
        jnp.where(lane["gate"] < 0.0, dev_ssd, pct >= lane["gate"]),
        True,
    )

    upd = {
        "win": win2,
        "win_n": n2,
        "win_p": p2,
        "static_rand": sr2,
        "cur_ssd": cur2,
    }
    return dev_ssd, allowed, upd


def _rg_row(lane, k):
    """Row ``k`` of the lane's region table (``appended = -1`` past it)."""

    rows = lane["rg"]
    at = (jnp.arange(rows.shape[0]) == k)[:, None]
    row = jnp.sum(jnp.where(at, rows, 0.0), axis=0)
    return row.at[_RG["appended"]].set(
        jnp.where(k < rows.shape[0], row[_RG["appended"]], -1.0))


def _region_flush_cost(lane, k, appended, est_seeks, sync):
    """``(live bytes, seeks)`` of flushing a region that holds
    ``appended`` bytes and is the lane's ``k``-th: the lane's region
    table row ``k`` where every stream so far went where the table's
    route sends it (``sync``) and that row lists exactly ``appended``
    bytes, else the appended bytes and the anchor estimate
    ``est_seeks``."""

    row = _rg_row(lane, k)
    hit = sync & (row[_RG["appended"]] == appended)
    live = jnp.where(hit, row[_RG["live"]].astype(jnp.int64), appended)
    seeks = jnp.where(hit, row[_RG["seeks"]], est_seeks)
    return live, seeks


def _listed_fill(lane, c, ev, fill, sync):
    """``(known, wall, progress)`` of a fill of ``fill`` bytes of a
    stream whose ``ssdup`` fills the tape lists (``sr``,
    :func:`_ssdup_regions`): whether the lane's region state is the one
    the tape expects (``sync`` as in :func:`_region_flush_cost`, and the
    region's ordinal and bytes), the fill's summed request walls, and
    the in-flight flush's progress over them, request by request,
    truncated to whole bytes each as the request-level replay counts it.
    A zero row lists nothing (``p = 0``)."""

    sr = ev["sr"]
    q = c["rg_n"] - sr[_SR["k"]].astype(jnp.int32)
    p = sr[_SR["p"]].astype(jnp.int32)
    row = _rg_row(lane, c["rg_n"])
    head, last = q == 0, (q > 0) & (q == p - 1)
    n_req, wall, n_odd, odd_w = (
        jnp.where(head, sr[_SR[a + "1"]],
                  jnp.where(last, sr[_SR[a + "l"]], row[_RG[a]]))
        for a in ("n", "wall", "g", "sw")
    )
    expect = jnp.where(head, sr[_SR["b1"]],
                       jnp.where(last, sr[_SR["bl"]], row[_RG["appended"]]))
    known = (
        sync & (lane["scheme"] == 2) & (sr[_SR["k"]] >= 0) & (q >= 0) & (q < p)
        & (c["a_used"] == jnp.where(head, sr[_SR["tail"]], 0.0).astype(jnp.int64))
        & (fill == expect.astype(jnp.int64))
    )
    prog = ((n_req - n_odd) * jnp.floor(c["j_rate"] * sr[_SR["w0"]])
            + jnp.floor(c["j_rate"] * odd_w))
    return known, wall, prog


def _ssd_fill_loop(g, lane, st, ev, allowed, dev_ssd):
    """SSD-routed stream: fill regions, swap/block/trigger, overflow.

    Returns the post-loop state pieces plus the overflowed byte count
    (plain BB only; 0 elsewhere).
    """

    scheme = lane["scheme"]
    is_bb = scheme == 1
    is_tworeg = (scheme == 2) | (scheme == 3)
    cap = lane["cap"]
    nb = ev["nbytes"]
    nb_f = jnp.maximum(nb, 1).astype(jnp.float64)
    margin = jnp.maximum(ev["mean_sz"], (cap // 256).astype(jnp.float64))

    def cond(c):
        return (c["rem"] > 0) & ~c["ovf"]

    def body(c):
        bb_ovf = is_bb & c["j_alive"]  # BB drains: whole rest overflows
        room = cap - c["a_used"]
        # plain BB stops at the eager-trigger request — the first append
        # that leaves free space below the margin — NOT at a full region.
        # The oracle appends whole requests, so the fill stops on a
        # request boundary: k = floor((room - margin)/size) + 1 more
        # requests land before the trigger fires (k*size <= room because
        # margin >= size).
        room_f = room.astype(jnp.float64)
        m = jnp.maximum(ev["mean_sz"], 1.0)
        k = jnp.floor((room_f - margin) / m) + 1.0
        bb_cap = jnp.ceil(jnp.maximum(k, 0.0) * m).astype(jnp.int64)
        # two-region fills also stop on a request boundary: the oracle
        # appends every request that fits ENTIRELY, then swaps/blocks
        tr_cap = (jnp.floor(room_f / m) * m).astype(jnp.int64)
        fill_cap = jnp.where(is_bb, jnp.minimum(room, bb_cap), tr_cap)
        fill = jnp.where(bb_ovf, 0, jnp.minimum(c["rem"], fill_cap))
        frac = fill / nb_f
        # -- storage-model device time for this fill.  Constant backend:
        # the pro-rated per-request SSD wall sum (bit-path identical to
        # the pre-FTL engine).  FTL backend: page programs on N channels
        # plus an analytic greedy-GC charge when the fill dips the free
        # pool below the low watermark — the aggregate counterpart of
        # FTLModel._collect with u = mean valid fraction of written
        # blocks (greedy victims are at-most-average, so clip at 0.97).
        pages = fill.astype(jnp.float64) / lane["ftl_page"]
        free1 = c["ftl_free"] - pages
        live1 = c["ftl_live"] + pages
        gc_on = lane["ftl_on"] & (fill > 0) & (free1 < lane["ftl_low"])
        u = jnp.clip(
            live1 / jnp.maximum(lane["ftl_phys"] - free1, 1.0), 0.0, 0.97
        )
        need = jnp.maximum(lane["ftl_high"] - free1, 0.0)
        nblk = need / jnp.maximum(lane["ftl_ppb"] * (1.0 - u), 1.0)
        reloc = nblk * lane["ftl_ppb"] * u
        gc_t = reloc * lane["ftl_tpp"] + nblk * lane["ftl_terase"]
        seg_dev = pages * lane["ftl_tpp"] + jnp.where(gc_on, gc_t, 0.0)
        segw = jnp.where(
            lane["ftl_on"],
            jnp.maximum(ev["net_t"] * frac, seg_dev),
            ev["ssd_w"] * frac,
        )
        # an ssdup lane whose region state is the one the tape expects at
        # this fill knows the fill's requests (where the tape lists fills)
        listed = (_listed_fill(lane, c, ev, fill, st["rg_sync"])
                  if ev["sr"].shape[-1] else None)
        if listed is not None:
            segw = jnp.where(listed[0], listed[1], segw)

        # flush bookkeeping while the foreground writes the SSD: the job
        # drains at its full Eq. 6 effective rate (no HDD contention)
        progressing = c["j_alive"] & allowed
        prog = c["j_rate"] * segw
        if listed is not None:
            prog = jnp.where(listed[0], listed[2], prog)
        completed = progressing & (prog >= c["j_left"])
        # a completing flush retires its region's log: the FTL trims
        # those pages (they stop being live on flash)
        trim_b = jnp.where(completed, c["s_used"], 0)
        j_left = jnp.where(
            completed,
            0.0,
            jnp.where(progressing, c["j_left"] - prog, c["j_left"]),
        )
        pause = c["pause"] + jnp.where(c["j_alive"] & ~allowed, segw, 0.0)
        flushes = c["flushes"] + _i32(completed)
        s_used = jnp.where(completed, 0, c["s_used"])
        j_alive = c["j_alive"] & ~completed

        clock = c["clock"] + segw
        a_used = c["a_used"] + fill
        # Eq. 6 seek accrual: the region sorts its arrival-window of the
        # stream ALONE, so score the fill against the dyadic window
        # anchors of the nearest scale — per window, the distinct-file
        # baseline lands whole with any coverage and only the extent
        # breaks scale with the covered fraction
        a0 = (nb_f - c["rem"].astype(jnp.float64)) / nb_f
        wfrac = fill.astype(jnp.float64) / nb_f
        a1 = a0 + wfrac
        scale = jnp.clip(
            jnp.round(-jnp.log2(jnp.maximum(wfrac, 1e-9))),
            0,
            WINDOW_SCALES - 1,
        ).astype(jnp.int32)
        seg_fs = jnp.zeros_like(nb_f)
        col = 0
        for s_ in range(WINDOW_SCALES):
            nw = 1 << s_
            acc = jnp.zeros_like(nb_f)
            for wj in range(nw):
                lo = wj / nw
                cov = jnp.clip(
                    (jnp.minimum(a1, lo + 1.0 / nw) - jnp.maximum(a0, lo))
                    * nw,
                    0.0,
                    1.0,
                )
                wfv = ev[f"wf_{col}"]
                wnv = ev[f"wn_{col}"]
                acc = acc + jnp.where(
                    cov > 0, wnv + (wfv - wnv) * cov, 0.0
                )
                col += 1
            seg_fs = jnp.where(scale == s_, acc, seg_fs)
        # prefix-aligned fills (every BB fill, the first two-region fill
        # of a stream) have EXACT anchors at the request quantiles: lerp
        # the prefix seek counts instead of the dyadic window estimate
        ppos = jnp.clip(a1 * SUFFIX_ANCHORS, 0.0, float(SUFFIX_ANCHORS))
        pj = jnp.clip(
            jnp.floor(ppos), 0.0, float(SUFFIX_ANCHORS - 1)
        ).astype(jnp.int32)
        plam = ppos - pj.astype(jnp.float64)
        pref_fs = jnp.zeros_like(nb_f)
        for j in range(SUFFIX_ANCHORS):
            sel = pj == j
            lerp = (1.0 - plam) * ev[f"pf_{j}"] + plam * ev[f"pf_{j + 1}"]
            pref_fs = jnp.where(sel, lerp, pref_fs)
        seg_fs = jnp.where(a0 <= 0.0, pref_fs, seg_fs)
        seg_fs = jnp.where(fill > 0, seg_fs, 0.0)
        # cross-stream merge correction: pairs this stream forms with a
        # predecessor still (fractionally) in the active region cost no
        # seek once the region sorts its union; pro-rate by this fill's
        # share of the stream
        merged = jnp.zeros_like(nb_f)
        for d in range(ev["xm"].shape[-1]):  # in order: zero columns add 0
            merged = merged + ev["xm"][d] * c["xf"][d]
        seg_xm = wfrac * merged
        a_fs = jnp.maximum(c["a_fs"] + seg_fs - seg_xm, 0.0)
        b_ssd = c["b_ssd"] + fill
        rem = c["rem"] - fill

        # -- plain BB eager trigger: the append that leaves free space
        #    below max(request, cap/256) schedules a forced flush
        bb_trig = is_bb & ~bb_ovf & ((room - fill) < margin)
        # -- two-region swap: the next request does not fit
        swap = is_tworeg & (rem > 0)
        # a live flush on the standby region blocks the writer: drain it
        # at the job's exclusive effective rate, then swap
        do_block = swap & j_alive
        dtb = jnp.where(do_block, j_left / c["j_rate"], 0.0)
        clock = clock + dtb
        blocked = c["blocked"] + dtb
        flushes = flushes + _i32(do_block)
        j_alive = j_alive & ~do_block
        j_left = jnp.where(do_block, 0.0, j_left)
        trim_b = trim_b + jnp.where(do_block, s_used, 0)
        s_used = jnp.where(do_block, 0, s_used)

        # schedule the filled region's flush (Eq. 6: seeks = pro-rated
        # extent-start count of the region's content, or the region
        # flush table's exact row where the region is the one it lists)
        sched = swap | bb_trig
        jb = a_used
        live, seeks = _region_flush_cost(lane, c["rg_n"], jb, a_fs, st["rg_sync"])
        live_f = live.astype(jnp.float64)
        service = seeks * g["seek_time"] + live_f / g["seq_bw"]
        n_rate = jnp.where(live > 0, live_f / service, g["seq_bw"])
        j_rate = jnp.where(sched, n_rate, c["j_rate"])
        j_left = jnp.where(sched, live_f, j_left)
        j_alive = j_alive | sched
        s_used = jnp.where(sched, jb, s_used)
        a_used = jnp.where(sched, 0, a_used)
        a_fs = jnp.where(sched, 0.0, a_fs)
        # scheduling hands the region's content to the flusher: earlier
        # streams leave the active region, and only fills AFTER the swap
        # count toward this stream's presence in it
        xf = jnp.where(sched, 0.0, c["xf"])
        cur_xf = jnp.where(sched, 0.0, c["cur_xf"] + wfrac)

        ovf = c["ovf"] | bb_ovf | (bb_trig & (rem > 0))
        # FTL occupancy columns: programs consume free pages, GC restores
        # the high watermark, retired (trimmed) region logs leave live
        trim_p = trim_b.astype(jnp.float64) / lane["ftl_page"]
        ftl_free = jnp.where(
            lane["ftl_on"],
            jnp.where(gc_on, lane["ftl_high"], free1),
            c["ftl_free"],
        )
        ftl_live = jnp.where(lane["ftl_on"], live1 - trim_p, c["ftl_live"])
        ftl_reloc = c["ftl_reloc"] + jnp.where(gc_on, reloc, 0.0)
        return {
            "rem": rem, "ovf": ovf, "clock": clock, "pause": pause,
            "blocked": blocked, "b_ssd": b_ssd, "flushes": flushes,
            "a_used": a_used, "s_used": s_used, "a_fs": a_fs,
            "j_left": j_left, "j_rate": j_rate, "j_alive": j_alive,
            "cur_xf": cur_xf, "xf": xf, "ftl_free": ftl_free,
            "ftl_live": ftl_live, "ftl_reloc": ftl_reloc,
            "rg_n": c["rg_n"] + _i32(sched),
            "fill_trips": c["fill_trips"] + 1,
        }

    # HDD-routed streams and capacity-less lanes (orangefs) must never
    # enter the loop: a vmapped while_loop spins until EVERY lane's
    # condition clears, and a cap=0 lane would make no progress
    init = {
        "rem": jnp.where(dev_ssd & (cap > 0), nb, 0),
        "ovf": jnp.asarray(False),
        "clock": st["clock"], "pause": st["pause"],
        "blocked": st["blocked"], "b_ssd": st["b_ssd"],
        "flushes": st["flushes"], "a_used": st["a_used"],
        "s_used": st["s_used"], "a_fs": st["a_fs"],
        "j_left": st["j_left"], "j_rate": st["j_rate"],
        "j_alive": st["j_alive"],
        "cur_xf": jnp.zeros_like(st["a_fs"]),
        "ftl_free": st["ftl_free"], "ftl_live": st["ftl_live"],
        "ftl_reloc": st["ftl_reloc"], "xf": st["xf"],
        "rg_n": st["rg_n"], "fill_trips": st["fill_trips"],
    }
    return lax.while_loop(cond, body, init)


def _hdd_advance(g, lane, c, hdd_b, nb, ev, allowed):
    """Foreground HDD write of ``hdd_b`` bytes (whole stream or BB
    overflow suffix), Eq. 7 interference with a concurrent flush.

    The HDD wall for a *suffix* of a stream is not proportional to its
    bytes — the oracle re-scores the overflow tail from scratch, and a
    strided tail loses the sorted contiguity of the whole stream.  The
    event tape carries ``SUFFIX_ANCHORS + 1`` precomputed suffix walls
    (anchor j = suffix keeping the last ``1 - j/A`` fraction of
    requests); we hat-weight interpolate between the two neighbouring
    anchors.  frac = 1 lands exactly on anchor 0, which is built from
    the stream scores, so pure-HDD whole streams stay bit-exact."""

    nb_f = jnp.maximum(nb, 1).astype(jnp.float64)
    frac = hdd_b.astype(jnp.float64) / nb_f
    pos = (1.0 - frac) * SUFFIX_ANCHORS
    dt = jnp.zeros_like(frac)
    for j in range(SUFFIX_ANCHORS + 1):
        w = jnp.maximum(0.0, 1.0 - jnp.abs(pos - j))
        dt = dt + w * ev[f"hddt_{j}"]
    net = ev["net_t"] * frac
    do = hdd_b > 0
    flushing = c["j_alive"]
    adv = flushing & allowed
    wall_alone = jnp.maximum(net, dt)
    wall_shared = jnp.maximum(net, dt * g["slowdown"])
    wall = jnp.where(adv, wall_shared, wall_alone)
    prog = c["j_rate"] * g["flush_frac"] * wall
    completed = do & adv & (prog >= c["j_left"])
    j_left = jnp.where(
        completed,
        0.0,
        jnp.where(do & adv, c["j_left"] - prog, c["j_left"]),
    )
    trim_p = jnp.where(completed, c["s_used"], 0).astype(
        jnp.float64
    ) / lane["ftl_page"]
    return {
        **c,
        "clock": c["clock"] + jnp.where(do, wall, 0.0),
        "pause": c["pause"]
        + jnp.where(do & flushing & ~adv, wall_alone, 0.0),
        "b_hdd": c["b_hdd"] + hdd_b,
        "flushes": c["flushes"] + _i32(completed),
        "s_used": jnp.where(completed, 0, c["s_used"]),
        "j_alive": c["j_alive"] & ~completed,
        "j_left": j_left,
        "ftl_live": jnp.where(
            lane["ftl_on"], c["ftl_live"] - trim_p, c["ftl_live"]
        ),
    }


def _gap_step(lane, st, sec):
    """Compute phase: the flusher gets the HDD to itself (Eq. 6 rate)."""

    need = st["j_left"] / st["j_rate"]
    full = st["j_alive"] & (need <= sec)
    partial = st["j_alive"] & ~full
    j_left = jnp.where(
        full, 0.0,
        jnp.where(partial, st["j_left"] - st["j_rate"] * sec, st["j_left"]),
    )
    trim_p = jnp.where(full, st["s_used"], 0).astype(
        jnp.float64
    ) / lane["ftl_page"]
    return {
        **st,
        "clock": st["clock"] + sec,
        "gap": st["gap"] + sec,
        "flushes": st["flushes"] + _i32(full),
        "s_used": jnp.where(full, 0, st["s_used"]),
        "j_alive": st["j_alive"] & ~full,
        "j_left": j_left,
        "ftl_live": jnp.where(
            lane["ftl_on"], st["ftl_live"] - trim_p, st["ftl_live"]
        ),
    }


def _stream_step(g, lane, st, ev):
    """One stream event for one lane (all schemes, flag-selected)."""

    scheme = lane["scheme"]
    is_tworeg = (scheme == 2) | (scheme == 3)

    dev_ssd, allowed, upd = _observe_and_route(g, lane, st, ev["pct"])
    if ev["sr"].shape[-1]:
        # the lane's region table holds while every stream goes where the
        # table's route sends it; once one does not, the estimates stand
        st = {**st, "rg_sync": st["rg_sync"]
              & (dev_ssd == (ev["sr"][_SR["ssd"]] > 0))}

    c = _ssd_fill_loop(g, lane, st, ev, allowed, dev_ssd)
    # bytes headed to the HDD in the foreground: the whole stream when
    # HDD-routed, the unbuffered suffix when plain BB overflows
    hdd_b = jnp.where(
        dev_ssd, jnp.where(c["ovf"], c["rem"], 0), ev["nbytes"]
    )
    # SSD-path state only applies to SSD-routed streams
    base = {
        k: jnp.where(dev_ssd, c[k], st[k])
        for k in ("clock", "pause", "blocked", "b_ssd", "flushes",
                  "a_used", "s_used", "a_fs", "j_left", "j_rate",
                  "j_alive", "ftl_free", "ftl_live", "ftl_reloc", "rg_n",
                  "fill_trips")
    }
    base["b_hdd"] = st["b_hdd"]
    base["gap"] = st["gap"]
    base["peak"] = st["peak"]

    out = _hdd_advance(g, lane, base, hdd_b, ev["nbytes"], ev, allowed)
    # shift the cross-merge partner window one stream: this stream's
    # active-region fraction enters at distance 1 (an HDD-routed stream
    # enters as 0 — its bytes never reached the region)
    out["xf"] = jnp.concatenate([
        jnp.where(dev_ssd, c["cur_xf"], 0.0)[None],
        jnp.where(dev_ssd, c["xf"], st["xf"])[:-1],
    ])
    # the oracle samples occupancy at END of stream — after the overflow
    # HDD writes, during which the flush may complete and reset the
    # region — so sample post-advance state
    out["peak"] = jnp.where(
        dev_ssd,
        jnp.maximum(st["peak"], out["a_used"] + out["s_used"]),
        st["peak"],
    )
    # threshold/routing state evolves on every stream of a threshold
    # scheme (observe happens whichever device served the stream)
    for k, v in upd.items():
        out[k] = jnp.where(is_tworeg, v, st[k])
    for k in ("win", "win_n", "win_p", "static_rand", "cur_ssd", "rg_sync"):
        out.setdefault(k, st[k])
    return out


def _event_step(g, lane, st, ev):
    """The per-lane transition: gap, stream, or padded no-op."""

    strm = _stream_step(g, lane, st, ev)
    gap = _gap_step(lane, st, ev["gap_sec"])
    pick = lambda a, b, c_: jnp.where(
        ev["valid"], jnp.where(ev["is_gap"], a, b), c_
    )
    return {k: pick(gap[k], strm[k], st[k]) for k in st}


def _final_drain(g, st, lanes=None):
    """End-of-trace drain (vectorized over lanes): finish the in-flight
    job, then flush the still-buffered active region (Eq. 6), costed
    from the lanes' region flush tables where ``lanes`` is given."""

    d1 = jnp.where(st["j_alive"], st["j_left"] / st["j_rate"], 0.0)
    has_active = st["a_used"] > 0
    if lanes is None:
        live, seeks = st["a_used"], st["a_fs"]
    else:
        live, seeks = jax.vmap(_region_flush_cost)(
            lanes, st["rg_n"], st["a_used"], st["a_fs"], st["rg_sync"])
    d2 = jnp.where(
        has_active,
        seeks * g["seek_time"] + live.astype(jnp.float64) / g["seq_bw"],
        0.0,
    )
    total = st["clock"] + d1 + d2
    return {
        "io_seconds": st["clock"] - st["gap"],
        "total_seconds": total,
        "bytes_to_ssd": st["b_ssd"],
        "bytes_to_hdd_direct": st["b_hdd"],
        "flushes": st["flushes"] + _i32(st["j_alive"]) + _i32(has_active),
        "flush_paused_seconds": st["pause"],
        "blocked_seconds": st["blocked"],
        "peak_ssd_occupancy": st["peak"],
        # FTL diagnostics (zeros on constant-backend lanes)
        "ftl_reloc_pages": st["ftl_reloc"],
        "ftl_live_pages": st["ftl_live"],
        "fill_trips": st["fill_trips"],
    }


def _replay_program(g, lanes, state0, events):
    def scan_step(st, ev):
        new = jax.vmap(
            lambda lane, s, e: _event_step(g, lane, s, e)
        )(lanes, st, ev)
        return new, None

    # fraction of each of the last D streams buffered in the ACTIVE
    # region (newest first): partners for the cross-stream merge
    # correction of the flush seek estimate
    xf = jnp.zeros(events["xm"].shape[1:], dtype=jnp.float64)
    final, _ = lax.scan(scan_step, {**state0, "xf": xf}, events)
    return _final_drain(g, final, lanes)


@functools.lru_cache(maxsize=1)
def _jitted_program():
    return jax.jit(_replay_program)


def _check_outputs(out):
    """checkify guards over the replay outputs (sanitize mode): any
    NaN/Inf produced inside the scan propagates through the accumulated
    clocks/ledgers to an output and trips a finite check; byte ledgers
    must be non-negative and io time can never exceed total time.

    A separate program from the replay itself: checkify cannot traverse
    the region-fill ``while_loop`` under ``vmap`` (batched while), so the
    replay runs unchecked and this checker discharges over its results.
    """

    for k in ("io_seconds", "total_seconds", "flush_paused_seconds",
              "blocked_seconds"):
        checkify.check(
            jnp.all(jnp.isfinite(out[k])), f"non-finite {k} in device replay"
        )
        checkify.check(
            jnp.all(out[k] >= 0), f"negative {k} in device replay"
        )
    for k in ("bytes_to_ssd", "bytes_to_hdd_direct", "flushes",
              "peak_ssd_occupancy"):
        checkify.check(
            jnp.all(out[k] >= 0), f"negative {k} in device replay"
        )
    checkify.check(
        jnp.all(out["total_seconds"] >= out["io_seconds"]),
        "io_seconds exceeds total_seconds in device replay",
    )


@functools.lru_cache(maxsize=1)
def _jitted_output_checker():
    checked = checkify.checkify(_check_outputs, errors=checkify.user_checks)
    return jax.jit(checked)


def _globals(
    hdd: HDDModel, interference: InterferenceModel
) -> dict[str, np.float64]:
    return {
        "seek_time": np.float64(hdd.seek_time),
        "seq_bw": np.float64(hdd.seq_bw),
        "slowdown": np.float64(interference.foreground_slowdown()),
        "flush_frac": np.float64(interference.flush_rate_fraction()),
        "default_thr": np.float64(DEFAULT_THRESHOLD),
        "static_high": np.float64(STATIC_HIGH),
        "static_low": np.float64(STATIC_LOW),
    }


@spans.spanned("replay")
def replay_lanes(
    events: Mapping[str, np.ndarray],
    lanes: Mapping[str, np.ndarray],
    state0: Mapping[str, np.ndarray],
    hdd: HDDModel | None = None,
    interference: InterferenceModel | None = None,
    sanitize: bool | None = None,
) -> dict[str, np.ndarray]:
    """Run every lane's replay in one jitted device call.

    Accuracy contract: float64 on device, accurate to the
    ``DEVICE_TOLERANCES`` tiers against the batched numpy oracle (scan
    reassociates float accumulation, so bit-exactness is not promised).

    ``events`` is the stacked ``(S, L)`` tape (:func:`stack_events`),
    ``lanes``/``state0`` are stacked ``(L,)``/``(L, ...)`` structs.
    Returns per-lane result arrays (io/total seconds, byte splits, flush
    and pause counters, peak occupancy) as host numpy.

    With ``sanitize`` on (``True``/``REPRO_SANITIZE=1``/the
    :func:`repro.analysis.sanitize.sanitizing` override) the program runs
    under :mod:`jax.experimental.checkify` — NaN/Inf reaching any
    result, negative ledgers, or a backwards clock raise
    :class:`~repro.analysis.sanitize.SanitizerError`.
    """

    g = _globals(hdd or HDDModel(), interference or InterferenceModel())
    with x64():
        # the call copies the numpy leaves to the device before it returns
        # and leaves the program running
        with spans.span("replay.upload"):
            out = _jitted_program()(
                g, dict(lanes), dict(state0), dict(events)
            )
        with spans.span("replay.run"):
            out = spans.wait(out)
            if _sanitize.resolve(sanitize):
                err, _ = _jitted_output_checker()(out)
                try:
                    err.throw()
                except Exception as e:
                    raise _sanitize.SanitizerError(
                        f"device replay invariant violated: {e}"
                    ) from e
        with spans.span("replay.readback"):
            out = {k: np.asarray(v) for k, v in out.items()}
    spans.count("replay.fill_trips", int(out["fill_trips"].sum()))
    return out


# ---------------------------------------------------------------------------
# single-lane entry point (IONodeSimulator engine="device")
# ---------------------------------------------------------------------------


def per_app_bytes(batch) -> dict[int, int]:
    """Per-app byte totals (order-independent, scheme-independent)."""

    if not batch.num_requests:
        return {}
    apps, inverse = np.unique(batch.app_ids, return_inverse=True)
    sums = np.zeros(len(apps), dtype=np.int64)
    np.add.at(sums, inverse, batch.sizes)
    return {int(a): int(s) for a, s in zip(apps, sums)}


def simulate_device(
    batch,
    scores,
    scheme: str = "ssdup+",
    ssd_capacity: int = 8 << 30,
    hdd: HDDModel | None = None,
    ssd: "SSDModel | object | None" = None,
    link: IngestLink | None = None,
    interference: InterferenceModel | None = None,
    stream_len: int = DEFAULT_STREAM_LEN,
    flush_gate: float | str = 0.5,
    adaptive_window: int = 64,
    threshold_warmup: Sequence[float] | None = None,
    sanitize: bool | None = None,
):
    """Replay one shard on one lane; returns a
    :class:`~repro.core.simulator.SimResult` (see the module docstring
    for the accuracy contract vs the numpy engines)."""

    from .simulator import SimResult  # deferred: simulator imports us lazily

    tape = build_events(
        batch, scores, stream_len=stream_len, hdd=hdd, ssd=ssd, link=link,
        region_cap=ssd_capacity // 2 if scheme == "ssdup" else None,
        static_rand=static_rand0(threshold_warmup),
    )
    events = stack_events([tape])
    lanes = _stack_lanes([
        lane_consts(scheme, ssd_capacity, flush_gate, ssd=ssd, regions=tape["rg"],
                    slots=_pad_len(len(tape["rg"])))
    ])
    state0 = _stack_lanes(
        [initial_lane_state(scheme, adaptive_window, threshold_warmup,
                            ssd=ssd)]
    )
    out = replay_lanes(events, lanes, state0, hdd=hdd,
                       interference=interference, sanitize=sanitize)
    b_ssd = int(out["bytes_to_ssd"][0])
    b_hdd = int(out["bytes_to_hdd_direct"][0])
    return SimResult(
        scheme=scheme,
        io_seconds=float(out["io_seconds"][0]),
        total_seconds=float(out["total_seconds"][0]),
        total_bytes=b_ssd + b_hdd,
        bytes_to_ssd=b_ssd,
        bytes_to_hdd_direct=b_hdd,
        flushes=int(out["flushes"][0]),
        flush_paused_seconds=float(out["flush_paused_seconds"][0]),
        blocked_seconds=float(out["blocked_seconds"][0]),
        peak_ssd_occupancy=int(out["peak_ssd_occupancy"][0]),
        metadata_bytes=0,
        per_app_bytes=per_app_bytes(batch),
    )

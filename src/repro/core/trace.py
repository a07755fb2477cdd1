"""Struct-of-arrays trace representation + batched per-stream scoring.

The seed simulator consumed a Python ``list[Request | Gap]`` and re-scored
every 128-request stream with per-stream NumPy calls (argsort + reductions
inside a Python loop).  This module is the columnar counterpart used by the
fleet layer (:mod:`repro.core.fleet`):

* :class:`TraceBatch` — one trace as parallel ``int64``/``float64`` arrays
  (offset, size, file_id, app_id, time) plus *gap markers*: compute phases
  (:class:`Gap`) are stored out-of-band as ``(position, seconds)`` pairs
  where ``position`` is the request index the gap precedes.  Converts
  losslessly to/from the simulator's item lists.
* :class:`TraceShards` — a trace's shards, which
  :func:`compute_stream_scores` scores together: ``FleetProgram`` scores
  every shard of a job in one device dispatch.
* :class:`StreamScores` — the three per-stream statistics the simulator
  needs (Eq. 1 random-factor sum, random percentage, sorted seek distance),
  precomputed for *all* streams of a trace in one vectorized call so
  :meth:`repro.core.simulator.IONodeSimulator.run` never re-sorts a stream
  in its hot loop.
* :func:`compute_stream_scores` — scoring entry point with three backends:
  ``numpy`` (vectorized ``int64`` host math, bit-exact against the scalar
  definitions — the default and the oracle), ``jnp`` (one device call via
  :func:`repro.core.random_factor.stream_stats_batch64` under a scoped
  x64 enable — int64 lanes, float64 division, bit-exact at any offset
  magnitude), and ``pallas`` (the fused ``repro.kernels.stream_rf``
  TPU kernel; int32 lanes, so a trace with offsets or sizes beyond
  ``2**31 - 1`` is refused with a ``ValueError``; exact below that).
  No backend falls back to another: :attr:`StreamScores.backend` names
  the path that ran.

Stream grouping follows :class:`repro.core.random_factor.StreamGrouper`
semantics exactly: requests are blocked in arrival order into windows of
``stream_len``; gaps do NOT flush a partial window.  The trailing partial
stream is padded into a score-neutral fixed-shape row
(:meth:`TraceBatch.padded_stream_matrix`) so every backend scores it in
the same pass as the full windows, a device backend in the same dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import numpy as np

from .. import spans
from .random_factor import (
    DEFAULT_STREAM_LEN,
    Request,
    stream_stats_batch64,
    stream_stats_batch_np,
)


@dataclasses.dataclass(frozen=True, slots=True)
class Gap:
    """A compute phase between I/O phases (no foreground I/O)."""

    seconds: float


TraceItem = Request | Gap


@dataclasses.dataclass(frozen=True, eq=False)  # ndarray fields: generated
class TraceBatch:                               # __eq__ would raise
    """A request trace in struct-of-arrays form (+ out-of-band gap markers).

    ``gap_positions[i]`` is the index of the request that gap ``i``
    *precedes* (``num_requests`` means "after the last request"); positions
    are non-decreasing.  Several gaps may share a position.
    """

    offsets: np.ndarray  # (R,) int64
    sizes: np.ndarray  # (R,) int64
    file_ids: np.ndarray  # (R,) int64
    app_ids: np.ndarray  # (R,) int64
    times: np.ndarray  # (R,) float64
    gap_positions: np.ndarray  # (G,) int64, non-decreasing, in [0, R]
    gap_seconds: np.ndarray  # (G,) float64

    def __post_init__(self):
        r = self.offsets.shape[0]
        for name in ("sizes", "file_ids", "app_ids", "times"):
            arr = getattr(self, name)
            if arr.shape[0] != r:
                raise ValueError(f"{name} length {arr.shape[0]} != offsets length {r}")
        g = self.gap_positions.shape[0]
        if self.gap_seconds.shape[0] != g:
            raise ValueError("gap_positions / gap_seconds length mismatch")
        if g and (np.any(self.gap_positions < 0) or np.any(self.gap_positions > r)):
            raise ValueError("gap position out of range")

    def validate(self) -> None:
        """Deep per-element invariants (sanitize mode; ``__post_init__``
        only checks shapes).  Raises :class:`ValueError` on the first
        violated one: non-negative sizes/offsets, finite non-negative gap
        durations, non-decreasing gap positions and request times."""

        if self.num_requests:
            if np.any(self.sizes < 0):
                raise ValueError("negative request size in trace")
            if np.any(self.offsets < 0):
                raise ValueError("negative request offset in trace")
            if not np.all(np.isfinite(self.times)):
                raise ValueError("non-finite request time in trace")
        if self.num_gaps:
            if np.any(np.diff(self.gap_positions) < 0):
                raise ValueError("gap_positions must be non-decreasing")
            if not np.all(np.isfinite(self.gap_seconds)):
                raise ValueError("non-finite gap duration in trace")
            if np.any(self.gap_seconds < 0):
                raise ValueError("negative gap duration in trace")

    # -- constructors ---------------------------------------------------
    @classmethod
    def from_items(cls, items: Iterable[TraceItem]) -> "TraceBatch":
        """Build from the simulator's mixed ``Request | Gap`` sequence."""

        offs: list[int] = []
        szs: list[int] = []
        fids: list[int] = []
        aids: list[int] = []
        tms: list[float] = []
        gpos: list[int] = []
        gsec: list[float] = []
        for item in items:
            if isinstance(item, Gap):
                gpos.append(len(offs))
                gsec.append(item.seconds)
                continue
            offs.append(item.offset)
            szs.append(item.size)
            fids.append(item.file_id)
            aids.append(item.app_id)
            tms.append(item.time)
        return cls(
            offsets=np.asarray(offs, dtype=np.int64),
            sizes=np.asarray(szs, dtype=np.int64),
            file_ids=np.asarray(fids, dtype=np.int64),
            app_ids=np.asarray(aids, dtype=np.int64),
            times=np.asarray(tms, dtype=np.float64),
            gap_positions=np.asarray(gpos, dtype=np.int64),
            gap_seconds=np.asarray(gsec, dtype=np.float64),
        )

    @classmethod
    def from_requests(cls, requests: Sequence[Request]) -> "TraceBatch":
        """Build from a gap-free request sequence (e.g. ``Workload.trace``)."""

        return cls.from_items(requests)

    # -- converters -----------------------------------------------------
    def to_items(self) -> list[TraceItem]:
        """Round-trip back to the simulator's item list (gaps in place)."""

        out: list[TraceItem] = []
        gi = 0
        ng = len(self.gap_positions)
        for i in range(self.num_requests):
            while gi < ng and self.gap_positions[gi] == i:
                out.append(Gap(float(self.gap_seconds[gi])))
                gi += 1
            out.append(
                Request(
                    offset=int(self.offsets[i]),
                    size=int(self.sizes[i]),
                    file_id=int(self.file_ids[i]),
                    app_id=int(self.app_ids[i]),
                    time=float(self.times[i]),
                )
            )
        while gi < ng:
            out.append(Gap(float(self.gap_seconds[gi])))
            gi += 1
        return out

    def to_requests(self) -> list[Request]:
        """Requests only (gap markers dropped)."""

        return [r for r in self.to_items() if isinstance(r, Request)]

    # -- basic queries --------------------------------------------------
    @property
    def num_requests(self) -> int:
        return int(self.offsets.shape[0])

    @property
    def num_gaps(self) -> int:
        return int(self.gap_positions.shape[0])

    @property
    def total_bytes(self) -> int:
        return int(self.sizes.sum())

    @property
    def gap_seconds_total(self) -> float:
        return float(self.gap_seconds.sum())

    def num_streams(self, stream_len: int = DEFAULT_STREAM_LEN) -> int:
        return -(-self.num_requests // stream_len) if self.num_requests else 0

    # -- slicing / sharding --------------------------------------------
    def select(self, indices: np.ndarray) -> "TraceBatch":
        """Sub-trace of the requests at ``indices`` (must be sorted).

        Gap markers are *replicated* into every selection — a compute phase
        idles the whole fleet, not one shard — with positions remapped to
        the local request indexing.
        """

        idx = np.asarray(indices, dtype=np.int64)
        if idx.size > 1 and np.any(np.diff(idx) < 0):
            raise ValueError("selection indices must be sorted (arrival order)")
        return TraceBatch(
            offsets=self.offsets[idx],
            sizes=self.sizes[idx],
            file_ids=self.file_ids[idx],
            app_ids=self.app_ids[idx],
            times=self.times[idx],
            # local position = how many selected requests precede the gap
            gap_positions=np.searchsorted(idx, self.gap_positions, side="left"),
            gap_seconds=self.gap_seconds.copy(),
        )

    def shard(self, assignment: np.ndarray, num_nodes: int) -> list["TraceBatch"]:
        """Split by a per-request node assignment into ``num_nodes`` batches."""

        assignment = np.asarray(assignment)
        if assignment.shape[0] != self.num_requests:
            raise ValueError("assignment length != num_requests")
        if assignment.size and (assignment.min() < 0 or assignment.max() >= num_nodes):
            raise ValueError("node assignment out of range")
        return [
            self.select(np.nonzero(assignment == node)[0])
            for node in range(num_nodes)
        ]

    # -- stream view ----------------------------------------------------
    def stream_bounds(self, stream_len: int = DEFAULT_STREAM_LEN) -> np.ndarray:
        """Request-index boundaries of the streams: ``bounds[s] .. bounds[s+1]``
        is stream ``s`` (full windows, then the trailing partial), matching
        :class:`repro.core.random_factor.StreamGrouper` emission order."""

        r = self.num_requests
        if r == 0:
            return np.zeros(1, dtype=np.int64)
        bounds = np.arange(0, r, stream_len, dtype=np.int64)
        return np.append(bounds, r)

    def stream_sums(
        self, stream_len: int = DEFAULT_STREAM_LEN
    ) -> tuple[np.ndarray, np.ndarray]:
        """Per-stream ``(nbytes, offset_sum)`` — the checksums the replay
        engine compares against :class:`StreamScores` to reject scores
        computed for a different trace."""

        bounds = self.stream_bounds(stream_len)
        starts = bounds[:-1]
        if not len(starts):
            z = np.zeros(0, dtype=np.int64)
            return z, z.copy()
        return (
            np.add.reduceat(self.sizes, starts),
            np.add.reduceat(self.offsets, starts),
        )

    def padded_stream_matrix(
        self, stream_len: int = DEFAULT_STREAM_LEN
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(offsets (S, L), sizes (S, L), true_lens (S,))`` — every stream
        as a fixed-shape row, the trailing partial padded to ``stream_len``.

        The padding is a *score-neutral contiguous run*: zero-size requests
        placed at ``sorted_last.offset + sorted_last.size``.  After the
        offset sort the pad block lands strictly past every real request;
        the (real_last, pad_0) gap equals the real last request's size and
        the pad-pad gaps are zero-against-zero-size, so Eq. 1 counts no
        extra seek and the seek-distance residuals are all zero.  Device
        kernels can therefore score the whole matrix — tail included — in
        one fixed-shape dispatch, with only the percentage denominator
        (``true_lens - 1``) applied host-side.
        """

        return _padded_streams((self,), stream_len)


def _padded_streams(
    shards: Sequence[TraceBatch], stream_len: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The shards' padded stream rows stacked in order, as
    :meth:`TraceBatch.padded_stream_matrix` builds them for one trace.

    The matrix has the most rows that ``R`` requests over ``S`` shards
    can need, ``(R + S * (L - 1)) // L``, so its shape depends only on
    the request count and the shard count, never on how the requests
    fall; for one shard that is exactly its stream count.  Rows past the
    shards' own are all-zero offsets and sizes with a true length of 0:
    they score 0 seeks and 0 distance.
    """

    n_req = sum(b.num_requests for b in shards)
    rows = (n_req + len(shards) * (stream_len - 1)) // stream_len
    offs = np.zeros((rows, stream_len), dtype=np.int64)
    szs = np.zeros((rows, stream_len), dtype=np.int64)
    lens = np.zeros(rows, dtype=np.int64)
    r = 0
    for b in shards:
        m = b.num_requests // stream_len
        full = m * stream_len
        offs[r:r + m] = b.offsets[:full].reshape(m, stream_len)
        szs[r:r + m] = b.sizes[:full].reshape(m, stream_len)
        lens[r:r + m] = stream_len
        r += m
        t = b.num_requests - full
        if t:
            tail_offs, tail_szs = b.offsets[full:], b.sizes[full:]
            # sorted-last real request = LAST occurrence of the max offset
            # (stable sort keeps arrival order among equal offsets)
            j = t - 1 - int(np.argmax(tail_offs[::-1]))
            offs[r, :t] = tail_offs
            offs[r, t:] = int(tail_offs[j]) + int(tail_szs[j])
            szs[r, :t] = tail_szs
            lens[r] = t
            r += 1
    return offs, szs, lens


@dataclasses.dataclass(frozen=True, eq=False)
class TraceShards:
    """A trace's shards, scored together in one call of
    :func:`compute_stream_scores`.

    It offers what the scoring reads from a :class:`TraceBatch`, over its
    shards in order: stream grouping restarts at every shard, and each
    shard's trailing partial is padded on its own.  The result covers
    every shard's streams in order; :meth:`StreamScores.split` with
    :meth:`stream_counts` hands each shard its own.
    """

    shards: tuple[TraceBatch, ...]

    @property
    def num_requests(self) -> int:
        return sum(b.num_requests for b in self.shards)

    def stream_counts(self, stream_len: int = DEFAULT_STREAM_LEN) -> list[int]:
        return [b.num_streams(stream_len) for b in self.shards]

    def stream_sums(
        self, stream_len: int = DEFAULT_STREAM_LEN
    ) -> tuple[np.ndarray, np.ndarray]:
        """Each shard's :meth:`TraceBatch.stream_sums`, concatenated."""

        sums = [b.stream_sums(stream_len) for b in self.shards]
        z = np.zeros(0, dtype=np.int64)
        return (np.concatenate([z, *(s[0] for s in sums)]),
                np.concatenate([z, *(s[1] for s in sums)]))

    def padded_stream_matrix(
        self, stream_len: int = DEFAULT_STREAM_LEN
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Every shard's padded rows in one matrix, its shape fixed by the
        request and shard counts (:func:`_padded_streams`)."""

        return _padded_streams(self.shards, stream_len)


# ---------------------------------------------------------------------------
# batched per-stream scoring
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)  # ndarray fields: generated
class StreamScores:                             # __eq__ would raise
    """Per-stream statistics in stream-emission order.

    One row per stream (full windows first, trailing partial last):
    Eq. 1 random-factor sum, random percentage ``S/(N-1)``, total sorted
    seek distance, the stream's byte count, and an offset checksum
    (plain sum) the simulator uses to reject scores that were computed
    for a different trace.
    """

    rf_sum: np.ndarray  # (S,) int64
    percentage: np.ndarray  # (S,) float64
    seek_distance: np.ndarray  # (S,) int64
    nbytes: np.ndarray  # (S,) int64
    offset_sum: np.ndarray  # (S,) int64
    stream_len: int
    backend: str  # the path that ran: numpy, jnp, pallas, pallas-interpret

    def __len__(self) -> int:
        return int(self.rf_sum.shape[0])

    def validate(self) -> None:
        """Deep per-element invariants (sanitize mode): every score row
        in range — random percentage in [0, 1], non-negative seek sums,
        byte counts and distances.  Raises :class:`ValueError`."""

        n = len(self)
        for name in ("percentage", "seek_distance", "nbytes", "offset_sum"):
            if getattr(self, name).shape[0] != n:
                raise ValueError(f"{name} length != rf_sum length {n}")
        if n == 0:
            return
        if np.any(self.rf_sum < 0) or np.any(self.seek_distance < 0):
            raise ValueError("negative seek score")
        if np.any(self.nbytes < 0):
            raise ValueError("negative stream byte count")
        if np.any((self.percentage < 0.0) | (self.percentage > 1.0)):
            raise ValueError("random percentage outside [0, 1]")

    def split(self, counts: Sequence[int]) -> list["StreamScores"]:
        """Consecutive parts of ``counts[i]`` streams each: one shard's
        scores apiece, checksums included."""

        if sum(counts) != len(self):
            raise ValueError(
                f"split into {sum(counts)} streams but the scores cover {len(self)}")
        cuts = np.cumsum(counts)[:-1]
        cols = ("rf_sum", "percentage", "seek_distance", "nbytes", "offset_sum")
        parts = [np.split(getattr(self, c), cuts) for c in cols]
        return [
            dataclasses.replace(self, **{c: p[i] for c, p in zip(cols, parts)})
            for i in range(len(counts))
        ]


SCORE_BACKENDS = ("numpy", "jnp", "pallas")


_INT32_MAX = np.int64(2**31 - 1)


def _score_streams_device(
    offs2d: np.ndarray, szs2d: np.ndarray, backend: str, interpret: bool,
) -> tuple[np.ndarray, np.ndarray]:
    """Seek counts and seek distances ``(rf, dist)`` of the padded
    (S, L) stream matrix, scored on device in one dispatch.

    ``jnp`` runs :func:`repro.core.random_factor.stream_stats_batch64` in
    the scoped 64-bit mode — int64 lanes, exact at any offset magnitude.
    ``pallas`` runs the fused kernel on int32 lanes, exact there; offsets
    or sizes beyond int32 would TRUNCATE into wrong seek counts, so such a
    trace is refused.
    """

    if backend == "pallas":
        if (np.abs(offs2d).max(initial=0) > _INT32_MAX
                or szs2d.max(initial=0) > _INT32_MAX):
            raise ValueError(
                "backend='pallas' scores on int32 lanes and this trace has "
                "offsets or sizes beyond 2**31 - 1; backend='jnp' is the "
                "exact device backend for it (int64 lanes)"
            )
        from repro.kernels.stream_rf.ops import stream_stats_op

        rf, dist = stream_stats_op(offs2d, szs2d, interpret=interpret)
    else:
        rf, _, dist = stream_stats_batch64(offs2d, szs2d)
    with spans.span("score.readback"):
        return np.asarray(rf, dtype=np.int64), np.asarray(dist, dtype=np.int64)


@spans.spanned("score")
def compute_stream_scores(
    trace: "TraceBatch | TraceShards | Sequence[TraceItem]",
    stream_len: int = DEFAULT_STREAM_LEN,
    backend: str = "numpy",
    interpret: bool = False,
) -> StreamScores:
    """Score every stream of a trace in one vectorized pass.

    Accuracy contract: every backend is bit-exact against the scalar
    ``stream_percentage`` / ``sorted_seek_distance`` definitions.

    Every backend scores the padded matrix of
    :meth:`TraceBatch.padded_stream_matrix`, trailing partial included
    through its score-neutral padding.  ``backend="numpy"`` (default)
    needs no accelerator.  ``"jnp"`` runs every stream as ONE device
    call in the scoped 64-bit mode.  ``"pallas"`` routes the same padded
    matrix through the fused ``stream_rf`` bitonic-sort kernel (int32
    lanes: requires power-of-two ``stream_len``, and raises
    ``ValueError`` on offsets or sizes beyond ``2**31 - 1``).  The kernel
    is compiled for the TPU unless ``interpret=True`` runs it in the
    Pallas interpreter; the result's ``backend`` is then
    ``"pallas-interpret"``.

    Given :class:`TraceShards`, the shards' padded rows are stacked into
    one matrix, which the device backends score in one dispatch; the
    result covers the shards in order, and :meth:`StreamScores.split`
    cuts it back into one per shard.
    """

    if backend not in SCORE_BACKENDS:
        raise ValueError(f"backend must be one of {SCORE_BACKENDS}, got {backend!r}")
    batch = (trace if isinstance(trace, (TraceBatch, TraceShards))
             else TraceBatch.from_items(trace))
    with spans.span("score.matrix"):
        nbytes, osum = batch.stream_sums(stream_len)
        offs_p, szs_p, lens = batch.padded_stream_matrix(stream_len)
    if backend == "numpy":
        rf, _, dist = stream_stats_batch_np(offs_p, szs_p)
    elif offs_p.shape[0]:
        rf, dist = _score_streams_device(offs_p, szs_p, backend, interpret)
    else:
        rf = dist = np.zeros(0, dtype=np.int64)
    # rows past the streams are the matrix's neutral padding; the
    # percentage divides by each row's true length host-side in float64,
    # as the oracle does
    n = len(nbytes)
    rf, dist = rf[:n], dist[:n]
    pct = rf / np.maximum(lens[:n] - 1, 1)

    return StreamScores(
        rf_sum=rf,
        percentage=pct,
        seek_distance=dist,
        nbytes=np.asarray(nbytes, dtype=np.int64),
        offset_sum=np.asarray(osum, dtype=np.int64),
        stream_len=stream_len,
        backend=(
            "pallas-interpret" if backend == "pallas" and interpret
            else backend
        ),
    )

"""Random-factor traffic detection (SSDUP+ paper, Section 2.2).

The paper's central metric: group incoming write requests into *request
streams* of ``stream_len`` (default 128, mirroring the CFQ queue depth), sort
the stream by logical offset, and count how many sorted-adjacent request pairs
are *not* contiguous.  Each non-contiguous pair costs one disk-head seek, so

    RF_i = 0  if sorted_offset[i+1] - sorted_offset[i] == size[i]   (merged)
    RF_i = 1  otherwise                                             (one seek)

    S = sum_i RF_i                       (Eq. 1)
    random_percentage = S / (N - 1)      (Section 2.3.1)

The detector works purely on request *metadata* (offset, size, file, app) —
it never touches payload bytes, which is why it is cheap enough to run on the
server side for every stream (paper Table 1 measures <1% overhead).

Two implementations live here:

* a scalar/NumPy path used by the host-side control plane
  (:class:`StreamGrouper`, :func:`random_factor_sum`), and
* a batched ``jnp`` path (:func:`random_factor_batch`) that scores many
  streams at once; it is also the oracle for the Pallas kernel in
  ``repro.kernels.stream_rf``.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from ..runtime import x64

DEFAULT_STREAM_LEN = 128  # paper: CFQ queue size, Section 2.3.1


@dataclasses.dataclass(frozen=True, slots=True)
class Request:
    """One write request's metadata, as traced by the I/O-node server.

    Mirrors the fields SSDUP+ records in the trove layer (Section 3):
    logical offset, request size, file handle and the issuing application.
    """

    offset: int
    size: int
    file_id: int = 0
    app_id: int = 0
    time: float = 0.0

    @property
    def end(self) -> int:
        return self.offset + self.size


def random_factor_sum(
    offsets: Sequence[int] | np.ndarray,
    sizes: Sequence[int] | np.ndarray | int,
) -> int:
    """Total random factor ``S`` of one stream (paper Eq. 1).

    ``sizes`` may be a scalar (uniform request size, the common IOR case) or a
    per-request array.  Offsets are sorted first — the paper sorts each
    128-request block exactly like the CFQ elevator would, and only then
    counts seeks; adjacent-after-sort contiguity is what matters, not arrival
    order (Fig. 4).
    """

    offs = np.asarray(offsets, dtype=np.int64)
    if offs.size <= 1:
        return 0
    szs = np.broadcast_to(np.asarray(sizes, dtype=np.int64), offs.shape)
    order = np.argsort(offs, kind="stable")
    so = offs[order]
    ss = szs[order]
    gaps = so[1:] - so[:-1]
    return int(np.sum(gaps != ss[:-1]))


def random_percentage(
    offsets: Sequence[int] | np.ndarray,
    sizes: Sequence[int] | np.ndarray | int,
) -> float:
    """``S / (N - 1)`` — the stream's level of randomness in [0, 1]."""

    offs = np.asarray(offsets, dtype=np.int64)
    n = offs.size
    if n <= 1:
        return 0.0
    return random_factor_sum(offs, sizes) / (n - 1)


def random_factor_batch(offsets, sizes):
    """Batched random factor: ``(M, N) -> (M,)`` on device.

    jnp oracle shared with the ``stream_rf`` Pallas kernel.  Sorting uses
    ``jnp.sort``; the seek count compares sorted-adjacent gaps against the
    size carried by the *lower-offset* request of each pair (requests are
    sorted together with their sizes).
    """

    offs = jnp.asarray(offsets, dtype=jnp.int32)
    szs = jnp.broadcast_to(jnp.asarray(sizes, dtype=jnp.int32), offs.shape)
    order = jnp.argsort(offs, axis=-1, stable=True)
    so = jnp.take_along_axis(offs, order, axis=-1)
    ss = jnp.take_along_axis(szs, order, axis=-1)
    gaps = so[..., 1:] - so[..., :-1]
    return jnp.sum((gaps != ss[..., :-1]).astype(jnp.int32), axis=-1)


def random_percentage_batch(offsets, sizes):
    """Batched ``S/(N-1)`` with float32 output."""

    offs = jnp.asarray(offsets)
    n = offs.shape[-1]
    s = random_factor_batch(offs, sizes)
    return s.astype(jnp.float32) / max(n - 1, 1)


def seek_distance_batch(offsets, sizes):
    """Batched sorted seek distance: ``(M, N) -> (M,)`` on device.

    Same definition as :func:`sorted_seek_distance` — total |gap - size|
    over sorted-adjacent pairs; see :func:`stream_stats_batch` for the
    dtype caveats.
    """

    return stream_stats_batch(offsets, sizes)[2]


def stream_stats_batch(offsets, sizes):
    """All three per-stream statistics in one device call.

    ``(M, N)`` offsets/sizes -> ``(rf_sum (M,), percentage (M,),
    seek_distance (M,))``.  One sort feeds both the Eq. 1 seek count and
    the seek-distance aggregate; this is the jnp oracle for the
    ``stream_rf`` Pallas kernel and the device fast path behind
    :func:`repro.core.trace.compute_stream_scores`.

    Dtypes: offsets/sizes ride int32 lanes (jax's default integer width
    here), so per-request values must fit below 2 GiB; the seek-distance
    *sum* can exceed int32 even then (127 residuals of up to 2 GiB), so
    it is accumulated in float32 — overflow-safe, with ~1e-7 relative
    rounding above 16 MiB totals (irrelevant to the timing model, which
    multiplies by seconds-per-byte).  The host path
    (:func:`stream_stats_batch_np`) is the full-range int64 exact oracle.
    """

    offs = jnp.asarray(offsets, dtype=jnp.int32)
    szs = jnp.broadcast_to(jnp.asarray(sizes, dtype=jnp.int32), offs.shape)
    n = offs.shape[-1]
    order = jnp.argsort(offs, axis=-1, stable=True)
    so = jnp.take_along_axis(offs, order, axis=-1)
    ss = jnp.take_along_axis(szs, order, axis=-1)
    resid = so[..., 1:] - so[..., :-1] - ss[..., :-1]
    rf = jnp.sum((resid != 0).astype(jnp.int32), axis=-1)
    pct = rf.astype(jnp.float32) / max(n - 1, 1)
    dist = jnp.sum(jnp.abs(resid).astype(jnp.float32), axis=-1)
    return rf, pct, dist


def stream_stats_batch64(offsets, sizes):
    """Exact int64/float64 device scoring — bit-equal to the numpy oracle.

    Same math as :func:`stream_stats_batch`, run under the scoped
    :func:`repro.runtime.x64` so offsets/sizes ride true int64 lanes
    and the percentage divides in float64.  This removes BOTH device-dtype
    caveats: offsets above 2 GiB no longer truncate, and the seek-distance
    sum accumulates as int64 with no float32 rounding.  ``(M, N)`` ->
    ``(rf int64, percentage float64, seek_distance int64)``.

    The scope is per-call: the global jax x64 flag is untouched, so f32
    kernels elsewhere in the process are unaffected.  The math is one
    jitted program, compiled once per matrix shape; offsets and sizes
    stay int64 on the device, and the sort carries the sizes with the
    offsets instead of gathering them after it.
    """

    with x64():
        # the copies start here; score.run waits for them with the program
        with spans.span("score.upload"):
            offs = jnp.asarray(np.asarray(offsets, dtype=np.int64))
            szs = jnp.broadcast_to(
                jnp.asarray(np.asarray(sizes, dtype=np.int64)), offs.shape)
        with spans.span("score.run"):
            return spans.wait(_stream_stats64(offs, szs))


@jax.jit
def _stream_stats64(offs, szs):
    n = offs.shape[-1]
    # one stable sort keyed on the offsets carries the sizes along: the
    # same permutation as a stable argsort, ties in arrival order, and no
    # gathers of the int64 columns after it
    so, ss = jax.lax.sort((offs, szs), dimension=offs.ndim - 1, num_keys=1,
                          is_stable=True)
    resid = so[..., 1:] - so[..., :-1] - ss[..., :-1]
    rf = jnp.sum((resid != 0).astype(jnp.int64), axis=-1)
    pct = rf.astype(jnp.float64) / max(n - 1, 1)
    dist = jnp.sum(jnp.abs(resid), axis=-1)
    return rf, pct, dist


def stream_stats_batch_np(offsets, sizes):
    """Vectorized host-side scoring of many streams at once (int64, exact).

    ``(M, N)`` -> ``(rf_sum int64, percentage float64, seek_distance
    int64)``, each ``(M,)``.  Bit-for-bit equal to looping the scalar
    :func:`random_factor_sum` / :func:`random_percentage` /
    :func:`sorted_seek_distance` over the rows — the fleet simulator's
    default scoring path and the correctness oracle for the device
    backends.
    """

    offs = np.asarray(offsets, dtype=np.int64)
    szs = np.broadcast_to(np.asarray(sizes, dtype=np.int64), offs.shape)
    m, n = offs.shape
    if n <= 1:
        z = np.zeros(m, dtype=np.int64)
        return z, np.zeros(m, dtype=np.float64), z.copy()
    order = np.argsort(offs, axis=-1, kind="stable")
    so = np.take_along_axis(offs, order, axis=-1)
    ss = np.take_along_axis(szs, order, axis=-1)
    resid = so[:, 1:] - so[:, :-1] - ss[:, :-1]
    rf = np.count_nonzero(resid, axis=-1).astype(np.int64)
    pct = rf / (n - 1)
    dist = np.abs(resid).sum(axis=-1)
    return rf, pct, dist


class StreamGrouper:
    """Groups an arriving request sequence into fixed-length streams.

    The paper's server groups requests in arrival order into blocks of
    ``stream_len`` (Section 2.1: "SSDUP+ groups the requests into blocks...
    also called a request stream").  A trailing partial stream can be flushed
    explicitly at end-of-trace.
    """

    def __init__(self, stream_len: int = DEFAULT_STREAM_LEN):
        if stream_len < 2:
            raise ValueError(f"stream_len must be >= 2, got {stream_len}")
        self.stream_len = stream_len
        self._pending: list[Request] = []
        self.streams_emitted = 0

    def push(self, req: Request) -> list[Request] | None:
        """Add one request; returns a full stream when one completes."""

        self._pending.append(req)
        if len(self._pending) >= self.stream_len:
            stream, self._pending = self._pending, []
            self.streams_emitted += 1
            return stream
        return None

    def push_many(self, reqs: Iterable[Request]) -> Iterator[list[Request]]:
        for r in reqs:
            out = self.push(r)
            if out is not None:
                yield out

    def flush(self) -> list[Request] | None:
        """Emit the trailing partial stream (end of trace / app barrier)."""

        if not self._pending:
            return None
        stream, self._pending = self._pending, []
        self.streams_emitted += 1
        return stream

    @property
    def pending(self) -> int:
        return len(self._pending)


def stream_percentage(stream: Sequence[Request]) -> float:
    """Random percentage of a list of :class:`Request`."""

    if len(stream) <= 1:
        return 0.0
    offs = np.fromiter((r.offset for r in stream), dtype=np.int64, count=len(stream))
    szs = np.fromiter((r.size for r in stream), dtype=np.int64, count=len(stream))
    return random_percentage(offs, szs)


def seek_distance_np(
    offsets: Sequence[int] | np.ndarray, sizes: Sequence[int] | np.ndarray
) -> int:
    """Sorted seek distance of one stream given as plain arrays (int64,
    exact) — the array-native form of :func:`sorted_seek_distance`, used
    by the batched replay engine for overflow subsets that have no
    precomputed score."""

    offs = np.asarray(offsets, dtype=np.int64)
    if offs.size <= 1:
        return 0
    szs = np.asarray(sizes, dtype=np.int64)
    order = np.argsort(offs, kind="stable")
    so, ss = offs[order], szs[order]
    gaps = so[1:] - so[:-1] - ss[:-1]
    return int(np.abs(gaps[gaps != 0]).sum())


def sorted_seek_distance(stream: Sequence[Request]) -> int:
    """Total logical seek distance after sorting (used by the HDD model).

    The paper argues seek time is roughly linear in logical-offset distance
    (Section 2.2, citing FS2); the device model consumes this aggregate.
    """

    if len(stream) <= 1:
        return 0
    offs = np.fromiter((r.offset for r in stream), dtype=np.int64, count=len(stream))
    szs = np.fromiter((r.size for r in stream), dtype=np.int64, count=len(stream))
    return seek_distance_np(offs, szs)

"""The numpy oracle of the tape's seek anchors.

:func:`repro.core.engine_device.build_events` computes every stream's
prefix, window and suffix seek anchors in one jitted device program per
shard (``engine_device._tape_anchors64``).  The functions here are the
host passes that program replaced, kept as the reference the tests
compare it with (``tests/test_tape_anchors.py``): one global lexsort per
family and a masked predecessor pass per anchor.
"""

from __future__ import annotations

import numpy as np

from ..core.engine_device import N_WINDOWS, SUFFIX_ANCHORS, WINDOW_SCALES


def _masked_predecessors(mask: np.ndarray) -> np.ndarray:
    """Index of each element's nearest PRECEDING masked element (-1: none).

    The anchor families below all reduce to "score a subset of a sorted
    sequence": the subset keeps the global sort order, so the element
    before ``v`` in the subset-restricted order is simply the nearest
    earlier index with ``mask`` set — one ``maximum.accumulate``, no
    re-sort.  This is what lets every anchor level reuse ONE global
    lexsort instead of paying its own (the tape build was ~38 lexsorts
    per shard before; it is 2 now).
    """

    idx = np.arange(mask.shape[0], dtype=np.int64)
    pidx = np.maximum.accumulate(np.where(mask, idx, -1))
    prev = np.empty_like(pidx)
    prev[0] = -1
    prev[1:] = pidx[:-1]
    return prev


def _window_seek_anchors(
    batch, bounds: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Eq. 6 seek anchors for dyadic arrival-windows of every stream.

    Returns ``(wf, wn)`` of shape ``(ns, N_WINDOWS)``: window ``(s, j)``
    (scale ``s`` splits the stream into ``2**s`` equal-request windows)
    is scored ALONE — extent count ``wf`` (per file: 1 + non-contiguous
    breaks) and distinct-file baseline ``wn``.  Column layout is
    scale-major: ``[whole, half0, half1, quarter0..3, eighth0..7]``.

    One global ``(stream, file, offset)`` lexsort serves all 15 windows:
    a window's elements keep their global sort order, so each window is
    scored with a masked predecessor pass (:func:`_masked_predecessors`)
    instead of its own sort.
    """

    ns = len(bounds) - 1
    lens = np.diff(bounds)
    wf = np.zeros((ns, N_WINDOWS), dtype=np.float64)
    wn = np.zeros((ns, N_WINDOWS), dtype=np.float64)
    if batch.num_requests == 0:
        return wf, wn
    sid = np.repeat(np.arange(ns, dtype=np.int64), lens)
    pos_in = np.arange(batch.num_requests, dtype=np.int64) - np.repeat(
        bounds[:-1], lens
    )
    order = np.lexsort((batch.offsets, batch.file_ids, sid))
    so = batch.offsets[order]
    ss = batch.sizes[order]
    sf = batch.file_ids[order]
    sdi = sid[order]
    spos = pos_in[order]
    slen = lens[sdi]
    col = 0
    for s in range(WINDOW_SCALES):
        w = 1 << s
        # window of position p: boundaries sit at round(k * len / w), so
        # p's window is the count of k >= 1 with floor(k*len/w + 0.5) <= p,
        # i.e. 2*len*k < (2p+1)*w — integer-exact, no float quantiles
        win = np.minimum(
            ((2 * spos + 1) * w - 1) // np.maximum(2 * slen, 1), w - 1
        )
        for k in range(w):
            m = win == k
            prev = _masked_predecessors(m)
            pc = np.maximum(prev, 0)
            same = m & (prev >= 0) & (sdi[pc] == sdi) & (sf[pc] == sf)
            contig = same & (so == so[pc] + ss[pc])
            wf[:, col + k] = np.bincount(sdi[m & ~contig], minlength=ns)
            wn[:, col + k] = np.bincount(sdi[m & ~same], minlength=ns)
        col += w
    return wf, wn


def _prefix_seek_anchors(batch, bounds: np.ndarray) -> np.ndarray:
    """``(ns, SUFFIX_ANCHORS + 1)`` Eq. 6 seek counts of every stream's
    arrival-order PREFIX at the request-quantile split points.

    Anchor ``j`` scores requests ``[0, round(j * n / A))`` of the stream
    sorted alone (per file: 1 + non-contiguous breaks), i.e. exactly the
    oracle's ``seek_count_sorted`` for a region buffering that prefix.
    Anchor 0 (empty prefix) is 0, anchor A is the whole stream.  Every
    plain-BB fill and every FIRST two-region fill of a stream is
    prefix-aligned, so these anchors are exact there up to the quantile
    lerp.  One global lexsort + one masked predecessor pass per anchor.
    """

    ns = len(bounds) - 1
    out = np.zeros((ns, SUFFIX_ANCHORS + 1), dtype=np.float64)
    if batch.num_requests == 0:
        return out
    lens = np.diff(bounds)
    sid = np.repeat(np.arange(ns, dtype=np.int64), lens)
    pos_in = np.arange(batch.num_requests, dtype=np.int64) - np.repeat(
        bounds[:-1], lens
    )
    order = np.lexsort((batch.offsets, batch.file_ids, sid))
    so = batch.offsets[order]
    ss = batch.sizes[order]
    sf = batch.file_ids[order]
    sdi = sid[order]
    spos = pos_in[order]
    for j in range(1, SUFFIX_ANCHORS + 1):
        k = np.floor(j * lens / SUFFIX_ANCHORS + 0.5).astype(np.int64)
        m = spos < k[sdi]
        prev = _masked_predecessors(m)
        pc = np.maximum(prev, 0)
        same = m & (prev >= 0) & (sdi[pc] == sdi) & (sf[pc] == sf)
        contig = same & (so == so[pc] + ss[pc])
        out[:, j] = np.bincount(sdi[m & ~contig], minlength=ns)
    return out


def _suffix_hdd_anchors(batch, bounds: np.ndarray, hdd) -> np.ndarray:
    """``(ns, SUFFIX_ANCHORS + 1)`` HDD device times of every stream's
    arrival-order suffix at the request-quantile split points.

    Anchor ``j`` of stream ``s`` scores the suffix starting at request
    ``round(j * n_s / SUFFIX_ANCHORS)`` exactly like the oracle's
    overflow path (sort the suffix alone, Eq. 1 seeks + sweep distance +
    sequential time); the last anchor (empty suffix) is 0.  One global
    ``(stream, offset)`` lexsort + a masked predecessor pass per anchor.
    """

    ns = len(bounds) - 1
    out = np.zeros((ns, SUFFIX_ANCHORS + 1), dtype=np.float64)
    if batch.num_requests == 0:
        return out
    lens = np.diff(bounds)
    sid = np.repeat(np.arange(ns, dtype=np.int64), lens)
    pos_in = np.arange(batch.num_requests, dtype=np.int64) - np.repeat(
        bounds[:-1], lens
    )
    order = np.lexsort((batch.offsets, sid))
    so = batch.offsets[order]
    ss = batch.sizes[order]
    sdi = sid[order]
    spos = pos_in[order]
    szf = ss.astype(np.float64)
    for j in range(SUFFIX_ANCHORS):
        k = np.floor(j * lens / SUFFIX_ANCHORS + 0.5).astype(np.int64)
        m = spos >= k[sdi]
        prev = _masked_predecessors(m)
        pc = np.maximum(prev, 0)
        pair = m & (prev >= 0) & (sdi[pc] == sdi)
        resid = np.where(pair, so - so[pc] - ss[pc], 0)
        rf = np.bincount(sdi[pair & (resid != 0)], minlength=ns)
        dist = np.bincount(
            sdi, weights=np.abs(resid).astype(np.float64), minlength=ns
        )
        nb = np.bincount(sdi[m], weights=szf[m], minlength=ns)
        # same term order as HDDModel.write_time
        out[:, j] = (
            rf * hdd.seek_time + dist * hdd.seek_dist_coeff + nb / hdd.seq_bw
        )
    return out

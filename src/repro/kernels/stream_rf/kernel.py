"""Pallas TPU kernel: batched random-factor scoring of request streams.

The paper's hot loop (sort 128 offsets, count non-contiguous neighbours) as
a TPU data-plane op.  The fixed-size sort is a **bitonic sorting network
over the 128-lane minor axis** — no data-dependent control flow, every
compare-exchange a full-width vector op.  Sizes ride along as a payload
through the same network, and each element's arrival lane breaks ties
between equal offsets, so the network orders them as a stable sort does.

Mosaic lowers lane rotations (``pltpu.roll``) but neither flips nor
unaligned lane slices, so both the partner exchange of a stage (lane
``i ^ j``) and the sorted-neighbour residual (lane ``i + 1``) are built
from rotations plus lane masks (:func:`_from_lane`).

Tiling: one VMEM block = (BLOCK_STREAMS, N) int32 for offsets + sizes and
a (BLOCK_STREAMS, 1) int32 column per statistic; with BLOCK_STREAMS=256
and N=128 that is 2 x 128 KiB in per grid step, far under the VMEM
budget.  Outputs are 2-D columns because the TPU tiling refuses 1-D
output blocks narrower than 1024 elements.

N must be a power of two (the stream length is the CFQ window, 128 by
default; the host pads partial tails before calling in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_STREAMS = 256


def _from_lane(x, src, d: int):
    """Per lane ``i``, ``x[:, src[i]]`` where every ``src[i]`` is ``i + d``
    or ``i - d`` (mod N): a select between the two rotations by ``d``.

    The source of each rotated lane is read back from a rotated iota, so
    the select does not depend on which way the hardware rotate counts.
    """

    n = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    fwd = pltpu.roll(lane, d, 1) == src
    return jnp.where(fwd, pltpu.roll(x, d, 1), pltpu.roll(x, n - d, 1))


def _compare_exchange(keys, payload, order, j: int, up_mask):
    """One bitonic stage: each lane meets its partner ``lane ^ j``.

    Keys compare as ``(key, order)``, ``order`` being each element's
    arrival lane, so equal keys keep arrival order: the network sorts as a
    stable sort does.
    """

    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    partner = lane ^ j
    pk = _from_lane(keys, partner, j)
    pp = _from_lane(payload, partner, j)
    po = _from_lane(order, partner, j)
    first = (lane & j) == 0  # lower element of each pair
    take_max = up_mask != first  # see bitonic min/max selection rule
    a_is_small = (keys < pk) | ((keys == pk) & (order < po))
    new = []
    for mine, theirs in ((keys, pk), (payload, pp), (order, po)):
        small = jnp.where(a_is_small, mine, theirs)
        big = jnp.where(a_is_small, theirs, mine)
        new.append(jnp.where(take_max, big, small))
    return tuple(new)


def _bitonic_sort_with_payload(keys, payload):
    """Stable ascending bitonic sort along the minor axis (power-of-two
    length)."""

    bs, n = keys.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bs, n), 1)
    order = lane
    k = 2
    while k <= n:
        up = (lane & k) == 0
        j = k // 2
        while j >= 1:
            keys, payload, order = _compare_exchange(keys, payload, order, j, up)
            j //= 2
        k *= 2
    return keys, payload


def _sorted_residuals(off_ref, size_ref):
    """Sorted-neighbour residuals ``so[i+1] - so[i] - ss[i]`` and the mask
    of the N - 1 lanes that hold one (the last lane has no neighbour)."""

    so, ss = _bitonic_sort_with_payload(off_ref[...], size_ref[...])
    n = so.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, so.shape, 1)
    nxt = _from_lane(so, lane + 1, 1)
    return nxt - so - ss, lane < n - 1


def _stream_rf_kernel(off_ref, size_ref, rf_ref):
    resid, has = _sorted_residuals(off_ref, size_ref)
    seek = has & (resid != 0)
    rf_ref[...] = jnp.sum(seek.astype(jnp.int32), axis=1, keepdims=True)


def _stream_stats_kernel(off_ref, size_ref, rf_ref, hi_ref, lo_ref):
    """Fused variant: Eq. 1 seek count + Eq. 6 seek-distance aggregate.

    One bitonic sort feeds both reductions.  A residual's magnitude is
    below 2**31, but N - 1 of them overflow int32, so the distance leaves
    as two exact int32 sums — of the high and of the low 16 bits of each
    magnitude — for the host to join in int64.
    """

    resid, has = _sorted_residuals(off_ref, size_ref)
    mag = jnp.where(has, jnp.abs(resid), 0)
    seek = has & (resid != 0)
    rf_ref[...] = jnp.sum(seek.astype(jnp.int32), axis=1, keepdims=True)
    hi_ref[...] = jnp.sum(mag >> 16, axis=1, keepdims=True)
    lo_ref[...] = jnp.sum(mag & 0xFFFF, axis=1, keepdims=True)


def _call(kernel, n_out: int, offsets, sizes, block_streams: int,
          interpret: bool):
    """Pad the (M, N) stream matrix to whole blocks and run ``kernel``;
    returns ``n_out`` int32 ``(M,)`` columns."""

    m, n = offsets.shape
    if n & (n - 1) != 0:
        raise ValueError(f"stream length {n} must be a power of two")
    offsets = jnp.asarray(offsets, jnp.int32)
    sizes = jnp.broadcast_to(jnp.asarray(sizes, jnp.int32), offsets.shape)

    bs = min(block_streams, m) if m else block_streams
    pad = (-m) % bs
    if pad:
        # padded rows are contiguous streams -> every statistic 0; sliced
        # off below
        offsets = jnp.pad(offsets, ((0, pad), (0, 0)))
        sizes = jnp.pad(sizes, ((0, pad), (0, 0)))
    mp = offsets.shape[0]
    col = pl.BlockSpec((bs, 1), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(mp // bs,),
        in_specs=[
            pl.BlockSpec((bs, n), lambda i: (i, 0)),
            pl.BlockSpec((bs, n), lambda i: (i, 0)),
        ],
        out_specs=[col] * n_out,
        out_shape=[jax.ShapeDtypeStruct((mp, 1), jnp.int32)] * n_out,
        interpret=interpret,
    )(offsets, sizes)
    return tuple(o[:m, 0] for o in outs)


@functools.partial(jax.jit, static_argnames=("block_streams", "interpret"))
def stream_rf(offsets: jax.Array, sizes: jax.Array,
              block_streams: int = BLOCK_STREAMS,
              interpret: bool = False) -> jax.Array:
    """Batched RF sums: (M, N) int32 offsets/sizes -> (M,) int32.

    M is padded up to a multiple of ``block_streams``; N must be a power of
    two (assignment default 128 = the CFQ queue window).
    """

    (rf,) = _call(_stream_rf_kernel, 1, offsets, sizes, block_streams,
                  interpret)
    return rf


@functools.partial(jax.jit, static_argnames=("block_streams", "interpret"))
def stream_stats(offsets: jax.Array, sizes: jax.Array,
                 block_streams: int = BLOCK_STREAMS,
                 interpret: bool = False
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused RF + seek distance: (M, N) int32 -> three (M,) int32 columns
    ``(rf, dist_hi, dist_lo)``; the exact distance is
    ``dist_hi * 2**16 + dist_lo`` (join it in int64).

    Same tiling and padding contract as :func:`stream_rf`: the flush-cost
    model (Eq. 6) needs both statistics and the sort dominates, so one
    dispatch serves both.
    """

    return _call(_stream_stats_kernel, 3, offsets, sizes, block_streams,
                 interpret)

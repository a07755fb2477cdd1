"""Public wrappers for the stream_rf kernel.

``interpret`` is always the caller's choice: ``False`` compiles the
kernel for the TPU (and fails where there is none), ``True`` runs it in
the Pallas interpreter, which is how the CPU tests check its results.
The random *percentage* variant matches ``repro.core.random_factor``'s
S/(N-1) definition.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.stream_rf.kernel import stream_rf, stream_stats


def stream_rf_op(offsets, sizes, *, interpret: bool,
                 block_streams: int = 256) -> jax.Array:
    return stream_rf(jnp.asarray(offsets), jnp.asarray(sizes),
                     block_streams=block_streams, interpret=interpret)


def random_percentage_op(offsets, sizes, *, interpret: bool) -> jax.Array:
    offsets = jnp.asarray(offsets)
    n = offsets.shape[-1]
    s = stream_rf_op(offsets, sizes, interpret=interpret)
    return s.astype(jnp.float32) / max(n - 1, 1)


def stream_stats_op(offsets, sizes, *, interpret: bool,
                    block_streams: int = 256,
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Kernel-backed per-stream stats: ``(M, N) -> (rf, dist)`` as host
    int64 arrays, equal to ``stream_stats_batch_np``'s rf and distance.

    Both the Eq. 1 seek count and the Eq. 6 seek-distance aggregate come
    out of ONE fused bitonic-sort dispatch (``kernel.stream_stats``); the
    distance's two int32 half-sums are joined here in int64.
    """

    offsets = jnp.asarray(offsets, jnp.int32)
    szs = jnp.broadcast_to(jnp.asarray(sizes, jnp.int32), offsets.shape)
    rf, hi, lo = stream_stats(offsets, szs, block_streams=block_streams,
                              interpret=interpret)
    dist = (np.asarray(hi, np.int64) << 16) + np.asarray(lo, np.int64)
    return np.asarray(rf, np.int64), dist

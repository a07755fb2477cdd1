"""Compile the main path's device programs for a described TPU v5e.

Nothing runs here: each test lowers a program for one chip of a
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what the chip would refuse (Mosaic lowering, tiling, memory) — the
faults the Pallas interpreter hides.  The topology is described inside a
module fixture, so only the worker that runs this file loads the TPU
compiler, and the file's tests skip where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine_device as ed
from repro.core.device_model import HDDModel, InterferenceModel
from repro.core.ftl import FTLModel
from repro.kernels.stream_rf.kernel import stream_rf, stream_stats
from repro.runtime import x64

STREAMS, STREAM_LEN = 8192, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.mark.parametrize("kernel", [stream_stats, stream_rf],
                         ids=["stream_stats", "stream_rf"])
def test_stream_kernel_compiles_for_v5e(one_chip, kernel):
    x = jax.ShapeDtypeStruct((STREAMS, STREAM_LEN), jnp.int32,
                             sharding=one_chip)
    compiled = kernel.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding),
        tree,
    )


def test_replay_program_compiles_for_v5e(one_chip):
    """The FleetProgram replay step for a mixed fleet: constant-backend
    and FTL lanes of every scheme in one program, 64-bit as it runs."""

    cap = 512 << 20
    ftl = FTLModel(logical_bytes=cap)
    lanes, state0 = [], []
    for ssd in (None, ftl):
        for scheme in ed.SCHEME_IDS:
            lanes.append(ed.lane_consts(scheme, cap, ssd=ssd))
            state0.append(ed.initial_lane_state(scheme, 64, ssd=ssd))
    n_lanes, n_events = len(lanes), 256
    events = ed._empty_events(n_events, n_lanes)
    g = ed._globals(HDDModel(), InterferenceModel())
    args = _shapes(
        (g, ed._stack_lanes(lanes), ed._stack_lanes(state0), events),
        one_chip,
    )
    with x64():
        compiled = ed._jitted_program().lower(*args).compile()
    out = compiled.output_shardings
    assert set(out) >= {"io_seconds", "bytes_to_ssd", "flushes"}


def test_tape_anchor_program_compiles_for_v5e(one_chip):
    """The tape build's seek-anchor program at a 64-node fleet's shard
    shape (128 streams of 128 requests), 64-bit as it runs."""

    with x64():
        rows = jax.ShapeDtypeStruct((4, 128, STREAM_LEN), jnp.int64,
                                    sharding=one_chip)
        compiled = ed._tape_anchors64.lower(rows).compile()
    n_rows = (sum(k for _, k in ed._COUNT_BLOCKS)
              + 2 * sum(k for _, k in ed._SUM_BLOCKS))
    assert compiled.out_info.shape == (n_rows, 128)
    assert compiled.out_info.dtype == jnp.int32


def test_scoring_program_compiles_for_v5e(one_chip):
    """The exact scoring program at a 64-node job's stacked shape: the
    most rows 1,048,480 requests over 64 shards can need, int64 as it
    runs, with the sizes carried through the sort (no gather)."""

    rows = (1_048_480 + 64 * (STREAM_LEN - 1)) // STREAM_LEN
    from repro.core.random_factor import _stream_stats64

    with x64():
        x = jax.ShapeDtypeStruct((rows, STREAM_LEN), jnp.int64, sharding=one_chip)
        compiled = _stream_stats64.lower(x, x).compile()
    rf, pct, dist = compiled.out_info
    assert rf.shape == pct.shape == dist.shape == (rows,)
    assert (rf.dtype, pct.dtype, dist.dtype) == (jnp.int64, jnp.float64, jnp.int64)
    hlo = compiled.as_text()
    assert " sort(" in hlo and " gather(" not in hlo

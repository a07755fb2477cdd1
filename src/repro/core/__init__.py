"""SSDUP+ core: traffic-aware burst buffering (the paper's contribution).

Layering:

* detection     — :mod:`repro.core.random_factor` (random factor, Eq. 1)
* policy        — :mod:`repro.core.adaptive` (Eq. 2/3 adaptive threshold)
* routing       — :mod:`repro.core.redirector` (Algorithm 1)
* buffering     — :mod:`repro.core.log_store` with two index backends:
                  :mod:`repro.core.avl` (oracle) and
                  :mod:`repro.core.extent_index` (vectorized) (§2.5)
* pipelining    — :mod:`repro.core.pipeline` (two-region + traffic-aware,
                  Eq. 6 flush costing, §2.4)
* timing model  — :mod:`repro.core.device_model`, :mod:`repro.core.simulator`
                  (batched + per-request replay engines)
* workloads     — :mod:`repro.core.workloads` (IOR/HPIO/MPI-Tile-IO)
* production IO — :mod:`repro.core.burst_buffer` (real-byte facade used by
                  the checkpoint path)
* trace batch   — :mod:`repro.core.trace` (struct-of-arrays traces +
                  vectorized per-stream scoring)
* device engine — :mod:`repro.core.engine_device` (the batched engine's
                  state transition as a jitted scan/vmap array program)
* fleet         — :mod:`repro.core.fleet` (multi-node sharded replay,
                  paper's aggregate evaluation scaled to N nodes)
"""

from .adaptive import AdaptiveThreshold, StaticWatermarkThreshold
from .avl import AVLTree, Extent
from .burst_buffer import BurstBufferWriter
from .device_model import (
    STORAGE_BACKENDS,
    HDDModel,
    InterferenceModel,
    SSDModel,
    StorageModel,
    clone_storage,
    make_storage_model,
)
from .ftl import FTLModel
from .extent_index import INDEX_BACKENDS, ExtentIndex, make_index
from .log_store import LogRegion, RegionFullError
from .pipeline import FlushState, SingleRegionBuffer, TwoRegionPipeline
from .random_factor import (
    DEFAULT_STREAM_LEN,
    Request,
    StreamGrouper,
    random_factor_sum,
    random_percentage,
    stream_percentage,
)
from .redirector import DataRedirector, Device, RoutedStream
from .simulator import Gap, IONodeSimulator, SimResult, run_schemes
from .trace import StreamScores, TraceBatch, TraceShards, compute_stream_scores
from .fleet import FleetProgram, FleetResult, FleetSimulator, run_fleet_schemes
from .workloads import (
    Workload,
    checkpoint_wave,
    hpio,
    ior,
    mixed,
    mpi_tile_io,
    relabel,
)

__all__ = [
    "AdaptiveThreshold",
    "StaticWatermarkThreshold",
    "AVLTree",
    "Extent",
    "ExtentIndex",
    "INDEX_BACKENDS",
    "make_index",
    "BurstBufferWriter",
    "HDDModel",
    "SSDModel",
    "StorageModel",
    "FTLModel",
    "STORAGE_BACKENDS",
    "make_storage_model",
    "clone_storage",
    "InterferenceModel",
    "LogRegion",
    "RegionFullError",
    "FlushState",
    "TwoRegionPipeline",
    "SingleRegionBuffer",
    "DEFAULT_STREAM_LEN",
    "Request",
    "StreamGrouper",
    "random_factor_sum",
    "random_percentage",
    "stream_percentage",
    "DataRedirector",
    "Device",
    "RoutedStream",
    "Gap",
    "IONodeSimulator",
    "SimResult",
    "run_schemes",
    "StreamScores",
    "TraceBatch",
    "TraceShards",
    "compute_stream_scores",
    "FleetProgram",
    "FleetResult",
    "FleetSimulator",
    "run_fleet_schemes",
    "Workload",
    "checkpoint_wave",
    "ior",
    "hpio",
    "mpi_tile_io",
    "mixed",
    "relabel",
]

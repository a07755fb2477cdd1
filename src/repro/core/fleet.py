"""Multi-node fleet simulator (the paper's testbed, scaled out).

The paper evaluates SSDUP+ on an OrangeFS deployment with multiple I/O
nodes and reports *aggregate* throughput (Fig. 6/8/11 are 2-node
aggregates).  The seed repo could only replay a trace against one node;
this module shards a server-side arrival trace across N I/O nodes and
replays each shard through :class:`repro.core.simulator.IONodeSimulator`,
with all per-stream scoring done up front in one vectorized pass
(:func:`repro.core.trace.compute_stream_scores`) instead of per-stream
NumPy calls inside the replay loop.

Sharding policies come from :mod:`repro.distributed.sharding`
(``round-robin-app``, ``hash-file``, ``range-offset``) — each is a pure
``request -> node`` assignment, so the shards partition the trace exactly
(no byte is dropped or duplicated) and compute gaps are replicated to
every node (a compute phase idles the whole fleet).

Aggregation matches the paper's accounting: the fleet's I/O time is the
**straggler's** (apps block on their slowest I/O server), aggregate
throughput is total bytes over that time, and ``load_imbalance`` is
max-over-mean node bytes (1.0 = perfectly balanced).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.distributed.sharding import TRACE_POLICIES, assign_nodes

from .. import spans
from .device_model import clone_storage, make_storage_model
from .random_factor import DEFAULT_STREAM_LEN
from ..analysis import sanitize as _sanitize
from .simulator import IONodeSimulator, SimResult
from .trace import (
    SCORE_BACKENDS,
    TraceBatch,
    TraceItem,
    TraceShards,
    compute_stream_scores,
)


@dataclasses.dataclass(frozen=True)
class FleetResult:
    """Aggregate of one fleet replay: per-node results + fleet metrics."""

    scheme: str
    policy: str
    num_nodes: int
    node_results: tuple[SimResult, ...]

    # -- fleet-level accounting ----------------------------------------
    @property
    def total_bytes(self) -> int:
        return sum(r.total_bytes for r in self.node_results)

    @property
    def bytes_to_ssd(self) -> int:
        return sum(r.bytes_to_ssd for r in self.node_results)

    @property
    def bytes_to_hdd_direct(self) -> int:
        return sum(r.bytes_to_hdd_direct for r in self.node_results)

    @property
    def ssd_byte_ratio(self) -> float:
        return self.bytes_to_ssd / self.total_bytes if self.total_bytes else 0.0

    @property
    def io_seconds(self) -> float:
        """Fleet I/O time = the straggler node's I/O time."""

        return max((r.io_seconds for r in self.node_results), default=0.0)

    @property
    def total_seconds(self) -> float:
        return max((r.total_seconds for r in self.node_results), default=0.0)

    @property
    def straggler(self) -> int:
        """Index of the node whose I/O time bounds the fleet."""

        secs = [r.io_seconds for r in self.node_results]
        return int(np.argmax(secs)) if secs else 0

    @property
    def throughput_mbs(self) -> float:
        """Aggregate fleet throughput (bytes over straggler time)."""

        t = self.io_seconds
        return self.total_bytes / t / 1e6 if t else 0.0

    @property
    def node_throughputs_mbs(self) -> tuple[float, ...]:
        return tuple(r.throughput_mbs for r in self.node_results)

    @property
    def node_bytes(self) -> tuple[int, ...]:
        return tuple(r.total_bytes for r in self.node_results)

    @property
    def load_imbalance(self) -> float:
        """max / mean of per-node byte loads; 1.0 = perfectly balanced."""

        if not self.node_results or not self.total_bytes:
            return 1.0
        loads = np.asarray(self.node_bytes, dtype=np.float64)
        return float(loads.max() / loads.mean())


class FleetSimulator:
    """Shard one arrival trace over N I/O nodes and replay each shard.

    Parameters mirror :class:`IONodeSimulator` (``node_kwargs`` are passed
    through to every node — ``ssd_capacity`` is *per node*), plus:

    num_nodes:
        Fleet size.
    policy:
        Trace-sharding policy name from
        :data:`repro.distributed.sharding.TRACE_POLICIES`.
    score_backend:
        Backend for the up-front batched stream scoring: ``"numpy"``
        (exact, default) or ``"jnp"`` (exact, on the device).
    threshold_scope:
        ``"node"`` (default): every node's detector starts cold and only
        ever observes its own shard's streams — the deployment where each
        I/O server runs an independent SSDUP+ daemon.  ``"fleet"``: each
        node's PercentList is warm-started with the *global* trace's
        stream-percentage history (in arrival order) before replay,
        modeling a fleet-scope detector whose history is shared across
        servers.  During replay each node still evolves independently;
        live cross-node coupling would need a merged arrival timeline.
        Used by ``experiments/anomaly_hunt.py`` to separate per-shard
        threshold-state effects from trace-composition effects.
    """

    def __init__(
        self,
        num_nodes: int = 2,
        scheme: str = "ssdup+",
        policy: str = "round-robin-app",
        stream_len: int = DEFAULT_STREAM_LEN,
        score_backend: str = "numpy",
        threshold_scope: str = "node",
        **node_kwargs,
    ):
        if num_nodes < 1:
            raise ValueError(f"num_nodes must be >= 1, got {num_nodes}")
        if threshold_scope not in ("node", "fleet"):
            raise ValueError(
                f"threshold_scope must be 'node' or 'fleet', "
                f"got {threshold_scope!r}"
            )
        if threshold_scope == "fleet" and "threshold_warmup" in node_kwargs:
            raise ValueError(
                "threshold_scope='fleet' derives each node's "
                "threshold_warmup from the global trace; passing an "
                "explicit threshold_warmup is ambiguous"
            )
        if policy not in TRACE_POLICIES:
            raise ValueError(
                f"unknown policy {policy!r}; choose from {sorted(TRACE_POLICIES)}"
            )
        if score_backend not in SCORE_BACKENDS:
            raise ValueError(
                f"score_backend must be one of {SCORE_BACKENDS}, "
                f"got {score_backend!r}"
            )
        self.num_nodes = num_nodes
        self.scheme = scheme
        self.policy = policy
        self.stream_len = stream_len
        self.score_backend = score_backend
        self.threshold_scope = threshold_scope
        self.node_kwargs = node_kwargs

    # ------------------------------------------------------------------
    def assignment(self, batch: TraceBatch) -> np.ndarray:
        """Per-request node assignment under the policy.

        Exposed separately from :meth:`shard` so the online service layer
        (:mod:`repro.service`) can release arriving requests to exactly
        the lanes the offline simulator would use — the precondition for
        a no-fault service run being bit-identical to :meth:`run`.
        """

        return assign_nodes(
            self.policy, batch.offsets, batch.file_ids, batch.app_ids,
            self.num_nodes,
        )

    def shard(self, batch: TraceBatch) -> list[TraceBatch]:
        """Partition a batch into per-node sub-batches under the policy."""

        return batch.shard(self.assignment(batch), self.num_nodes)

    def run(self, trace: TraceBatch | Sequence[TraceItem]) -> FleetResult:
        """Shard ``trace`` and replay every node with the per-node engine.

        Accuracy contract: inherits the node engine's — bit-identical to
        the per-request oracle for the numpy engines, ``DEVICE_TOLERANCES``
        tiers for ``engine="device"``; aggregation is deterministic
        (nodes reduced in index order).
        """

        batch = (
            trace if isinstance(trace, TraceBatch) else TraceBatch.from_items(trace)
        )
        shards = self.shard(batch)
        if _sanitize.resolve(self.node_kwargs.get("sanitize")):
            # sharding must conserve the trace: every request lands on
            # exactly one node
            n_req = sum(s.num_requests for s in shards)
            _sanitize.check(
                n_req == batch.num_requests,
                "sharding dropped/duplicated requests: %d across shards "
                "vs %d offered", n_req, batch.num_requests,
            )
            n_bytes = sum(s.total_bytes for s in shards)
            _sanitize.check(
                n_bytes == batch.total_bytes,
                "sharding dropped/duplicated bytes: %d across shards "
                "vs %d offered", n_bytes, batch.total_bytes,
            )
        node_kwargs = dict(self.node_kwargs)
        if self.threshold_scope == "fleet" and self.scheme in ("ssdup",
                                                               "ssdup+"):
            global_scores = compute_stream_scores(
                batch, self.stream_len, backend=self.score_backend
            )
            node_kwargs["threshold_warmup"] = tuple(
                float(p) for p in global_scores.percentage
            )
        results = []
        for shard in shards:
            scores = compute_stream_scores(
                shard, self.stream_len, backend=self.score_backend
            )
            kw = node_kwargs
            if "ssd" in kw:
                # stateful storage (FTL) must never share mapping state
                # across nodes — each I/O server has its own device
                kw = dict(kw)
                kw["ssd"] = clone_storage(kw["ssd"])
            node = IONodeSimulator(
                scheme=self.scheme, stream_len=self.stream_len,
                **kw,
            )
            # shards stay columnar end-to-end: the batched replay engine
            # consumes the TraceBatch directly (no item materialization)
            results.append(node.run(shard, scores=scores))
        return FleetResult(
            scheme=self.scheme,
            policy=self.policy,
            num_nodes=self.num_nodes,
            node_results=tuple(results),
        )


class FleetProgram:
    """One jitted device sweep over the whole shard matrix.

    Where :class:`FleetSimulator` loops Python over nodes (and callers
    loop over schemes), ``FleetProgram`` lowers every shard to an event
    tape ONCE (every scheme replays the same tapes), stacks one lane per
    ``scheme × node`` combination, and replays all of them in a single
    ``jit(scan(vmap(step)))`` device call through the lane builder of
    :mod:`repro.core.engine_device` (:func:`~repro.core.engine_device.build_tapes`,
    :func:`~repro.core.engine_device.replay_tapes`), the path
    ``engine="device"`` takes for one lane.  A 64-node × 4-scheme sweep
    is one XLA executable launch instead of 256 Python replays.

    Results carry the device engine's documented tolerances
    (:data:`repro.core.engine_device.DEVICE_TOLERANCES`) vs the numpy
    engines.  What a call costs on a TPU, layer by layer, is measured by
    ``chipbench`` (``python3 chipbench/run.py``) and broken down in
    ``PERF.md`` section 5, from the spans of :mod:`repro.spans` that a
    call records.

    Parameters mirror :class:`FleetSimulator` /
    :class:`IONodeSimulator`; ``ssd_capacity`` is per node.
    """

    def __init__(
        self,
        num_nodes: int = 2,
        schemes: Sequence[str] = (
            "orangefs", "orangefs-bb", "ssdup", "ssdup+",
        ),
        policy: str = "round-robin-app",
        stream_len: int = DEFAULT_STREAM_LEN,
        score_backend: str = "numpy",
        ssd_capacity: int = 8 << 30,
        hdd=None,
        ssd=None,
        link=None,
        interference=None,
        flush_gate: float | str = 0.5,
        adaptive_window: int = 64,
        threshold_warmup: Sequence[float] | None = None,
    ):
        from . import engine_device  # deferred: needs jax at run time

        if num_nodes < 1:
            raise ValueError(f"num_nodes must be >= 1, got {num_nodes}")
        if policy not in TRACE_POLICIES:
            raise ValueError(
                f"unknown policy {policy!r}; choose from "
                f"{sorted(TRACE_POLICIES)}"
            )
        if score_backend not in SCORE_BACKENDS:
            raise ValueError(
                f"score_backend must be one of {SCORE_BACKENDS}, "
                f"got {score_backend!r}"
            )
        unknown = [s for s in schemes if s not in engine_device.SCHEME_IDS]
        if unknown:
            raise ValueError(f"unknown schemes {unknown}")
        self.num_nodes = num_nodes
        self.schemes = tuple(schemes)
        self.policy = policy
        self.stream_len = stream_len
        self.score_backend = score_backend
        self.ssd_capacity = ssd_capacity
        self.hdd = hdd
        # resolve ssd= specs ("constant"/"ftl"/instance) once; every lane
        # shares the template's geometry but carries its own FTL columns
        # in the lane state, so one resolved model serves the whole sweep
        self.ssd = (
            make_storage_model(ssd, logical_bytes=ssd_capacity)
            if isinstance(ssd, str) else ssd
        )
        self.link = link
        self.interference = interference
        self.flush_gate = flush_gate
        self.adaptive_window = adaptive_window
        self.threshold_warmup = threshold_warmup
        self._ed = engine_device
        # every scheme replays the same tapes, pure functions of the
        # trace: repeat sweeps of the same TraceBatch (parameter studies, the
        # steady-state benchmark) reuse them instead of re-sharding and
        # re-scoring. Keyed by object identity with a liveness anchor so
        # a recycled id can never alias a different trace.
        self._tape_cache: tuple[int, TraceBatch, list, list, list] | None = None

    # ------------------------------------------------------------------
    @spans.spanned("fleet.shard")
    def shard(self, batch: TraceBatch) -> list[TraceBatch]:
        assignment = assign_nodes(
            self.policy, batch.offsets, batch.file_ids, batch.app_ids,
            self.num_nodes,
        )
        return batch.shard(assignment, self.num_nodes)

    @spans.spanned("fleet.run")
    def run(
        self, trace: TraceBatch | Sequence[TraceItem]
    ) -> dict[str, FleetResult]:
        """Replay every ``scheme × node`` lane in one device call.

        Accuracy contract: each lane matches the device engine's
        ``DEVICE_TOLERANCES`` tiers against the batched numpy oracle.
        """

        # the layer entries (self.shard, compute_stream_scores, and the
        # build_events, stack_events and replay_lanes that build_tapes and
        # replay_tapes call) are looked up at call time, so that a caller
        # can wrap them
        ed = self._ed
        batch = (
            trace if isinstance(trace, TraceBatch)
            else TraceBatch.from_items(trace)
        )
        cached = self._tape_cache
        if cached is not None and cached[0] == id(batch) and cached[1] is batch:
            _, _, shards, tapes, per_app = cached
        else:
            shards = self.shard(batch)
            # every shard's streams in one scoring call, one device
            # dispatch on the device backend
            job = TraceShards(tuple(shards))
            scores = compute_stream_scores(
                job, self.stream_len, backend=self.score_backend
            ).split(job.stream_counts(self.stream_len))
            tapes = ed.build_tapes(
                shards, scores, self.schemes, self.ssd_capacity,
                hdd=self.hdd, ssd=self.ssd, link=self.link,
                stream_len=self.stream_len,
                threshold_warmup=self.threshold_warmup,
            )
            with spans.span("fleet.lanes"):
                per_app = [ed.per_app_bytes(shard) for shard in shards]
            self._tape_cache = (id(batch), batch, shards, tapes, per_app)
        nodes = ed.replay_tapes(
            tapes, per_app, self.schemes, self.ssd_capacity,
            hdd=self.hdd, ssd=self.ssd, interference=self.interference,
            flush_gate=self.flush_gate, adaptive_window=self.adaptive_window,
            threshold_warmup=self.threshold_warmup,
        )
        return {
            scheme: FleetResult(
                scheme=scheme, policy=self.policy,
                num_nodes=self.num_nodes, node_results=results,
            )
            for scheme, results in nodes.items()
        }


def run_fleet_schemes(
    trace: TraceBatch | Sequence[TraceItem],
    num_nodes: int = 2,
    schemes: Sequence[str] = ("orangefs", "orangefs-bb", "ssdup", "ssdup+"),
    policy: str = "round-robin-app",
    **kwargs,
) -> dict[str, FleetResult]:
    """Fleet counterpart of :func:`repro.core.simulator.run_schemes`.

    Accuracy contract: same as :meth:`FleetSimulator.run` — bit-identical
    to the per-request oracle on numpy engines, ``DEVICE_TOLERANCES``
    tiers on the device engine.
    """

    batch = trace if isinstance(trace, TraceBatch) else TraceBatch.from_items(trace)
    return {
        s: FleetSimulator(
            num_nodes=num_nodes, scheme=s, policy=policy, **kwargs
        ).run(batch)
        for s in schemes
    }
